"""Trace reduction: interval arithmetic, and the whole reduction on a small
trace recorded on a v5e chip (``data/trace_k8s5k_backlog.json``: the
device's ops and modules and the benchmark's host spans of a few batches)."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import trace  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_counts_overlap_once_and_clips():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31), (40, 50)]
    assert trace.union_ns(iv, 0, 100) == 15 + 10 + 1 + 10
    assert trace.union_ns(iv, 8, 25) == 7 + 5
    assert trace.union_ns([], 0, 10) == 0


def test_idle_gaps_are_the_complement():
    iv = [(5, 10), (8, 12), (20, 25)]
    assert trace.idle_gaps(iv, 0, 30) == [(0, 5), (12, 20), (25, 30)]
    assert trace.idle_gaps(iv, 6, 22) == [(12, 20)]
    assert trace.idle_gaps([], 0, 4) == [(0, 4)]


@pytest.mark.parametrize("hlo,want", [
    ("%fusion.12 = f32[32]{0} fusion(f32[5000] %p), kind=kLoop", "fusion"),
    ("%vmap_jit_sdqn_score_afterstate__.2 = f32[32,40,128] custom-call(...)",
     "vmap_jit_sdqn_score_afterstate__"),
    ("%copy-start.3 = (f32[8]) copy-start(f32[8] %a)", "copy-start"),
])
def test_short_op_name(hlo, want):
    assert trace.short_op_name(hlo) == want


def _synthetic():
    # window 0..1000 ns; ops cover 100..300 and 500..600; module jit_score
    # covers 100..300; host: one poll 50..650 holding a snapshot 60..90
    return {
        "chips": 1,
        "device_ops": [["%a.1 = f32[] add()", 100, 150],
                       ["%b = f32[] mul()", 200, 100],
                       ["%a.2 = f32[] add()", 500, 100]],
        "device_modules": [["jit_score(123)", 100, 200],
                           ["jit_other(9)", 500, 100]],
        "host": [["bench.window", 0, 1000], ["bench.poll", 50, 600],
                 ["bench.snapshot", 60, 30]],
    }


def test_reduce_synthetic_trace():
    r = trace.reduce_trace(_synthetic(), ("jit_score(",))
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share"] == pytest.approx(0.7)
    assert r["modules"]["jit_score("] == {"device_s": pytest.approx(200e-9),
                                          "launches": 1}
    assert r["device_ops"][0] == ["a", pytest.approx(250e-9)]
    gaps = dict(r["idle_gaps"])
    # 0..100: midpoint 50 is inside the poll (50..650), not the snapshot
    assert gaps[trace.HOST_LABELS["bench.poll"]] == pytest.approx(300e-9)
    assert gaps[trace.HOST_LABELS["bench.window"]] == pytest.approx(400e-9)


def test_host_spans_join_the_trace_clock():
    t = _synthetic()
    t["host"] = [["bench.window", 5000, 1000]]
    trace.add_host_spans(t, [("bench.poll", 10.0000002, 3e-7)], 10.0)
    name, start, dur = t["host"][1]
    assert name == "bench.poll"
    assert start == pytest.approx(5200, abs=1e-3)
    assert dur == pytest.approx(300)


def test_reduce_needs_one_window():
    t = _synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(RuntimeError, match="bench.window"):
        trace.reduce_trace(t)


def test_reduce_recorded_chip_trace():
    with open(os.path.join(DATA, "trace_k8s5k_backlog.json")) as f:
        rec = json.load(f)
    r = trace.reduce_trace(rec["trace"], ("jit_score(",))
    want = rec["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert r["modules"]["jit_score("]["launches"] == want["launches"]
    assert 0.0 < r["idle_share"] < 1.0
    # the scorer's Pallas kernel is among the ops that took most time
    assert any("sdqn_score_afterstate" in name for name, _ in
               r["device_ops"])


def test_load_xplane_reads_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) * 2.0)
    x = jnp.ones((64,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.poll"):
                f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    raw = trace.load_xplane(trace.find_xplane(str(tmp_path)))
    names = [n for n, _, _ in raw["host"]]
    assert "bench.window" in names and "bench.poll" in names
    assert all(n.startswith("bench.") for n in names)
    assert raw["chips"] == 0          # the CPU has no TPU plane
