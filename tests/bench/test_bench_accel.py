"""The accelerator deployment (``eks-100k-gpu``): its reference module
(``bench/refs/accel.py``), the check that holds the program to it, its work
count, its pre-fill and its per-layer reader, on the CPU.

``serve.run_cell`` drives whole runs at the tiny size of
``data/tiny-accel.json`` (the configuration's node and pod shapes at 512
nodes), on the flat path and the two-stage path, and with the program's
accelerator filter planted away where a test says so."""
import importlib.util
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import check, cluster, reference, serve, work  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
ACCEL = "bench/refs/accel.py"
MIX = {"kind": "backlog", "depth": 16}
SEED = 2**31 + 1616


def _config(path):
    with open(path) as f:
        return json.load(f)


def _tiny(path="two-stage"):
    cfg = _config(os.path.join(DATA, "tiny-accel.json"))
    if path == "flat":
        cfg["scoring"] = {"path": "flat"}
    return cfg


def _run(path, faults=None, seed=SEED):
    cfg = _tiny(path)
    run = serve.run_cell(cfg, MIX, seed, 0.6, False, time.perf_counter(),
                         faults=faults)
    return run, {n: (v, lim) for n, v, lim in
                 check.serving_checks(run, cfg["limits"])}


def _failed(nums):
    return sorted(n for n, (v, lim) in nums.items()
                  if not (np.isfinite(v) and v <= lim))


@pytest.mark.parametrize("path", ["flat", "two-stage"])
def test_sound_run_is_correct_and_the_control_is_not(path):
    run, nums = _run(path)
    assert type(run["ref"]).__name__ == "Reference"
    assert "accel_request" in run["ref"].POD_FIELDS
    assert run["window_bound"] > 0 and len(run["samples"]) > 0
    assert _failed(nums) == [], nums
    live = run["live_cols"]
    assert (live["accel_requested"] <= live["accel_capacity"]).all()
    assert (live["accel_requested"] >= 0).all()
    assert live["accel_requested"].sum() > 0
    # the samples carry the packed accelerator requests, one of the mix's
    pods = np.concatenate([s["pods"][:s["n_real"]] for s in run["samples"]])
    assert set(pods[:, -1].tolist()) <= {1.0, 2.0, 4.0, 8.0}
    ctrl = check.control_numbers(run)
    limit = nums["score_err"][1]
    assert ctrl["score_err"] > 3 * limit > 3 * nums["score_err"][0]


def _scorer_without_accelerators(scorer):
    """The program's filter without the accelerator predicate: the scorer
    is handed the snapshot and the pods without their accelerator
    columns."""
    def f(params, snap, pods, carry, n_real):
        return scorer(params,
                      snap._replace(accel_capacity=None,
                                    accel_requested=None),
                      pods._replace(accel_request=None), carry, n_real)
    return f


def _revalidation_without_accelerators(sub, daemon):
    """Bind-time re-validation that no longer checks the accelerators."""
    sub.accel_fits = lambda node, pod: True


@pytest.mark.parametrize("path,faults,expect", [
    ("flat", {"scorer": _scorer_without_accelerators}, "feasible_mismatch"),
    ("two-stage", {"scorer": _scorer_without_accelerators},
     "feasible_mismatch"),
    ("flat", {"substrate": _revalidation_without_accelerators},
     "bind_violations"),
    ("two-stage", {"substrate": _revalidation_without_accelerators},
     "bind_violations"),
])
def test_a_program_without_the_accelerator_predicate_is_not_correct(
        path, faults, expect):
    run, nums = _run(path, faults)
    assert expect in _failed(nums), nums
    assert nums[expect][0] > 0
    assert not check.passed([(n, v, lim) for n, (v, lim) in nums.items()])


def test_the_work_count_is_the_bases_and_the_accelerators():
    ref = reference.for_config({"reference": ACCEL})
    assert work.node_bytes(ref) == work.node_bytes() + 8
    assert ref.POD_ROW_BYTES == reference.BASE.POD_ROW_BYTES + 4
    assert ref.pair_flops() == reference.BASE.pair_flops() + 2
    for cand in (0, 64):
        flops, nbytes = work.serve_batch(100000, 32, cand)
        assert work.serve_batch(100000, 32, cand, ref) == (
            flops + 2 * 32 * 100000, nbytes + 8 * 100000 + 4 * 32)


def test_the_prefill_holds_about_half_of_the_accelerators():
    """The configuration's own pre-fill, drawn in numpy as a run draws it:
    one pod a node, about half of the 800,000 accelerators, none
    overbooked, and the FIFO that holds them."""
    cfg = _config(os.path.join(ROOT, "bench", "configs",
                               "eks-100k-gpu.json"))
    ref = reference.for_config(cfg)
    rng = np.random.default_rng(SEED)
    types = ref.pod_types(cfg)
    cols = ref.reset(cfg, rng)
    assert cols["accel_capacity"].dtype == np.int32
    fifo = cluster.prefill(cols, types, cfg["prefill"]["fill_frac"],
                           cluster.PodStream(types, rng), rng, ref)
    cap = int(cols["accel_capacity"].sum())
    booked = cols["accel_requested"]
    assert cap == 800000 and len(fifo) == 100000
    assert 0.4 < booked.sum() / cap < 0.5
    assert (booked <= cols["accel_capacity"]).all()
    assert np.isclose((booked == 8).mean(), 0.3, atol=0.01)
    per_type = np.asarray([t.accel_request for t in types])
    assert per_type[fifo[:, 1]].sum() == booked.sum()


def _reader(name):
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(name.replace(".", "_"),
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_stranded_share_reads_free_accelerators_below_the_largest_ask():
    read = _reader("accel_stranded_share.tput")
    ref = reference.for_config({"reference": ACCEL})
    types = [ref.PodType("a", 1.0, 1.0, 1.0, 1.0, 1.0, accel_request=a)
             for a in (1, 8)]
    run = {"types": types, "live_cols": {
        "accel_capacity": np.full(4, 8, np.int32),
        "accel_requested": np.int32([0, 5, 8, 3])}}
    assert read(run) == (3 + 5) / (8 + 3 + 5)
    run["live_cols"]["accel_requested"] = np.full(4, 8, np.int32)
    assert read(run) is None                     # nothing free
    cpu = {"types": reference.BASE.pod_types(
        _config(os.path.join(DATA, "tiny-flat.json"))),
        "live_cols": {"cpu_capacity": np.ones(4)}}
    assert read(cpu) is None
    # a CPU-only state's fields left at None, as ``run_cell`` copies them
    cpu["live_cols"].update(accel_capacity=np.array(None),
                            accel_requested=np.array(None))
    assert read(cpu) is None
