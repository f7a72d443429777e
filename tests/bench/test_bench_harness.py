"""The harness as a whole: its refusal to run without a chip, the shape of
``BENCHMARK.json``, and the files each name in it must find."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def _no_result(p):
    assert p.returncode != 0
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_no_chip_no_metrics(spec):
    cell = spec["workloads"][0]["name"]
    p = _run(ROOT, "--workload", cell, "--seed", "3", "--seconds", "1",
             "--trace", "0")
    _no_result(p)
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_do_not_run(spec, tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's paths has no
    system under test: the harness exits non-zero and prints no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in spec["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), "--workload", spec["workloads"][0]["name"],
             "--seed", "3", "--seconds", "1")
    _no_result(p)


def test_benchmark_json_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert 1 <= spec["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (spec["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    names = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    for group in (names, cells, metrics):
        assert len(group) == len(set(group))
        assert all(NAME.match(n) for n in group)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/configs/")
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert c["name"] in {w["config"] for w in spec["workloads"]}
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                           w["traffic"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= set(cells)
        assert os.path.exists(os.path.join(ROOT, "bench", "metrics",
                                           m["name"] + ".py"))
    for m in spec["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            # a cell that reports a per-layer metric reports what it moves
            assert w in e2e[m["moves"]].get("workloads", cells)
        assert m["layer"] and "\n" not in m["layer"]
        if m["name"].split(".")[0].endswith("_roofline"):
            assert m["unit"] == "%"
    for w in cells:
        mine = [m for m in spec["end_to_end"]
                if w in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(w in m.get("workloads", cells) for m in spec["per_layer"])


def test_every_metric_reader_loads(spec):
    from bench import run as harness

    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_metric_readers_on_a_record():
    from bench import run as harness
    from bench.lib import reference, work

    run = {
        "setup_s": 12.5, "window_s": 10.0, "window_bound": 25_000,
        "n_nodes": 5000,
        "config": {"scoring": {"path": "flat"}}, "ref": reference.BASE,
        "spans": {"poll_s": [0.010, 0.014], "snapshot_s": [0.002, 0.002],
                  "score_s": [0.001, 0.003], "batch_sizes": [32, 32]},
        "window_conflicts": 30, "commit_attempts": 40,
        "peak": work.peak_for("TPU v5 lite"),
        "trace": {"idle_share": 0.9, "modules": {
            "jit_score(": {"device_s": 2e-4, "launches": 2}}},
    }
    read = {n: harness.metric_reader(n)(run) for n in (
        "setup_s", "placements_per_s", "snapshot_ms.tput", "score_ms.tput",
        "commit_ms.tput", "conflict_share.tput", "device_idle_share.tput",
        "score_roofline.tput")}
    assert read["setup_s"] == 12.5
    assert read["placements_per_s"] == 2500.0
    assert read["snapshot_ms.tput"] == pytest.approx(2.0)
    assert read["score_ms.tput"] == pytest.approx(2.0)
    assert read["commit_ms.tput"] == pytest.approx(12.0 - 2.0 - 2.0)
    assert read["conflict_share.tput"] == pytest.approx(0.75)
    assert read["device_idle_share.tput"] == 0.9
    least = work.least_time(*work.serve_batch(5000, 32), run["peak"])[0]
    assert read["score_roofline.tput"] == pytest.approx(100 * least / 1e-4)
    run["trace"] = None
    assert harness.metric_reader("score_roofline.tput")(run) is None
    assert harness.metric_reader("device_idle_share.tput")(run) is None
