"""Each configuration brings its own reference module (the key
``reference`` of its file; ``bench/lib/reference.py`` without it).

* Nothing moved: on the CPU, the seeded start columns, the pre-fill FIFO,
  the weights, the packed pod rows, the work counts at the cells' real sizes
  and the check numbers of a whole ``serve.run_cell`` are pinned, bit for
  bit, to what the harness gave before configurations could name a module.
  A run's window is a fixed number of clock readings (``_FixedClock``), so
  that it makes the same batches every time.
* The check reads the configuration's module: a tighter filter there makes
  a sound program incorrect; a module that changes nothing changes no
  number.
* A module that states a column or pod field the program lacks is turned
  away before any device work, with the missing names; its work count is
  its own.
* No reference module imports the program.
"""
import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import check, cluster, reference, serve, work  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
BASE_MODULE = "bench/lib/reference.py"
PASSTHROUGH = "tests/bench/data/ref_passthrough.py"
TIGHT = "tests/bench/data/ref_tight_filter.py"
GPU = "tests/bench/data/ref_gpu_count.py"
RUN_SEED = 2**31 + 11
MIX = {"kind": "backlog", "depth": 16}


def _config(name, module=None):
    path = os.path.join(DATA, f"{name}.json")
    if not os.path.exists(path):
        path = os.path.join(ROOT, "bench", "configs", f"{name}.json")
    with open(path) as f:
        cfg = json.load(f)
    if module is not None:
        cfg["reference"] = module
    return cfg


def _digest(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(str(a.dtype).encode() + str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _cols_digest(cols) -> str:
    return _digest([cols[k] for k in sorted(cols)])


class _FixedClock:
    """``time`` for ``serve``: every reading 1 ms after the last."""

    def __init__(self):
        self.t = 1000.0

    def perf_counter(self):
        self.t += 1e-3
        return self.t


def _fixed_run(monkeypatch, cfg, faults=None):
    monkeypatch.setattr(serve, "time", _FixedClock())
    run = serve.run_cell(cfg, MIX, RUN_SEED, 0.08, False, 0.0, faults=faults)
    monkeypatch.setattr(serve, "time", time)
    return run


# -- nothing moved -----------------------------------------------------------

# (reset columns, start columns, FIFO, weights) of each cell's and each
# tiny configuration at two seeds, from the harness as it was before
# configurations could name a reference module
SETUP = {
    ("k8s-5k", 2**31 + 11): ("89603317af19418c", "a5d4931174784c9a",
                             "4ee17b53af8213d5", "38d9cfcbf9346810"),
    ("k8s-5k", 7): ("212ddd6801e9dca6", "213b8ca18c2b1db9",
                    "3bf586d61113541d", "38d9cfcbf9346810"),
    ("eks-100k", 2**31 + 11): ("cd589bfa8f8ba15e", "37f67be602db5aa9",
                               "03a36a55905b2a86", "38d9cfcbf9346810"),
    ("eks-100k", 7): ("f2523a32ab4397e3", "ceda16e26edc9025",
                      "d63a04ccaf8cb487", "38d9cfcbf9346810"),
    ("tiny-flat", 2**31 + 11): ("c1fb6be4887552d0", "78c3fdb1a01e93c8",
                                "8400c00515469012", "38d9cfcbf9346810"),
    ("tiny-flat", 7): ("38c8f3b69f8593ef", "d6fa83f01987e85a",
                       "d01127ad265b17f7", "38d9cfcbf9346810"),
    ("tiny-sharded", 2**31 + 11): ("a368836ed555e3db", "8ea3c79e22768aa6",
                                   "3eb9ca2ad3ad86e7", "38d9cfcbf9346810"),
    ("tiny-sharded", 7): ("5378c581ce2e3379", "d8c311cd28d00a8b",
                          "df81bd457dd5f2bf", "38d9cfcbf9346810"),
}
# a whole run at RUN_SEED: (start columns, weights, packed pod rows of the
# samples), (samples, events, submitted, bound in the window), the check
# numbers as float.hex, and the control's
RUNS = {
    "tiny-flat": (
        ("78c3fdb1a01e93c8", "38d9cfcbf9346810", "3e588f4b8b7da5de"),
        (6, 176, 88, 64),
        {"score_err": "0x1.1adac6b4acd8bp-22"},
        {"score_err": "0x1.ae59fa35d6b5dp-7", "choice_gap": "0x0.0p+0",
         "bind_gap": "0x1.5f9440de88785p-11"}),
    "tiny-sharded": (
        ("8ea3c79e22768aa6", "38d9cfcbf9346810", "a57455b07e7ccb32"),
        (6, 176, 88, 64),
        {"score_err": "0x1.491dfb6c9be93p-22"},
        {"score_err": "0x1.526f2452e198bp-7", "choice_gap": "0x0.0p+0",
         "bind_gap": "0x0.0p+0"}),
}
# every number not named above reads 0
LIMITS = {"score_err": 1e-4, "choice_gap": 1e-4, "bind_gap": 1e-4}


@pytest.mark.parametrize("name,seed", sorted(SETUP))
@pytest.mark.parametrize("module", [None, BASE_MODULE])
def test_setup_is_pinned(name, seed, module):
    cfg = _config(name, module)
    ref = reference.for_config(cfg)
    assert ref is reference.BASE
    r_cluster, r_prefill, r_order = (
        np.random.default_rng(s)
        for s in np.random.SeedSequence(seed).spawn(5)[:3])
    types = ref.pod_types(cfg)
    cols = ref.reset(cfg, r_cluster)
    reset_d = _cols_digest(cols)
    fifo = cluster.prefill(cols, types, cfg["prefill"]["fill_frac"],
                           cluster.PodStream(types, r_prefill), r_order, ref)
    w = ref.config_weights(cfg)
    got = (reset_d, _cols_digest(cols), _digest([fifo]),
           _digest([w[k] for k in sorted(w)]))
    assert got == SETUP[(name, seed)]


@pytest.mark.parametrize("n_nodes,n_real,cand,want", [
    (5000, 32, 0, (87040000.0, 1050512.0)),          # k8s-5k
    (100000, 32, 64, (1740800000.0, 5016896.0)),     # eks-100k
    (5000, 1, 0, (2720000.0, 275016.0)),
    (100000, 17, 64, (924800000.0, 5008976.0)),
])
def test_work_counts_are_pinned(n_nodes, n_real, cand, want):
    assert work.serve_batch(n_nodes, n_real, cand) == want
    assert work.serve_batch(n_nodes, n_real, cand, reference.BASE) == want


@pytest.mark.parametrize("name,module", [
    ("tiny-flat", None), ("tiny-flat", BASE_MODULE),
    ("tiny-flat", PASSTHROUGH), ("tiny-sharded", None),
    ("tiny-sharded", BASE_MODULE),
])
def test_a_whole_run_is_pinned(monkeypatch, name, module):
    """The same run whether the configuration names the base module, names
    nothing, or names a module that overrides nothing."""
    cfg = _config(name, module)
    run = _fixed_run(monkeypatch, cfg)
    digests, sizes, floats, control = RUNS[name]
    assert (_cols_digest(run["start_cols"]), _cols_digest(run["weights"]),
            _digest([s["pods"] for s in run["samples"]])) == digests
    assert (len(run["samples"]), len(run["events"]), run["submitted"],
            run["window_bound"]) == sizes
    nums = check.serving_checks(run, cfg["limits"])
    assert [n for n, _, _ in nums] == list(check.ORDER)
    for n, v, lim in nums:
        assert float(v).hex() == floats.get(n, "0x0.0p+0"), n
        assert lim == LIMITS.get(n, 0.0)
    assert check.passed(nums)
    got = {k: float(v).hex() for k, v in check.control_numbers(run).items()}
    assert got == control


# -- the check reads the configuration's module ------------------------------

def test_a_tighter_filter_in_the_module_fails_the_check(monkeypatch):
    cfg = _config("tiny-flat", TIGHT)
    run = _fixed_run(monkeypatch, cfg)
    assert type(run["ref"]).__module__ != reference.__name__
    nums = {n: v for n, v, _ in check.serving_checks(run, cfg["limits"])}
    assert nums["feasible_mismatch"] > 0
    assert not check.passed(check.serving_checks(run, cfg["limits"]))


# -- a module the program cannot run -----------------------------------------

def test_the_field_check_names_what_the_program_lacks():
    assert serve.program_lacks(reference.BASE) == ""
    gpu = reference.for_config({"reference": GPU})
    assert serve.program_lacks(gpu) == ("ClusterState lacks gpu_capacity; "
                                        "PodSpec lacks gpu_request")


def test_the_harness_refuses_it_before_any_device_work(tmp_path):
    """``bench/run.py`` in a checkout whose configuration names the module:
    exit 2, the missing names, no result, and no look for a chip."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    (tmp_path / os.path.dirname(GPU)).mkdir(parents=True)
    shutil.copy(os.path.join(ROOT, GPU), tmp_path / GPU)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell = spec["workloads"][0]
    conf = next(c for c in spec["configs"] if c["name"] == cell["config"])
    with open(tmp_path / conf["file"]) as f:
        cfg = json.load(f)
    cfg["reference"] = GPU
    with open(tmp_path / conf["file"], "w") as f:
        json.dump(cfg, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        cell["name"], "--seed", str(2**31 + 3), "--seconds",
                        "1", "--trace", "0"], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert (f"the program cannot run {conf['name']}: ClusterState lacks "
            f"gpu_capacity; PodSpec lacks gpu_request") in p.stderr
    assert "needs a TPU" not in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_the_work_count_is_the_modules():
    gpu = reference.for_config({"reference": GPU})
    assert work.node_bytes(gpu) == work.node_bytes() + 4 == 54
    flops, nbytes = work.serve_batch(5000, 32, 0)
    assert work.serve_batch(5000, 32, 0, gpu) == (flops, nbytes + 4 * 5000)


def test_a_module_without_a_reference_is_refused():
    with pytest.raises(ValueError, match="no subclass"):
        reference.for_config({"reference": "bench/lib/traffic.py"})
    with pytest.raises(OSError):
        reference.for_config({"reference": "bench/lib/no_such_module.py"})


# -- reference modules stand apart from the program --------------------------

def _reference_modules():
    named = set()
    for path in (glob.glob(os.path.join(ROOT, "bench", "configs", "*.json"))
                 + glob.glob(os.path.join(DATA, "*.json"))):
        with open(path) as f:
            cfg = json.load(f)
        if isinstance(cfg, dict) and "pods" in cfg:
            named.add(cfg.get("reference", BASE_MODULE))
    named |= {BASE_MODULE, PASSTHROUGH, TIGHT, GPU}
    return sorted(named)


@pytest.mark.parametrize("module", _reference_modules())
def test_reference_modules_import_nothing_of_the_program(module):
    with open(os.path.join(ROOT, module)) as f:
        src = f.read()
    assert not re.search(r"^\s*(from|import)\s+repro\b", src, re.M)
    assert "repro." not in src
