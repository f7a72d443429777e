#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload k8s-5k.backlog --seed 7 --seconds 20 --trace 0

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a cluster
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); the configuration names its plain
reference (``bench/lib/reference.py`` where it names none).  One run builds
the cluster, the pod stream and the Q-net weights from ``--seed``, warms up
every shape the window uses, measures for ``--seconds``, drains, and checks
what the timed path produced against the reference (``bench/lib/check.py``).

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
window under the profiler and reports the per-layer metrics, each computed
by its reader ``bench/metrics/<metric>.py``.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, (traced) ``breakdown``, and last ``checks``, each
number compared beside its limit.  The same numbers end standard error.

Without a TPU, with fewer chips than the cell asks for, or with a program
that lacks a node column or pod field the reference states (checked before
JAX touches a device), it exits 2 and prints no result.  JAX's persistent
compilation cache goes to ``JAX_COMPILATION_CACHE_DIR`` when that is set,
else to the fixed directory ``<checkout>/.jax_cache``.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_spec(spec: dict, workload: str):
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return cell, configs[cell["config"]]


def cell_metrics(spec: dict, cell: dict, trace: bool) -> list:
    """The cell's end-to-end metrics (``--trace 0``) or per-layer metrics
    (``--trace 1``): those that list the cell, or list no cells."""
    group = spec["per_layer"] if trace else spec["end_to_end"]
    return [m for m in group if cell["name"] in m.get("workloads",
                                                      [cell["name"]])]


def metric_reader(name: str):
    """``read(run) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = os.path.join(ROOT, "bench", "metrics", f"{name}.py")
    mod_name = "bench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def enable_compile_cache() -> str:
    import jax

    path = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_compilation_cache_dir", path)
    # every program, however quick to compile, is served from the cache on
    # the next run, so set-up repeats the same work
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
        cell, conf_entry = cell_spec(spec, args.workload)
        config = load_json(os.path.join(ROOT, conf_entry["file"]))
        mix = load_json(os.path.join(ROOT, "bench", "traffic",
                                     f"{cell['traffic']}.json"))
        metrics_wanted = cell_metrics(spec, cell, bool(args.trace))
        readers = {m["name"]: metric_reader(m["name"])
                   for m in metrics_wanted}
        from bench.lib import reference

        ref = reference.for_config(config)
    except (OSError, KeyError, ValueError, ImportError) as e:
        return fail(f"cannot load the cell: {e}")
    try:
        import repro  # noqa: F401  (the system under test, from src/)
    except ImportError as e:
        return fail(f"the system under test is not in this checkout: {e}")
    from bench.lib import serve

    lacks = serve.program_lacks(ref)
    if lacks:
        return fail(f"the program cannot run {conf_entry['name']}: {lacks}")

    import jax

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < int(cell["chips"]):
        return fail(f"{cell['name']} needs {cell['chips']} chips, JAX found "
                    f"{len(devices)}")

    from bench.lib import check, work

    run = serve.run_cell(config, mix, args.seed, args.seconds,
                         bool(args.trace), T_PROCESS, ref=ref)
    if run["compiles_in_window"]:
        return fail(f"{run['compiles_in_window']} programs compiled inside "
                    f"the measured window", 3)
    run["peak"] = work.peak_for(run["device"]["kind"])
    numbers = check.serving_checks(run, config["limits"])

    metrics = {}
    for m in metrics_wanted:
        value = readers[m["name"]](run)
        if value is None:
            print(f"bench: {m['name']}: found nothing to read",
                  file=sys.stderr)
            continue
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    window = run["window_requests"]
    failed = sum(d.node < 0 for d in window)
    result = {
        "correct": check.passed(numbers),
        "attempted": len(window),
        "failed": failed,
        "metrics": metrics,
        "device": dict(run["device"]),
    }
    if run["trace"] is not None:
        tr = run["trace"]
        result["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in numbers}

    c = run["counters"]
    print(f"bench: {cell['name']} seed {args.seed}: window "
          f"{run['window_s']:.3f} s, set-up {run['setup_s']:.3f} s, "
          f"{run['n_nodes']} nodes, {run['resident']} resident pods, cache "
          f"{cache_dir}", file=sys.stderr)
    print(f"bench: counters {json.dumps(c)}, window conflicts "
          f"{run['window_conflicts']}, scorer compiles "
          f"{run['scorer_compiles']}", file=sys.stderr)
    print(f"bench: set-up phases (s) {json.dumps(run['setup_phases_s'])}",
          file=sys.stderr)
    for n, v, lim in numbers:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
