#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload k8s-5k.backlog --seconds 3 --seeds 11 12 13

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it (a short window at the cell's own load), the numbers the check
compares, and the control's numbers on the same sampled batches -- the
reference put in the program's place in bfloat16, the precision below the
configuration's float32, by the configuration's own reference module.  One
JSON line a seed; the benchmark's own runs never run this.  Without a TPU,
or with a program that lacks what the reference states, it exits 2.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    spec = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, conf = harness.cell_spec(spec, args.workload)
    config = harness.load_json(os.path.join(harness.ROOT, conf["file"]))
    mix = harness.load_json(os.path.join(harness.ROOT, "bench", "traffic",
                                         f"{cell['traffic']}.json"))
    from bench.lib import reference, serve

    ref = reference.for_config(config)
    lacks = serve.program_lacks(ref)
    if lacks:
        return harness.fail(f"the program cannot run {conf['name']}: {lacks}")
    import jax

    harness.enable_compile_cache()
    if jax.devices()[0].platform != "tpu":
        return harness.fail("needs a TPU")
    from bench.lib import check

    for seed in args.seeds:
        t0 = time.perf_counter()
        run = serve.run_cell(config, mix, seed, args.seconds, False, t0,
                             ref=ref)
        numbers = check.serving_checks(run, config["limits"])
        print(json.dumps({
            "workload": cell["name"], "seed": seed,
            "program": {n: v for n, v, _ in numbers},
            "control_bf16": check.control_numbers(run),
            "samples": len(run["samples"]),
            "rows": sum(s["n_real"] for s in run["samples"]),
            "window_bound": run["window_bound"],
            "seconds": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
