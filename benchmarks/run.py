"""Benchmark harness — one function per paper table + scenario, fleet-scale
and roofline benches.  Prints ``name,us_per_call,derived`` CSV at the end.

    PYTHONPATH=src python -m benchmarks.run                     # everything
    PYTHONPATH=src python -m benchmarks.run --fast              # skip RL training
    PYTHONPATH=src python -m benchmarks.run --scenario spot-flaky
    PYTHONPATH=src python -m benchmarks.run --smoke --json out.json   # CI job
"""
from __future__ import annotations

import argparse
import json
import platform
import sys


def _write_json(path: str, rows) -> None:
    payload = {
        "schema": "repro-bench-v1",
        "python": platform.python_version(),
        "argv": sys.argv[1:],
        "rows": [
            {"name": name, "us_per_call": us, "derived": derived}
            for name, us, derived in rows
        ],
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {len(payload['rows'])} rows to {path}")


def _run_manifest(path: str, nightly: bool = False) -> int:
    """Run every suite of a gates manifest (benchmarks/gates.json).

    Each suite runs as its own subprocess — ``python -m benchmarks.run
    <run_args> --json BENCH_<suite>.json`` — so one suite's crash (or
    memory) cannot poison the others, and each bench JSON lands where the
    CI gate step (``check_smoke --manifest``) and the artifact upload
    expect it.  Suites that fail to run are reported at the end; the exit
    code is the number of failed suites.
    """
    import subprocess

    with open(path) as f:
        manifest = json.load(f)
    suites = manifest["nightly"] if nightly else manifest["suites"]
    lane = "nightly" if nightly else "smoke"
    failed = []
    for suite in suites:
        name, run_args = suite["name"], list(suite["run_args"])
        cmd = [sys.executable, "-m", "benchmarks.run", *run_args,
               "--json", f"BENCH_{name}.json"]
        print(f"\n=== [{lane}] suite {name}: {' '.join(cmd)} ===", flush=True)
        if subprocess.call(cmd) != 0:
            failed.append(name)
    if failed:
        print(f"\nmanifest: {len(failed)}/{len(suites)} suites failed: "
              f"{', '.join(failed)}", file=sys.stderr)
    else:
        print(f"\nmanifest: all {len(suites)} {lane} suites completed")
    return len(failed)


def main() -> None:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--fast", action="store_true", help="skip policy training benches")
    mode.add_argument("--scenario", metavar="NAME",
                      help="run one registry scenario (see repro.scenarios)")
    mode.add_argument("--list-scenarios", action="store_true",
                      help="print the scenario registry and exit")
    mode.add_argument("--smoke", action="store_true",
                      help="CI-sized run: scenario sweep + hot-path benches, tiny configs")
    mode.add_argument("--sched-scale", action="store_true",
                      help="CI-sized benchmarks/sched_scale.py sweep (training "
                           "throughput + seed-parallel engine speedup included)")
    mode.add_argument("--sweep", action="store_true",
                      help="full (non-smoke) scenario sweep over the whole "
                           "registry — the nightly CI lane")
    mode.add_argument("--lifecycle", action="store_true",
                      help="pod-lifecycle / green-consolidation benchmark "
                           "(SDQN vs SDQN-n vs kube on churn scenarios)")
    mode.add_argument("--lifecycle-smoke", action="store_true",
                      help="CI-sized lifecycle benchmark (the sizing "
                           "benchmarks/baseline_lifecycle.json is gated at)")
    mode.add_argument("--pareto", action="store_true",
                      help="full green-Pareto-frontier sweep: kube / TOPSIS / "
                           "SDQN-n across the energy_weight grid on every "
                           "churn scenario — the nightly lane")
    mode.add_argument("--pareto-smoke", action="store_true",
                      help="CI-sized Pareto sweep (the sizing "
                           "benchmarks/baseline_pareto.json is gated at)")
    mode.add_argument("--online-serve", action="store_true",
                      help="online-learning serving benchmark: p99 with the "
                           "refresher on/off (overhead ratio) + served "
                           "avg-CPU gain of the refreshed policy (the sizing "
                           "baseline_online.json is gated at)")
    mode.add_argument("--policy-compare", action="store_true",
                      help="CI-sized policy-class comparison: every "
                           "core.policy registry class vs kube on two "
                           "scenarios + per-class train-step throughput (the "
                           "sizing baseline_policy_compare.json is gated at)")
    mode.add_argument("--placement-serve", action="store_true",
                      help="placement-daemon serving benchmark: decisions/sec "
                           "and p50/p99 latency at several offered rates (the "
                           "sizing baseline_placement_serve.json is gated at)")
    mode.add_argument("--chaos", action="store_true",
                      help="full chaos grid (offered rate x node failures, "
                           "SDQN-with-fallback vs kube) — the nightly lane")
    mode.add_argument("--chaos-smoke", action="store_true",
                      help="CI-sized chaos benchmark (the sizing "
                           "benchmarks/baseline_chaos.json is gated at)")
    mode.add_argument("--fleet-scale", action="store_true",
                      help="two-stage hierarchical sharded scoring sweep over "
                           "the cluster-of-clusters family, 4k -> 128k nodes "
                           "(the sizing baseline_fleet_scale.json is gated at)")
    mode.add_argument("--manifest", metavar="PATH",
                      help="run every suite in a benchmarks/gates.json "
                           "manifest (each as a subprocess, writing "
                           "BENCH_<suite>.json next to the cwd); gate the "
                           "results separately with check_smoke --manifest")
    ap.add_argument("--nightly", action="store_true",
                    help="with --manifest: run the manifest's nightly lane "
                         "instead of the gated smoke suites")
    ap.add_argument("--trials", type=int, default=None,
                    help="episodes per measurement (default: 3, or 1 with --smoke)")
    ap.add_argument("--pods", type=int, default=None,
                    help="override pods per episode (default: scenario's n_pods, "
                         "or 20 with --smoke)")
    ap.add_argument("--train-episodes", type=int, default=None,
                    help="episodes for the mixture-trained SDQN policy "
                         "(default: 120, or 12 with --smoke)")
    ap.add_argument("--json", metavar="PATH", help="also dump rows as JSON")
    args = ap.parse_args()
    for flag in ("trials", "pods", "train_episodes"):
        val = getattr(args, flag)
        if val is not None and val < 1:
            ap.error(f"--{flag.replace('_', '-')} must be >= 1")
    if args.fast and (args.pods is not None or args.train_episodes is not None):
        ap.error("--fast skips the training/scenario benches; "
                 "--pods/--train-episodes have no effect with it")
    if args.nightly and not args.manifest:
        ap.error("--nightly only applies to --manifest runs")

    if args.manifest:
        # the parent stays off JAX: each suite's child owns the device
        raise SystemExit(_run_manifest(args.manifest, nightly=args.nightly))

    from repro.launch import compile_cache

    compile_cache.enable()

    if args.list_scenarios:
        from repro import scenarios

        for name in scenarios.scenario_names():
            scn = scenarios.get_scenario(name)
            classes = "+".join(f"{c.count}x{c.name}" for c in scn.node_classes)
            pods = "/".join(p.name for p in scn.pod_types)
            print(f"{name:18s} nodes=[{classes}] pods=[{pods}] "
                  f"arrival={scn.arrival.kind} n_pods={scn.n_pods}")
        return

    rows = []

    if args.scenario:
        from benchmarks import scenario_bench
        from repro import scenarios

        try:  # validate only the name here: real bench errors must traceback
            scenarios.get_scenario(args.scenario)
        except KeyError as e:
            ap.error(str(e.args[0]) if e.args else str(e))
        rows += scenario_bench.bench_scenario(
            args.scenario, trials=args.trials or 3, n_pods=args.pods,
            train_episodes=args.train_episodes or 120)
    elif args.smoke:
        from benchmarks import scenario_bench, sched_scale

        rows += scenario_bench.smoke_rows(
            trials=args.trials or 1, n_pods=args.pods or 20,
            train_episodes=args.train_episodes or 12)
        rows += sched_scale.afterstate_throughput()
        rows += sched_scale.scoring_throughput()
        rows += sched_scale.fused_scoring()
        rows += sched_scale.eval_engine_speedup(trials=16)
    elif args.sched_scale:
        from benchmarks import sched_scale

        rows += sched_scale.ci_rows()
    elif args.sweep:
        from benchmarks import scenario_bench

        rows += scenario_bench.sweep(
            trials=args.trials or 3, n_pods=args.pods,
            train_episodes=args.train_episodes or 120)
    elif args.lifecycle:
        from benchmarks import lifecycle_bench

        rows += lifecycle_bench.rows(
            trials=args.trials or 3, n_pods=args.pods,
            train_episodes=args.train_episodes or 120)
    elif args.lifecycle_smoke:
        from benchmarks import lifecycle_bench

        rows += lifecycle_bench.smoke_rows()
    elif args.pareto:
        from benchmarks import lifecycle_bench

        rows += lifecycle_bench.pareto_rows(
            trials=args.trials or 3, n_pods=args.pods,
            train_episodes=args.train_episodes or 120)
    elif args.pareto_smoke:
        from benchmarks import lifecycle_bench

        rows += lifecycle_bench.pareto_smoke_rows()
    elif args.online_serve:
        from benchmarks import online_bench

        rows += online_bench.rows()
    elif args.policy_compare:
        from benchmarks import policy_compare

        rows += policy_compare.smoke_rows(
            trials=args.trials or 1, n_pods=args.pods or 20,
            train_episodes=args.train_episodes or 12)
    elif args.placement_serve:
        from benchmarks import placement_serve

        rows += placement_serve.serve_rows()
    elif args.chaos:
        from benchmarks import chaos_bench

        rows += chaos_bench.rows()
    elif args.chaos_smoke:
        from benchmarks import chaos_bench

        rows += chaos_bench.smoke_rows()
    elif args.fleet_scale:
        from benchmarks import fleet_scale

        rows += fleet_scale.rows()
    else:
        from benchmarks import roofline_report, sched_scale

        if not args.fast:
            from benchmarks import paper_tables

            for fn in (paper_tables.table8, paper_tables.table9, paper_tables.table10,
                       paper_tables.table11, paper_tables.table12):
                name, us, derived = fn()
                rows.append((f"paper_{fn.__name__}_{name}", us, derived))
            (fname, us, derived), claims, _ = paper_tables.figure6()
            rows.append((fname, us, derived))
            rows.append(("claims_validated", 0.0,
                         float(sum(claims.values())) / len(claims)))
            name, us, derived = paper_tables.literal_ablation()
            rows.append((name, us, derived))
            rows += paper_tables.scenario_generalization(
                trials=args.trials or 3, n_pods=args.pods,
                train_episodes=args.train_episodes)
            rows += paper_tables.policy_class_table()

        rows += sched_scale.run_all()
        rows += roofline_report.report(mesh="16x16")

    print("\nname,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")

    if args.json:
        _write_json(args.json, rows)


if __name__ == "__main__":
    main()
