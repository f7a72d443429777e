#!/usr/bin/env python3
"""Smoke run of the scheduler's main path on a TPU.

    python3 chip_smoke.py             # one chip: serve, fleet, train, kernels
    python3 chip_smoke.py --chips 4   # four chips: the sharded paths only

One process drives the chip through the library's own entry points, at
sizes Kubernetes users run, with random weights made from a seed:

* serve   -- ``PlacementDaemon`` over ``ClusterSubstrate(fleet_cluster(5000))``
             (5,000 nodes at 110 pods a node: upstream Kubernetes' documented
             large-cluster limits) and over a ``FleetSubstrate`` of 5,000
             hosts, ~2,000 requests each from a seeded arrival trace.  Every
             request must resolve (bound, dropped or shed), no batch may fall
             back to the host heuristic, the batch scorer must compile once,
             and its program must hold the Pallas kernel (``tpu_custom_call``).
* fleet   -- one two-stage sharded decision over ``cluster-of-clusters-128k``
             (131,072 nodes, 8 shards, in-kernel top-k); its winner must equal
             the flat masked argmax.
* train   -- a few episodes of ``train_rl.train`` at 4,096 nodes, where the
             fused afterstate kernel runs inside the training scan; the
             program must hold the kernel, losses and params must be finite.
* kernels -- every SDQN kernel in Pallas at 5,000 and 131,072 nodes against
             the unfused reference run with float32 matmuls, and the top-k
             kernels against their XLA twins (``lax.top_k``).

``--chips 4`` runs only what exists across chips, each beside what it is
compared with: the 131,072-node decision with a 4-device ``FleetLayout``
against the 1-device flat winner, and ``engine.train_seeds`` over
``make_train_mesh(4)`` against ``mesh=None``.

Times printed on the way are one-off smoke readings, not benchmark numbers.
The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a
TPU, or when any phase fails, the script exits non-zero and prints no such
line.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch import compile_cache  # noqa: E402  (needs the checkout)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import scenarios  # noqa: E402
from repro.core import dqn, env as kenv, train_rl  # noqa: E402
from repro.core.types import NO_PLACEMENT, fleet_cluster  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import mesh as meshmod  # noqa: E402
from repro.scenarios import arrival_trace  # noqa: E402
from repro.sched import api, placement  # noqa: E402
from repro.sched.daemon import (  # noqa: E402
    ClusterSubstrate, DaemonConfig, FleetSubstrate, PlacementDaemon,
    replay_trace)
from repro.train import engine  # noqa: E402

SEED = 0
SERVE_NODES = 5000
SERVE_REQUESTS = 2000
SERVE_RATE_PER_S = 1000.0
SERVE_BATCH = 32
FLEET_SCENARIO = "cluster-of-clusters-128k"
FLEET_SHARDS = 8
TRAIN_NODES = 4096
TRAIN_RL = train_rl.RLConfig(episodes=3, pods_per_episode=16, n_envs=8,
                             batch_size=128)
TRAIN_SEEDS = 2            # with 4 devices: a (2 seeds x 2 env shards) grid
KERNEL_SIZES = (5000, 131072)
KERNEL_MODE = "pallas"
KERNEL_MARK = "tpu_custom_call"   # a Mosaic kernel in compiled program text
# The column kernels run the MLP as float32 multiply-adds on the vector unit,
# ``sdqn_score`` as float32 (HIGHEST) matmuls, and the reference as float32
# matmuls (jax.default_matmul_precision).  They differ only by reassociation,
# far inside this tolerance; bfloat16 operands would miss it by ~100x.
RTOL = ATOL = 1e-4
# 4-chip training vs one device: the same program, partitioned; the learner's
# reductions may reassociate across devices.
TRAIN_RTOL, TRAIN_ATOL = 1e-4, 1e-5


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def reading(name: str, **vals) -> None:
    body = ", ".join(f"{k}={v}" for k, v in vals.items())
    print(f"  one-off smoke reading, {name}: {body}", flush=True)


def _timed(fn, *args):
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def _serve(label: str, sub, items, t_s) -> None:
    params = dqn.init_qnet(jax.random.PRNGKey(SEED))
    daemon = PlacementDaemon(
        sub, params, DaemonConfig(batch_size=SERVE_BATCH,
                                  score_deadline_s=None))
    t0 = time.perf_counter()
    daemon.warmup()
    compile_s = time.perf_counter() - t0
    dur = replay_trace(daemon, t_s, items)
    m, n = daemon.metrics, len(items)
    ids = sorted(d.req_id for d in daemon.decisions)
    check(ids == list(range(n)), f"{label}: {len(ids)} decisions for {n} "
          f"requests, or a request decided twice")
    check(m.bound + m.dropped + m.shed == n,
          f"{label}: bound {m.bound} + dropped {m.dropped} + shed {m.shed} "
          f"!= {n} submitted")
    check(m.fallback_batches == 0,
          f"{label}: {m.fallback_batches} batches served by the heuristic")
    check(daemon.scorer_cache_size() == 1,
          f"{label}: scorer compiled {daemon.scorer_cache_size()} times")
    check(KERNEL_MARK in daemon.scorer_text(),
          f"{label}: the batch scorer holds no Pallas kernel")
    reading(f"serve/{label}", warmup_compile_s=round(compile_s, 3),
            offered_per_s=SERVE_RATE_PER_S, served_per_s=round(n / dur, 1),
            p50_ms=round(m.bind_latencies_s.p50() * 1e3, 3),
            p99_ms=round(m.bind_latencies_s.p99() * 1e3, 3),
            bound=m.bound, dropped=m.dropped, shed=m.shed,
            conflicts=m.conflicts, requeued=m.requeued, batches=m.batches)


def phase_serve() -> None:
    cfg = fleet_cluster(SERVE_NODES)
    key = jax.random.PRNGKey(SEED)
    trace = arrival_trace(jax.random.fold_in(key, 1), cfg, SERVE_REQUESTS,
                          rate_per_s=SERVE_RATE_PER_S)
    _serve("cluster", ClusterSubstrate(kenv.reset(key, cfg), cfg),
           trace.pods, trace.t_s)
    rng = np.random.default_rng(SEED)
    jobs = [placement.JobSpec(cpu_pct_demand=float(c), mem_pct_demand=float(m))
            for c, m in zip(rng.uniform(1.0, 8.0, SERVE_REQUESTS),
                            rng.uniform(0.5, 4.0, SERVE_REQUESTS))]
    _serve("fleet", FleetSubstrate(placement.fresh_fleet(SERVE_NODES, key)),
           jobs, trace.t_s)


def _fleet_case():
    cfg = scenarios.make_env(FLEET_SCENARIO)
    key = jax.random.PRNGKey(SEED)
    return (cfg, kenv.reset(key, cfg), kenv.default_pod(cfg),
            dqn.init_qnet(key))


def _decide(label, layout, cfg, state, pod, params) -> int:
    """Winner of one jitted ``api.select`` decision, with compile and
    decision-time readings."""
    select = jax.jit(lambda st: api.select(st, pod, params=params, cfg=cfg,
                                           shard=layout, fused=True))
    choice, first_s = _timed(select, state)
    reps = 20
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(select(state))
    reading(label, nodes=cfg.n_nodes, compile_and_first_s=round(first_s, 3),
            decision_ms=round((time.perf_counter() - t0) / reps * 1e3, 4))
    return int(choice)


def phase_fleet() -> None:
    cfg, state, pod, params = _fleet_case()
    layout = meshmod.plan_fleet_layout(cfg.n_nodes, shards=FLEET_SHARDS)
    got = _decide(f"fleet/{FLEET_SHARDS}-shard", layout, cfg, state, pod,
                  params)
    want = _decide("fleet/flat", False, cfg, state, pod, params)
    check(want != NO_PLACEMENT, "flat decision placed nothing")
    check(got == want, f"sharded winner {got} != flat winner {want}")


def _all_finite(tree) -> bool:
    return all(bool(jnp.all(jnp.isfinite(x))) for x in jax.tree.leaves(tree))


def phase_train() -> None:
    cfg = fleet_cluster(TRAIN_NODES)
    rl = TRAIN_RL
    key = jax.random.PRNGKey(SEED)
    t0 = time.perf_counter()
    compiled = train_rl.train_jit.lower(key, cfg, rl).compile()
    compile_s = time.perf_counter() - t0
    check(KERNEL_MARK in compiled.as_text(),
          "the training program holds no Pallas kernel")
    (params, metrics), run_s = _timed(compiled, key)
    check(_all_finite(params), "trained params are not finite")
    check(_all_finite(metrics["loss"]), "training losses are not finite")
    steps = rl.episodes * rl.pods_per_episode * rl.n_envs
    reading("train", nodes=TRAIN_NODES, compile_s=round(compile_s, 3),
            run_s=round(run_s, 3), transitions_per_s=round(steps / run_s, 1),
            last_loss=float(metrics["loss"][-1]))


def _close(name, got, want) -> None:
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=RTOL,
                               atol=ATOL, err_msg=name)


def _topk_agrees(name, vals, idx, q_ref, other_idx) -> int:
    """Candidates ``(vals, idx)`` against the masked reference scores
    ``q_ref``: each value is its node's reference score, the values are the
    reference's top-k, and the indices equal ``other_idx`` wherever the
    reference has no near-tie.  Returns the near-tied positions."""
    vals, idx = np.asarray(vals), np.asarray(idx)
    q_ref, other_idx = np.asarray(q_ref), np.asarray(other_idx)
    k = vals.shape[0]
    top = np.sort(q_ref)[::-1][:k]
    _close(f"{name} values", vals, top)
    _close(f"{name} scores at its indices", vals, q_ref[idx])
    check(len(set(idx.tolist())) == k, f"{name}: repeated candidate")
    tied = np.array([np.sum(np.abs(q_ref - v) <= ATOL + RTOL * abs(v)) > 1
                     for v in top])
    check(np.array_equal(idx[~tied], other_idx[~tied]),
          f"{name}: indices {idx} != {other_idx}")
    return int(tied.sum())


def phase_kernels() -> None:
    key = jax.random.PRNGKey(SEED)
    f32 = jax.default_matmul_precision("float32")
    for n in KERNEL_SIZES:
        cfg = dataclasses.replace(fleet_cluster(n), unhealthy_prob=0.2,
                                  randomize_workload=True)
        state = kenv.reset(jax.random.fold_in(key, n), cfg)
        pod = kenv.default_pod(cfg)
        params = dqn.init_qnet(jax.random.fold_in(key, 1))
        ok = np.asarray(kenv.feasible(state, pod, cfg))

        with f32:
            q_ref = ops.sdqn_score_afterstate(state, pod, cfg, params,
                                              mode="ref")
        _close(f"afterstate n={n}", ops.sdqn_score_afterstate(
            state, pod, cfg, params, mode=KERNEL_MODE), q_ref)
        vals, idx = ops.sdqn_topk_afterstate(state, pod, cfg, params,
                                             mode=KERNEL_MODE)
        _, twin_idx = ops.sdqn_topk_afterstate(state, pod, cfg, params,
                                               mode="xla")
        tied = _topk_agrees(f"afterstate_topk n={n}", vals, idx,
                            np.where(ok, q_ref, -np.inf), twin_idx)

        fleet = placement.fresh_fleet(n, jax.random.fold_in(key, 2))
        ku, kh = jax.random.split(jax.random.fold_in(key, 3))
        fleet = fleet._replace(
            cpu_pct=jax.random.uniform(ku, (n,), maxval=95.0),
            healthy=(jax.random.uniform(kh, (n,)) > 0.1).astype(jnp.float32))
        job = placement.JobSpec(cpu_pct_demand=6.0, mem_pct_demand=3.0)
        cols, delta = placement.fleet_cols(fleet), placement.job_delta(job)
        with f32:
            q_cols = ops.sdqn_score_delta(cols, delta, params, mode="ref")
        _close(f"cols n={n}", ops.sdqn_score_delta(
            cols, delta, params, mode=KERNEL_MODE), q_cols)
        fok = np.asarray(placement.PlacementEngine(params).feasible(fleet,
                                                                    job))
        vals, idx = ops.sdqn_topk_delta(cols, delta, params, mode=KERNEL_MODE)
        _, twin_idx = ops.sdqn_topk_delta(cols, delta, params, mode="xla")
        tied += _topk_agrees(f"cols_topk n={n}", vals, idx,
                             np.where(fok, q_cols, -np.inf), twin_idx)

        feats = jax.random.normal(jax.random.fold_in(key, 4), (n, 6))
        with f32:
            want = ops.sdqn_score(feats, params, mode="ref")
        _close(f"score n={n}", ops.sdqn_score(feats, params, mode=KERNEL_MODE),
               want)
        reading(f"kernels n={n}", checked=5, near_tied_topk_positions=tied,
                rtol=RTOL, atol=ATOL)


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def phase_fleet_mesh(devices) -> None:
    cfg, state, pod, params = _fleet_case()
    mesh = jax.sharding.Mesh(np.array(devices), ("data",))
    layout = meshmod.plan_fleet_layout(cfg.n_nodes, mesh)
    check(layout is not None and layout.mesh is not None
          and layout.shards == len(devices),
          f"no {len(devices)}-device fleet layout: {layout}")
    got = _decide(f"fleet/{len(devices)}-device", layout, cfg, state, pod,
                  params)
    want = _decide("fleet/flat 1-device", False, cfg, state, pod, params)
    check(want != NO_PLACEMENT, "flat decision placed nothing")
    check(got == want, f"{len(devices)}-device winner {got} != flat {want}")


def phase_train_mesh(devices) -> None:
    cfg, rl = fleet_cluster(TRAIN_NODES), TRAIN_RL
    mesh = meshmod.make_train_mesh(len(devices))
    layout = meshmod.plan_seed_env_layout(TRAIN_SEEDS, rl.n_envs, mesh)
    check(layout is not None, "no seed x env layout for the mesh")
    key = jax.random.PRNGKey(SEED)
    (got, got_m), mesh_s = _timed(
        lambda k: engine.train_seeds(k, cfg, rl, TRAIN_SEEDS, mesh=mesh), key)
    (want, want_m), one_s = _timed(
        lambda k: engine.train_seeds(k, cfg, rl, TRAIN_SEEDS), key)
    check(_all_finite(got) and _all_finite(got_m["loss"]),
          "mesh-trained params or losses are not finite")
    diff = max(float(jnp.max(jnp.abs(got[k] - want[k]))) for k in want)
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=TRAIN_RTOL,
                                   atol=TRAIN_ATOL, err_msg=name)
    np.testing.assert_allclose(np.asarray(got_m["loss"]),
                               np.asarray(want_m["loss"]), rtol=TRAIN_RTOL,
                               atol=TRAIN_ATOL, err_msg="loss")
    reading("train_seeds", nodes=TRAIN_NODES, seeds=TRAIN_SEEDS,
            layout=f"{layout.seed_shards}x{layout.env_shards}",
            mesh_compile_and_run_s=round(mesh_s, 3),
            one_device_compile_and_run_s=round(one_s, 3),
            max_abs_param_diff=diff)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip (default); 4: only "
                         "the sharded fleet decision and training layouts")
    args = ap.parse_args(argv)
    cache_dir = compile_cache.enable()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache_dir}", flush=True)
    if args.chips == 1:
        phases = [("serve", phase_serve), ("fleet", phase_fleet),
                  ("train", phase_train), ("kernels", phase_kernels)]
    else:
        used = devices[:args.chips]
        phases = [("fleet-mesh", lambda: phase_fleet_mesh(used)),
                  ("train-mesh", lambda: phase_train_mesh(used))]
    failed = []
    t_all = time.perf_counter()
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            print(f"[{name}] FAILED", file=sys.stderr, flush=True)
        else:
            print(f"[{name}] ok in {time.perf_counter() - t0:.1f} s", flush=True)
    print(f"phases wall time: {time.perf_counter() - t_all:.1f} s", flush=True)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
