"""Fleet-scale scheduler benchmarks (beyond-paper: 1000+ nodes).

1. SDQN scoring throughput vs fleet size (the scheduler's hot loop) —
   XLA path vs the fused Pallas kernel in interpret mode (CPU container;
   on TPU the compiled kernel path is selected automatically).
2. Afterstate feature construction: the O(N) incremental scorer vs the
   vmap-of-place reference (O(N^2)) it replaced.
3. Fused afterstate *scoring*: features + Q-net in one pass
   (``ops.sdqn_score_afterstate``) vs the unfused
   ``hypothetical_place`` -> normalize -> ``qvalues`` chain.
4. Batched evaluation engine: 64 vmapped trials in one launch vs the
   per-trial Python dispatch loop it replaced.
5. End-to-end placement throughput (pods/s) on 1024-node clusters,
   homogeneous and heterogeneous (fleet-hetero scenario).
6. On-device RL training throughput (Anakin-style, transitions/s).
7. Seed-parallel training: `train_and_select`'s candidates as ONE vmapped,
   mesh-sharded launch vs the sequential Python seed loop it replaced.
   Runs in a child process pinned to the CPU (``JAX_PLATFORMS=cpu``) with
   the host platform split into ``min(cpu_count, n_seeds)`` devices, so the
   engine's seed-axis sharding is exercised on CPU virtual devices; rows
   are prefixed ``cpu_``.
8. Joint seed×env sharding: the 2-D ``("seed", "data")`` layout vs pure
   seed sharding at ``n_seeds < n_devices`` (a force-split 4-device CPU
   host, where seed-only sharding's ceiling is 2 busy devices at n_seeds=2
   and the joint planner runs a (2, 2) grid over all 4); ``cpu_`` rows.
9. Replay marginal cost: the fused-ring add + one-gather sample exactly as
   the training loop drives them — the residual per-seed cost the
   struct-of-arrays rework targets.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import List, Tuple

import jax
import jax.numpy as jnp

from repro.core import dqn, env as kenv, schedulers, train_rl
from repro.core.types import fleet_cluster, paper_cluster, training_cluster
from repro.eval import engine as eval_engine
from repro.kernels import ops
from repro.scenarios import make_env


def _time(fn, *args, iters=20, warmup=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters


def scoring_throughput() -> List[Tuple[str, float, float]]:
    rows = []
    params = dqn.init_qnet(jax.random.PRNGKey(0))
    score = jax.jit(lambda f: dqn.qvalues(params, f))
    for n in (1024, 16384, 131072):
        feats = jax.random.normal(jax.random.PRNGKey(1), (n, 6))
        dt = _time(score, feats)
        rows.append((f"sdqn_score_xla_n{n}", dt * 1e6, n / dt))
    return rows


def afterstate_throughput() -> List[Tuple[str, float, float]]:
    """The scoring hot path: O(N) incremental afterstates vs vmap reference.

    ``derived`` is nodes scored per second for the timed rows and the
    measured speedup for the summary rows.  The reference materializes N
    full cluster states per call, so it is only timed up to 2048 nodes.
    """
    rows = []
    pod = kenv.default_pod(fleet_cluster(4))
    fast_times = {}
    for n in (1024, 4096, 16384):
        cfg = fleet_cluster(n)
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        fast = jax.jit(lambda s, _cfg=cfg: kenv.hypothetical_place(s, pod, _cfg))
        dt = _time(fast, state)
        fast_times[n] = dt
        rows.append((f"afterstate_incremental_n{n}", dt * 1e6, n / dt))
    for n in (1024, 2048):
        cfg = fleet_cluster(n)
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        ref = jax.jit(lambda s, _cfg=cfg: kenv.hypothetical_place_reference(s, pod, _cfg))
        dt_ref = _time(ref, state, iters=5, warmup=2)
        rows.append((f"afterstate_vmap_ref_n{n}", dt_ref * 1e6, n / dt_ref))
        dt_fast = fast_times.get(n) or _time(
            jax.jit(lambda s, _cfg=cfg: kenv.hypothetical_place(s, pod, _cfg)), state)
        rows.append((f"afterstate_speedup_n{n}", 0.0, dt_ref / dt_fast))
    return rows


def fused_scoring() -> List[Tuple[str, float, float]]:
    """Fused in-kernel afterstate scoring vs the unfused jnp chain.

    The unfused baseline is ``schedulers.score_afterstates``'s small-N path
    (``hypothetical_place`` -> normalize -> ``qvalues``), jitted as one
    program; the fused path computes the features inside the scorer
    (Pallas on TPU, the fused-XLA twin on CPU — the interpret-safe
    fallback) without materializing the (N, 6) matrix.  ``derived`` is
    nodes/s for timed rows and measured speedup for summary rows.
    """
    rows = []
    params = dqn.init_qnet(jax.random.PRNGKey(0))
    for n in (4096, 16384, 131072):
        cfg = fleet_cluster(n)
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        pod = kenv.default_pod(cfg)
        unfused = jax.jit(lambda s, _cfg=cfg: ops.sdqn_score_afterstate(
            s, pod, _cfg, params, mode="ref"))
        fused = jax.jit(lambda s, _cfg=cfg: ops.sdqn_score_afterstate(
            s, pod, _cfg, params))
        dt_un = _time(unfused, state)
        dt_fu = _time(fused, state)
        rows.append((f"afterscore_unfused_n{n}", dt_un * 1e6, n / dt_un))
        rows.append((f"afterscore_fused_n{n}", dt_fu * 1e6, n / dt_fu))
        rows.append((f"afterscore_fused_speedup_n{n}", 0.0, dt_un / dt_fu))
    return rows


def eval_engine_speedup(trials: int = 64) -> List[Tuple[str, float, float]]:
    """Batched evaluation engine vs the per-trial Python dispatch loop.

    Same episodes (identical trial keys), same jitted episode body; the only
    difference is one vmapped launch vs ``trials`` sequential dispatches.
    ``derived`` is episodes/s for the timed rows, speedup for the summary.
    """
    cfg = paper_cluster()
    sel = schedulers.make_kube_selector(cfg)
    n_pods = 50
    keys = eval_engine.trial_keys(jax.random.PRNGKey(0), trials)

    loop_ep = jax.jit(lambda kk: kenv.run_episode(kk, cfg, sel, n_pods).metric)

    def loop(keys):
        return [loop_ep(keys[t]) for t in range(trials)]

    batch = eval_engine.make_batch_episode(cfg, sel, n_pods)
    dt_loop = _time(loop, keys, iters=3, warmup=1)
    dt_batch = _time(batch, keys, iters=3, warmup=1)
    return [
        (f"eval_loop_{trials}trials", dt_loop * 1e6, trials / dt_loop),
        (f"eval_batched_{trials}trials", dt_batch * 1e6, trials / dt_batch),
        (f"eval_engine_speedup_{trials}trials", 0.0, dt_loop / dt_batch),
    ]


def placement_throughput() -> List[Tuple[str, float, float]]:
    rows = []
    cfg = fleet_cluster(1024)
    qp = dqn.init_qnet(jax.random.PRNGKey(0))
    sel = schedulers.make_sdqn_selector(qp, cfg)
    n_pods = 200
    ep = jax.jit(lambda kk: kenv.run_episode(kk, cfg, sel, n_pods).metric)
    dt = _time(ep, jax.random.PRNGKey(0), iters=3, warmup=1)
    rows.append(("sdqn_place_1024node_ep", dt * 1e6, n_pods / dt))

    # heterogeneous 1024-node pool with a mixed Poisson stream
    hcfg = make_env("fleet-hetero")
    hsel = schedulers.make_sdqn_selector(qp, hcfg)
    hn = hcfg.scenario.n_pods
    hep = jax.jit(lambda kk: kenv.run_episode(kk, hcfg, hsel, hn).metric)
    dt = _time(hep, jax.random.PRNGKey(0), iters=3, warmup=1)
    rows.append(("sdqn_place_fleet_hetero_ep", dt * 1e6, hn / dt))
    return rows


def training_throughput(smoke: bool = False) -> List[Tuple[str, float, float]]:
    """On-device RL training transitions/s.  ``smoke`` shrinks the episode
    budget for CI; the row name stays ``sdqn_train_ondevice`` because
    ``check_smoke`` gates its ``derived`` column against the committed
    ``benchmarks/baseline_sched_scale.json``."""
    tcfg = training_cluster()
    rl = train_rl.RLConfig(variant="sdqn", episodes=10 if smoke else 50,
                           n_envs=16, batch_size=256)
    fn = jax.jit(lambda k: train_rl.train(k, tcfg, rl)[1]["loss"][-1])
    dt = _time(fn, jax.random.PRNGKey(0), iters=2, warmup=1)
    transitions = rl.episodes * rl.pods_per_episode * rl.n_envs
    return [("sdqn_train_ondevice", dt * 1e6, transitions / dt)]


def _cpu_child_env(devices: int) -> dict:
    """Environment of a measurement child: pinned to the CPU, split into
    ``devices`` virtual devices.  The parent has already touched JAX, so on
    an accelerator host it holds the device and a child could not get it;
    these children measure CPU layouts by design."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={devices}").strip()
    return env


def _pick_seed_devices(n_seeds: int, cpus: int) -> int:
    """Largest divisor of ``n_seeds`` that fits the core count (the seed
    axis shards evenly or not at all)."""
    for d in range(min(n_seeds, max(cpus, 1)), 0, -1):
        if n_seeds % d == 0:
            return d
    return 1


def _seed_parallel_measurements(n_seeds: int, episodes: int) -> List[Tuple[str, float, float]]:
    """Measure sequential-vs-engine in THIS process (child of
    ``seed_parallel_speedup``, which forces the multi-device host platform).
    """
    from repro.launch import mesh as meshmod
    from repro.train import engine

    tcfg = training_cluster()
    rl = train_rl.RLConfig(variant="sdqn", episodes=episodes, n_envs=16,
                           batch_size=256)
    key = jax.random.PRNGKey(0)
    # the pre-engine train_and_select loop: jit once, dispatch per seed.
    # Return (params, metrics) whole — indexing [0] inside the jit would let
    # XLA dead-code-eliminate the per-episode metrics the engine computes,
    # skewing the comparison in the baseline's favor.
    train_fn = jax.jit(lambda k: train_rl.train(k, tcfg, rl))

    def sequential(k):
        return [train_fn(jax.random.fold_in(k, s)) for s in range(n_seeds)]

    n_dev = len(jax.devices())
    mesh = meshmod.make_train_mesh(n_dev) if n_dev > 1 else None

    def parallel(k):
        return engine.train_seeds(k, tcfg, rl, n_seeds, mesh=mesh)

    dt_seq = _time(sequential, key, iters=3, warmup=1)
    dt_par = _time(parallel, key, iters=3, warmup=1)
    per_seed = rl.episodes * rl.pods_per_episode * rl.n_envs
    return [
        (f"cpu_seed_sequential_s{n_seeds}", dt_seq * 1e6,
         n_seeds * per_seed / dt_seq),
        (f"cpu_seed_parallel_s{n_seeds}_d{n_dev}", dt_par * 1e6,
         n_seeds * per_seed / dt_par),
        ("cpu_seed_parallel_speedup", 0.0, dt_seq / dt_par),
    ]


def seed_parallel_speedup(n_seeds: int = 4, episodes: int = 20) -> List[Tuple[str, float, float]]:
    """Seed-parallel training engine vs the sequential Python seed loop.

    Spawns a child with ``--xla_force_host_platform_device_count`` set to a
    divisor of ``n_seeds`` that fits the machine, so the engine's seed-axis
    ``data`` sharding actually executes in parallel (the flag only takes
    effect before jax initializes, hence the subprocess).  The ceiling is
    ``min(cpu_count, n_seeds) x`` the vmap amortization; a 2-core container
    tops out near 2x while a >=4-device training cluster reaches the full
    n_seeds multiple.
    """
    devices = _pick_seed_devices(n_seeds, os.cpu_count() or 1)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sched_scale",
         "--seed-parallel-child", str(n_seeds), str(episodes)],
        env=_cpu_child_env(devices), capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(
            f"seed-parallel child failed ({out.returncode}):\n{out.stderr}")
    return [tuple(r) for r in json.loads(out.stdout.strip().splitlines()[-1])]


def _joint_sharding_measurements(n_seeds: int, episodes: int) -> List[Tuple[str, float, float]]:
    """Measure seed-only vs joint seed×env sharding in THIS process (child of
    ``joint_sharding_speedup``, which forces a 4-device host platform).

    Seed-only sharding at ``n_seeds=2`` can occupy at most 2 devices however
    many exist (its ceiling: one whole replica per device); the joint layout
    splits the remaining factor across the env axis — here a (2, 2) grid
    over all 4.  Both run through ``engine.train_seeds``; only the mesh
    handed to the planner differs.

    The workload is a 256-node fleet, not the 4-node paper cluster: env-axis
    sharding splits the per-step environment work (O(N) afterstate scoring,
    feature stacks) but pays fixed per-step partition/collective overhead
    (the replay add all-gathers one (n_envs, 8) row into the replicated
    ring, and every env-batched op forks across devices), so it is only
    profitable when the sharded env work dominates the replicated learner —
    on the 4-node cluster the overhead measures ~7x *slower*, at 256 nodes
    env stepping dominates and the layout wins.  That threshold is a
    property of the program, not the host: callers should hand
    ``train_seeds`` a multi-device mesh for fleet-scale configs and leave
    ``mesh=None`` for toy ones.
    """
    from repro.launch import mesh as meshmod
    from repro.train import engine

    tcfg = fleet_cluster(256)
    rl = train_rl.RLConfig(variant="sdqn", episodes=episodes, n_envs=16,
                           batch_size=256)
    key = jax.random.PRNGKey(0)
    n_dev = len(jax.devices())
    n_seed_dev = min(n_seeds, n_dev)

    def seed_only(k):
        return engine.train_seeds(k, tcfg, rl, n_seeds,
                                  mesh=meshmod.make_train_mesh(n_seed_dev))

    def joint(k):
        return engine.train_seeds(k, tcfg, rl, n_seeds,
                                  mesh=meshmod.make_train_mesh(n_dev))

    dt_seed = _time(seed_only, key, iters=3, warmup=1)
    dt_joint = _time(joint, key, iters=3, warmup=1)
    per_seed = rl.episodes * rl.pods_per_episode * rl.n_envs
    return [
        (f"cpu_seedonly_s{n_seeds}_d{n_seed_dev}", dt_seed * 1e6,
         n_seeds * per_seed / dt_seed),
        (f"cpu_joint_s{n_seeds}_d{n_dev}", dt_joint * 1e6,
         n_seeds * per_seed / dt_joint),
        ("cpu_joint_sharding_speedup", 0.0, dt_seed / dt_joint),
    ]


def joint_sharding_speedup(n_seeds: int = 2, episodes: int = 20,
                           devices: int = 4) -> List[Tuple[str, float, float]]:
    """Joint seed×env layout vs pure seed sharding on a force-split host.

    Spawns a child with ``--xla_force_host_platform_device_count=4``
    regardless of the physical core count: the *layout* question is how many
    devices the program keeps busy, and forcing 4 exposes it on any host.
    The measured speedup only materializes with >= 4 physical cores backing
    the 4 devices (CI runners; any real multi-core/TPU host) — on a 2-core
    container both programs time-share the same 2 cores and the ratio sits
    near 1x, which is why the committed gate floor is conservative.
    """
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sched_scale",
         "--joint-sharding-child", str(n_seeds), str(episodes)],
        env=_cpu_child_env(devices), capture_output=True, text=True)
    if out.returncode != 0:
        raise RuntimeError(
            f"joint-sharding child failed ({out.returncode}):\n{out.stderr}")
    return [tuple(r) for r in json.loads(out.stdout.strip().splitlines()[-1])]


def replay_marginal_cost(lane: int = 16, batch: int = 256, steps: int = 512,
                         cap: int = 4096) -> List[Tuple[str, float, float]]:
    """The replay slice of the training step, exactly as the loop drives it:
    one lane-wide ``replay_add`` + one ``replay_sample`` per scanned step.

    This is the residual per-seed marginal cost the fused ring targets (one
    contiguous slot write + one gather per step, vs three scatters + three
    gathers in the per-column layout).  ``derived`` is stored transitions/s.
    """
    from repro.core.replay import replay_add, replay_init, replay_sample

    key = jax.random.PRNGKey(0)

    def run(k):
        def step(buf, t):
            tf = t.astype(jnp.float32)
            feats = jnp.broadcast_to(tf, (lane, 6))
            targets = jnp.broadcast_to(tf, (lane,))
            weights = (jnp.arange(lane) % 7 != 0).astype(jnp.float32)
            buf = replay_add(buf, feats, targets, weights)
            f, tg, w = replay_sample(buf, jax.random.fold_in(k, t), batch)
            return buf, f.sum() + tg.sum() + w.sum()
        _, acc = jax.lax.scan(step, replay_init(cap, lane=lane),
                              jnp.arange(steps))
        return acc.sum()

    dt = _time(jax.jit(run), key, iters=5, warmup=2)
    return [("replay_marginal_cost", dt * 1e6, steps * lane / dt)]


def ci_rows() -> List[Tuple[str, float, float]]:
    """The CI-sized sweep behind ``benchmarks.run --sched-scale``: only the
    training rows (the hot-path benches already run — and are archived — in
    the ``--smoke`` job; re-timing the 131072-node sweeps per push would buy
    nothing but wall-clock)."""
    return (training_throughput(smoke=True) + seed_parallel_speedup(episodes=10)
            + joint_sharding_speedup(episodes=10) + replay_marginal_cost())


def run_all() -> List[Tuple[str, float, float]]:
    out = []
    out += scoring_throughput()
    out += afterstate_throughput()
    out += fused_scoring()
    out += eval_engine_speedup()
    out += placement_throughput()
    out += training_throughput()
    out += seed_parallel_speedup()
    out += joint_sharding_speedup()
    out += replay_marginal_cost()
    return out


if __name__ == "__main__":
    if len(sys.argv) >= 2 and sys.argv[1] == "--seed-parallel-child":
        child_rows = _seed_parallel_measurements(int(sys.argv[2]), int(sys.argv[3]))
        print(json.dumps(child_rows))
    elif len(sys.argv) >= 2 and sys.argv[1] == "--joint-sharding-child":
        child_rows = _joint_sharding_measurements(int(sys.argv[2]), int(sys.argv[3]))
        print(json.dumps(child_rows))
    else:
        for name, us, derived in run_all():
            print(f"{name},{us:.1f},{derived}")
