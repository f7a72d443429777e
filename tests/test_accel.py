"""Accelerators as a Kubernetes extended resource.

An integer count a node (``ClusterState.accel_capacity`` /
``accel_requested``) and a pod (``PodSpec.accel_request``), never
overcommitted: every filter of a ``ClusterState`` gains ``accel_requested +
accel_request <= accel_capacity`` and every bind and unbind counts it --
``env.feasible`` / ``place`` / ``remove_pod``, the ledger's retirements and
evictions, the top-k kernel and its XLA twin, and the daemon's substrate.
A state without the columns has the same leaves, and so the same programs,
as before they existed.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import config as jax_config

from repro.core import dqn, env as kenv, schedulers
from repro.core.types import (
    NO_PLACEMENT,
    NodeClass,
    PodSpec,
    PodType,
    ScenarioConfig,
    fleet_cluster,
    scenario_env,
)
from repro.kernels import ops
from repro.launch.mesh import plan_fleet_layout
from repro.sched.daemon import (
    DELTA_ROWS,
    ClusterSubstrate,
    DaemonConfig,
    PlacementDaemon,
)

GiB = 1024.0
PARAMS = dqn.init_qnet(jax.random.PRNGKey(0))


def _gpu_pod_types(lifetime=float("inf"), cv=0.0):
    """1, 2, 4 and 8 accelerators, 20 vCPU and 200 GiB an accelerator."""
    return tuple(
        PodType(f"gpu{a}", w, 20000.0 * a, 12000.0 * a, 200 * GiB * a,
                160 * GiB * a, lifetime_mean_s=lifetime, lifetime_cv=cv,
                accel_request=a)
        for a, w in ((1, 0.5), (2, 0.1), (4, 0.1), (8, 0.3)))


def _gpu_cfg(n=16, lifetime=float("inf"), cv=0.0, mtbf=float("inf"),
             **overrides):
    node = NodeClass("p5", n, cpu_capacity=192000.0,
                     mem_capacity=2048 * GiB, accel_capacity=8,
                     base_cpu_frac=(0.01, 0.04), requested_frac=(0.01, 0.05),
                     mtbf_s=mtbf, mttr_s=30.0)
    scn = ScenarioConfig("tiny-gpu", (node,), _gpu_pod_types(lifetime, cv))
    return scenario_env(scn, **overrides)


def _gpu_pod(a):
    return PodSpec(jnp.float32(20000.0 * a), jnp.float32(12000.0 * a),
                   jnp.float32(200 * GiB * a), jnp.float32(160 * GiB * a),
                   jnp.int32(a))


def _gpu_state(n=16, seed=0, booked=None):
    cfg = _gpu_cfg(n)
    state = kenv.reset(jax.random.PRNGKey(seed), cfg)
    if booked is not None:
        state = state._replace(accel_requested=jnp.asarray(booked, jnp.int32))
    return cfg, state


# -- the environment ---------------------------------------------------------

def test_reset_builds_the_columns_only_where_the_scenario_has_accelerators():
    cfg, state = _gpu_state(8)
    assert state.accel_capacity.dtype == jnp.int32
    assert state.accel_capacity.tolist() == [8] * 8
    assert state.accel_requested.tolist() == [0] * 8
    table = kenv.sample_pod_table(jax.random.PRNGKey(1), cfg, 64)
    assert table.specs.accel_request.dtype == jnp.int32
    assert set(np.asarray(table.specs.accel_request).tolist()) <= {1, 2, 4, 8}
    cpu = kenv.reset(jax.random.PRNGKey(0), fleet_cluster(8))
    assert cpu.accel_capacity is None and cpu.accel_requested is None
    assert len(jax.tree.leaves(cpu)) == 15
    assert kenv.default_pod(cfg).accel_request is None


def test_feasible_place_and_remove_pod_count_accelerators():
    cfg, state = _gpu_state(4, booked=[0, 4, 7, 8])
    ok = {a: np.asarray(kenv.feasible(state, _gpu_pod(a), cfg)).tolist()
          for a in (1, 4, 8)}
    assert ok == {1: [True, True, True, False],
                  4: [True, True, False, False],
                  8: [True, False, False, False]}
    placed = kenv.place(state, jnp.int32(1), _gpu_pod(4), cfg)
    assert placed.accel_requested.tolist() == [0, 8, 7, 8]
    assert not bool(kenv.feasible(placed, _gpu_pod(1), cfg)[1])
    dropped = kenv.place(state, jnp.int32(NO_PLACEMENT), _gpu_pod(8), cfg)
    assert dropped.accel_requested.tolist() == [0, 4, 7, 8]
    back = kenv.remove_pod(placed, jnp.int32(1), _gpu_pod(4))
    assert back.accel_requested.tolist() == [0, 4, 7, 8]
    two = kenv.remove_pod(state, jnp.int32(3), _gpu_pod(2), count=2)
    assert two.accel_requested.tolist() == [0, 4, 7, 4]
    # a pod without the field asks for none: only the other predicates
    plain = kenv.default_pod(cfg)
    assert np.asarray(kenv.feasible(state, plain, cfg)).all()
    assert kenv.place(state, jnp.int32(3), plain, cfg
                      ).accel_requested.tolist() == [0, 4, 7, 8]


def test_asking_for_accelerators_without_the_column_raises():
    cfg = fleet_cluster(4)
    state = kenv.reset(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ValueError, match="no accelerator column"):
        kenv.feasible(state, _gpu_pod(1), cfg)
    with pytest.raises(ValueError, match="no accelerator column"):
        kenv.place(state, jnp.int32(0), _gpu_pod(1), cfg)
    with pytest.raises(ValueError, match="no accelerator column"):
        ops.sdqn_topk_afterstate(state, _gpu_pod(1), cfg, PARAMS, mode="xla")
    d = PlacementDaemon(ClusterSubstrate(state, cfg), PARAMS, DaemonConfig())
    pod = PodSpec(140.0, 20.0, 128.0, 100.0, 2)
    with pytest.raises(ValueError, match="requests 2 accelerators"):
        d.submit(pod)
    assert d.pending == 0 and d.metrics.submitted == 0
    d.submit(pod._replace(accel_request=0))     # asks for none: admitted
    assert d.pending == 1


@pytest.mark.parametrize("chaos", [False, True])
def test_episode_never_overcommits_and_releases_exactly(chaos):
    """Pods that retire (and, with ``chaos``, are evicted off failed nodes
    and re-placed) hand back exactly what they booked: with every pod
    retired by the episode's end the accelerators read 0 again, and no
    state a selection sees is overcommitted."""
    cfg = _gpu_cfg(6, lifetime=20.0, cv=0.5,
                   mtbf=60.0 if chaos else float("inf"), settle_steps=150)
    seen = []
    kube = schedulers.make_kube_selector(cfg)

    def select(key, state, pod):
        jax.debug.callback(lambda r, c: seen.append((np.array(r),
                                                     np.array(c))),
                           state.accel_requested, state.accel_capacity)
        return kube(key, state, pod)

    res = jax.jit(lambda k: kenv.run_episode(k, cfg, select, 40))(
        jax.random.PRNGKey(3))
    jax.effects_barrier()
    assert len(seen) >= 40
    assert all(((r >= 0) & (r <= c)).all() for r, c in seen)
    assert max(int(r.sum()) for r, _ in seen) > 8   # the pods did book
    assert int(res.stats.retired) > 0
    if chaos:
        assert int(res.stats.evicted) > 0
    assert np.asarray(res.state.accel_requested).tolist() == [0] * 6
    assert int(res.state.num_pods.sum()) == int(
        kenv.reset(jax.random.split(jax.random.PRNGKey(3), 3)[0],
                   cfg).num_pods.sum())


# -- the top-k kernel ----------------------------------------------------------

@pytest.mark.parametrize("n", [5, 1024, 3000, 12500, 40000])
@pytest.mark.parametrize("accel", [1, 4, 8])
def test_topk_kernel_filters_accelerators_like_its_twin_and_numpy(n, accel):
    cfg, state = _gpu_state(n, seed=n)
    booked = jax.random.randint(jax.random.PRNGKey(n + accel), (n,), 0, 9)
    state = state._replace(
        accel_requested=booked.astype(jnp.int32).at[0].set(8))
    pod = _gpu_pod(accel)
    k = 4
    vals, idx = ops.sdqn_topk_afterstate(state, pod, cfg, PARAMS, k=k,
                                         mode="interpret")
    tvals, tidx = ops.sdqn_topk_afterstate(state, pod, cfg, PARAMS, k=k,
                                           mode="xla")
    rvals, ridx = ops.sdqn_topk_afterstate(state, pod, cfg, PARAMS, k=k,
                                           mode="ref")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(tidx))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(tvals),
                               rtol=1e-5, atol=1e-5)
    s = {f: np.asarray(v) for f, v in state._asdict().items()
         if v is not None and np.ndim(v)}
    ok = (s["healthy"]
          & (s["cpu_requested"] + np.float32(20000.0 * accel)
             <= s["cpu_capacity"])
          & (s["mem_requested"] + np.float32(200 * GiB * accel)
             <= s["mem_capacity"])
          & (s["num_pods"] < s["max_pods"])
          & (s["accel_requested"] + accel <= s["accel_capacity"]))
    fin = np.isfinite(np.asarray(vals))
    assert fin.sum() == min(k, int(ok.sum()))
    assert ok[np.asarray(idx)[fin]].all()
    assert ok.sum() < n          # the accelerators did turn nodes away


# -- the daemon's substrate --------------------------------------------------

def _leaves_close(a, b):
    for name, x, y in zip(a._fields, a, b):
        assert (x is None) == (y is None), name
        if x is not None:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-5, err_msg=name)


def test_substrate_mirrors_env_with_accelerators():
    cfg, state = _gpu_state(6, booked=[0, 1, 4, 7, 8, 2])
    sub = ClusterSubstrate(state, cfg)
    for a in (1, 2, 4, 8):
        want = np.asarray(kenv.feasible(state, _gpu_pod(a), cfg))
        got = [sub.feasible_one(i, _gpu_pod(a)) for i in range(6)]
        assert got == want.tolist(), a
    ref = jax.tree.map(jnp.asarray, sub.live)
    for node, a in ((0, 8), (1, 4), (5, 2), (1, 1)):
        ref = kenv.place(ref, jnp.int32(node), _gpu_pod(a), cfg)
        sub.bind(node, _gpu_pod(a))
        _leaves_close(ref, sub.live)
    for node, a in ((1, 4), (0, 8)):
        ref = kenv.remove_pod(ref, jnp.int32(node), _gpu_pod(a))
        sub.unbind(node, _gpu_pod(a))
        _leaves_close(ref, sub.live)
    assert sub.live.accel_requested.tolist() == [0, 2, 4, 7, 8, 4]
    q, ok = sub.heuristic_batch([_gpu_pod(8), _gpu_pod(1)])
    assert ok[0].tolist() == [True, False, False, False, False, False]
    assert ok[1].tolist() == [True, True, True, True, False, True]


@pytest.mark.parametrize("shards", [None, 2])
def test_delta_publish_carries_the_accelerator_columns(shards):
    cfg, state = _gpu_state(256)
    layout = plan_fleet_layout(256, shards=shards) if shards else None
    sub = ClusterSubstrate(state, cfg, layout=layout)
    sub.snapshot()
    assert sub.last_publish.full
    # the index, 14 node columns and the two accelerator columns
    assert sub._snap._buffer().shape == (min(DELTA_ROWS, 256), 17)
    sub.bind(7, _gpu_pod(8))
    sub.live.accel_requested[200] = 3           # a direct write
    snap = sub.snapshot()
    assert not sub.last_publish.full and sub.last_publish.rows == 2
    got = np.asarray(snap.accel_requested).reshape(-1)[:256]
    assert got.tolist() == sub.live.accel_requested.tolist()
    assert got[7] == 8 and got[200] == 3
    np.testing.assert_array_equal(
        np.asarray(snap.accel_capacity).reshape(-1)[:256], 8)



def _tiny_daemon(booked, layout=None, **config):
    cfg, state = _gpu_state(len(booked), booked=booked)
    sub = ClusterSubstrate(state, cfg, layout=layout, topk=4)
    return sub, PlacementDaemon(
        sub, PARAMS, DaemonConfig(batch_size=4, conflict_policy="next-best",
                                  max_retries=1, **config))


@pytest.mark.parametrize("path", ["flat", "two-stage", "heuristic"])
def test_commit_loop_counts_accelerators_bound_and_conflicts(path):
    """Three whole-node pods race in one batch for the two nodes with 8
    free: two bind, the loser's re-validations fail on the accelerators
    (1 for the second pod, 2 for the third), and the third is dropped once
    no node can take it.  The pods ask for little CPU and memory, so the
    accelerators alone turn them away."""
    booked = [4, 0, 7, 8, 0, 8, 8, 8]
    layout = plan_fleet_layout(8, shards=2) if path == "two-stage" else None
    sub, d = _tiny_daemon(booked, layout,
                          heuristic_only=(path == "heuristic"))
    lean = PodSpec(1000.0, 500.0, GiB, 512.0, 8)
    for _ in range(3):
        d.submit(lean, now=0.0)
    d.drain(0.0)
    m = d.metrics
    assert (m.bound, m.dropped, m.batches) == (2, 1, 2)
    assert sorted(dec.node for dec in d.decisions) == [NO_PLACEMENT, 1, 4]
    assert m.accel_bound == 16
    assert m.accel_conflicts == 3
    live = sub.live
    assert (live.accel_requested <= live.accel_capacity).all()
    assert int(live.accel_requested.sum()) == sum(booked) + 16


def test_cpu_only_counters_stay_zero():
    cfg = fleet_cluster(8)
    sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(0), cfg), cfg)
    d = PlacementDaemon(sub, PARAMS, DaemonConfig(batch_size=4))
    for _ in range(6):
        d.submit(kenv.default_pod(cfg), now=0.0)
    d.drain(0.0)
    assert d.metrics.bound == 6
    assert (d.metrics.accel_bound, d.metrics.accel_conflicts) == (0, 0)
    assert sub.pack([kenv.default_pod(cfg)], 4).accel_request is None


# -- a CPU-only state's programs are the ones it always had -------------------

# sha256 of the daemon scorer's StableHLO (no source locations), flat at 64
# nodes and two-stage at 512 in 4 shards, batch 8, for the backend-default
# (XLA twin) and the interpret-mode Pallas paths: the programs
# ``ClusterState``'s accelerator fields left as they were, the two-stage
# interpret-mode one with the top-k kernel that selects once a shard.  A
# change to what a CPU-only cluster's scorer computes must update them.
CPU_ONLY_DIGESTS = {
    (64, None, "auto"):
        "dddde3ed687e1cefd68d7b22d9c8f1661300d94d53273474a9a85c770518db31",
    (64, None, "interpret"):
        "07c1bb3d7401a110da6810c7f57c4c1450ebcac4279f6c74e2745e490c2faf44",
    (512, 4, "auto"):
        "23dac2502cd6e05f916aff3ffe120a2d3f5ce22fede3c40209b074577ca97bef",
    (512, 4, "interpret"):
        "2a159bef6f1e538a66b0d9aa4ea882b75b71f2e3a6002feeff40f0494b4b8cbd",
}


@pytest.mark.parametrize("n,shards,fused", sorted(
    CPU_ONLY_DIGESTS, key=lambda k: (k[0], k[2])))
def test_cpu_only_scorer_lowers_to_the_program_without_the_fields(
        n, shards, fused):
    cfg = fleet_cluster(n)
    layout = plan_fleet_layout(n, shards=shards) if shards else None
    sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(0), cfg), cfg,
                           layout=layout, topk=4)
    pods = sub.pack([kenv.default_pod(cfg)], 8)
    with jax_config.traceback_in_locations_limit(0):
        text = sub.make_scorer(fused).lower(PARAMS, sub.snapshot(), pods,
                                            (), 0).as_text()
    assert "accel" not in text
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == CPU_ONLY_DIGESTS[(n, shards, fused)]
