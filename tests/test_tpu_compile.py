"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip: the TPU compiler compiles for a v5e that is
described, not attached, and refuses what the chip's compiler would refuse
(block shapes off the (8, 128) tiling, too much VMEM, unsupported Mosaic
ops).  Each kernel test asserts that the compiled program holds a Mosaic
kernel (``tpu_custom_call``), so an XLA fallback cannot pass for the kernel.
The daemon's delta publish, a plain XLA scatter, is compiled at the cells'
sizes too, and so is the daemon's scorer over 100,000 nodes that carry
accelerators.  A CPU-only cluster's scorer for the chip is pinned to its
program text.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dqn, env as kenv, policy as pol
from repro.core.types import (
    NodeClass,
    PodType,
    ScenarioConfig,
    fleet_cluster,
    scenario_env,
)
from repro.kernels import ops
from repro.launch.mesh import plan_fleet_layout
from repro.sched import api, placement
from repro.sched.daemon import (
    DELTA_ROWS,
    ClusterSubstrate,
    FleetSubstrate,
    _scatter_rows,
)

SIZES = (5000, 131072)       # upstream's large-cluster node limit; 128k fleet
KERNELS = ("afterstate", "afterstate_topk", "cols", "cols_topk", "score")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def pallas_default(monkeypatch):
    """Make the library's backend-default dispatch pick the Pallas kernels,
    as it does on a TPU (here ``default_backend()`` is the CPU)."""
    monkeypatch.setattr(ops, "_default_mode", lambda: "pallas")


def _shapes(tree, sharding, batch=()):
    """Shapes (with an optional leading batch) of arrays, scalars or shapes,
    placed on ``sharding``."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(batch + tuple(jnp.shape(x)),
                                       jnp.result_type(x), sharding=sharding),
        tree)


def _assert_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _cluster(n):
    cfg = fleet_cluster(n)
    return cfg, jax.eval_shape(lambda: kenv.reset(jax.random.PRNGKey(0), cfg))


PARAMS = dqn.init_qnet(jax.random.PRNGKey(0))


@pytest.mark.parametrize("n", SIZES)
@pytest.mark.parametrize("kernel", KERNELS)
def test_sdqn_kernel_compiles(one_chip, kernel, n):
    params = _shapes(PARAMS, one_chip)
    if kernel.startswith("afterstate"):
        cfg, state = _cluster(n)
        pod = _shapes(kenv.default_pod(cfg), one_chip)
        fn = (ops.sdqn_score_afterstate if kernel == "afterstate"
              else ops.sdqn_topk_afterstate)
        _assert_kernel(lambda s, p, w: fn(s, p, cfg, w, mode="pallas"),
                       _shapes(state, one_chip), pod, params)
    elif kernel.startswith("cols"):
        fleet = _shapes(jax.eval_shape(lambda: placement.fresh_fleet(n)),
                        one_chip)
        delta = _shapes(jnp.zeros((6,)), one_chip)
        fn = ops.sdqn_score_delta if kernel == "cols" else ops.sdqn_topk_delta
        _assert_kernel(
            lambda f, d, w: fn(placement.fleet_cols(f), d, w, mode="pallas"),
            fleet, delta, params)
    else:
        feats = _shapes(jnp.zeros((n, 6)), one_chip)
        _assert_kernel(lambda f, w: ops.sdqn_score(f, w, mode="pallas"),
                       feats, params)


@pytest.mark.parametrize("per_seed_params", [False, True])
def test_afterstate_kernel_compiles_under_trainer_vmap(one_chip,
                                                       per_seed_params):
    """The training scan scores every env of the batch in one vmapped call
    (``train``), and ``train_seeds`` vmaps again over per-seed params."""
    n_envs = 8
    cfg, state = _cluster(4096)
    states = _shapes(state, one_chip, batch=(n_envs,))
    pods = _shapes(kenv.default_pod(cfg), one_chip, batch=(n_envs,))
    if per_seed_params:
        params = _shapes(PARAMS, one_chip, batch=(n_envs,))
        axes = (0, 0, 0)
    else:
        params = _shapes(PARAMS, one_chip)
        axes = (0, 0, None)
    _assert_kernel(
        lambda s, p, w: jax.vmap(
            lambda s1, p1, w1: ops.sdqn_score_afterstate(
                s1, p1, cfg, w1, mode="pallas"), in_axes=axes)(s, p, w),
        states, pods, params)


@pytest.mark.parametrize("accel", [False, True])
def test_topk_kernel_compiles_at_the_daemons_shape(one_chip, accel):
    """The top-k kernel as the daemon's two-stage scorer maps it: over 32
    requests and 8 shards of 12,500 nodes, one (104, 128) block a shard,
    with 14 columns, or 16 where the nodes carry accelerators."""
    n, shards, batch = 12500, 8, 32
    cfg = _gpu_cluster(n) if accel else fleet_cluster(n)
    state = jax.eval_shape(lambda: kenv.reset(jax.random.PRNGKey(0), cfg))
    pod = kenv.default_pod(cfg)
    if accel:
        pod = pod._replace(accel_request=jnp.int32(2))

    def score(states, pods, params):
        return jax.vmap(lambda p: jax.vmap(
            lambda s: ops.sdqn_topk_afterstate(s, p, cfg, params, k=8,
                                               mode="pallas"))(states))(pods)

    _assert_kernel(score, _shapes(state, one_chip, batch=(shards,)),
                   _shapes(pod, one_chip, batch=(batch,)),
                   _shapes(PARAMS, one_chip))


@pytest.mark.parametrize("n", [64, 4096])
def test_attention_policy_kernel_compiles(one_chip, n):
    spec = pol.get("attention")
    params = _shapes(spec.init(jax.random.PRNGKey(0)), one_chip)
    feats = _shapes(jnp.zeros((n, spec.feature_dim)), one_chip)
    _assert_kernel(lambda w, f: pol.attention_score_set(w, f, mode="pallas"),
                   params, feats)


def test_mamba_policy_kernel_compiles(one_chip):
    params = _shapes(pol.get("mamba").init(jax.random.PRNGKey(0)), one_chip)
    hist = _shapes(jnp.zeros((512, pol.ENCODER_IN)), one_chip)
    _assert_kernel(lambda w, x: pol.mamba_encode_sequence(w, x, mode="pallas"),
                   params, hist)


def test_sharded_fleet_decision_compiles(one_chip, pallas_default):
    """``api.select`` over 131,072 nodes in 8 forced shards: the per-shard
    top-k kernel runs under ``vmap`` over the shard axis."""
    n = 131072
    cfg, state = _cluster(n)
    layout = plan_fleet_layout(n, shards=8)
    pod = kenv.default_pod(cfg)
    _assert_kernel(
        lambda s, w: api.select(s, pod, params=w, cfg=cfg, shard=layout,
                                fused=True),
        _shapes(state, one_chip), _shapes(PARAMS, one_chip))


def test_sharded_fleet_decision_compiles_on_four_chips(topo, pallas_default):
    """The same decision with a 4-device ``FleetLayout``: each chip runs the
    top-k kernel on its own shard (``shard_map``; the compiler cannot
    partition a Mosaic kernel)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    n = 131072
    cfg, state = _cluster(n)
    mesh = Mesh(np.array(topo.devices[:4]), ("data",))
    layout = plan_fleet_layout(n, mesh)
    assert layout.shards == 4 and layout.mesh is not None
    replicated = NamedSharding(mesh, PartitionSpec())
    pod = kenv.default_pod(cfg)
    _assert_kernel(
        lambda s, w: api.select(s, pod, params=w, cfg=cfg, shard=layout,
                                fused=True),
        _shapes(state, replicated), _shapes(PARAMS, replicated))


@pytest.mark.parametrize("substrate", ["cluster", "fleet"])
def test_daemon_scorer_compiles(one_chip, pallas_default, substrate):
    """The daemon's one-launch batch scorer at 5,000 nodes, batch 32."""
    n, batch = 5000, 32
    if substrate == "cluster":
        cfg = fleet_cluster(n)
        sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(0), cfg), cfg)
        pods = sub.pack([kenv.default_pod(cfg)], batch)
    else:
        sub = FleetSubstrate(placement.fresh_fleet(n))
        pods = sub.pack([placement.JobSpec()], batch)
    snap = sub.snapshot()
    scorer = sub.make_scorer("auto")
    compiled = scorer.lower(
        _shapes(PARAMS, one_chip), _shapes(snap, one_chip),
        _shapes(pods, one_chip), (),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,shards", [(5000, None), (100000, 8)])
def test_delta_publish_scatter_compiles(one_chip, n, shards):
    """The snapshot's delta publish (one C-row scatter into the resident
    columns) at the benchmark cells' sizes: 5,000 flat, 100,000 in 8
    shards."""
    cfg = fleet_cluster(n)
    layout = plan_fleet_layout(n, shards=shards) if shards else None
    sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(0), cfg), cfg,
                           layout=layout)
    cols = [x for x in jax.tree.leaves(sub.snapshot()) if x.ndim]
    buf = jax.ShapeDtypeStruct((DELTA_ROWS, 1 + len(cols)), jnp.int32,
                               sharding=one_chip)
    compiled = _scatter_rows.lower(_shapes(cols, one_chip), buf,
                                   layout).compile()
    assert "scatter" in compiled.as_text()
    out = jax.eval_shape(_scatter_rows, cols, buf, layout)
    assert [(x.shape, x.dtype) for x in out] == [(x.shape, x.dtype)
                                                 for x in cols]


def _gpu_cluster(n):
    """``n`` nodes of 8 accelerators, 192 vCPU and 2,048 GiB; pods of 1, 2,
    4 and 8 accelerators (the ``eks-100k-gpu`` benchmark's shapes)."""
    node = NodeClass("p5", n, cpu_capacity=192000.0,
                     mem_capacity=2048 * 1024.0, accel_capacity=8)
    pods = tuple(PodType(f"gpu{a}", w, 20000.0 * a, 12000.0 * a,
                         204800.0 * a, 163840.0 * a, accel_request=a)
                 for a, w in ((1, 0.5), (2, 0.1), (4, 0.1), (8, 0.3)))
    return scenario_env(ScenarioConfig("gpu", (node,), pods))


def test_daemon_scorer_with_accelerators_compiles(one_chip, pallas_default):
    """The daemon's one-launch scorer over 100,000 nodes with accelerators,
    in 8 shards, batch 32: the top-k kernel takes 16 columns and filters
    the accelerators in-kernel; the delta publish scatters 16 columns."""
    n, batch = 100000, 32
    cfg = _gpu_cluster(n)
    layout = plan_fleet_layout(n, shards=8)
    sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(0), cfg), cfg,
                           layout=layout)
    pods = sub.pack([kenv.default_pod(cfg)], batch)
    assert pods.accel_request.dtype == jnp.int32
    snap = sub.snapshot()
    text = sub.make_scorer("auto").lower(
        _shapes(PARAMS, one_chip), _shapes(snap, one_chip),
        _shapes(pods, one_chip), (),
        jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    ).compile().as_text()
    assert "tpu_custom_call" in text
    cols = [x for x in jax.tree.leaves(snap) if x.ndim]
    assert len(cols) == 16
    buf = jax.ShapeDtypeStruct((DELTA_ROWS, 1 + len(cols)), jnp.int32,
                               sharding=one_chip)
    compiled = _scatter_rows.lower(_shapes(cols, one_chip), buf,
                                   layout).compile()
    assert "scatter" in compiled.as_text()


# sha256 of the daemon scorer's StableHLO for the chip (Pallas kernels, no
# source locations) at the benchmark's CPU-only shapes, batch 32: the
# programs ``ClusterState``'s accelerator fields left as they were, the
# two-stage one with the top-k kernel that selects once a shard.  A change
# to what a CPU-only cluster's scorer computes must update them.
CPU_ONLY_CHIP_DIGESTS = {
    5000: "452b5205fd6985bef4fdbaa408e0b7ab9fca727d8f85a8e4eefa94b76e67fb38",
    100000: "645694515f85a87a925e2219ceffaae71359dac74de182c2f2e26d8c2c6967bc",
}


@pytest.mark.parametrize("n,shards", [(5000, None), (100000, 8)])
def test_cpu_only_daemon_scorer_is_the_program_it_was(one_chip,
                                                      pallas_default, n,
                                                      shards):
    import hashlib

    from jax._src import config as jax_config

    cfg = fleet_cluster(n)
    layout = plan_fleet_layout(n, shards=shards) if shards else None
    sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(0), cfg), cfg,
                           layout=layout)
    pods = sub.pack([kenv.default_pod(cfg)], 32)
    with jax_config.traceback_in_locations_limit(0):
        text = sub.make_scorer("auto").lower(
            _shapes(PARAMS, one_chip), _shapes(sub.snapshot(), one_chip),
            _shapes(pods, one_chip), (),
            jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        CPU_ONLY_CHIP_DIGESTS[n]
