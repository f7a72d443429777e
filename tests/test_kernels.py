"""Pallas kernel correctness sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dqn
from repro.kernels import ops, ref
from repro.models import layers as mlayers
from repro.models import mamba as mmamba


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("b,sq,skv,hq,hkv,d", [
    (1, 64, 64, 4, 4, 32),      # MHA square
    (2, 128, 128, 4, 2, 32),    # GQA
    (2, 64, 128, 8, 1, 16),     # MQA, cross-length
    (1, 256, 256, 2, 2, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_sweep(b, sq, skv, hq, hkv, d, dtype, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, sq, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, hkv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, hkv, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, mode="interpret",
                              block_q=32, block_k=64)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               **tol(dtype))


@pytest.mark.parametrize("b,hq,hkv,skv,d", [
    (1, 4, 4, 128, 32),
    (2, 8, 2, 256, 64),
    (3, 4, 1, 512, 16),
])
@pytest.mark.parametrize("kv_len", [1, 17, -1])  # -1 = full
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(b, hq, hkv, skv, d, kv_len, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (b, hq, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), dtype)
    n = jnp.int32(skv if kv_len == -1 else kv_len)
    out = ops.decode_attention(q, k, v, n, mode="interpret", block_k=64)
    want = ref.decode_attention_ref(q, k, v, n)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(want, np.float32),
                               **tol(dtype))


@pytest.mark.parametrize("b,s,di,n", [(1, 32, 8, 4), (2, 64, 16, 8), (1, 128, 32, 16)])
@pytest.mark.parametrize("block_s", [16, 32])
def test_mamba_scan_sweep(b, s, di, n, block_s):
    ks = jax.random.split(jax.random.PRNGKey(2), 6)
    x = jax.random.normal(ks[0], (b, s, di)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) * 0.3 - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
    cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
    dsk = jnp.ones((di,))
    h0 = jax.random.normal(ks[5], (b, di, n)) * 0.1
    y, hT = ops.mamba_scan(x, dt, a, bm, cm, dsk, h0, mode="interpret",
                           block_d=max(di // 2, 4), block_s=block_s)
    y_ref, h_ref = ref.mamba_scan_ref(x, dt, a, bm, cm, dsk, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=4e-5, atol=4e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref), rtol=4e-5, atol=4e-5)


@pytest.mark.parametrize("n", [1, 63, 128, 1000])
def test_sdqn_score_sweep(n):
    params = dqn.init_qnet(jax.random.PRNGKey(3))
    feats = jax.random.normal(jax.random.PRNGKey(4), (n, 6))
    out = ops.sdqn_score(feats, params, mode="interpret", block_n=64)
    want = ref.sdqn_score_ref(feats, params["w1"], params["b1"], params["w2"], params["b2"])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=2e-5, atol=2e-5)


# N inside one (8, 128) tile (padding path), a whole tile, and N spanning
# several grid steps with a ragged last block
@pytest.mark.parametrize("n", [1, 37, 64, 100, 1000, 2500])
@pytest.mark.parametrize("mode", ["interpret", "xla"])
def test_sdqn_score_afterstate_sweep(n, mode):
    """In-kernel afterstate scoring == hypothetical_place + qvalues (<=1e-5).

    The fused path recomputes the Table-2 afterstate features (startup
    transient, crowding, contention knee) inside the scorer from the raw
    state columns; any drift from ``env.hypothetical_place``'s arithmetic
    shows up here.
    """
    import dataclasses

    from repro.core import env as kenv
    from repro.core.types import fleet_cluster

    # unhealthy_prob > 0 exercises the healthy feature column
    cfg = dataclasses.replace(fleet_cluster(n), unhealthy_prob=0.2,
                              randomize_workload=True)
    state = kenv.reset(jax.random.PRNGKey(5), cfg)
    pod = kenv.default_pod(cfg)
    params = dqn.init_qnet(jax.random.PRNGKey(6))
    want = dqn.qvalues(params, kenv.normalize_features(
        kenv.hypothetical_place(state, pod, cfg)))
    got = ops.sdqn_score_afterstate(state, pod, cfg, params, mode=mode,
                                    block_n=1024)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [3, 64, 129, 2500])
def test_sdqn_score_cols_sweep(n):
    """Fused column scorer (serving path) vs stack + normalize + qvalues."""
    from repro.core import env as kenv

    params = dqn.init_qnet(jax.random.PRNGKey(7))
    ks = jax.random.split(jax.random.PRNGKey(8), 6)
    cols = tuple(jax.random.uniform(k, (n,), minval=0.0, maxval=80.0) for k in ks)
    deltas = jnp.array([5.0, 2.0, 4.0, 0.0, 0.0, 1.0])
    want = dqn.qvalues(params, (jnp.stack(cols, axis=-1) + deltas[None, :])
                       / kenv.FEATURE_SCALE)
    for mode in ("interpret", "xla"):
        got = ops.sdqn_score_delta(cols, deltas, params, mode=mode,
                                   block_n=1024)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [5, 1024, 3000, 12500, 40000])
@pytest.mark.parametrize("kind", ["afterstate", "cols"])
@pytest.mark.parametrize("case", ["random", "ties", "none_feasible"])
def test_sdqn_topk_kernels_match_twins(kind, n, case):
    """The in-kernel per-block top-k (interpret mode) emits exactly the XLA
    twin's ``lax.top_k`` candidates.  ``ties`` makes every feasible node
    score alike, so the first-index rule decides within a block and in the
    merge of several; with no feasible node both list the lowest indices at
    ``-inf``.  ``n = 12500`` is one block (a shard at 100,000 nodes in 8);
    ``n = 40000`` is three, for either kernel.  From 12,500 nodes the first
    feasible nodes straddle node 1,024, the edge of the (8, 128)-tile
    blocks the kernels once selected from."""
    ties = case == "ties"
    first_ok = 2 if n <= 3000 else 1022
    import dataclasses

    from repro.core import env as kenv
    from repro.core.types import fleet_cluster
    from repro.sched import placement

    params = dqn.init_qnet(jax.random.PRNGKey(9))
    if kind == "afterstate":
        cfg = dataclasses.replace(fleet_cluster(n), unhealthy_prob=0.2,
                                  randomize_workload=True)
        state = kenv.reset(jax.random.PRNGKey(10), cfg)
        if ties:  # identical nodes, with the first two made infeasible
            state = kenv.reset(jax.random.PRNGKey(10), fleet_cluster(n))
            state = state._replace(
                healthy=jnp.ones((n,), bool).at[:first_ok].set(False),
                uptime_hours=jnp.full((n,), 10.0),
                num_pods=jnp.zeros((n,), state.num_pods.dtype),
                cpu_requested=jnp.full((n,), 500.0),
                base_cpu=jnp.full((n,), 100.0))
        if case == "none_feasible":
            state = state._replace(healthy=jnp.zeros((n,), bool))
        pod = kenv.default_pod(cfg)

        def run(mode):
            return ops.sdqn_topk_afterstate(state, pod, cfg, params, k=4,
                                            mode=mode)
    else:
        fleet = placement.fresh_fleet(n, jax.random.PRNGKey(11))
        if ties:
            fleet = fleet._replace(cpu_pct=jnp.full((n,), 50.0),
                                   uptime_hours=jnp.full((n,), 10.0),
                                   healthy=jnp.ones((n,)).at[:first_ok].set(0.0))
        if case == "none_feasible":
            fleet = fleet._replace(healthy=jnp.zeros((n,)))
        cols = placement.fleet_cols(fleet)
        delta = placement.job_delta(placement.JobSpec(cpu_pct_demand=6.0))

        def run(mode):
            return ops.sdqn_topk_delta(cols, delta, params, k=4, mode=mode)

    vals, idx = run("interpret")
    tvals, tidx = run("xla")
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(tidx))
    np.testing.assert_allclose(np.asarray(vals), np.asarray(tvals),
                               rtol=1e-5, atol=1e-5)
    if ties:  # the lowest feasible indices, in order
        assert np.asarray(idx)[:min(4, n - 2)].tolist() == list(
            range(first_ok, first_ok + min(4, n - 2)))


@pytest.mark.parametrize("n", [1, 1000, 1024, 1025, 12500, 12544, 16384,
                               16385, 32768, 40000, 100000, 131072, 1000000])
def test_topk_blocks_are_the_fewest_under_the_row_cap(n):
    """Every top-k block is whole (8, 128) tiles, at most the cap; no fewer
    blocks would do; the padding is under a tile a block."""
    from repro.kernels import sdqn_score as ss

    block_rows, rows = ss._topk_tiling(n)
    blocks = rows // block_rows
    need = -(-n // 1024) * 8                   # rows of whole (8, 128) tiles
    assert block_rows % 8 == 0 and rows == blocks * block_rows >= need
    assert block_rows <= ss._TOPK_BLOCK_ROWS
    assert (blocks - 1) * ss._TOPK_BLOCK_ROWS < need
    assert rows - need < 8 * blocks


def test_topk_blocks_at_the_cells_shapes():
    """One block a 12,500-node shard (the 100,000-node cells' 8 shards);
    40,000 nodes split in three, and the 4-chip path's 32,768-node shards
    in two."""
    from repro.kernels import sdqn_score as ss

    assert ss._topk_tiling(12500) == (104, 104)
    assert ss._topk_tiling(40000) == (112, 336)
    assert ss._topk_tiling(32768) == (128, 256)


class TestXlaPathsMatchOracles:
    """The jnp fallbacks used on CPU/dry-run must agree with the oracles too."""

    def test_chunked_attention(self):
        ks = jax.random.split(jax.random.PRNGKey(5), 3)
        q = jax.random.normal(ks[0], (2, 96, 4, 16))
        k = jax.random.normal(ks[1], (2, 96, 2, 16))
        v = jax.random.normal(ks[2], (2, 96, 2, 16))
        out = mlayers.attention(q, k, v, causal=True, q_chunk=32)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)

    def test_chunked_attention_non_divisible(self):
        ks = jax.random.split(jax.random.PRNGKey(6), 3)
        q = jax.random.normal(ks[0], (1, 150, 2, 16))  # 150 % 32 != 0 (whisper case)
        k = jax.random.normal(ks[1], (1, 150, 2, 16))
        v = jax.random.normal(ks[2], (1, 150, 2, 16))
        out = mlayers.attention(q, k, v, causal=False, q_chunk=32)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=3e-5, atol=3e-5)

    def test_chunked_selective_scan(self):
        ks = jax.random.split(jax.random.PRNGKey(7), 6)
        b, s, di, n = 2, 64, 8, 4
        x = jax.random.normal(ks[0], (b, s, di)) * 0.5
        dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di)) * 0.3 - 1.0)
        a = -jnp.exp(jax.random.normal(ks[2], (di, n)) * 0.3)
        bm = jax.random.normal(ks[3], (b, s, n)) * 0.5
        cm = jax.random.normal(ks[4], (b, s, n)) * 0.5
        dsk = jnp.ones((di,))
        h0 = jnp.zeros((b, di, n))
        y, hT = mmamba.selective_scan(x, dt, a, bm, cm, dsk, h0, chunk=16)
        y_ref, h_ref = ref.mamba_scan_ref(x, dt, a, bm, cm, dsk, h0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=4e-5, atol=4e-5)
        np.testing.assert_allclose(np.asarray(hT), np.asarray(h_ref), rtol=4e-5, atol=4e-5)
