"""Pallas TPU fused SDQN node-scoring kernels.

The paper's hot loop at fleet scale: score N candidate nodes through the
6->32->1 Q-network (Table 4).  Three entry points:

* ``sdqn_score`` — score a pre-built (N, 6) feature matrix.  Both matmuls
  and the ReLU are fused in one VMEM pass; at N ~ 10^5-10^6 nodes the layer
  is memory-bound and the fusion removes two HBM round-trips of the (N, 32)
  intermediate.
* ``sdqn_score_afterstate`` — the full afterstate scorer: takes the *raw*
  per-node ``ClusterState`` columns plus the pod's placement delta and
  computes the Table-2 afterstate features (mirroring the O(N)
  ``env.hypothetical_place`` arithmetic: startup transient, CFS crowding,
  contention knee), normalizes them, and applies the Q-net — all inside the
  kernel.  The (N, 6) afterstate matrix never touches HBM, which is the
  dominant traffic of the scoring path in both training and serving.
* ``sdqn_score_cols`` — afterstate scoring for column-structured fleets
  (``sched.placement``): six raw feature columns plus a per-feature
  afterstate delta, features assembled and scored in-kernel.

Each kernel has a ``*_xla`` twin with identical arithmetic (broadcast
multiply-accumulate, no (N, 6) stack, no GEMM) used as the fused fallback on
CPU/GPU backends and as the reference for the interpret-mode sweeps.
The column kernels view each per-node column as (rows, 128) and stream
(8·m, 128) tiles — the TPU's (8, 128) vreg tiling, so every block is dense
in both sublanes and lanes; the top-k kernels take a whole shard of up to
16,384 nodes as one block, so they select from it once.  The Q-net weights
and the scalar pack ride in one SMEM vector, so the 6->H->1 MLP is unrolled
as scalar-times-tile multiply-accumulates on the VPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _score_kernel(x_ref, w1_ref, b1_ref, w2_ref, b2_ref, o_ref):
    # HIGHEST: float32 matmuls, as the reference computes; the TPU default
    # rounds the operands to bfloat16 (~1e-2 off on unit-scale scores)
    dot = functools.partial(jax.lax.dot, precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    x = x_ref[...].astype(jnp.float32)           # (bn, F)
    h = jnp.maximum(dot(x, w1_ref[...]) + b1_ref[...], 0.0)  # (bn, H)
    q = dot(h, w2_ref[...])
    o_ref[...] = (q + b2_ref[...]).astype(o_ref.dtype)  # (bn, 1)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sdqn_score(
    feats: jnp.ndarray,  # (N, F) float32 — normalized Table-2 features
    w1: jnp.ndarray,     # (F, H)
    b1: jnp.ndarray,     # (H,)
    w2: jnp.ndarray,     # (H, 1)
    b2: jnp.ndarray,     # (1,)
    *,
    block_n: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns Q-values (N,)."""
    n, f = feats.shape
    h = w1.shape[1]
    block_n = min(block_n, n)
    pad_n = (-n) % block_n
    if pad_n:
        feats = jnp.pad(feats, ((0, pad_n), (0, 0)))
    np_ = feats.shape[0]

    out = pl.pallas_call(
        _score_kernel,
        grid=(np_ // block_n,),
        in_specs=[
            pl.BlockSpec((block_n, f), lambda i: (i, 0)),
            pl.BlockSpec((f, h), lambda i: (0, 0)),
            pl.BlockSpec((1, h), lambda i: (0, 0)),
            pl.BlockSpec((h, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((np_, 1), jnp.float32),
        interpret=interpret,
    )(feats, w1, b1.reshape(1, h), w2, b2.reshape(1, 1))
    return out[:n, 0]


# ---------------------------------------------------------------------------
# fused afterstate scoring: raw state columns + placement delta -> Q, with
# the Table-2 afterstate features computed in-kernel (no (N, 6) in HBM)
# ---------------------------------------------------------------------------

# scalar-pack layout shared by the afterstate kernel and its XLA twin
_S_CPU_DEMAND, _S_MEM_DEMAND, _S_PULL, _S_WARM, _S_OVERHEAD = 0, 1, 2, 3, 4
_S_CROWD_KNEE, _S_CROWD_COEFF, _S_CONT_KNEE, _S_CONT_COEFF = 5, 6, 7, 8
_S_UPTIME_SCALE, _S_EXP_SCALE, _S_B2 = 9, 10, 11
# the top-k variants also filter in-kernel, so the pod's *requests* (the k8s
# filtering phase operates on requests, not demands) ride in the pack too
_S_CPU_REQ, _S_MEM_REQ = 12, 13
# ... and, where the state carries accelerators, the pod's accelerator count
_S_ACCEL_REQ = 14
_N_SCALARS = 16  # padded pack width


def _afterstate_norm_features(base_cpu, pods_cpu, startup_cpu, num_pods,
                              exp_pods, mem_used, cached, healthy, uptime,
                              cap, mem_cap, max_pods, s):
    """Normalized Table-2 afterstate features, elementwise on any shape.

    ``s(i)`` reads scalar ``i`` of the pack.  Mirrors the placement delta of
    ``env.hypothetical_place`` + ``env._node_cpu_used`` + normalization
    exactly: one definition shared by the Pallas kernel body (operating on
    (block_rows, 128) tiles) and the fused XLA twin (operating on (N,) columns).
    """
    start_cost = jnp.where(cached > 0.5, s(_S_WARM), s(_S_PULL))
    num_pods1 = num_pods + 1.0
    exp_pods1 = exp_pods + 1.0
    crowd = jnp.maximum(num_pods1 - s(_S_CROWD_KNEE), 0.0)
    # the placed node is always active, so the overhead term is unconditional
    raw = (base_cpu + s(_S_OVERHEAD) + pods_cpu + s(_S_CPU_DEMAND)
           + startup_cpu + start_cost + s(_S_CROWD_COEFF) * crowd * crowd)
    util = raw / cap
    over = jnp.maximum(util - s(_S_CONT_KNEE), 0.0)
    used = jnp.minimum(raw + s(_S_CONT_COEFF) * over * over * cap, cap)
    return (
        used / cap,                                  # 100 * used/cap, /100
        (mem_used + s(_S_MEM_DEMAND)) / mem_cap,     # 100 * mem/cap, /100
        num_pods1 / max_pods,                        # 100 * pods/max, /100
        healthy,
        uptime / s(_S_UPTIME_SCALE),
        exp_pods1 / s(_S_EXP_SCALE),
    )


_LANES, _SUBLANES = 128, 8


def _tiling(n: int, block_n: int):
    """(block_rows, padded_rows) of the (rows, 128) column view.

    ``block_n`` nodes per grid step, rounded to whole (8, 128) tiles and
    capped at the padded column, so every block obeys the TPU's tiling rule.
    """
    rows = -(-n // _LANES)
    cap = -(-rows // _SUBLANES) * _SUBLANES
    block_rows = min(max(block_n // _LANES // _SUBLANES, 1) * _SUBLANES, cap)
    return block_rows, -(-rows // block_rows) * block_rows


# Most rows in one top-k block.  The k selection rounds hold a block's
# scores, node indices and not-yet-taken mask live at once, each one vreg
# an (8, 128) tile, so the block is capped near the 100,000-node cells' 104
# rows (a 12,500-node shard, 13 vregs an array) against spills from the
# 64-vreg file.  VMEM does not bind under the cap: 16 double-buffered
# float32 columns of 128 rows take 16 x 128 x 128 x 4 B x 2 = 2 MiB of
# v5e's 16 MiB default scoped VMEM.
_TOPK_BLOCK_ROWS = 128


def _topk_tiling(n: int):
    """(block_rows, padded_rows) of the top-k kernels' (rows, 128) view.

    One block spans the whole padded column up to ``_TOPK_BLOCK_ROWS`` rows
    (16,384 nodes); a longer column takes the fewest blocks of whole
    multiples of 8 rows under the cap.  Each block runs the k selection
    rounds once, so the block count is the number of selections a call
    makes.
    """
    rows = -(-n // _LANES)
    cap = -(-rows // _SUBLANES) * _SUBLANES
    blocks = -(-cap // _TOPK_BLOCK_ROWS)
    block_rows = -(-cap // (blocks * _SUBLANES)) * _SUBLANES
    return block_rows, blocks * block_rows


def _grid_cols(cols, padded_rows, pad_value=0.0):
    """Pad each (N,) column to ``padded_rows * 128`` and view it as
    (padded_rows, 128)."""
    out = []
    for c in cols:
        c = c.astype(jnp.float32)
        pad_n = padded_rows * _LANES - c.shape[0]
        if pad_n:
            c = jnp.pad(c, (0, pad_n), constant_values=pad_value)
        out.append(c.reshape(padded_rows, _LANES))
    return out


def _pack(scalars, w1, b1, w2):
    """One (1, L) SMEM row: the scalar pack, then w1 (6, H) row-major, b1,
    w2.  Two-dimensional so that under ``vmap`` the batched block
    ``(squeezed, 1, L)`` still spans whole trailing dimensions."""
    return jnp.concatenate([scalars.astype(jnp.float32), w1.reshape(-1),
                            b1.reshape(-1), w2.reshape(-1)])[None, :]


def _qnet(feats, pack_ref, n_hidden: int):
    """6 -> H -> 1 MLP over six feature tiles, weights read from the pack.

    Same multiply-accumulate order as the XLA twins: b1 + sum_f w1[f] x_f,
    ReLU, then the w2 reduction over hidden units (b2 is added by callers).
    """
    w1_at, b1_at = _N_SCALARS, _N_SCALARS + 6 * n_hidden
    w2_at = b1_at + n_hidden
    q = None
    for j in range(n_hidden):
        h = pack_ref[0, b1_at + j]
        for f in range(6):
            h = h + pack_ref[0, w1_at + f * n_hidden + j] * feats[f]
        t = jnp.maximum(h, 0.0) * pack_ref[0, w2_at + j]
        q = t if q is None else q + t
    return q


def _col_spec(block_rows):
    return pl.BlockSpec((block_rows, _LANES), lambda i: (i, 0))


_SMEM_SPEC = pl.BlockSpec(memory_space=pltpu.SMEM)


def _afterstate_kernel(n_hidden, base_ref, pcpu_ref, scpu_ref, npod_ref,
                       epod_ref, mem_ref, cached_ref, health_ref, up_ref,
                       cap_ref, mcap_ref, mpod_ref, pack_ref, o_ref):
    def s(i):
        return pack_ref[0, i]

    feats = _afterstate_norm_features(
        base_ref[...], pcpu_ref[...], scpu_ref[...], npod_ref[...],
        epod_ref[...], mem_ref[...], cached_ref[...], health_ref[...],
        up_ref[...], cap_ref[...], mcap_ref[...], mpod_ref[...], s,
    )  # six (block_rows, 128) tiles
    o_ref[...] = _qnet(feats, pack_ref, n_hidden) + s(_S_B2)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sdqn_score_afterstate(
    node_cols: tuple,    # 12 x (N,): base_cpu, pods_cpu, startup_cpu,
    #                      num_pods, exp_pods, mem_used, image_cached,
    #                      healthy, uptime_hours, cpu_capacity,
    #                      mem_capacity, max_pods
    scalars: jnp.ndarray,  # (_N_SCALARS,) pack, see _S_* layout
    w1: jnp.ndarray,     # (F, H)
    b1: jnp.ndarray,     # (H,)
    w2: jnp.ndarray,     # (H, 1)
    *,
    block_n: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Q-values (N,) for every candidate afterstate, features fused in-kernel."""
    n = node_cols[0].shape[0]
    h = w1.shape[1]
    block_rows, rows = _tiling(n, block_n)
    # capacities pad with 1 so padded lanes stay finite (they are sliced off)
    grids = _grid_cols(node_cols[:9], rows) + _grid_cols(
        node_cols[9:], rows, pad_value=1.0)

    out = pl.pallas_call(
        functools.partial(_afterstate_kernel, h),
        grid=(rows // block_rows,),
        in_specs=[_col_spec(block_rows)] * 12 + [_SMEM_SPEC],
        out_specs=_col_spec(block_rows),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
    )(*grids, _pack(scalars, w1, b1, w2))
    return out.reshape(-1)[:n]


@jax.jit
def sdqn_score_afterstate_xla(node_cols: tuple, scalars: jnp.ndarray,
                              w1: jnp.ndarray, b1: jnp.ndarray,
                              w2: jnp.ndarray) -> jnp.ndarray:
    """Fused XLA twin of the afterstate kernel (CPU/GPU fallback).

    Same arithmetic, expressed as broadcast multiply-accumulates over the
    raw columns so XLA fuses the whole scorer into one elementwise loop —
    no (N, 6) feature stack, no GEMM dispatch, no (N, H) round-trip.
    """
    cols = [c.astype(jnp.float32) for c in node_cols]

    def s(i):
        return scalars[i]

    feats = _afterstate_norm_features(*cols, s)
    hid = b1[None, :]                                # (1, H)
    for f in range(6):
        hid = hid + feats[f][:, None] * w1[f][None, :]
    return jnp.sum(jnp.maximum(hid, 0.0) * w2[:, 0][None, :], axis=-1) + s(_S_B2)


# ---------------------------------------------------------------------------
# fused column scoring for feature-structured fleets (sched.placement):
# six raw feature columns + per-feature afterstate delta -> Q in one pass
# ---------------------------------------------------------------------------


def _cols_kernel(n_hidden, c0, c1, c2, c3, c4, c5, pack_ref, o_ref):
    cols = (c0, c1, c2, c3, c4, c5)
    feats = [cols[f][...] + pack_ref[0, f] for f in range(6)]
    o_ref[...] = _qnet(feats, pack_ref, n_hidden) + pack_ref[0, 6]


def _cols_pack(deltas, b2, w1n, b1, w2, ceilings=(0.0, 0.0, 0.0)):
    """Pack layout of the column kernels: deltas at 0..5, b2 at 6, the
    top-k feasibility ceilings at 7..9, then the weights (``_pack``)."""
    scal = jnp.zeros((_N_SCALARS,), jnp.float32)
    scal = scal.at[:6].set(deltas.astype(jnp.float32))
    scal = scal.at[6].set(jnp.reshape(b2, ()))
    scal = scal.at[7:10].set(jnp.asarray(ceilings, jnp.float32))
    return _pack(scal, w1n, b1, w2)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def sdqn_score_cols(
    cols: tuple,          # 6 x (N,) raw feature columns
    deltas: jnp.ndarray,  # (6,) afterstate delta per feature (raw units)
    scale: jnp.ndarray,   # (6,) feature normalization (env.FEATURE_SCALE)
    w1: jnp.ndarray,      # (F, H)
    b1: jnp.ndarray,      # (H,)
    w2: jnp.ndarray,      # (H, 1)
    b2: jnp.ndarray,      # (1,)
    *,
    block_n: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """Q((cols + deltas) / scale) without materializing the (N, 6) matrix.

    Normalization folds into the first-layer weights (w1[f] / scale[f]), so
    the kernel streams the six raw columns straight into the MAC.
    """
    n = cols[0].shape[0]
    h = w1.shape[1]
    block_rows, rows = _tiling(n, block_n)
    grids = _grid_cols(cols, rows)

    out = pl.pallas_call(
        functools.partial(_cols_kernel, h),
        grid=(rows // block_rows,),
        in_specs=[_col_spec(block_rows)] * 6 + [_SMEM_SPEC],
        out_specs=_col_spec(block_rows),
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.float32),
        interpret=interpret,
    )(*grids, _cols_pack(deltas, b2, w1 / scale[:, None], b1, w2))
    return out.reshape(-1)[:n]


@jax.jit
def sdqn_score_cols_xla(cols: tuple, deltas: jnp.ndarray, scale: jnp.ndarray,
                        w1: jnp.ndarray, b1: jnp.ndarray, w2: jnp.ndarray,
                        b2: jnp.ndarray) -> jnp.ndarray:
    """Fused XLA twin of ``sdqn_score_cols`` (CPU/GPU fallback)."""
    w1n = w1 / scale[:, None]
    hid = b1[None, :]
    for f in range(6):
        hid = hid + (cols[f].astype(jnp.float32) + deltas[f])[:, None] * w1n[f][None, :]
    return jnp.sum(jnp.maximum(hid, 0.0) * w2[:, 0][None, :], axis=-1) + b2[0]


# ---------------------------------------------------------------------------
# in-kernel per-shard top-k: score + filter + reduce without ever writing the
# shard's full score vector to HBM.  The two-stage hierarchical dispatch
# (``sched.shard``) runs one of these per node shard and merges the tiny
# (shards, k) candidate sets globally.
# ---------------------------------------------------------------------------

# tie-break sentinel: "no index".  A plain Python literal on purpose — a
# jnp constant here would be captured by the Pallas kernel closure as a
# traced value, which pallas_call rejects.
_IDX_INF = 2**31 - 1


def _tile_reduce(x, op):
    """Reduce a (rows, 128) tile to (1, 1): over sublanes, then lanes.
    Sublanes first, so a tall tile folds elementwise across its vregs before
    the one cross-lane reduction."""
    return op(op(x, axis=0, keepdims=True), axis=1, keepdims=True)


def _iter_topk(scores, idx, k: int):
    """k iterative (max, first-index) extractions over a whole tile.

    Ties break to the LOWEST index — exactly ``jnp.argmax``'s first-
    occurrence rule, applied k times, and ``lax.top_k``'s order, ``-inf``
    entries included — so a hierarchical merge of these candidates
    reproduces the flat argmax bit-for-bit.  Elementwise max / where / min
    only (no sort, no gather, no concatenate), so it lowers inside a Pallas
    TPU kernel body.  Returns two lane-dense (1, 128) rows: candidate j in
    lane j, lanes >= k carry ``-inf`` / ``_IDX_INF``.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    vals = jnp.full((1, _LANES), -jnp.inf, jnp.float32)
    ids = jnp.full((1, _LANES), _IDX_INF, jnp.int32)
    left = idx >= 0                     # not yet extracted
    for j in range(k):
        m = _tile_reduce(jnp.where(left, scores, -jnp.inf), jnp.max)
        a = _tile_reduce(jnp.where(left & (scores == m), idx, _IDX_INF),
                         jnp.min)
        vals = jnp.where(lane == j, m, vals)
        ids = jnp.where(lane == j, a, ids)
        left = left & (idx != a)
    return vals, ids


def _merge_topk(vals, idx, k: int):
    """Merge the per-block candidate rows into the global (k,) top-k.

    ``vals``/``idx`` are the kernels' (1, G * 128) lane-dense outputs; block
    ``g``'s candidates sit in lanes ``[g * 128, g * 128 + k)``.  ``lax.top_k``
    over the block-major flatten keeps ties in ascending flat position;
    blocks cover ascending index ranges and ``_iter_topk`` emits within-block
    ties in ascending index, so the merged ties stay in ascending GLOBAL
    index — the first-occurrence argmax rule survives the hierarchy.  Same
    rule merges shard candidates in ``sched.shard``.
    """
    flat_v = vals.reshape(-1, _LANES)[:, :k].reshape(-1)
    flat_i = idx.reshape(-1, _LANES)[:, :k].reshape(-1)
    top_v, pos = jax.lax.top_k(flat_v, k)
    return top_v, flat_i[pos]


def _global_idx(block_rows):
    """(block_rows, 128) global node index of each slot of this grid step."""
    shape = (block_rows, _LANES)
    row = (pl.program_id(0) * block_rows
           + jax.lax.broadcasted_iota(jnp.int32, shape, 0))
    return row * _LANES + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _score_then_select(masked_scores, k, q_ref, ov_ref, oi_ref):
    """The body of a top-k grid step: fill the block's scores scratch one
    (8, 128) tile at a time, then run the k selection rounds once over the
    whole block.  ``masked_scores(rows)`` gives the tile's feasible-masked
    scores; scoring a tile at a time lets its working set stay in vregs."""
    block_rows = q_ref.shape[0]

    def tile(t, carry):
        rows = pl.ds(pl.multiple_of(t * _SUBLANES, _SUBLANES), _SUBLANES)
        q_ref[rows, :] = masked_scores(rows)
        return carry

    jax.lax.fori_loop(0, block_rows // _SUBLANES, tile, 0)
    vals, ids = _iter_topk(q_ref[...], _global_idx(block_rows), k)
    ov_ref[...] = vals
    oi_ref[...] = ids


def _topk_call(kernel, grids, block_rows, pack, k, interpret):
    """Run a top-k kernel over its (rows, 128) column views: one grid step a
    block, each emitting one lane-dense (1, 128) candidate row, merged to
    the (k,) top-k."""
    g = grids[0].shape[0] // block_rows
    out_spec = pl.BlockSpec((1, _LANES), lambda i: (0, i))
    vals, idx = pl.pallas_call(
        kernel,
        grid=(g,),
        in_specs=[_col_spec(block_rows)] * len(grids) + [_SMEM_SPEC],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((1, g * _LANES), jnp.float32),
                   jax.ShapeDtypeStruct((1, g * _LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((block_rows, _LANES), jnp.float32)],
        interpret=interpret,
    )(*grids, pack)
    return _merge_topk(vals, idx, k)


def _afterstate_topk_kernel(k, n_hidden, *refs):
    col_refs = refs[:-4]         # 14 columns, 16 with accelerators
    pack_ref, ov_ref, oi_ref, q_ref = refs[-4:]

    def s(i):
        return pack_ref[0, i]

    def masked_scores(rows):
        (base, pcpu, scpu, npod, epod, mem, cached, health, up, cap, mcap,
         mpod, creq, mreq) = [r[rows, :] for r in col_refs[:14]]
        feats = _afterstate_norm_features(
            base, pcpu, scpu, npod, epod, mem, cached, health, up, cap, mcap,
            mpod, s)
        q = _qnet(feats, pack_ref, n_hidden) + s(_S_B2)
        # k8s filtering phase, in-kernel (env.feasible): padded lanes arrive
        # with healthy == 0 and capacity == 1, so they are masked right here
        ok = ((health > 0.5)
              & (creq + s(_S_CPU_REQ) <= cap)
              & (mreq + s(_S_MEM_REQ) <= mcap)
              & (npod < mpod))
        if len(col_refs) == 16:
            # extended resources are never overcommitted; counts are exact
            # in float32 up to 2**24
            acap, areq = (r[rows, :] for r in col_refs[14:])
            ok = ok & (areq + s(_S_ACCEL_REQ) <= acap)
        return jnp.where(ok, q, -jnp.inf)

    _score_then_select(masked_scores, k, q_ref, ov_ref, oi_ref)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def sdqn_score_afterstate_topk(
    node_cols: tuple,      # 14 x (N,): the 12 afterstate columns (see
    #                        ``sdqn_score_afterstate``) + cpu_requested,
    #                        mem_requested (filtering-phase columns); 16 with
    #                        accel_capacity, accel_requested
    scalars: jnp.ndarray,  # (_N_SCALARS,) pack incl. _S_CPU_REQ/_S_MEM_REQ
    #                        (and _S_ACCEL_REQ with 16 columns)
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    *,
    k: int = 4,
    interpret: bool = False,
):
    """((k,) scores, (k,) indices): the shard's feasible top-k, in-kernel.

    A grid step scores its block into a VMEM scratch and selects the
    block's k candidates from it; only those leave the kernel, so the full
    score vector never reaches HBM.  The block is the whole shard up to
    16,384 nodes (``_topk_tiling``), so a shard is selected from once; a
    longer column is cut into a few blocks whose candidate rows
    ``_merge_topk`` merges.  Infeasible nodes score
    ``-inf``; an all-infeasible shard returns all ``-inf`` (the merge layer
    maps that to the NO_PLACEMENT sentinel).  The column count picks the
    variant: 16 columns add the accelerator predicate to the in-kernel
    filter.
    """
    if not 1 <= k <= _LANES:
        raise ValueError(f"k must be in [1, {_LANES}], got {k}")
    if len(node_cols) not in (14, 16):
        raise ValueError(f"14 or 16 node columns, got {len(node_cols)}")
    n = node_cols[0].shape[0]
    block_rows, rows = _topk_tiling(n)
    grids = _grid_cols(node_cols[:9], rows) + _grid_cols(
        node_cols[9:12], rows, pad_value=1.0) + _grid_cols(node_cols[12:], rows)
    return _topk_call(
        functools.partial(_afterstate_topk_kernel, k, w1.shape[1]), grids,
        block_rows, _pack(scalars, w1, b1, w2), k, interpret)


@functools.partial(jax.jit, static_argnames=("k",))
def sdqn_score_afterstate_topk_xla(node_cols: tuple, scalars: jnp.ndarray,
                                   w1: jnp.ndarray, b1: jnp.ndarray,
                                   w2: jnp.ndarray, *, k: int = 4):
    """XLA twin: fused scoring + in-register filtering + ``lax.top_k``.

    ``lax.top_k`` breaks ties to the lowest index, matching the kernel's
    iterative extraction exactly; the shard-local (N,) intermediate lives
    only inside this fused computation.
    """
    cols = [c.astype(jnp.float32) for c in node_cols]
    q = sdqn_score_afterstate_xla(tuple(cols[:12]), scalars, w1, b1, w2)
    ok = ((cols[7] > 0.5)
          & (cols[12] + scalars[_S_CPU_REQ] <= cols[9])
          & (cols[13] + scalars[_S_MEM_REQ] <= cols[10])
          & (cols[3] < cols[11]))
    if len(cols) == 16:
        ok = ok & (cols[15] + scalars[_S_ACCEL_REQ] <= cols[14])
    k = min(k, q.shape[0])
    return jax.lax.top_k(jnp.where(ok, q, -jnp.inf), k)


def _cols_topk_kernel(k, n_hidden, c0, c1, c2, c3, c4, c5, pack_ref, ov_ref,
                      oi_ref, q_ref):
    def masked_scores(rows):
        feats = [c[rows, :] + pack_ref[0, f]
                 for f, c in enumerate((c0, c1, c2, c3, c4, c5))]
        q = _qnet(feats, pack_ref, n_hidden) + pack_ref[0, 6]
        # placement.fleet_feasible, in-kernel: healthy + post-delta ceilings
        # on the cpu / mem / job-util percent columns (pack 7..9)
        ok = ((c3[rows, :] > 0.5)
              & (feats[0] <= pack_ref[0, 7])
              & (feats[1] <= pack_ref[0, 8])
              & (feats[2] <= pack_ref[0, 9]))
        return jnp.where(ok, q, -jnp.inf)

    _score_then_select(masked_scores, k, q_ref, ov_ref, oi_ref)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def sdqn_score_cols_topk(
    cols: tuple,
    deltas: jnp.ndarray,
    scale: jnp.ndarray,
    w1: jnp.ndarray,
    b1: jnp.ndarray,
    w2: jnp.ndarray,
    b2: jnp.ndarray,
    ceilings,          # (3,): max cpu_pct, max mem_pct, max job_util_pct
    *,
    k: int = 4,
    interpret: bool = False,
):
    """Per-shard feasible top-k of ``sdqn_score_cols``, reduced in-kernel
    (blocks as in ``sdqn_score_afterstate_topk``)."""
    if not 1 <= k <= _LANES:
        raise ValueError(f"k must be in [1, {_LANES}], got {k}")
    block_rows, rows = _topk_tiling(cols[0].shape[0])
    # healthy (col 3) pads 0 -> infeasible; the rest pad 0 and stay finite
    return _topk_call(
        functools.partial(_cols_topk_kernel, k, w1.shape[1]),
        _grid_cols(cols, rows), block_rows,
        _cols_pack(deltas, b2, w1 / scale[:, None], b1, w2, ceilings), k,
        interpret)


@functools.partial(jax.jit, static_argnames=("k",))
def sdqn_score_cols_topk_xla(cols: tuple, deltas: jnp.ndarray,
                             scale: jnp.ndarray, w1: jnp.ndarray,
                             b1: jnp.ndarray, w2: jnp.ndarray,
                             b2: jnp.ndarray, ceilings, *, k: int = 4):
    """XLA twin of ``sdqn_score_cols_topk`` (fused score + mask + top_k)."""
    q = sdqn_score_cols_xla(cols, deltas, scale, w1, b1, w2, b2)
    cl = jnp.asarray(ceilings, jnp.float32)
    ok = ((cols[3] > 0.5)
          & (cols[0] + deltas[0] <= cl[0])
          & (cols[1] + deltas[1] <= cl[1])
          & (cols[2] + deltas[2] <= cl[2]))
    k = min(k, q.shape[0])
    return jax.lax.top_k(jnp.where(ok, q, -jnp.inf), k)
