"""Jit'd public wrappers around the Pallas kernels with backend dispatch.

Policy:
  * on TPU       -> the Pallas kernel (compiled)
  * on CPU/GPU   -> the XLA path (chunked-jnp implementations from
                    ``repro.models`` — semantically identical, memory-safe)
  * ``mode="interpret"`` -> the Pallas kernel body executed in interpret
                    mode (used by the kernel correctness sweeps on CPU)
  * ``mode="ref"`` -> the pure-jnp oracle

The model code calls these entry points, so the same model runs under
dry-run lowering on the CPU container and under real kernels on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as _da
from repro.kernels import flash_attention as _fa
from repro.kernels import mamba_scan as _ms
from repro.kernels import ref
from repro.kernels import sdqn_score as _ss


def _default_mode() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def flash_attention(q, k, v, *, causal=True, mode: Optional[str] = None,
                    block_q: int = 256, block_k: int = 256):
    mode = mode or _default_mode()
    if mode == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)
    if mode == "interpret":
        return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                                   block_k=block_k, interpret=True)
    if mode == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal)
    from repro.models import layers  # XLA path: query-chunked online attention

    return layers.attention(q, k, v, causal=causal, q_chunk=block_q)


def decode_attention(q, k, v, kv_len, *, mode: Optional[str] = None, block_k: int = 512):
    mode = mode or _default_mode()
    if mode == "pallas":
        return _da.decode_attention(q, k, v, kv_len, block_k=block_k)
    if mode == "interpret":
        return _da.decode_attention(q, k, v, kv_len, block_k=block_k, interpret=True)
    return ref.decode_attention_ref(q, k, v, kv_len)


def mamba_scan(x, dt, a, bmat, cmat, d_skip, h0, *, mode: Optional[str] = None,
               block_d: int = 512, block_s: int = 256, chunk: int = 64):
    mode = mode or _default_mode()
    if mode == "pallas":
        return _ms.mamba_scan(x, dt, a, bmat, cmat, d_skip, h0,
                              block_d=block_d, block_s=block_s)
    if mode == "interpret":
        return _ms.mamba_scan(x, dt, a, bmat, cmat, d_skip, h0,
                              block_d=block_d, block_s=block_s, interpret=True)
    if mode == "ref":
        return ref.mamba_scan_ref(x, dt, a, bmat, cmat, d_skip, h0)
    from repro.models import mamba  # XLA path: chunked associative scan

    return mamba.selective_scan(x, dt, a, bmat, cmat, d_skip, h0, chunk=chunk)


def _mlp_weights(params):
    """The fused SDQN kernels hardwire the Table-4 MLP over the canonical
    ``types.FEATURE_DIM``-wide afterstate row; reject any other policy
    class's params up front (wider sequence-policy rows must take the
    unfused ``PolicySpec.score_set`` path, never the column kernels)."""
    from repro.core.types import FEATURE_DIM

    w1 = params["w1"]
    if w1.shape[0] != FEATURE_DIM:
        raise ValueError(
            f"fused SDQN kernels score {FEATURE_DIM}-wide afterstate rows; "
            f"got w1 input width {w1.shape[0]} (non-MLP policy params?)")
    return w1, params["b1"], params["w2"], params["b2"]


def sdqn_score(feats, params, *, mode: Optional[str] = None, block_n: int = 1024):
    """Score N nodes through the Table-4 Q-net. params: repro.core.dqn pytree."""
    mode = mode or _default_mode()
    w1, b1, w2, b2 = _mlp_weights(params)
    if mode == "pallas":
        return _ss.sdqn_score(feats, w1, b1, w2, b2, block_n=block_n)
    if mode == "interpret":
        return _ss.sdqn_score(feats, w1, b1, w2, b2, block_n=block_n, interpret=True)
    return ref.sdqn_score_ref(feats, w1, b1, w2, b2)


def _afterstate_inputs(state, pod, cfg, params, pull_cost=None):
    """(12 raw columns, scalar pack, w1, b1, w2) for the afterstate kernels.

    ``pull_cost`` overrides the in-flight pull-contention scalar — a GLOBAL
    reduction over ``startup_cpu`` that sharded scoring (``sched.shard``)
    must compute once from the full fleet and thread into every shard.
    """
    from repro.core import env as kenv

    cols = (
        state.base_cpu, state.pods_cpu, state.startup_cpu,
        state.num_pods, state.exp_pods, state.mem_used,
        state.image_cached, state.healthy, state.uptime_hours,
        state.cpu_capacity, state.mem_capacity, state.max_pods,
    )
    pull = kenv.pull_cost_now(state, cfg) if pull_cost is None else pull_cost
    scalars = jnp.zeros((_ss._N_SCALARS,), jnp.float32)
    scalars = scalars.at[_ss._S_CPU_DEMAND].set(pod.cpu_demand)
    scalars = scalars.at[_ss._S_MEM_DEMAND].set(pod.mem_demand)
    scalars = scalars.at[_ss._S_PULL].set(pull)
    scalars = scalars.at[_ss._S_WARM].set(cfg.warm_start_cost)
    scalars = scalars.at[_ss._S_OVERHEAD].set(cfg.node_active_overhead)
    scalars = scalars.at[_ss._S_CROWD_KNEE].set(cfg.crowd_knee)
    scalars = scalars.at[_ss._S_CROWD_COEFF].set(cfg.crowd_coeff)
    scalars = scalars.at[_ss._S_CONT_KNEE].set(cfg.contention_knee)
    scalars = scalars.at[_ss._S_CONT_COEFF].set(cfg.contention_coeff)
    scalars = scalars.at[_ss._S_UPTIME_SCALE].set(kenv.FEATURE_SCALE[4])
    scalars = scalars.at[_ss._S_EXP_SCALE].set(kenv.FEATURE_SCALE[5])
    w1, b1, w2, b2 = _mlp_weights(params)
    scalars = scalars.at[_ss._S_B2].set(jnp.reshape(b2, ()))
    return cols, scalars, w1, b1, w2


def sdqn_score_afterstate(state, pod, cfg, params, *, mode: Optional[str] = None,
                          block_n: int = 1024, pull_cost=None):
    """Q-values (N,) of every candidate afterstate, features fused in-kernel.

    Accepts the raw ``ClusterState`` columns plus the pod's placement delta
    and mirrors ``env.hypothetical_place``'s O(N) arithmetic inside the
    scoring kernel, so the (N, 6) afterstate feature matrix is never
    materialized in HBM.  ``mode``: ``pallas`` (TPU) / ``interpret`` /
    ``xla`` (fused jnp twin, default off-TPU) / ``ref`` (unfused oracle:
    ``hypothetical_place`` + ``dqn.qvalues``).
    """
    from repro.core import env as kenv

    mode = mode or _default_mode()
    if mode == "ref":
        from repro.core import dqn

        after = kenv.hypothetical_place(state, pod, cfg, pull_cost=pull_cost)
        return dqn.qvalues(params, kenv.normalize_features(after))

    cols, scalars, w1, b1, w2 = _afterstate_inputs(state, pod, cfg, params,
                                                   pull_cost)
    if mode == "xla":
        return _ss.sdqn_score_afterstate_xla(cols, scalars, w1, b1, w2)
    return _ss.sdqn_score_afterstate(cols, scalars, w1, b1, w2,
                                     block_n=block_n,
                                     interpret=(mode == "interpret"))


def sdqn_topk_afterstate(state, pod, cfg, params, *, k: int = 4,
                         mode: Optional[str] = None, pull_cost=None):
    """((k,) scores, (k,) node indices): the feasible top-k of one shard's
    candidate afterstates, scored AND reduced in-kernel.

    The per-shard stage of two-stage hierarchical scoring (``sched.shard``):
    the k8s filtering phase (``env.feasible``, with the accelerator
    predicate where the state carries accelerators) and the Q-net both run
    inside the kernel, and only k candidates per shard ever reach HBM.  Infeasible
    nodes carry ``-inf``; ties break to the lowest index (``jnp.argmax``'s
    first-occurrence rule), so merging shard candidates reproduces the flat
    masked argmax exactly.  ``mode="ref"`` is the unfused oracle:
    ``hypothetical_place`` + ``qvalues`` + ``feasible`` + ``lax.top_k``.
    """
    from repro.core import env as kenv

    mode = mode or _default_mode()
    if mode == "ref":
        from repro.core import dqn

        after = kenv.hypothetical_place(state, pod, cfg, pull_cost=pull_cost)
        q = dqn.qvalues(params, kenv.normalize_features(after))
        ok = kenv.feasible(state, pod, cfg)
        return jax.lax.top_k(jnp.where(ok, q, -jnp.inf), min(k, q.shape[0]))

    cols, scalars, w1, b1, w2 = _afterstate_inputs(state, pod, cfg, params,
                                                   pull_cost)
    cols = cols + (state.cpu_requested, state.mem_requested)
    scalars = scalars.at[_ss._S_CPU_REQ].set(pod.cpu_request)
    scalars = scalars.at[_ss._S_MEM_REQ].set(pod.mem_request)
    accel = kenv.accel_ask(state, pod)
    if state.accel_capacity is not None:
        # the extended-resource filter: two more columns, and the request
        cols = cols + (state.accel_capacity, state.accel_requested)
        if accel is not None:
            scalars = scalars.at[_ss._S_ACCEL_REQ].set(
                accel.astype(jnp.float32))
    if mode == "xla":
        return _ss.sdqn_score_afterstate_topk_xla(cols, scalars, w1, b1, w2,
                                                  k=k)
    return _ss.sdqn_score_afterstate_topk(cols, scalars, w1, b1, w2, k=k,
                                          interpret=(mode == "interpret"))


def sdqn_score_delta(cols, deltas, params, *, mode: Optional[str] = None,
                     block_n: int = 1024):
    """Q((cols + deltas) / FEATURE_SCALE) for column-structured fleets.

    The serving-path scorer (``sched.placement``): six raw feature columns
    plus the job's afterstate delta, assembled and scored in one fused pass
    (Pallas on TPU, fused XLA twin elsewhere, ``ref`` = stack + qvalues).
    """
    from repro.core import env as kenv

    mode = mode or _default_mode()
    w1, b1, w2, b2 = _mlp_weights(params)
    if mode == "ref":
        feats = (jnp.stack(cols, axis=-1) + deltas[None, :]) / kenv.FEATURE_SCALE
        return ref.sdqn_score_ref(feats, w1, b1, w2, b2)
    if mode == "xla":
        return _ss.sdqn_score_cols_xla(tuple(cols), deltas, kenv.FEATURE_SCALE,
                                       w1, b1, w2, b2)
    return _ss.sdqn_score_cols(tuple(cols), deltas, kenv.FEATURE_SCALE, w1, b1,
                               w2, b2, block_n=block_n,
                               interpret=(mode == "interpret"))


def sdqn_topk_delta(cols, deltas, params, *, k: int = 4,
                    mode: Optional[str] = None,
                    ceilings=(88.0, 95.0, 100.0 + 1e-6)):
    """((k,) scores, (k,) host indices): feasible top-k of the column scorer.

    The FleetState arm of per-shard top-k scoring: the
    ``PlacementEngine.feasible`` predicates (healthy + post-delta cpu / mem /
    job-util ceilings) and the Q-net both run in-kernel, emitting only k
    candidates per shard.  ``ceilings`` are the three predicate bounds (the
    default mirrors ``PlacementEngine``'s 88 / 95 / 100).
    """
    from repro.core import env as kenv

    mode = mode or _default_mode()
    w1, b1, w2, b2 = _mlp_weights(params)
    if mode == "ref":
        feats = (jnp.stack(cols, axis=-1) + deltas[None, :]) / kenv.FEATURE_SCALE
        q = ref.sdqn_score_ref(feats, w1, b1, w2, b2)
        cl = jnp.asarray(ceilings, jnp.float32)
        ok = ((cols[3] > 0.5) & (cols[0] + deltas[0] <= cl[0])
              & (cols[1] + deltas[1] <= cl[1])
              & (cols[2] + deltas[2] <= cl[2]))
        return jax.lax.top_k(jnp.where(ok, q, -jnp.inf), min(k, q.shape[0]))
    if mode == "xla":
        return _ss.sdqn_score_cols_topk_xla(tuple(cols), deltas,
                                            kenv.FEATURE_SCALE, w1, b1, w2,
                                            b2, ceilings, k=k)
    return _ss.sdqn_score_cols_topk(tuple(cols), deltas, kenv.FEATURE_SCALE,
                                    w1, b1, w2, b2, ceilings, k=k,
                                    interpret=(mode == "interpret"))
