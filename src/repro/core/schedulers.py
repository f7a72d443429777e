"""Scheduler policies: SDQN, SDQN-n, and the neural baselines, as
``(key, state, pod) -> node`` selectors compatible with ``env.run_episode``.

All policies apply the k8s *filtering* phase first (paper §3.2) and only
score feasible nodes; SDQN/SDQN-n score afterstates with the DQN.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core import dqn, env as kenv
from repro.core.types import ClusterState, EnvConfig, PodSpec

NEG_INF = -jnp.inf

# Above this node count, SDQN scoring goes through the fused afterstate
# kernel (repro.kernels.ops.sdqn_score_afterstate): afterstate features are
# computed *inside* the scoring kernel, so the (N, 6) feature matrix is never
# materialized in HBM.  Below it, the plain O(N) jnp path wins on dispatch
# overhead.  n_nodes is a static shape, so the branch resolves at trace time.
FUSED_SCORE_MIN_NODES = 4096


def masked_argmax(key: jax.Array, scores: jnp.ndarray, ok: jnp.ndarray,
                  epsilon: jnp.ndarray | float = 0.0) -> jnp.ndarray:
    """Greedy over feasible nodes, with epsilon-greedy exploration.

    Returns ``env.NO_NODE`` (-1) when no node is feasible: an argmax over
    all ``-inf`` scores would silently return node 0, binding pods to
    full/unhealthy nodes during infeasible bursts.  ``env.place`` treats the
    sentinel as a no-op and ``env.run_episode`` counts it as a drop.
    """
    scores = jnp.where(ok, scores, NEG_INF)
    greedy = jnp.argmax(scores).astype(jnp.int32)
    ke, kr = jax.random.split(key)
    explore = jax.random.uniform(ke) < epsilon
    noise = jnp.where(ok, jax.random.uniform(kr, scores.shape), NEG_INF)
    rand = jnp.argmax(noise).astype(jnp.int32)
    choice = jnp.where(explore, rand, greedy)
    return jnp.where(jnp.any(ok), choice, jnp.int32(kenv.NO_NODE))


def with_embedding(feats: jnp.ndarray, embed=None) -> jnp.ndarray:
    """``(..., F)`` afterstate rows with a sequence policy's history
    ``embed`` appended to every row (``feats`` itself where it is None)."""
    if embed is None:
        return feats
    return jnp.concatenate(
        [feats, jnp.broadcast_to(embed, feats.shape[:-1] + embed.shape)],
        axis=-1)


def score_afterstates(qparams: dict, state: ClusterState, pod: PodSpec,
                      cfg: EnvConfig, score_fn=None,
                      fused: bool | str = "auto", policy=None,
                      embed=None, pull_cost=None) -> jnp.ndarray:
    """(N,) scores: Q(afterstate_i) for each candidate node i.

    This is the ONE scoring dispatch the trainer, the serving daemon, the
    consolidator, and the public ``repro.sched.api.score`` entry point share.

    ``fused`` selects the backend:
      * ``"auto"`` (default) — the fused kernel path (Pallas on TPU, a fused
        XLA twin elsewhere; afterstate features are computed in-kernel and
        the (N, 6) matrix never hits HBM) when the default Table-4 Q-net is
        used and ``N >= FUSED_SCORE_MIN_NODES``; the plain O(N) jnp path
        below that, where dispatch overhead dominates;
      * ``True`` — force the fused path at any N;
      * ``"interpret"`` — the Pallas kernel body in interpret mode (kernel
        correctness sweeps on CPU);
      * ``False`` — force the unfused jnp path.

    ``policy`` (a ``core.policy.PolicySpec``) swaps the scorer for a
    registered policy class: candidates are scored through
    ``policy.score_set`` over the whole (N, F) set, with ``embed`` (the
    policy's history embedding, for ``embed_dim > 0`` specs) appended to
    every row.  Fused-capable specs ("mlp") keep the kernel path; every
    other spec — like a custom ``score_fn`` (LSTM/Transformer baselines) —
    always takes the jnp path, since it cannot be fused into the afterstate
    kernel.

    ``pull_cost`` pins the image-pull contention scalar instead of reducing
    it from ``state`` — sharded scoring (``sched.shard``) computes this
    GLOBAL reduction once over the full fleet and threads it into each
    per-shard call, keeping shard-local scores identical to the unsharded
    program.
    """
    if score_fn is not None and policy is not None:
        raise ValueError("pass either score_fn or policy, not both")
    fusable = score_fn is None and (policy is None or policy.fused_kernel)
    if fused in (True, "interpret") and not fusable:
        raise ValueError("custom score_fn / non-fusable policy cannot take "
                         "the fused kernel path")
    use_fused = fused in (True, "interpret") or (
        fused == "auto" and fusable
        and state.n_nodes >= FUSED_SCORE_MIN_NODES)
    if use_fused:
        from repro.kernels import ops

        mode = "interpret" if fused == "interpret" else None
        return ops.sdqn_score_afterstate(state, pod, cfg, qparams, mode=mode,
                                         pull_cost=pull_cost)
    after = kenv.hypothetical_place(state, pod, cfg,
                                    pull_cost=pull_cost)   # (N, 6) raw
    feats = kenv.normalize_features(after)
    if policy is not None:
        return policy.score_set(qparams, with_embedding(feats, embed))
    fn = score_fn or dqn.qvalues
    return fn(qparams, feats)


def make_sdqn_selector(qparams: dict, cfg: EnvConfig, epsilon: float = 0.0,
                       score_fn=None) -> Callable:
    def select(key, state, pod):
        ok = kenv.feasible(state, pod, cfg)
        q = score_afterstates(qparams, state, pod, cfg, score_fn)
        return masked_argmax(key, q, ok, epsilon)

    return select


# SDQN-n uses the same scoring machinery; consolidation comes from the reward
# the network was trained on (Table 5), not from a different selector.
make_sdqn_n_selector = make_sdqn_selector


def make_policy_selector(spec, params: dict, cfg: EnvConfig,
                         epsilon: float = 0.0):
    """Episode selector for any registered policy class.

    Returns ``(select, carry0)``:

      * stateless specs (``embed_dim == 0``, or ``spec is None`` = the
        default Table-4 net): ``select(key, state, pod) -> node`` and
        ``carry0 is None`` — drop-in for ``env.run_episode``;
      * sequence specs: ``select(key, state, pod, carry) -> (node, carry)``
        plus the initial carry — pass both to ``env.run_episode`` via
        ``select_carry`` so the history threads through the scanned episode.
    """
    if spec is None or spec.embed_dim == 0:

        def select(key, state, pod):
            ok = kenv.feasible(state, pod, cfg)
            q = score_afterstates(params, state, pod, cfg, policy=spec)
            return masked_argmax(key, q, ok, epsilon)

        return select, None

    from repro.core import policy as policy_mod

    def select(key, state, pod, carry):
        carry2, emb = spec.encode_step(
            params, carry, policy_mod.pod_workload_features(pod))
        ok = kenv.feasible(state, pod, cfg)
        q = score_afterstates(params, state, pod, cfg, policy=spec, embed=emb)
        return masked_argmax(key, q, ok, epsilon), carry2

    return select, spec.carry_init(params)


def make_neural_selector(params: dict, score_fn, cfg: EnvConfig) -> Callable:
    """LSTM / Transformer baselines: same afterstate scoring protocol."""

    def select(key, state, pod):
        ok = kenv.feasible(state, pod, cfg)
        scores = score_afterstates(params, state, pod, cfg, score_fn)
        return masked_argmax(key, scores, ok, 0.0)

    return select


def make_kube_selector(cfg: EnvConfig) -> Callable:
    from repro.core import baselines

    def select(key, state, pod):
        return baselines.kube_select(key, state, pod, cfg)

    return select
