"""The unified public scheduling API.

Five PRs grew three overlapping scoring entry points — the trainer called
``schedulers.score_afterstates``, the job-placement engine called
``ops.sdqn_score_delta``, and the serving path stitched the two together.
This module is the ONE documented surface that wraps that shared dispatch;
the placement daemon (``sched.daemon``), the trainer, and the
``PlacementEngine`` all route through it (directly or via the same
underlying ``schedulers.score_afterstates`` dispatch).

    from repro.sched import api

    q = api.score(cluster_state, pod, params=qparams, cfg=env_cfg)   # (N,)
    q = api.score(fleet_state, job, params=qparams)                  # (N,)
    qb = api.score_batch(cluster_state, pods, params=qparams, cfg=env_cfg)

``score`` dispatches on the fleet's type:

  * ``core.types.ClusterState`` + ``core.types.PodSpec`` — the paper's pod
    scheduler: Q(afterstate) per candidate node through
    ``schedulers.score_afterstates`` (fused Pallas kernel on TPU at fleet
    scale, fused XLA twin elsewhere, plain O(N) jnp below the threshold).
    ``cfg`` (the ``EnvConfig``) is required.
  * ``sched.placement.FleetState`` + ``sched.placement.JobSpec`` — job→host
    placement: the six raw fleet columns + the job's afterstate delta
    through the fused column kernel (``ops.sdqn_score_delta``).

``fused`` selects the backend uniformly across both substrates:
``"auto"`` (default heuristics), ``True`` (force the fused path),
``"interpret"`` (Pallas kernel body in interpret mode, for CPU kernel
sweeps), ``False`` (force the unfused reference path).

``NO_PLACEMENT`` (== ``env.NO_NODE`` == ``placement.NO_HOST``) is the
sentinel every selector in the repo returns when the filtering phase leaves
no feasible target.

``shard`` mirrors ``fused`` as the *fleet-axis* knob: ``"auto"`` (default)
shards node columns across the visible devices' ``data`` axis when there is
more than one device and runs two-stage hierarchical scoring
(``sched.shard``: per-shard in-kernel top-k, then a tiny global merge — no
full N-length score vector on one device); on a single device it resolves
to the unsharded program, bit-identically.  ``False`` disables sharding; an
int forces that shard count (single-device two-stage execution, for tests
and benchmarks); a ``launch.mesh.FleetLayout`` pins an explicit layout.
"""
from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from repro.core import schedulers
from repro.core.types import NO_PLACEMENT, ClusterState, EnvConfig, PodSpec
from repro.sched import placement as _placement
from repro.sched.placement import FleetState, JobSpec

__all__ = ["DIVERGENCE_LIMIT", "NO_PLACEMENT", "heuristic_score", "score",
           "score_batch", "scores_valid", "select", "topk", "topsis_score"]

Fleet = Union[ClusterState, FleetState]
Workload = Union[PodSpec, JobSpec]

# |Q| beyond this is treated as a diverged net (a blown-up training run or a
# corrupted checkpoint), not a preference — the guard swaps in the heuristic
DIVERGENCE_LIMIT = 1e6


def heuristic_score(fleet: Fleet, pod: Workload, *,
                    cfg: Optional[EnvConfig] = None) -> jnp.ndarray:
    """(N,) kube-style LeastRequested+Balanced scores — no Q-net involved.

    The graceful-degradation fallback: when a policy class's scores miss a
    serving deadline, go NaN, or diverge, every dispatcher in the repo can
    fall back to this closed-form scorer and keep placing pods.  On a
    ``ClusterState`` it IS ``baselines.kube_scores``; on a ``FleetState`` it
    is the same formula over the fleet's percent-utilization columns.
    """
    if isinstance(fleet, ClusterState):
        if cfg is None:
            raise ValueError("cfg (EnvConfig) is required to score a "
                             "ClusterState fleet")
        from repro.core import baselines

        return baselines.kube_scores(fleet, pod, cfg)
    if isinstance(fleet, FleetState):
        delta = _placement.job_delta(pod)
        cpu_free = (100.0 - fleet.cpu_pct - delta[0]) / 100.0
        mem_free = (100.0 - fleet.mem_pct - delta[1]) / 100.0
        least_requested = 10.0 * (cpu_free + mem_free) / 2.0
        balanced = 10.0 * (1.0 - jnp.abs(cpu_free - mem_free))
        return least_requested + balanced
    raise TypeError(f"unsupported fleet type: {type(fleet).__name__}")


def topsis_score(fleet: Fleet, pod: Workload, *,
                 cfg: Optional[EnvConfig] = None,
                 weights=None) -> jnp.ndarray:
    """(N,) TOPSIS closeness coefficients — the multi-objective non-RL
    baseline (``sched.topsis``, GreenPod-shaped: CPU / memory / wake-energy
    / imbalance cost columns, distance-to-ideal ranking).  Same substrate
    dispatch as ``heuristic_score``; higher = better, mask feasibility at
    the caller like every other scorer."""
    from repro.sched import topsis as _topsis

    weights = _topsis.DEFAULT_WEIGHTS if weights is None else weights
    return _topsis.topsis_scores(fleet, pod, cfg=cfg, weights=weights)


def scores_valid(q: jnp.ndarray) -> jnp.ndarray:
    """Scalar bool: all scores finite and inside ``DIVERGENCE_LIMIT``."""
    return jnp.all(jnp.isfinite(q) & (jnp.abs(q) <= DIVERGENCE_LIMIT))


def _fleet_mode(fused) -> Optional[str]:
    """Map the uniform ``fused`` knob onto ``ops.sdqn_score_delta`` modes."""
    if fused == "auto":
        return None          # backend default: Pallas on TPU, fused XLA twin
    if fused is True:
        return None
    if fused == "interpret":
        return "interpret"
    if fused is False:
        return "ref"
    raise ValueError(f"fused must be 'auto', True, False or 'interpret'; "
                     f"got {fused!r}")


def _fleet_scores(fleet: FleetState, delta: jnp.ndarray, *, params: dict,
                  fused="auto", policy=None, embed=None) -> jnp.ndarray:
    """(N,) FleetState scores of the job whose afterstate ``delta`` is
    given: the fused column kernel (``ops.sdqn_score_delta``) for the
    default net and fusable specs, else ``policy.score_set`` over the
    afterstate rows with ``embed`` appended."""
    if policy is not None and not policy.fused_kernel:
        return policy.score_set(
            params, _placement.afterstate_rows(fleet, delta, embed))
    from repro.kernels import ops

    return ops.sdqn_score_delta(_placement.fleet_cols(fleet), delta, params,
                                mode=_fleet_mode(fused))


def _fleet_size(fleet: Fleet) -> int:
    return (fleet.n_nodes if isinstance(fleet, ClusterState)
            else fleet.cpu_pct.shape[0])


def score(fleet: Fleet, pod: Workload, *, params: dict,
          cfg: Optional[EnvConfig] = None, fused="auto", shard="auto",
          score_fn=None, policy=None, embed=None,
          guard: bool = False) -> jnp.ndarray:
    """(N,) Q-scores of placing ``pod`` on each target in ``fleet``.

    See the module docstring for the dispatch rules.  ``score_fn`` swaps the
    Table-4 Q-net for a custom scorer (LSTM/Transformer baselines;
    ClusterState substrate only, always the unfused path).  ``policy`` (a
    ``core.policy.PolicySpec``) swaps in a registered policy class on either
    substrate; ``embed`` is its history embedding for sequence specs.

    ``shard`` (module docstring) distributes the fleet axis: with a
    resolved layout the vector is computed shard-by-shard and stays
    device-sharded along ``data`` — logically (N,), physically never
    gathered until the caller syncs it.  Selection-only callers should
    prefer ``topk``/``select``, which never build the vector at all.

    ``guard=True`` validates the scores at this dispatch — NaN/inf or
    ``|Q| > DIVERGENCE_LIMIT`` anywhere in the vector swaps the WHOLE vector
    for ``heuristic_score`` (jit-safe ``where``, so it composes with every
    policy class and both substrates).  Serving paths set it; the training
    loop keeps the unguarded hot path.
    """
    from repro.sched import shard as _shard

    layout = _shard.resolve_layout(shard, _fleet_size(fleet))
    if layout is None:
        q = _score_raw(fleet, pod, params=params, cfg=cfg, fused=fused,
                       score_fn=score_fn, policy=policy, embed=embed)
    else:
        q = _shard.sharded_scores(fleet, pod, params=params, cfg=cfg,
                                  layout=layout, fused=fused,
                                  score_fn=score_fn, policy=policy,
                                  embed=embed)
    if not guard:
        return q
    return jnp.where(scores_valid(q), q, heuristic_score(fleet, pod, cfg=cfg))


def _score_raw(fleet: Fleet, pod: Workload, *, params: dict,
               cfg: Optional[EnvConfig] = None, fused="auto",
               score_fn=None, policy=None, embed=None) -> jnp.ndarray:
    if isinstance(fleet, ClusterState):
        if cfg is None:
            raise ValueError("cfg (EnvConfig) is required to score a "
                             "ClusterState fleet")
        return schedulers.score_afterstates(params, fleet, pod, cfg,
                                            score_fn=score_fn, fused=fused,
                                            policy=policy, embed=embed)
    if isinstance(fleet, FleetState):
        if score_fn is not None:
            raise ValueError("score_fn is not supported on the FleetState "
                             "column-kernel path")
        return _fleet_scores(fleet, _placement.job_delta(pod), params=params,
                             fused=fused, policy=policy, embed=embed)
    raise TypeError(f"unsupported fleet type: {type(fleet).__name__}")


def score_batch(fleet: Fleet, pods: Workload, *, params: dict,
                cfg: Optional[EnvConfig] = None, fused="auto",
                score_fn=None, policy=None) -> jnp.ndarray:
    """(B, N) Q-scores for a batch of workloads against ONE fleet snapshot.

    ``pods``: a ``PodSpec`` with a leading (B,) batch dim on every field
    (ClusterState substrate), or a sequence of B ``JobSpec``s (FleetState
    substrate).  Under ``jit`` the whole batch lowers to one device launch —
    this is the serving daemon's batched scoring pass.
    """
    if isinstance(fleet, ClusterState):
        if cfg is None:
            raise ValueError("cfg (EnvConfig) is required to score a "
                             "ClusterState fleet")
        return jax.vmap(lambda p: schedulers.score_afterstates(
            params, fleet, p, cfg, score_fn, fused, policy=policy))(pods)
    if isinstance(fleet, FleetState):
        return jnp.stack([_fleet_scores(fleet, _placement.job_delta(j),
                                        params=params, fused=fused,
                                        policy=policy) for j in pods])
    raise TypeError(f"unsupported fleet type: {type(fleet).__name__}")


def topk(fleet: Fleet, pod: Workload, *, params: dict,
         cfg: Optional[EnvConfig] = None, k: int = 4, fused="auto",
         shard="auto", score_fn=None, policy=None, embed=None):
    """The ``k`` best feasible targets: ``(values, indices)`` sorted
    descending, ties by ascending index.  Infeasible slots carry ``-inf`` /
    index ``-1``; element 0 matches ``select`` exactly (modulo the sentinel).

    With a resolved shard layout this is the two-stage hierarchical path —
    per-shard in-kernel top-k, global merge over ``shards × k`` candidates —
    and the result may hold up to ``shards * k`` entries (all candidates
    that survived stage 1, the daemon's conflict-fallback depth).  Unsharded
    it is a plain masked ``lax.top_k``.
    """
    from repro.sched import shard as _shard

    n = _fleet_size(fleet)
    layout = _shard.resolve_layout(shard, n)
    if layout is not None:
        return _shard.topk(fleet, pod, params=params, cfg=cfg, layout=layout,
                           k=k, fused=fused, score_fn=score_fn,
                           policy=policy, embed=embed)
    q = _score_raw(fleet, pod, params=params, cfg=cfg, fused=fused,
                   score_fn=score_fn, policy=policy, embed=embed)
    ok = _feasible(fleet, pod, cfg)
    vals, idx = jax.lax.top_k(jnp.where(ok, q, -jnp.inf), max(1, min(k, n)))
    return vals, jnp.where(jnp.isfinite(vals), idx, -1)


def _feasible(fleet: Fleet, pod: Workload, cfg) -> jnp.ndarray:
    if isinstance(fleet, ClusterState):
        from repro.core import env as kenv

        return kenv.feasible(fleet, pod, cfg)
    return _placement.fleet_feasible(fleet, _placement.job_delta(pod))


def select(fleet: Fleet, pod: Workload, *, params: dict,
           cfg: Optional[EnvConfig] = None, fused="auto", shard="auto",
           score_fn=None, policy=None, guard: bool = False) -> jnp.ndarray:
    """Greedy feasible argmax over ``score``; ``NO_PLACEMENT`` if none fit.

    The one-shot convenience wrapper (scores + k8s filtering phase in one
    call).  For continuous serving use ``sched.daemon.PlacementDaemon``,
    which batches requests and binds with optimistic concurrency.
    ``guard=True`` falls back to the kube heuristic on NaN/diverged scores
    (see ``score``) — invalid Q values degrade the placement, never wedge it.

    With a resolved ``shard`` layout (module docstring) selection goes
    through the two-stage candidate merge and the full score vector is
    never materialized on one device; the winner is identical to the flat
    masked argmax (ties break to the lowest index at every merge stage).
    """
    from repro.sched import shard as _shard

    layout = _shard.resolve_layout(shard, _fleet_size(fleet))
    if layout is not None:
        return _shard.select_candidates(fleet, pod, params=params, cfg=cfg,
                                        layout=layout, fused=fused,
                                        score_fn=score_fn, policy=policy,
                                        guard=guard)
    q = score(fleet, pod, params=params, cfg=cfg, fused=fused, shard=False,
              score_fn=score_fn, policy=policy, guard=guard)
    ok = _feasible(fleet, pod, cfg)
    masked = jnp.where(ok, q, -jnp.inf)
    choice = jnp.argmax(masked).astype(jnp.int32)
    return jnp.where(jnp.any(ok), choice, jnp.int32(NO_PLACEMENT))
