"""Two-stage hierarchical sharded fleet scoring.

The paper's schedulers score every candidate node per decision; a single
device caps that at a few thousand nodes.  This module scales the *fleet*
axis the way the training engine scaled seed×env (``launch/mesh.py``):

  1. **Shard** — the fleet's node columns split into ``layout.shards``
     contiguous slices of ``layout.shard_size`` (``launch.mesh.FleetLayout``,
     planned by ``plan_fleet_layout``), optionally pinned to a 1-D
     ``("data",)`` device mesh with sharding constraints so each device holds
     only its own slice.
  2. **Per-shard top-k, in-kernel** — each shard runs the fused scoring
     dispatch with the k8s filtering phase *and* a top-k reduction inside the
     kernel (``ops.sdqn_topk_afterstate`` / ``ops.sdqn_topk_delta``), so only
     ``k`` (score, global-index) candidates per shard ever leave it.  On a
     device mesh this stage runs under ``shard_map``, each device scoring
     its own shards.
     Non-fusable policy classes reduce their shard-local ``score_set``
     output with ``lax.top_k`` instead — same candidate contract.
  3. **Global merge** — one tiny top-k over the ``shards × k`` candidates.
     Ties break to the lowest global index at every stage (the
     first-occurrence ``jnp.argmax`` rule), so the merged winner is exactly
     the flat masked argmax.

No full N-length score vector ever materializes on one device.  Padding to
``shards * shard_size`` uses infeasible filler (``healthy=False``, unit
capacities), so padded lanes score ``-inf`` and can never win.

Two semantics caveats, both pinned in tests/test_fleet_shard.py:

  * ``env.pull_cost_now`` is a GLOBAL reduction over in-flight startup
    transients — it is computed once from the full fleet here and threaded
    into every per-shard call as a scalar, keeping shard-local scores
    identical to the unsharded program.
  * the "attention" policy class mixes context over the node *set*, so under
    sharding it becomes block-local attention over each shard's nodes — an
    approximation by construction.  Pointwise classes ("mlp", "mamba") and
    the default Table-4 net are exact.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import baselines, env as kenv, schedulers
from repro.core.types import NO_PLACEMENT, ClusterState
from repro.kernels import ops
from repro.launch.mesh import FleetLayout, plan_fleet_layout
from repro.sched import placement as _pl

__all__ = [
    "FleetLayout", "cluster_topk", "fleet_topk", "plan_fleet_layout",
    "resolve_layout", "select_candidates", "shard_cluster", "shard_fleet",
    "sharded_scores",
]

# per-column pad fill for ClusterState: unit capacities keep padded lanes'
# arithmetic finite; healthy defaults to 0 (False) which makes them
# infeasible, so they mask to -inf before any reduction sees them
_CLUSTER_PAD = {"cpu_capacity": 1, "mem_capacity": 1, "max_pods": 1}

# |Q| beyond this is a diverged net, not a preference (sched.api's limit;
# re-declared here to keep this module importable without the api surface)
_DIVERGENCE_LIMIT = 1e6


def resolve_layout(shard, n_nodes: int, mesh=None) -> Optional[FleetLayout]:
    """Map the public ``shard=`` knob onto a :class:`FleetLayout`.

    ``"auto"`` plans one shard per visible device (``None`` on a single
    device — the bit-identical fallback); ``False``/``None`` disables
    sharding; an ``int`` forces that shard count on the current device set
    (single-device two-stage execution: same reduction tree, one device —
    the benchmarking/test path); a ``FleetLayout`` passes through.
    """
    if shard is None or shard is False:
        return None
    if isinstance(shard, FleetLayout):
        return shard if shard.shards > 1 else None
    if shard == "auto":
        if mesh is None:
            devs = jax.devices()
            if len(devs) <= 1:
                return None
            mesh = jax.sharding.Mesh(np.array(devs), ("data",))
        return plan_fleet_layout(n_nodes, mesh)
    if isinstance(shard, int) and not isinstance(shard, bool):
        return plan_fleet_layout(n_nodes, mesh, shards=shard)
    raise ValueError(f"shard must be 'auto', False, an int shard count or a "
                     f"FleetLayout; got {shard!r}")


def _pad_reshape(col, layout: FleetLayout, fill=0):
    pad = layout.padded - col.shape[0]
    if pad:
        col = jnp.pad(col, (0, pad), constant_values=fill)
    col = col.reshape(layout.shards, layout.shard_size)
    if layout.mesh is not None:
        col = jax.lax.with_sharding_constraint(
            col, jax.sharding.NamedSharding(
                layout.mesh, jax.sharding.PartitionSpec("data", None)))
    return col


def shard_cluster(state: ClusterState, layout: FleetLayout) -> ClusterState:
    """Pad each (N,) column with infeasible filler and view it as
    (shards, shard_size); scalar fields (``time_s``) pass through.  Accepts
    already-padded columns (the daemon's sharded snapshot) unchanged."""
    return type(state)(*[
        _pad_reshape(c, layout, _CLUSTER_PAD.get(name, 0))
        if getattr(c, "ndim", 0) == 1 else c
        for name, c in zip(state._fields, state)])


def shard_fleet(fleet: _pl.FleetState, layout: FleetLayout) -> _pl.FleetState:
    """FleetState analogue of :func:`shard_cluster` (all-zero filler:
    ``healthy == 0`` makes padded hosts infeasible)."""
    return type(fleet)(*[_pad_reshape(c, layout)
                         if getattr(c, "ndim", 0) == 1 else c
                         for c in fleet])


def _shard_axes(tree):
    """vmap ``in_axes`` over the shard axis: 0 for sharded columns, None for
    scalar fields."""
    return type(tree)(*[0 if getattr(c, "ndim", 0) >= 2 else None
                        for c in tree])


def _over_shards(fn, layout: FleetLayout, tree, *shared):
    """``fn(shard, *shared)`` for every shard of the sharded ``tree``.

    On one device this is a ``vmap`` over the shard axis.  With a layout
    mesh it runs under ``shard_map`` over ``data``, each device mapping its
    own shards: the compiler cannot partition a Pallas kernel itself.
    ``shared`` arguments (params, the pod, global scalars) are replicated.
    """
    mapped = jax.vmap(fn, in_axes=(_shard_axes(tree),) + (None,) * len(shared))
    if layout.mesh is None:
        return mapped(tree, *shared)
    P = jax.sharding.PartitionSpec
    specs = type(tree)(*[P("data") if getattr(c, "ndim", 0) >= 2 else P()
                         for c in tree])
    # check_vma=False: pallas_call's outputs carry no varying-axes type
    return jax.shard_map(mapped, mesh=layout.mesh,
                         in_specs=(specs,) + (P(),) * len(shared),
                         out_specs=P("data"), check_vma=False)(tree, *shared)


def _global_index(vals, local_idx, layout: FleetLayout):
    """(S, k) shard-local indices -> global node indices (−1 on dead slots)."""
    offs = (jnp.arange(layout.shards, dtype=jnp.int32)
            * layout.shard_size)[:, None]
    return jnp.where(jnp.isfinite(vals), local_idx + offs, -1)


def _merge(vals, gidx):
    """Merge the (S, k) candidate sets: full descending sort of the tiny
    flattened list.  ``lax.top_k`` keeps ties in ascending flat position ==
    ascending global index (shards cover ascending index ranges, per-shard
    candidates are emitted lowest-index-first), preserving first-occurrence
    argmax semantics end to end."""
    flat_v, flat_i = vals.reshape(-1), gidx.reshape(-1)
    top_v, pos = jax.lax.top_k(flat_v, flat_v.shape[0])
    return top_v, flat_i[pos]


def cluster_topk(params: dict, state: ClusterState, pod, cfg, layout: FleetLayout,
                 *, k: int = 4, fused="auto", score_fn=None, policy=None,
                 embed=None, heuristic: bool = False, pull_cost=None):
    """Two-stage feasible top-k over a ClusterState fleet.

    Returns ``(values, indices)`` of length ``shards * k``, sorted
    descending (ties by ascending node index): element 0 is exactly
    ``masked_argmax`` of the flat program.  Infeasible/exhausted slots carry
    ``-inf`` / index ``-1``.  ``heuristic=True`` scores with the closed-form
    kube formula instead of the Q-net (the degraded-mode arm — same
    two-stage shape, so the fallback also never gathers the fleet).
    """
    k = max(1, min(k, layout.shard_size))
    if pull_cost is None:
        pull_cost = kenv.pull_cost_now(state, cfg)
    st = shard_cluster(state, layout)
    fusable = score_fn is None and (policy is None or policy.fused_kernel)
    use_fused = not heuristic and fusable and (
        fused in (True, "interpret")
        or (fused == "auto"
            and layout.shard_size >= schedulers.FUSED_SCORE_MIN_NODES))

    def one_shard(sub, params, pod, embed, pull_cost):
        if heuristic:
            q = baselines.kube_scores(sub, pod, cfg)
        elif use_fused:
            mode = "interpret" if fused == "interpret" else None
            return ops.sdqn_topk_afterstate(sub, pod, cfg, params, k=k,
                                            mode=mode, pull_cost=pull_cost)
        else:
            q = schedulers.score_afterstates(params, sub, pod, cfg,
                                             score_fn=score_fn, fused=fused,
                                             policy=policy, embed=embed,
                                             pull_cost=pull_cost)
        ok = kenv.feasible(sub, pod, cfg)
        return jax.lax.top_k(jnp.where(ok, q, -jnp.inf), k)

    vals, lidx = _over_shards(one_shard, layout, st, params, pod, embed,
                              pull_cost)
    return _merge(vals, _global_index(vals, lidx, layout))


def fleet_topk(params: dict, fleet: _pl.FleetState, job, layout: FleetLayout,
               *, k: int = 4, fused="auto", policy=None, embed=None,
               heuristic: bool = False, max_host_cpu_pct: float = 88.0,
               delta=None):
    """Two-stage feasible top-k over a FleetState fleet (job→host placement).

    Same contract as :func:`cluster_topk`; feasibility is
    ``PlacementEngine.feasible`` (healthy + post-delta cpu/mem/job-util
    ceilings), run in-kernel on the fused path.  ``delta`` overrides
    ``job_delta(job)`` with a pre-packed (6,) afterstate delta row (the
    daemon's batched path, where ``job`` may be a tracer-free placeholder).
    """
    from repro.sched.api import _fleet_mode, heuristic_score

    k = max(1, min(k, layout.shard_size))
    if delta is None:
        delta = _pl.job_delta(job)
    ceilings = (max_host_cpu_pct, 95.0, 100.0 + 1e-6)
    ft = shard_fleet(fleet, layout)
    fused_path = not heuristic and (policy is None or policy.fused_kernel)

    def feasible(sub, delta):
        return ((sub.healthy > 0.5)
                & (sub.cpu_pct + delta[0] <= ceilings[0])
                & (sub.mem_pct + delta[1] <= ceilings[1])
                & (sub.job_util_pct + delta[2] <= ceilings[2]))

    def one_shard(sub, params, delta, embed):
        if fused_path:
            return ops.sdqn_topk_delta(_pl.fleet_cols(sub), delta, params,
                                       k=k, mode=_fleet_mode(fused),
                                       ceilings=ceilings)
        if heuristic:
            q = heuristic_score(sub, job)
        else:
            feats = (jnp.stack(_pl.fleet_cols(sub), axis=-1)
                     + delta[None, :]) / kenv.FEATURE_SCALE
            if embed is not None:
                feats = jnp.concatenate(
                    [feats,
                     jnp.broadcast_to(embed, feats.shape[:-1] + embed.shape)],
                    axis=-1)
            q = policy.score_set(params, feats)
        return jax.lax.top_k(jnp.where(feasible(sub, delta), q, -jnp.inf), k)

    vals, lidx = _over_shards(one_shard, layout, ft, params, delta, embed)
    return _merge(vals, _global_index(vals, lidx, layout))


def topk(fleet, pod, *, params: dict, cfg=None, layout: FleetLayout,
         k: int = 4, fused="auto", score_fn=None, policy=None, embed=None,
         heuristic: bool = False):
    """Substrate-dispatching wrapper (mirrors ``sched.api.score``'s rules)."""
    if isinstance(fleet, ClusterState):
        if cfg is None:
            raise ValueError("cfg (EnvConfig) is required to score a "
                             "ClusterState fleet")
        return cluster_topk(params, fleet, pod, cfg, layout, k=k, fused=fused,
                            score_fn=score_fn, policy=policy, embed=embed,
                            heuristic=heuristic)
    if isinstance(fleet, _pl.FleetState):
        if score_fn is not None:
            raise ValueError("score_fn is not supported on the FleetState "
                             "column-kernel path")
        return fleet_topk(params, fleet, pod, layout, k=k, fused=fused,
                          policy=policy, embed=embed, heuristic=heuristic)
    raise TypeError(f"unsupported fleet type: {type(fleet).__name__}")


def candidates_valid(vals: jnp.ndarray) -> jnp.ndarray:
    """Scalar bool: no NaN and every *finite* candidate inside the
    divergence limit.  ``-inf`` marks infeasible slots — legitimate here,
    unlike in ``api.scores_valid`` which sees unmasked scores."""
    finite = jnp.isfinite(vals)
    bounded = jnp.where(finite, jnp.abs(vals), 0.0) <= _DIVERGENCE_LIMIT
    return jnp.all(bounded) & ~jnp.any(jnp.isnan(vals))


def select_candidates(fleet, pod, *, params: dict, cfg=None,
                      layout: FleetLayout, k: int = 4, fused="auto",
                      score_fn=None, policy=None, embed=None,
                      guard: bool = False):
    """Greedy selection via the two-stage path: the merged candidate winner,
    or ``NO_PLACEMENT`` when every candidate is infeasible.

    ``guard=True`` mirrors ``api.select``'s degraded mode: NaN/diverged
    candidates swap the WHOLE candidate list for the kube-heuristic list
    (computed through the same two-stage shape — still no fleet gather).
    """
    vals, idx = topk(fleet, pod, params=params, cfg=cfg, layout=layout, k=k,
                     fused=fused, score_fn=score_fn, policy=policy,
                     embed=embed)
    if guard:
        hvals, hidx = topk(fleet, pod, params=params, cfg=cfg, layout=layout,
                           k=k, fused=fused, score_fn=None, policy=None,
                           heuristic=True)
        valid = candidates_valid(vals)
        vals = jnp.where(valid, vals, hvals)
        idx = jnp.where(valid, idx, hidx)
    choice = jnp.where(jnp.isfinite(vals[0]), idx[0], NO_PLACEMENT)
    return choice.astype(jnp.int32)


def sharded_scores(fleet, pod, *, params: dict, cfg=None,
                   layout: FleetLayout, fused="auto", score_fn=None,
                   policy=None, embed=None) -> jnp.ndarray:
    """The (N,) score vector, computed shard-by-shard.

    The vector is *logically* full-length (``api.score``'s contract) but
    physically distributed when the layout carries a mesh: each device
    computes and holds only its own ``shard_size`` slice.  On a single
    device this is plain chunked evaluation — bit-identical to the flat
    program for pointwise scorers.
    """
    if isinstance(fleet, ClusterState):
        if cfg is None:
            raise ValueError("cfg (EnvConfig) is required to score a "
                             "ClusterState fleet")
        pull = kenv.pull_cost_now(fleet, cfg)
        q = _over_shards(
            lambda sub, params, pod, embed, pull: schedulers.score_afterstates(
                params, sub, pod, cfg, score_fn=score_fn, fused=fused,
                policy=policy, embed=embed, pull_cost=pull),
            layout, shard_cluster(fleet, layout), params, pod, embed, pull)
        n = fleet.n_nodes
    elif isinstance(fleet, _pl.FleetState):
        from repro.sched import api as _api

        q = _over_shards(
            lambda sub, params, embed: _api._score_raw(
                sub, pod, params=params, fused=fused, policy=policy,
                embed=embed),
            layout, shard_fleet(fleet, layout), params, embed)
        n = fleet.cpu_pct.shape[0]
    else:
        raise TypeError(f"unsupported fleet type: {type(fleet).__name__}")
    return q.reshape(-1)[:n]
