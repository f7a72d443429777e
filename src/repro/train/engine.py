"""Multi-candidate training engine: every seed of ``train_and_select`` is one
XLA program.

The paper's "Algorithm Selection and Scheduler Development" step trains
several candidate SDQN/SDQN-n policies and keeps the best on held-out
validation bursts.  Sequentially that costs ``n_seeds`` full training
dispatches from Python; here the *entire* training scan —
``lax.scan(episodes) ∘ lax.scan(arrivals) ∘ vmap(n_envs)`` — is vmapped once
more over the seed ladder, so all candidates compile once and run as a
single launch:

    stacked_params, metrics = train_seeds(key, cfg, rl, n_seeds=4)

The seed keys are ``fold_in(key, s)`` — the exact ladder the sequential loop
used — so per-seed results match the one-seed-at-a-time path exactly up to
float reassociation (vmap batches the learner's matmul/reduction
accumulations; the drift is ~1e-9 per step, pinned to <=1e-6 in tests, and
the PRNG streams are identical).
Validation feeds the stacked params through one batched evaluator
(``eval.engine.make_multi_param_evaluator``: all (seed, trial) episodes in
one launch) and the winner is a NaN-guarded on-device argmin.

On a mesh, ``launch.mesh.plan_seed_env_layout`` picks the joint seed×env
layout: a 2-D ``("seed", "data")`` grid that shards the seed ladder over
``seed`` (whole training replicas per device group — the cheapest layout:
zero cross-device traffic until selection) and each seed's ``n_envs`` batch
over ``data``, so **all** devices are busy whenever the device count
divides ``n_seeds * n_envs``.  ``env_shards == 1`` degenerates to PR 3's pure
seed sharding (one flattened parallel axis), ``seed_shards == 1`` to pure
env sharding; an indivisible batch — and ``mesh=None``, the CPU/test
default — runs the bit-compatible single-device vmap.
"""
from __future__ import annotations

import functools
import warnings
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import policy as policy_mod, schedulers, train_rl
from repro.core.types import EnvConfig
from repro.eval import engine as eval_engine
from repro.launch import mesh as meshmod


def seed_fold_keys(key: jax.Array, n_seeds: int) -> jax.Array:
    """(S, ...) candidate-seed keys, identical to ``fold_in(key, s)``."""
    return jax.vmap(lambda s: jax.random.fold_in(key, s))(jnp.arange(n_seeds))


@functools.partial(jax.jit, static_argnames=("env_cfg", "rl", "mesh"))
def _seed_train(keys, env_cfg: EnvConfig, rl: train_rl.RLConfig, mesh=None):
    """Jitted ``(seed_keys) -> (stacked_params, stacked_metrics)``; jax's own
    cache keys on the static (env_cfg, rl, mesh), so repeated selection
    rounds (benchmark sweeps, hyperparameter scans) reuse one executable.

    ``plan_seed_env_layout`` maps the (n_seeds, n_envs) batch onto the mesh:
    the key ladder is pinned to the layout's ``seed`` axis and, when the
    layout splits devices across envs too (``env_shards > 1``), the inner
    ``train``'s ``n_envs`` constraints run under ``vmap(spmd_axis_name=
    "seed")`` so every batched ``with_sharding_constraint`` spec re-anchors
    as ``("seed", ..., "data")`` instead of being dropped on the batched
    seed dimension.  No layout (``mesh=None``, one device, indivisible
    batch) is the plain single-device vmap, bit-compatible with PR 3.
    """
    layout = meshmod.plan_seed_env_layout(keys.shape[0], rl.n_envs, mesh)
    if layout is None:
        return jax.vmap(lambda k: train_rl.train(k, env_cfg, rl))(keys)
    from jax.sharding import NamedSharding, PartitionSpec as P

    if layout.env_shards == 1:
        # pure seed sharding: whole replicas per device, each device training
        # its own seeds (shard_map: the compiler cannot partition the Pallas
        # scoring kernels inside a replica)
        return jax.shard_map(
            jax.vmap(lambda k: train_rl.train(k, env_cfg, rl)),
            mesh=layout.mesh, in_specs=P("seed"), out_specs=P("seed"),
            check_vma=False)(keys)
    keys = jax.lax.with_sharding_constraint(
        keys, NamedSharding(layout.mesh, P("seed")))
    return jax.vmap(
        lambda k: train_rl.train(k, env_cfg, rl, mesh=layout.mesh),
        spmd_axis_name="seed",
    )(keys)


def train_seeds(
    key: jax.Array,
    env_cfg: EnvConfig,
    rl: train_rl.RLConfig,
    n_seeds: int,
    mesh=None,
) -> Tuple[dict, dict]:
    """Train ``n_seeds`` candidate policies in ONE compiled launch.

    Returns (stacked qparams with leading seed dim, stacked metrics dict of
    (S, episodes) arrays).  Seed s of the stack equals
    ``train(fold_in(key, s), ...)``: same PRNG streams, values equal up to
    float reassociation from batching (<=1e-6 over a training run).
    """
    return _seed_train(seed_fold_keys(key, n_seeds), env_cfg, rl, mesh)


class Selection(NamedTuple):
    """``select_best``'s result; unpacks as ``(params, metric, diverged)``."""

    params: dict
    metric: jnp.ndarray    # () guarded validation metric of the winner
    diverged: jnp.ndarray  # () bool: EVERY candidate was NaN — params are
                           # the seed-0 fallback, not a real selection


def select_best(stacked_params: dict, metrics: jnp.ndarray) -> Selection:
    """NaN-guarded candidate selection: (params of best seed, its metric,
    all-NaN warning flag).

    NaN metrics never win (``x < NaN`` and ``NaN < x`` are both False, so a
    naive running-min would keep its ``inf`` start and return no params at
    all) — they are demoted to ``+inf`` before the argmin.  If *every* seed
    is NaN the argmin lands on seed 0, so callers always get real params —
    and ``diverged`` is True so they can tell "seed 0 won" apart from
    "everything diverged" (the metric alone cannot: both report seed 0).
    """
    guarded = jnp.where(jnp.isnan(metrics), jnp.inf, metrics)
    best = jnp.argmin(guarded)
    return Selection(jax.tree.map(lambda x: x[best], stacked_params),
                     guarded[best], jnp.all(jnp.isnan(metrics)))


def train_and_select(
    key: jax.Array,
    train_cfg: EnvConfig,
    eval_cfg: EnvConfig,
    rl: train_rl.RLConfig,
    n_seeds: int = 4,
    val_trials: int = 12,
    val_pods: Optional[int] = 50,
    mesh=None,
):
    """Seed-parallel train + batched validation + on-device selection.

    The engine form of ``train_rl.train_and_select`` (which delegates here):
    one launch trains all seeds, one launch runs all (seed, trial)
    validation episodes, and the argmin happens on device.  Returns
    ``(best_params, float(best_val_metric))``.
    """
    stacked, _ = train_seeds(key, train_cfg, rl, n_seeds, mesh=mesh)
    # validation uses the same policy class that trained: the factory pair
    # form threads sequence specs' history carry through each episode; for
    # "mlp" it scores identically to make_sdqn_selector (same qvalues path)
    spec = policy_mod.get(rl.policy)
    evaluator = eval_engine.make_multi_param_evaluator(
        eval_cfg, lambda p: schedulers.make_policy_selector(spec, p, eval_cfg),
        val_pods)
    val_keys = eval_engine.fixed_trial_keys(5000, val_trials)
    metrics = jnp.mean(evaluator(stacked, val_keys).metric, axis=1)   # (S,)
    best_params, best_metric, diverged = select_best(stacked, metrics)
    if bool(diverged):
        warnings.warn(
            f"train_and_select: every candidate's validation metric was NaN "
            f"({n_seeds} seeds) — returning seed 0's params unselected; "
            f"treat them as diverged",
            RuntimeWarning, stacklevel=2)
    return best_params, float(best_metric)
