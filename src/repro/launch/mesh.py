"""Production mesh construction and the seed×env training-layout planner.

A function (not a module-level constant) so importing this module never
touches jax device state.  Single-pod: 16x16 = 256 chips ("data","model");
multi-pod: 2 pods x 256 = 512 chips ("pod","data","model") — the "pod" axis
carries only gradient all-reduce (DCN-economical DP across pods).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with ``Auto`` axes: the engines pin layouts with
    ``with_sharding_constraint``, which only accepts ``Auto`` mesh axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh():
    """A 1-device mesh with the same axis names (CPU tests / examples)."""
    return _auto_mesh((1, 1), ("data", "model"))


def make_train_mesh(n_data: int | None = None):
    """Data-only mesh over the local devices for Anakin-style RL training.

    The training engine shards seed/env batches over ``data`` and keeps the
    tiny Table-4 learner replicated, so ``model`` stays 1.  Defaults to all
    visible devices; on the 1-device CPU container this is the host mesh.
    """
    n = n_data if n_data is not None else len(jax.devices())
    return _auto_mesh((n, 1), ("data", "model"))


# ---------------------------------------------------------------------------
# joint seed×env layout planning for the seed-parallel training engine
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SeedEnvLayout:
    """How ``train_seeds``'s (n_seeds, n_envs) batch maps onto devices.

    ``mesh`` is a 2-D ``("seed", "data")`` mesh over every device of the
    source mesh: the seed ladder shards over ``seed`` (``seed_shards``
    device groups, each holding whole training replicas) and, inside each
    group, the per-seed env batch shards over ``data`` (``env_shards``
    devices).  ``env_shards == 1`` degenerates to pure seed sharding — one
    flattened parallel axis — and ``seed_shards == 1`` to pure env sharding;
    both are just the 2-D layout with a trivial axis, so the engine runs one
    code path.  Hashable (meshes hash by device ids + axis names), so the
    layout can ride along as a jit static.
    """

    mesh: jax.sharding.Mesh
    seed_shards: int
    env_shards: int


def _split_seed_env(n_seeds: int, n_envs: int, n_dev: int) -> Optional[tuple]:
    """Factor ``n_dev = s * e`` with ``s | n_seeds`` and ``e | n_envs``,
    maximizing ``s`` (whole replicas per device are the cheapest layout:
    zero cross-device traffic until selection).  Returns ``None`` when the
    device count does not divide the total ``n_seeds * n_envs`` batch.

    Such a split always exists when ``n_seeds * n_envs % n_dev == 0``: for
    every prime power ``p^k`` of ``n_dev``, the seed axis takes
    ``min(k, multiplicity of p in n_seeds)`` factors and the env axis covers
    the remainder (which it can, since the product divides).
    """
    if n_dev <= 0 or (n_seeds * n_envs) % n_dev != 0:
        return None
    s, rem, p = 1, n_dev, 2
    while rem > 1:
        while rem % p == 0:
            if n_seeds % (s * p) == 0:
                s *= p
            rem //= p
        p += 1 if p == 2 else 2
    e = n_dev // s
    if n_envs % e != 0:  # unreachable when the product divides; kept as a guard
        return None
    return s, e


# ---------------------------------------------------------------------------
# fleet-axis layout planning for two-stage hierarchical sharded scoring
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FleetLayout:
    """How a fleet's N node columns map onto shards for two-stage scoring.

    The node axis splits into ``shards`` contiguous slices of ``shard_size``
    nodes (the last slice padded with infeasible filler up to
    ``padded = shards * shard_size``); each shard scores its own columns and
    reduces to a per-shard top-k in-kernel, and only the tiny
    ``shards × k`` candidate set is merged globally — no full N-length score
    vector ever materializes on one device (``sched.shard``).

    ``mesh`` is an optional 1-D ``("data",)`` device mesh: when present the
    shard axis is pinned to it with sharding constraints so each device
    holds ``shard_size`` node columns; when ``None`` the same two-stage
    program runs on one device (forced-shard benchmarking / tests — the
    reduction tree is identical, only the placement differs).  Hashable, so
    a layout can ride along as a jit static.
    """

    shards: int
    shard_size: int
    n_nodes: int
    mesh: Optional[jax.sharding.Mesh] = None

    @property
    def padded(self) -> int:
        return self.shards * self.shard_size


def plan_fleet_layout(n_nodes: int, mesh=None, *,
                      shards: Optional[int] = None) -> Optional[FleetLayout]:
    """Pick the node-column sharding for a two-stage scoring launch.

    ``shards`` forces an explicit shard count (any ``n_nodes``, padded to
    divisibility — the single-device benchmarking/test path).  Otherwise the
    plan follows ``mesh``: one shard per device of its flattened device set.
    Returns ``None`` — run today's unsharded program, bit-identically —
    when the result would be a single shard: no mesh and no forced count, a
    1-device mesh, or a fleet smaller than the device count.
    """
    if shards is not None:
        if shards <= 1 or n_nodes < shards:
            return None
        size = -(-n_nodes // shards)
        lmesh = None
        if mesh is not None and int(mesh.devices.size) == shards:
            lmesh = jax.sharding.Mesh(mesh.devices.reshape(shards), ("data",))
        return FleetLayout(shards=shards, shard_size=size, n_nodes=n_nodes,
                           mesh=lmesh)
    if mesh is None:
        return None
    n_dev = int(mesh.devices.size)
    if n_dev <= 1 or n_nodes < n_dev:
        return None
    lmesh = jax.sharding.Mesh(mesh.devices.reshape(n_dev), ("data",))
    return FleetLayout(shards=n_dev, shard_size=-(-n_nodes // n_dev),
                       n_nodes=n_nodes, mesh=lmesh)


def plan_seed_env_layout(n_seeds: int, n_envs: int, mesh) -> Optional[SeedEnvLayout]:
    """Pick the joint seed×env sharding for a ``train_seeds`` launch.

    Given the candidate count, the per-seed env batch and a device mesh,
    returns a :class:`SeedEnvLayout` whose 2-D ``("seed", "data")`` mesh
    keeps **all** devices busy whenever the device count divides
    ``n_seeds * n_envs`` — the case PR 3's seed-only sharding left on the table
    whenever ``n_seeds < n_devices`` (e.g. 2 seeds on a 4-device host ran on
    2 devices; the joint layout runs them as a (2, 2) grid).  ``None`` means
    run unsharded: no mesh, a single device, or an indivisible batch (the
    bit-compatible single-device fallback).
    """
    if mesh is None:
        return None
    n_dev = int(mesh.devices.size)
    if n_dev <= 1:
        return None
    split = _split_seed_env(n_seeds, n_envs, n_dev)
    if split is None:
        return None
    s, e = split
    lmesh = jax.sharding.Mesh(mesh.devices.reshape(s, e), ("seed", "data"))
    return SeedEnvLayout(mesh=lmesh, seed_shards=s, env_shards=e)
