"""Vectorized, jittable Kubernetes-cluster environment.

Reproduces the paper's experimental substrate (§4.3, §5): a cluster of slave
nodes receiving batches of compute-intensive no-op pods.  Everything is pure
JAX on static shapes so episodes can be ``lax.scan``-ed and whole populations
of clusters ``vmap``-ed / ``shard_map``-ed for fleet-scale policy training.

CPU accounting per node (millicores):

    used = base_cpu                               (pre-existing load)
         + active * node_active_overhead          (kubelet/runtime/monitoring)
         + pods_cpu                               (pod compute demand)
         + startup_cpu                            (decaying pull/start transients)
         + contention(used/capacity)              (super-linear above the knee)

Image pulls are cold only for the first experiment pod on a node
(`image_cached`), matching the paper's §4.3.2 image-caching/shared-I/O
explanation for why consolidation saves CPU.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import (FEATURE_DIM, NO_PLACEMENT, ClusterState,
                              EnvConfig, EpisodeResult, EpisodeStats,
                              FailureTrace, PodLedger, PodSpec, PodTable)

# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------


def _profile(key, profile: tuple, jitter: float, n: int) -> jnp.ndarray:
    """Tile `profile` to n entries, permute, jitter — stable totals, varied layout."""
    kp, kj = jax.random.split(key)
    reps = -(-n // len(profile))  # ceil
    vals = jnp.tile(jnp.asarray(profile, jnp.float32), reps)[:n]
    vals = jax.random.permutation(kp, vals)
    return vals + jax.random.uniform(kj, (n,), minval=-jitter, maxval=jitter)


def _scenario_pool(scn) -> dict:
    """Static per-node arrays for a heterogeneous pool (trace-time numpy)."""

    def col(get, dtype=np.float32):
        return np.concatenate(
            [np.full(c.count, get(c), dtype) for c in scn.node_classes]
        )

    return {
        "cpu_capacity": col(lambda c: c.cpu_capacity),
        "mem_capacity": col(lambda c: c.mem_capacity),
        "max_pods": col(lambda c: c.max_pods, np.int32),
        "unhealthy_prob": col(lambda c: c.unhealthy_prob),
        "cached_prob": col(lambda c: c.image_cached_prob),
        "base_lo": col(lambda c: c.base_cpu_frac[0]),
        "base_hi": col(lambda c: c.base_cpu_frac[1]),
        "req_lo": col(lambda c: c.requested_frac[0]),
        "req_hi": col(lambda c: c.requested_frac[1]),
        "idle_watts": col(lambda c: c.idle_watts),
        "peak_watts": col(lambda c: c.peak_watts),
        "mtbf": col(lambda c: c.mtbf_s),
        "mttr": col(lambda c: c.mttr_s),
        "accel_capacity": col(lambda c: c.accel_capacity, np.int32),
    }


def has_accel(cfg: EnvConfig) -> bool:
    """True when the scenario's pool or catalog has any accelerator.

    A *static* (trace-time) property: only then does ``reset`` build the
    accelerator columns and ``sample_pod_table`` the pods' requests, so a
    CPU-only scenario traces exactly the programs it always did.
    """
    scn = cfg.scenario
    return scn is not None and (
        any(c.accel_capacity > 0 for c in scn.node_classes)
        or any(p.accel_request > 0 for p in scn.pod_types))


def node_watts(cfg: EnvConfig) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Static per-node (idle_watts, peak_watts) arrays for the energy model."""
    if cfg.scenario is None:
        return (jnp.full((cfg.n_nodes,), cfg.idle_watts, jnp.float32),
                jnp.full((cfg.n_nodes,), cfg.peak_watts, jnp.float32))
    pool = _scenario_pool(cfg.scenario)
    return jnp.asarray(pool["idle_watts"]), jnp.asarray(pool["peak_watts"])


def reset(key: jax.Array, cfg: EnvConfig) -> ClusterState:
    n = cfg.n_nodes
    k1, k2, k3, k4 = jax.random.split(key, 4)
    uptime = jax.random.uniform(
        k2, (n,), minval=cfg.init_uptime_range_h[0], maxval=cfg.init_uptime_range_h[1]
    )
    if cfg.scenario is None:
        cap = jnp.full((n,), cfg.cpu_capacity)
        mem_cap = jnp.full((n,), cfg.mem_capacity)
        max_pods = jnp.full((n,), cfg.max_pods, jnp.int32)
        base = jnp.maximum(_profile(k1, cfg.base_cpu_profile, cfg.base_cpu_jitter, n), 0.0)
        healthy = jax.random.uniform(k3, (n,)) >= cfg.unhealthy_prob
        # pre-existing *requests* (control-plane bookings by other tenants) are
        # permuted independently of pre-existing *usage* — see EnvConfig docstring.
        requested0 = cfg.cpu_capacity * jnp.clip(
            _profile(k4, cfg.requested_frac_profile, cfg.requested_frac_jitter, n), 0.0, 0.95
        )
        cached_prob = jnp.zeros((n,), jnp.float32)
    else:
        pool = _scenario_pool(cfg.scenario)
        cap = jnp.asarray(pool["cpu_capacity"])
        mem_cap = jnp.asarray(pool["mem_capacity"])
        max_pods = jnp.asarray(pool["max_pods"])
        # base load and bookings scale with each class's own capacity, so a
        # 2-core edge node and a 16-core crunch node are proportionately busy.
        base = cap * jax.random.uniform(
            k1, (n,), minval=jnp.asarray(pool["base_lo"]), maxval=jnp.asarray(pool["base_hi"])
        )
        healthy = jax.random.uniform(k3, (n,)) >= jnp.asarray(pool["unhealthy_prob"])
        requested0 = cap * jnp.clip(
            jax.random.uniform(
                k4, (n,), minval=jnp.asarray(pool["req_lo"]), maxval=jnp.asarray(pool["req_hi"])
            ),
            0.0, 0.95,
        )
        cached_prob = jnp.asarray(pool["cached_prob"])
    z = jnp.zeros((n,), jnp.float32)
    pod0 = mean_pod(cfg)

    # bookings come from tenant pods: a node with X millicores requested is
    # hosting ~X/pod_request pods of other tenants (visible to the Table-2
    # num_pods / pod-utilization features; their CPU usage is part of base).
    tenant_pods = (requested0 / pod0.cpu_request).astype(jnp.int32)

    exp_pods0 = jnp.zeros((n,), jnp.int32)
    cached0 = jax.random.uniform(jax.random.fold_in(key, 11), (n,)) < cached_prob
    startup0 = z
    if cfg.randomize_workload:
        # training-only domain randomization: nodes start mid-flight so the
        # Q-net sees (features -> reward) decorrelated from episode time.
        kr1, kr2, kr3, kr4 = jax.random.split(jax.random.fold_in(key, 7), 4)
        pods = jax.random.randint(kr1, (n,), 0, cfg.randomize_max_pods + 1)
        # keep randomized starts physical on every node class: a node hosts
        # only what fits its own memory and pod slots (a small-edge node must
        # not wake up with a big node's worth of pods).
        mem_den = jnp.maximum(jnp.maximum(pod0.mem_request, pod0.mem_demand), 1e-6)
        mem_fit = jnp.floor(0.9 * mem_cap / mem_den).astype(jnp.int32)
        slot_fit = max_pods - tenant_pods
        pods = jnp.minimum(pods, jnp.maximum(jnp.minimum(mem_fit, slot_fit), 0))
        empty = jax.random.uniform(kr2, (n,)) < cfg.randomize_empty_prob
        exp_pods0 = jnp.where(empty, 0, pods).astype(jnp.int32)
        cached0 = cached0 | (exp_pods0 > 0) | (
            jax.random.uniform(kr3, (n,)) < cfg.randomize_cached_prob
        )
        startup0 = jax.random.uniform(kr4, (n,), maxval=0.3 * cfg.image_pull_cost)

    fexp = exp_pods0.astype(jnp.float32)
    accel = {}
    if has_accel(cfg):
        # randomized starts' resident pods are CPU-side stand-ins: they
        # book no accelerators
        accel = dict(accel_capacity=jnp.asarray(pool["accel_capacity"]),
                     accel_requested=jnp.zeros((n,), jnp.int32))
    return ClusterState(
        cpu_capacity=cap,
        mem_capacity=mem_cap,
        max_pods=max_pods,
        healthy=healthy,
        uptime_hours=uptime,
        num_pods=tenant_pods + exp_pods0,
        exp_pods=exp_pods0,
        cpu_requested=jnp.minimum(requested0 + fexp * pod0.cpu_request, 0.98 * cap),
        mem_requested=fexp * pod0.mem_request,
        pods_cpu=fexp * pod0.cpu_demand,
        mem_used=fexp * pod0.mem_demand,
        base_cpu=base,
        startup_cpu=startup0,
        image_cached=cached0,
        time_s=jnp.float32(0.0),
        **accel,
    )


def default_pod(cfg: EnvConfig) -> PodSpec:
    return PodSpec(
        cpu_request=jnp.float32(cfg.pod_cpu_request),
        cpu_demand=jnp.float32(cfg.pod_cpu_demand),
        mem_request=jnp.float32(cfg.pod_mem_request),
        mem_demand=jnp.float32(cfg.pod_mem_demand),
    )


def mean_pod(cfg: EnvConfig) -> PodSpec:
    """Mixture-weighted mean PodSpec of the scenario's catalog (falls back to
    the homogeneous default pod).  Used for pre-existing workload accounting
    at reset; the per-arrival specs come from the sampled pod table."""
    scn = cfg.scenario
    if scn is None:
        return default_pod(cfg)
    w = np.asarray([p.weight for p in scn.pod_types], np.float64)
    w = w / w.sum()

    def m(get):
        return jnp.float32(float(np.sum(w * np.asarray([get(p) for p in scn.pod_types]))))

    return PodSpec(
        cpu_request=m(lambda p: p.cpu_request),
        cpu_demand=m(lambda p: p.cpu_demand),
        mem_request=m(lambda p: p.mem_request),
        mem_demand=m(lambda p: p.mem_demand),
    )


# ---------------------------------------------------------------------------
# arrival stream (pre-sampled pod table; lax.scan consumes it row by row)
# ---------------------------------------------------------------------------


def _arrival_gaps(key: jax.Array, cfg: EnvConfig, n_pods: int) -> jnp.ndarray:
    """Inter-arrival times (n_pods,) for the scenario's arrival process."""
    arr = cfg.scenario.arrival if cfg.scenario is not None else None
    if arr is None or arr.kind == "burst":
        return jnp.full((n_pods,), cfg.schedule_dt_s, jnp.float32)
    e = jax.random.exponential(key, (n_pods,), jnp.float32)
    if arr.kind == "poisson":
        return e / arr.rate_per_s

    if arr.kind != "diurnal":
        raise ValueError(f"unknown arrival kind: {arr.kind!r}")

    # diurnal: Poisson stream with sinusoidally modulated rate.  The arrival
    # clock advances sequentially (each gap depends on the rate at the current
    # wall-clock), so thin through a tiny scan over the pre-sampled unit
    # exponentials — still one fused XLA loop.
    def step(t, e_i):
        rate = arr.rate_per_s * (1.0 + arr.depth * jnp.sin(2.0 * jnp.pi * t / arr.period_s))
        dt = e_i / jnp.maximum(rate, 1e-6)
        return t + dt, dt

    _, dts = jax.lax.scan(step, jnp.float32(0.0), e)
    return dts


def _sample_lifetimes(key: jax.Array, scn, type_idx: jnp.ndarray,
                      n_pods: int) -> jnp.ndarray:
    """Per-arrival running durations from each ``PodType``'s distribution.

    Lognormal with the type's mean and coefficient of variation (``cv=0``
    degenerates to the deterministic mean; ``mean=inf`` pods never finish).
    The lognormal's heavy tail is the empirically observed shape of container
    job durations (a few stragglers dominate the drain window).
    """
    if scn is None:
        return jnp.full((n_pods,), jnp.inf, jnp.float32)
    mean = jnp.asarray([p.lifetime_mean_s for p in scn.pod_types], jnp.float32)
    cv = jnp.asarray([p.lifetime_cv for p in scn.pod_types], jnp.float32)
    sigma2 = jnp.log1p(cv * cv)
    # mean = exp(mu + sigma^2/2)  =>  mu = log(mean) - sigma^2/2; inf means
    # propagate: log(inf) = inf, exp(inf) = inf — the pod runs forever.
    mu = jnp.log(mean) - 0.5 * sigma2
    z = jax.random.normal(key, (n_pods,), jnp.float32)
    return jnp.exp(mu[type_idx] + jnp.sqrt(sigma2)[type_idx] * z)


def sample_pod_table(key: jax.Array, cfg: EnvConfig, n_pods: int) -> PodTable:
    """Draw the episode's arrival stream from the scenario (jittable).

    Without a scenario this is the paper's homogeneous burst: `n_pods` copies
    of the default pod every `schedule_dt_s` seconds, all running forever.
    Lifetimes draw from a dedicated ``fold_in(key, 3)`` stream so the
    type/gap draws stay identical to the pre-lifecycle tables.
    """
    k_type, k_dt = jax.random.split(key)
    k_life = jax.random.fold_in(key, 3)
    scn = cfg.scenario
    if scn is None:
        specs = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_pods,)), default_pod(cfg)
        )
        return PodTable(specs=specs, dt_s=_arrival_gaps(k_dt, cfg, n_pods),
                        type_idx=jnp.zeros((n_pods,), jnp.int32),
                        lifetime_s=jnp.full((n_pods,), jnp.inf, jnp.float32))
    w = jnp.asarray([p.weight for p in scn.pod_types], jnp.float32)
    type_idx = jax.random.categorical(k_type, jnp.log(w), shape=(n_pods,))
    by_type = PodSpec(
        cpu_request=jnp.asarray([p.cpu_request for p in scn.pod_types], jnp.float32),
        cpu_demand=jnp.asarray([p.cpu_demand for p in scn.pod_types], jnp.float32),
        mem_request=jnp.asarray([p.mem_request for p in scn.pod_types], jnp.float32),
        mem_demand=jnp.asarray([p.mem_demand for p in scn.pod_types], jnp.float32),
        accel_request=(jnp.asarray([p.accel_request for p in scn.pod_types],
                                   jnp.int32) if has_accel(cfg) else None),
    )
    specs = jax.tree.map(lambda col: col[type_idx], by_type)
    return PodTable(specs=specs, dt_s=_arrival_gaps(k_dt, cfg, n_pods),
                    type_idx=type_idx.astype(jnp.int32),
                    lifetime_s=_sample_lifetimes(k_life, scn, type_idx, n_pods))


# ---------------------------------------------------------------------------
# observation (Table 2 features)
# ---------------------------------------------------------------------------


def _node_cpu_used(base_cpu, active, pods_cpu, startup_cpu, num_pods,
                   cpu_capacity, cfg: EnvConfig) -> jnp.ndarray:
    """Elementwise per-node CPU model, shared by state scoring and the O(N)
    afterstate fast path (one definition, so they cannot diverge).

    Three super-linearities (all invisible to request-based scoring):
      * contention — CFS pressure once utilization passes the knee;
      * crowding — context-switch/cgroup cost once a node hosts many pods;
      * both stack on the base + overhead + pod-demand + startup transients.
    """
    crowd = jnp.maximum(num_pods.astype(jnp.float32) - cfg.crowd_knee, 0.0)
    raw = (
        base_cpu
        + jnp.where(active, cfg.node_active_overhead, 0.0)
        + pods_cpu
        + startup_cpu
        + cfg.crowd_coeff * crowd * crowd
    )
    util = raw / cpu_capacity
    over = jnp.maximum(util - cfg.contention_knee, 0.0)
    contention = cfg.contention_coeff * over * over * cpu_capacity
    return jnp.minimum(raw + contention, cpu_capacity)


def _feature_stack(used, mem_used, num_pods, max_pods, healthy, uptime_hours,
                   exp_pods, cpu_capacity, mem_capacity) -> jnp.ndarray:
    """The six Table-2 columns from elementwise node quantities: (..., 6)."""
    return jnp.stack(
        [
            100.0 * used / cpu_capacity,
            100.0 * mem_used / mem_capacity,
            100.0 * num_pods / max_pods,               # utilization: ALL pods
            healthy.astype(jnp.float32),
            uptime_hours,
            exp_pods.astype(jnp.float32),              # count: OUR workload's pods
        ],
        axis=-1,
    )


def cpu_used(state: ClusterState, cfg: EnvConfig) -> jnp.ndarray:
    """Actual per-node CPU usage in millicores, incl. contention inflation."""
    return _node_cpu_used(state.base_cpu, state.exp_pods > 0, state.pods_cpu,
                          state.startup_cpu, state.num_pods, state.cpu_capacity, cfg)


def cpu_pct(state: ClusterState, cfg: EnvConfig) -> jnp.ndarray:
    return 100.0 * cpu_used(state, cfg) / state.cpu_capacity


def features(state: ClusterState, cfg: EnvConfig) -> jnp.ndarray:
    """The six Table-2 inputs, one row per node: (N, 6) float32."""
    return _feature_stack(cpu_used(state, cfg), state.mem_used, state.num_pods,
                          state.max_pods, state.healthy, state.uptime_hours,
                          state.exp_pods, state.cpu_capacity, state.mem_capacity)


FEATURE_SCALE = jnp.array([100.0, 100.0, 100.0, 1.0, 24.0, 32.0], jnp.float32)
assert FEATURE_SCALE.shape == (FEATURE_DIM,), \
    "FEATURE_SCALE must cover exactly the canonical afterstate width"


def normalize_features(feats: jnp.ndarray) -> jnp.ndarray:
    """Scale raw Table-2 features to O(1) for the neural scorers."""
    return feats / FEATURE_SCALE


# ---------------------------------------------------------------------------
# scheduling predicates (k8s filtering phase)
# ---------------------------------------------------------------------------


def accel_ask(state: ClusterState, pod: PodSpec) -> Optional[jnp.ndarray]:
    """The pod's accelerator request as int32, or None where it asks for
    none.  Asking for accelerators on a state without an accelerator column
    is an error, never a silent bind."""
    if pod.accel_request is None:
        return None
    if state.accel_capacity is None:
        raise ValueError("the pod requests accelerators but the cluster "
                         "state has no accelerator column")
    return jnp.asarray(pod.accel_request, jnp.int32)


def feasible(state: ClusterState, pod: PodSpec, cfg: EnvConfig) -> jnp.ndarray:
    """k8s predicates: Ready, CPU/mem requests fit, below max-pods, and the
    accelerators not yet requested cover the pod's (extended resources are
    never overcommitted; int32, exact). (N,) bool."""
    ok = (
        state.healthy
        & (state.cpu_requested + pod.cpu_request <= state.cpu_capacity)
        & (state.mem_requested + pod.mem_request <= state.mem_capacity)
        & (state.num_pods < state.max_pods)
    )
    accel = accel_ask(state, pod)
    if accel is not None:
        ok = ok & (state.accel_requested + accel <= state.accel_capacity)
    return ok


# ---------------------------------------------------------------------------
# transitions
# ---------------------------------------------------------------------------


def pull_cost_now(state: ClusterState, cfg: EnvConfig) -> jnp.ndarray:
    """Cost of starting a cold image pull *right now* (scalar).

    Cold image pulls contend for registry/network bandwidth: each pull already
    in flight (startup transient still large) inflates a new pull's cost by
    ``pull_concurrency_coeff`` — spreading a burst of pods across many cold
    nodes at once (what the request-blind default scheduler does) is
    super-additively expensive, while warm reuse is cheap (paper §4.3.2).
    """
    in_flight = jnp.sum(state.startup_cpu > 0.25 * cfg.image_pull_cost).astype(jnp.float32)
    return cfg.image_pull_cost * (1.0 + cfg.pull_concurrency_coeff * in_flight)


# sentinel action: no feasible node, the pod is dropped (no-op bind).  A
# re-export of the unified ``core.types.NO_PLACEMENT`` constant (the old
# per-module spelling, kept for callers that import it from here).
NO_NODE = NO_PLACEMENT


def place(state: ClusterState, action: jnp.ndarray, pod: PodSpec, cfg: EnvConfig) -> ClusterState:
    """Bind one pod to node `action` (int32 scalar).

    ``action == NO_NODE`` (-1) is the drop sentinel emitted by the selectors
    when the filtering phase leaves no candidate: the one-hot of -1 is all
    zeros, so the bind is a no-op and the cluster state passes through
    unchanged (no phantom pod on node 0 / a random node).
    """
    onehot = jax.nn.one_hot(action, state.n_nodes, dtype=jnp.float32)
    onehot_i = onehot.astype(jnp.int32)
    cold = jnp.logical_not(state.image_cached)[jnp.clip(action, 0, state.n_nodes - 1)]
    start_cost = jnp.where(cold, pull_cost_now(state, cfg), cfg.warm_start_cost)
    accel = accel_ask(state, pod)
    if accel is not None:
        state = state._replace(
            accel_requested=state.accel_requested + onehot_i * accel)
    return state._replace(
        num_pods=state.num_pods + onehot_i,
        exp_pods=state.exp_pods + onehot_i,
        cpu_requested=state.cpu_requested + onehot * pod.cpu_request,
        mem_requested=state.mem_requested + onehot * pod.mem_request,
        pods_cpu=state.pods_cpu + onehot * pod.cpu_demand,
        mem_used=state.mem_used + onehot * pod.mem_demand,
        startup_cpu=state.startup_cpu + onehot * start_cost,
        image_cached=state.image_cached | (onehot_i > 0),
    )


def hypothetical_place(state: ClusterState, pod: PodSpec, cfg: EnvConfig,
                       pull_cost: jnp.ndarray | None = None) -> jnp.ndarray:
    """Afterstate features for *every* candidate node: (N, 6).

    Row i = Table-2 features of node i as if the pod were placed there.
    This is the SDQN scoring input (Q is evaluated on afterstates) and the
    hottest function in both training and serving-time placement.

    Row i of ``features(place(state, i, ...))`` depends only on node i's own
    columns, so instead of materializing N full placed cluster states
    (vmap-of-place: O(N^2) work and memory), apply the placement delta to
    every node at once and evaluate the feature formula elementwise — O(N).
    The ops mirror ``place``/``cpu_used``/``features`` exactly so the result
    is bit-identical to ``hypothetical_place_reference``.
    """
    # placement deltas (same arithmetic as `place` restricted to the chosen
    # row).  ``pull_cost`` overrides the in-flight pull-contention scalar:
    # it is a GLOBAL reduction over startup transients, so sharded scoring
    # (sched.shard) computes it ONCE from the full fleet and threads it into
    # every per-shard call — a per-shard recompute would silently diverge
    # from the unsharded program.
    pull = pull_cost_now(state, cfg) if pull_cost is None else pull_cost
    start_cost = jnp.where(jnp.logical_not(state.image_cached),
                           pull, cfg.warm_start_cost)
    num_pods = state.num_pods + 1
    exp_pods = state.exp_pods + 1
    pods_cpu = state.pods_cpu + 1.0 * pod.cpu_demand
    mem_used = state.mem_used + 1.0 * pod.mem_demand
    startup_cpu = state.startup_cpu + start_cost

    used = _node_cpu_used(state.base_cpu, exp_pods > 0, pods_cpu, startup_cpu,
                          num_pods, state.cpu_capacity, cfg)
    return _feature_stack(used, mem_used, num_pods, state.max_pods, state.healthy,
                          state.uptime_hours, exp_pods, state.cpu_capacity,
                          state.mem_capacity)


def hypothetical_place_one(state: ClusterState, pod: PodSpec, cfg: EnvConfig,
                           node: jnp.ndarray) -> jnp.ndarray:
    """Afterstate features of a single candidate node: one (6,) row.

    Row ``node`` of ``hypothetical_place`` without building the (N, 6)
    matrix — the training loop scores through the fused kernel dispatch and
    only ever *stores* the one afterstate it actually bound, so the full
    matrix is never needed on the replay path.  (Still O(N) *time*:
    ``pull_cost_now`` scans the in-flight startup transients; what this
    saves is the (N, 6) materialization and HBM round-trip.)  ``node`` must
    be a valid index (callers clamp the ``NO_NODE`` sentinel and zero-weight
    the sample).  Same elementwise arithmetic as ``hypothetical_place``,
    applied to the gathered columns, so the row matches bit-for-bit.
    """
    start_cost = jnp.where(jnp.logical_not(state.image_cached[node]),
                           pull_cost_now(state, cfg), cfg.warm_start_cost)
    num_pods = state.num_pods[node] + 1
    exp_pods = state.exp_pods[node] + 1
    pods_cpu = state.pods_cpu[node] + 1.0 * pod.cpu_demand
    mem_used = state.mem_used[node] + 1.0 * pod.mem_demand
    startup_cpu = state.startup_cpu[node] + start_cost

    used = _node_cpu_used(state.base_cpu[node], exp_pods > 0, pods_cpu,
                          startup_cpu, num_pods, state.cpu_capacity[node], cfg)
    return _feature_stack(used, mem_used, num_pods, state.max_pods[node],
                          state.healthy[node], state.uptime_hours[node],
                          exp_pods, state.cpu_capacity[node],
                          state.mem_capacity[node])


def hypothetical_place_reference(state: ClusterState, pod: PodSpec, cfg: EnvConfig) -> jnp.ndarray:
    """Reference afterstate scorer: vmap of the full transition (O(N^2)).

    Kept as the semantic ground truth the fast path is verified against
    (tests/test_scenarios.py) and as the baseline in benchmarks/sched_scale.py.
    """
    n = state.n_nodes

    def one(i):
        return features(place(state, i, pod, cfg), cfg)[i]

    return jax.vmap(one)(jnp.arange(n))


def remove_pod(state: ClusterState, node: jnp.ndarray, pod: PodSpec,
               count: jnp.ndarray | int = 1) -> ClusterState:
    """Unbind ``count`` pods of spec ``pod`` from ``node``: the exact inverse
    of ``place``'s resource accounting (startup transients and the cached
    image stay — pulling is not undone by a pod finishing or migrating)."""
    c = jnp.asarray(count, jnp.float32)
    onehot = jax.nn.one_hot(node, state.n_nodes, dtype=jnp.float32) * c
    onehot_i = onehot.astype(jnp.int32)
    accel = accel_ask(state, pod)
    if accel is not None:
        state = state._replace(
            accel_requested=state.accel_requested - onehot_i * accel)
    return state._replace(
        num_pods=state.num_pods - onehot_i,
        exp_pods=state.exp_pods - onehot_i,
        cpu_requested=state.cpu_requested - onehot * pod.cpu_request,
        mem_requested=state.mem_requested - onehot * pod.mem_request,
        pods_cpu=state.pods_cpu - onehot * pod.cpu_demand,
        mem_used=state.mem_used - onehot * pod.mem_demand,
    )


# ---------------------------------------------------------------------------
# pod lifecycle: fixed-shape expiry ledger, retirement, energy accounting
# ---------------------------------------------------------------------------


def ledger_init(n_slots: int, accel: bool = False) -> PodLedger:
    """Empty expiry ledger with one slot per episode arrival (static shape).
    ``accel``: the pods ask for accelerators, so the spec carries their
    requests."""
    z = jnp.zeros((n_slots,), jnp.float32)
    return PodLedger(
        node=jnp.full((n_slots,), -1, jnp.int32),
        expiry_s=jnp.full((n_slots,), jnp.inf, jnp.float32),
        spec=PodSpec(cpu_request=z, cpu_demand=z, mem_request=z, mem_demand=z,
                     accel_request=(jnp.zeros((n_slots,), jnp.int32)
                                    if accel else None)),
    )


def _release(state: ClusterState, ledger: PodLedger, mask: jnp.ndarray,
             seg: jnp.ndarray) -> ClusterState:
    """Hand back the resources of every ledger pod in ``mask`` to its node
    (``seg``): one fused scatter-add (``segment_sum``) per column."""
    n = state.n_nodes
    w = mask.astype(jnp.float32)

    def released(col):
        return jax.ops.segment_sum(w * col, seg, num_segments=n)

    mask_i = mask.astype(jnp.int32)
    cnt = jax.ops.segment_sum(mask_i, seg, num_segments=n)
    accel = accel_ask(state, ledger.spec)
    if accel is not None:
        state = state._replace(accel_requested=state.accel_requested
                               - jax.ops.segment_sum(mask_i * accel, seg,
                                                     num_segments=n))
    return state._replace(
        num_pods=state.num_pods - cnt,
        exp_pods=state.exp_pods - cnt,
        cpu_requested=state.cpu_requested - released(ledger.spec.cpu_request),
        mem_requested=state.mem_requested - released(ledger.spec.mem_request),
        pods_cpu=state.pods_cpu - released(ledger.spec.cpu_demand),
        mem_used=state.mem_used - released(ledger.spec.mem_demand),
    )


def ledger_record(ledger: PodLedger, slot, action, expiry_s, pod: PodSpec) -> PodLedger:
    """Write arrival ``slot``: where the pod went and when it completes.

    Dropped arrivals (``action == NO_NODE``) record as empty slots, so they
    are never retired (no resources were ever acquired).
    """
    action = jnp.asarray(action, jnp.int32)
    return PodLedger(
        node=ledger.node.at[slot].set(action),
        expiry_s=ledger.expiry_s.at[slot].set(
            jnp.where(action >= 0, jnp.asarray(expiry_s, jnp.float32), jnp.inf)),
        spec=jax.tree.map(lambda col, v: col.at[slot].set(v), ledger.spec, pod),
    )


def retire_expired(state: ClusterState, ledger: PodLedger
                   ) -> Tuple[ClusterState, PodLedger, jnp.ndarray]:
    """Retire every ledger pod whose expiry has passed: release its CPU/mem
    requests, compute demand, pod slots and accelerators on its node, and
    free the slot.

    One fused scatter-add (``segment_sum`` over the ledger's node column) per
    resource column — O(K + N) with static shapes, so the scanned episode
    loop and the vmapped eval/train engines batch over lifecycle episodes
    unchanged.  With all-``inf`` lifetimes every mask is false and the state
    passes through bit-for-bit (the static-table parity case).
    """
    n = state.n_nodes
    done = (ledger.node >= 0) & (ledger.expiry_s <= state.time_s)
    state = _release(state, ledger, done, jnp.clip(ledger.node, 0, n - 1))
    ledger = ledger._replace(node=jnp.where(done, -1, ledger.node))
    return state, ledger, jnp.sum(done).astype(jnp.int32)


def has_lifecycle(cfg: EnvConfig) -> bool:
    """True when the scenario's catalog contains any finite-lifetime pod.

    A *static* (trace-time) property: scenarios are hashable jit statics, so
    episodes over purely-immortal workloads skip the ledger bookkeeping
    entirely — the hot training loop pays for retirement scatters only when
    pods can actually retire.
    """
    scn = cfg.scenario
    return scn is not None and any(
        np.isfinite(p.lifetime_mean_s) for p in scn.pod_types)


# ---------------------------------------------------------------------------
# chaos: mid-episode node failures (fixed-shape, jit/vmap-safe)
# ---------------------------------------------------------------------------


def has_chaos(cfg: EnvConfig) -> bool:
    """True when any node class can fail mid-episode (finite ``mtbf_s``).

    Like ``has_lifecycle`` this is a *static* (trace-time) property: the
    default all-``inf`` MTBF keeps every pre-chaos scenario's episode trace
    byte-identical — no eviction scatters, no reschedule ring in the carry.
    """
    scn = cfg.scenario
    return scn is not None and any(
        np.isfinite(c.mtbf_s) for c in scn.node_classes)


def empty_failure_trace(n_nodes: int, cycles: int = 1) -> FailureTrace:
    """A trace in which no node ever fails (all windows at ``inf``).

    Threading this through ``run_episode`` exercises the chaos code path with
    every mask false — the parity case the tests pin (≤1e-6 vs no trace).
    """
    full = jnp.full((cycles, n_nodes), jnp.inf, jnp.float32)
    return FailureTrace(fail_s=full, recover_s=full)


def sample_failure_trace(key: jax.Array, cfg: EnvConfig,
                         cycles: Optional[int] = None) -> FailureTrace:
    """Draw per-node fail/recover schedules from each class's MTBF/MTTR.

    An alternating-renewal (Poisson fail / Poisson repair) process: node
    ``n``'s ``c``-th outage starts ``Exp(mtbf)`` after its previous recovery
    and lasts ``Exp(mttr)``.  Cycles accumulate sequentially in a *static*
    python loop so ``mtbf = inf`` stays ``inf`` all the way down (a vectorized
    cumsum would hit ``inf - inf`` NaNs); the unit exponentials are clamped
    away from zero so ``inf * 0`` can never appear either.
    """
    cycles = cfg.chaos_cycles if cycles is None else cycles
    if cfg.scenario is None:
        mtbf = jnp.full((cfg.n_nodes,), jnp.inf, jnp.float32)
        mttr = jnp.full((cfg.n_nodes,), 60.0, jnp.float32)
    else:
        pool = _scenario_pool(cfg.scenario)
        mtbf = jnp.asarray(pool["mtbf"])
        mttr = jnp.asarray(pool["mttr"])
    n = mtbf.shape[0]
    prev = jnp.zeros((n,), jnp.float32)
    fails, recovers = [], []
    for c in range(cycles):
        ku = jax.random.fold_in(key, 2 * c)
        kd = jax.random.fold_in(key, 2 * c + 1)
        up = mtbf * jnp.maximum(jax.random.exponential(ku, (n,), jnp.float32), 1e-6)
        down = mttr * jnp.maximum(jax.random.exponential(kd, (n,), jnp.float32), 1e-6)
        f = prev + up
        r = f + down
        fails.append(f)
        recovers.append(r)
        prev = r
    return FailureTrace(fail_s=jnp.stack(fails), recover_s=jnp.stack(recovers))


def trace_down(trace: FailureTrace, t_s: jnp.ndarray) -> jnp.ndarray:
    """Per-node down mask at episode time ``t_s``: (N,) bool."""
    return jnp.any((trace.fail_s <= t_s) & (t_s < trace.recover_s), axis=0)


class RescheduleQueue(NamedTuple):
    """Fixed-capacity ring of evicted pods awaiting re-placement.

    Each entry points back at the pod's own pre-reserved ledger slot (its
    spec is still recorded there) plus the run time it had left when its node
    died — re-placement restarts the pod from scratch with that remaining
    duration (checkpoint/restart semantics).  ``head``/``count`` bound the
    live window; pushes past capacity are *lost* (counted, never silent).
    """

    slot: jnp.ndarray         # (R,) int32 ledger slot of each queued pod
    remaining_s: jnp.ndarray  # (R,) float32 run time left at eviction
    head: jnp.ndarray         # int32 index of the oldest entry
    count: jnp.ndarray        # int32 number of live entries


def reschedule_queue_init(cap: int) -> RescheduleQueue:
    return RescheduleQueue(
        slot=jnp.full((cap,), -1, jnp.int32),
        remaining_s=jnp.zeros((cap,), jnp.float32),
        head=jnp.int32(0),
        count=jnp.int32(0),
    )


def _queue_push(q: RescheduleQueue, mask: jnp.ndarray, values: jnp.ndarray,
                cap: int) -> Tuple[RescheduleQueue, jnp.ndarray]:
    """Push every masked ledger slot into the ring (oldest-first FIFO order).

    Rank-by-cumsum turns the boolean mask into contiguous ring positions;
    entries past the remaining space scatter to an out-of-range index and
    are dropped by ``mode="drop"`` — returned as the overflow (lost) count.
    """
    space = cap - q.count
    rank = jnp.cumsum(mask.astype(jnp.int32)) - 1
    ok = mask & (rank < space)
    pos = jnp.where(ok, (q.head + q.count + rank) % cap, cap)
    slot_ids = jnp.arange(mask.shape[0], dtype=jnp.int32)
    n_mask = jnp.sum(mask.astype(jnp.int32))
    n_push = jnp.minimum(n_mask, space)
    q = q._replace(
        slot=q.slot.at[pos].set(slot_ids, mode="drop"),
        remaining_s=q.remaining_s.at[pos].set(values, mode="drop"),
        count=q.count + n_push,
    )
    return q, n_mask - n_push


def evict_down_pods(state: ClusterState, ledger: PodLedger, q: RescheduleQueue,
                    healthy_base: jnp.ndarray, trace: FailureTrace, cap: int
                    ) -> Tuple[ClusterState, PodLedger, RescheduleQueue,
                               jnp.ndarray, jnp.ndarray]:
    """Apply the failure trace at the current episode time.

    Flips ``healthy`` to ``healthy_base & ~down(t)`` and evicts every ledger
    pod hosted on a down node through the same fused ``segment_sum`` release
    as ``retire_expired``, pushing each into the reschedule ring with its
    remaining run time.  Idempotent across steps: an evicted slot's node is
    ``-1``, so a node staying down evicts nothing new.  Returns
    ``(state, ledger, queue, n_evicted, n_overflow_lost)``.
    """
    n = state.n_nodes
    down = trace_down(trace, state.time_s)
    state = state._replace(healthy=healthy_base & jnp.logical_not(down))
    seg = jnp.clip(ledger.node, 0, n - 1)
    evict = (ledger.node >= 0) & down[seg]
    state = _release(state, ledger, evict, seg)
    remaining = ledger.expiry_s - state.time_s
    ledger = ledger._replace(node=jnp.where(evict, -1, ledger.node))
    q, n_lost = _queue_push(q, evict, remaining, cap)
    return state, ledger, q, jnp.sum(evict).astype(jnp.int32), n_lost


def nodes_active(state: ClusterState) -> jnp.ndarray:
    """Nodes hosting >= 1 experiment pod — the nodes our workload keeps up."""
    return jnp.sum(state.exp_pods > 0).astype(jnp.int32)


def fleet_power_w(state: ClusterState, cfg: EnvConfig) -> jnp.ndarray:
    """Instantaneous power draw (watts) billed to the experiment workload.

    Each node hosting our pods draws ``idle + (peak - idle) * cpu_util``;
    nodes without experiment pods are releasable (a green autoscaler could
    power them down), so they bill nothing — consolidation savings show up
    directly in the integral of this quantity.
    """
    idle, peak = node_watts(cfg)
    util = cpu_used(state, cfg) / state.cpu_capacity
    return jnp.sum(jnp.where(state.exp_pods > 0,
                             idle + (peak - idle) * util, 0.0))


def tick(state: ClusterState, cfg: EnvConfig, dt_s) -> ClusterState:
    """Advance wall-clock: decay startup transients, accrue uptime.

    ``startup_decay`` is calibrated per ``schedule_dt_s`` step, so with
    variable arrival gaps (Poisson/diurnal scenarios) the transient decays
    by ``decay ** (dt / schedule_dt_s)`` — wall-clock time, not arrival
    count, governs how long an image pull saturates a node.
    """
    decay = cfg.startup_decay ** (dt_s / cfg.schedule_dt_s)
    return state._replace(
        startup_cpu=state.startup_cpu * decay,
        uptime_hours=state.uptime_hours + dt_s / 3600.0,
        time_s=state.time_s + dt_s,
    )


# ---------------------------------------------------------------------------
# the paper's evaluation metric (§4.3.2)
# ---------------------------------------------------------------------------


def average_cpu_utilization(state: ClusterState, cfg: EnvConfig) -> jnp.ndarray:
    """Cluster-wide average CPU% per node (idle nodes included)."""
    return jnp.mean(cpu_pct(state, cfg))


class _EpisodeAcc(NamedTuple):
    """Scan-carried accumulators of the dt-weighted episode integrals."""

    metric: jnp.ndarray        # sum of avg-CPU% * dt
    dt: jnp.ndarray            # total integrated wall-clock
    node_seconds: jnp.ndarray  # sum of nodes_active * dt
    energy_j: jnp.ndarray      # sum of fleet power * dt (joules)
    peak_active: jnp.ndarray   # max nodes_active seen
    retired: jnp.ndarray       # int32 pods completed + released
    evicted: jnp.ndarray       # int32 pods killed by node failures
    rescheduled: jnp.ndarray   # int32 evicted pods re-placed in-episode
    lost: jnp.ndarray          # int32 evicted pods dropped off the ring


def _acc_init() -> _EpisodeAcc:
    z = jnp.float32(0.0)
    zi = jnp.int32(0)
    return _EpisodeAcc(z, z, z, z, z, zi, zi, zi, zi)


def run_episode(
    key: jax.Array,
    cfg: EnvConfig,
    select_action,  # (key, state, pod) -> int32 node index
    n_pods: int,
    pod_table: Optional[PodTable] = None,
    consolidate: Optional[Callable] = None,
    select_carry=None,
    failure_trace: Optional[FailureTrace] = None,
) -> EpisodeResult:
    """Schedule `n_pods` arrivals with `select_action`, settle, retire.

    Arrivals come from `pod_table` when given, otherwise they are sampled
    from `cfg.scenario` (homogeneous fixed burst when no scenario is set).
    The reset / arrival-stream / per-step action keys are split up front so
    the initial cluster layout is independent of the exploration noise.

    The cluster is *dynamic*: every placement records its sampled lifetime in
    a fixed-shape ``PodLedger`` and ``retire_expired`` releases completed
    pods' CPU/mem/slots inside the scanned loop, so idle nodes appear over
    time and the SDQN-n consolidation/energy story becomes measurable.  With
    all-``inf`` lifetimes (the default pod, any catalog entry without a
    duration) retirement is the identity and episodes reproduce the
    pre-lifecycle static-table trajectories bit-for-bit.

    ``consolidate`` (see ``sched.elastic.make_consolidator``) runs every
    ``cfg.consolidate_every_s`` seconds of episode time: a jit-safe SDQN-n
    pass that migrates pods off nearly-idle nodes through the fused
    ``score_afterstates`` dispatch.

    ``select_carry`` (a pytree, e.g. ``PolicySpec.carry_init``'s state)
    switches ``select_action`` to the carrying protocol
    ``(key, state, pod, carry) -> (node, carry)``: sequence policy classes
    (Mamba arrival-history encoders) thread their recurrent state through
    the scanned arrivals.  ``None`` (the default) keeps the stateless
    three-argument selector protocol unchanged.

    ``failure_trace`` injects mid-episode node failures (see
    ``sample_failure_trace``): whenever a node's outage window opens, its
    ``healthy`` flips off, its ledger pods are evicted through the fused
    ``segment_sum`` release, and the evictees queue in a fixed-capacity
    reschedule ring — each subsequent arrival step attempts one re-placement
    back into the pod's own pre-reserved ledger slot with its remaining run
    time.  When ``None`` and the scenario has any finite-MTBF node class, a
    trace is auto-sampled from a dedicated ``fold_in(key, 13)`` stream (the
    reset/arrival/action streams are untouched).  With no trace and an
    all-``inf`` MTBF catalog the chaos path is skipped at trace time, and
    with an ``empty_failure_trace`` every chaos mask is false — both pin the
    pre-chaos trajectories (the parity the tests assert).

    Returns an ``EpisodeResult`` ``(state, placements, metric, dropped,
    stats)`` where ``metric`` is the dt-weighted cluster-average CPU% (the
    paper's objective), ``placements`` is the final (N,) pod distribution,
    ``dropped`` counts ``NO_NODE`` arrivals, and ``stats`` is an
    ``EpisodeStats`` of the time-resolved lifecycle metrics (active nodes,
    node-seconds, energy, retirements).  The field order matches the legacy
    positional 5-tuple, so old-style unpacking still works through the
    NamedTuple shim.
    """
    k_reset, k_pods, k_act = jax.random.split(key, 3)
    state = reset(k_reset, cfg)
    # ledger bookkeeping is skipped at trace time when nothing can ever
    # retire: the scenario's catalog is all-inf AND no caller-supplied table
    # (whose lifetimes are runtime values) or consolidation pass needs slots
    do_consolidate = consolidate is not None and cfg.consolidate_every_s > 0.0
    use_chaos = failure_trace is not None or has_chaos(cfg)
    use_ledger = (pod_table is not None or has_lifecycle(cfg) or do_consolidate
                  or use_chaos)
    if pod_table is None:
        pod_table = sample_pod_table(k_pods, cfg, n_pods)
    if use_chaos and failure_trace is None:
        failure_trace = sample_failure_trace(jax.random.fold_in(key, 13), cfg)
    healthy_base = state.healthy
    requeue_cap = cfg.chaos_requeue_cap if use_chaos else 1

    # the metric integrates cluster-average CPU% over wall-clock (dt-weighted),
    # so bursty arrival phases don't over-weight the average under Poisson /
    # diurnal streams; with constant gaps this reduces to the plain mean.
    def advance(st, ledger, q, dt, acc: _EpisodeAcc):
        """Shared post-placement body: tick, retire, evict, consolidate,
        integrate."""
        t_before = st.time_s
        st = tick(st, cfg, dt)
        if use_ledger:
            st, ledger, n_ret = retire_expired(st, ledger)
        else:
            n_ret = jnp.int32(0)
        if use_chaos:
            # retire-then-evict: a pod both expired and on a dead node
            # releases exactly once (retirement already freed its slot)
            st, ledger, q, n_ev, n_lost = evict_down_pods(
                st, ledger, q, healthy_base, failure_trace, requeue_cap)
        else:
            n_ev = n_lost = jnp.int32(0)
        if do_consolidate:
            period = cfg.consolidate_every_s
            crossed = jnp.floor(st.time_s / period) > jnp.floor(t_before / period)
            st, ledger = jax.lax.cond(
                crossed,
                lambda args: consolidate(args[0], args[1])[:2],
                lambda args: args,
                (st, ledger),
            )
        m = average_cpu_utilization(st, cfg)
        na = nodes_active(st).astype(jnp.float32)
        acc = acc._replace(
            metric=acc.metric + m * dt,
            dt=acc.dt + dt,
            node_seconds=acc.node_seconds + na * dt,
            energy_j=acc.energy_j + fleet_power_w(st, cfg) * dt,
            peak_active=jnp.maximum(acc.peak_active, na),
            retired=acc.retired + n_ret,
            evicted=acc.evicted + n_ev,
            lost=acc.lost + n_lost,
        )
        return st, ledger, q, acc

    def try_reschedule(k, st, ledger, q, acc, pc):
        """One re-placement attempt per arrival step (fixed shape).

        Pops the ring head, re-scores it through the same selector as the
        arrival stream (a dedicated ``fold_in`` of the step key, so the
        arrival draws are untouched), and re-records into the pod's original
        ledger slot with its remaining run time.  A failed attempt rotates
        the entry to the tail — no head-of-line blocking while its resources
        are still scarce.  Every branch is ``where``-masked, so with an
        empty ring the whole block is the identity.
        """
        n_slots = ledger.node.shape[0]
        has = q.count > 0
        slot = jnp.clip(q.slot[q.head], 0, n_slots - 1)
        remaining = q.remaining_s[q.head]
        rpod = jax.tree.map(lambda col: col[slot], ledger.spec)
        a, pc2 = _select(jax.random.fold_in(k, 17), st, rpod, pc)
        pc = jax.tree.map(lambda new, old: jnp.where(has, new, old), pc2, pc)
        placed = has & (a >= 0)
        a_eff = jnp.where(placed, a, NO_NODE)
        st = place(st, a_eff, rpod, cfg)
        led2 = ledger_record(ledger, slot, a_eff, st.time_s + remaining, rpod)
        ledger = jax.tree.map(
            lambda new, old: jnp.where(placed, new, old), led2, ledger)
        # ring update: success pops the head; failure rotates it to the tail
        # (writing at (head+count) mod cap then advancing head is a correct
        # rotation even when the ring is full)
        tail = (q.head + q.count) % requeue_cap
        rotated = has & jnp.logical_not(placed)
        q = q._replace(
            slot=jnp.where(rotated, q.slot.at[tail].set(q.slot[q.head]), q.slot),
            remaining_s=jnp.where(
                rotated, q.remaining_s.at[tail].set(remaining), q.remaining_s),
            head=jnp.where(has, (q.head + 1) % requeue_cap, q.head),
            count=jnp.where(placed, q.count - 1, q.count),
        )
        acc = acc._replace(
            rescheduled=acc.rescheduled + placed.astype(jnp.int32))
        return st, ledger, q, acc, pc

    # the selector's carry rides the scan as an (empty for stateless
    # selectors) pytree — the () case adds no arrays, so the trace of the
    # historical three-argument protocol is unchanged
    if select_carry is None:
        sel_carry0 = ()

        def _select(k, st, pod, pc):
            return select_action(k, st, pod), pc
    else:
        sel_carry0 = select_carry
        _select = select_action

    def sched_step(carry, xs):
        st, ledger, q, acc, pc = carry
        t, k, pod, dt, lifetime = xs
        a, pc = _select(k, st, pod, pc)
        st = place(st, a, pod, cfg)
        if use_ledger:
            ledger = ledger_record(ledger, t, a, st.time_s + lifetime, pod)
        if use_chaos:
            st, ledger, q, acc, pc = try_reschedule(k, st, ledger, q, acc, pc)
        st, ledger, q, acc = advance(st, ledger, q, dt, acc)
        return (st, ledger, q, acc, pc), a

    keys = jax.random.split(k_act, n_pods)
    (state, ledger, q, acc, _), actions = jax.lax.scan(
        sched_step, (state, ledger_init(n_pods if use_ledger else 1,
                                        pod_table.specs.accel_request
                                        is not None),
                     reschedule_queue_init(requeue_cap), _acc_init(),
                     sel_carry0),
        (jnp.arange(n_pods), keys, pod_table.specs, pod_table.dt_s,
         pod_table.lifetime_s),
    )

    def settle_step(carry, _):
        st, ledger, q, acc = carry
        st, ledger, q, acc = advance(st, ledger, q, cfg.schedule_dt_s, acc)
        return (st, ledger, q, acc), None

    (state, ledger, q, acc), _ = jax.lax.scan(
        settle_step, (state, ledger, q, acc), None, length=cfg.settle_steps
    )
    stats = EpisodeStats(
        nodes_active_mean=acc.node_seconds / acc.dt,
        nodes_active_final=nodes_active(state),
        nodes_active_peak=acc.peak_active.astype(jnp.int32),
        node_seconds=acc.node_seconds,
        energy_wh=acc.energy_j / 3600.0,
        retired=acc.retired,
        evicted=acc.evicted,
        rescheduled=acc.rescheduled,
        # still-queued evictees never re-entered before the episode ended
        lost=acc.lost + q.count,
    )
    return EpisodeResult(
        state=state,
        placements=state.num_pods,
        metric=acc.metric / acc.dt,
        dropped=jnp.sum(actions < 0).astype(jnp.int32),
        stats=stats,
    )
