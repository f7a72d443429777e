"""Pytree types for the cluster scheduling environment and its scenarios."""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp

# Unified "no feasible target" sentinel.  Historically ``env.NO_NODE`` (pod
# scheduling) and ``sched.placement.NO_HOST`` (job->host placement) were two
# independently-defined -1 constants; both are now re-exports of this one.
# Selectors return it when the filtering phase leaves no candidate; ``place``
# treats it as a no-op bind and episode accounting counts it as a drop.
NO_PLACEMENT = -1

# Width of the Table-2 afterstate feature row.  This is THE canonical
# definition: ``env.FEATURE_SCALE``, the replay ring's row layout, the MLP
# Q-net's input width and the fused kernels all derive from it (policy
# classes with history embeddings store ``FEATURE_DIM + embed_dim`` rows —
# see ``core.policy.PolicySpec.feature_dim``).
FEATURE_DIM = 6


class ClusterState(NamedTuple):
    """Vectorized node state. All arrays have leading dim N (nodes).

    The environment distinguishes *requested* resources (what the k8s control
    plane accounts: used by filtering and by the default scheduler's scoring)
    from *used* resources (what metrics-server/Grafana would report: used by
    the RL state features and by the paper's evaluation metric).
    """

    cpu_capacity: jnp.ndarray    # (N,) millicores
    mem_capacity: jnp.ndarray    # (N,) MiB
    max_pods: jnp.ndarray        # (N,) int32
    healthy: jnp.ndarray         # (N,) bool
    uptime_hours: jnp.ndarray    # (N,) fp32
    num_pods: jnp.ndarray        # (N,) int32 — ALL pods (tenant + experiment)
    exp_pods: jnp.ndarray        # (N,) int32 — experiment pods (our image)
    cpu_requested: jnp.ndarray   # (N,) millicores booked by requests
    mem_requested: jnp.ndarray   # (N,) MiB booked by requests
    pods_cpu: jnp.ndarray        # (N,) millicores of actual pod compute demand
    mem_used: jnp.ndarray        # (N,) MiB actually used
    base_cpu: jnp.ndarray        # (N,) pre-existing (non-experiment) load
    startup_cpu: jnp.ndarray     # (N,) transient startup/image-pull CPU, decays
    image_cached: jnp.ndarray    # (N,) bool — experiment image present on node
    time_s: jnp.ndarray          # () seconds since episode start
    # accelerators as a Kubernetes extended resource (``nvidia.com/gpu``,
    # ``aws.amazon.com/neuron``, ``google.com/tpu``): an integer count a
    # node, never overcommitted, never shared.  ``None`` (the default) is a
    # cluster without them: no leaves, so such a state traces exactly the
    # programs a CPU-only cluster always traced.
    accel_capacity: Optional[jnp.ndarray] = None   # (N,) int32
    accel_requested: Optional[jnp.ndarray] = None  # (N,) int32 booked

    @property
    def n_nodes(self) -> int:
        return self.cpu_capacity.shape[-1]


class PodSpec(NamedTuple):
    """One compute-intensive pod (the paper's no-op CPU burner)."""

    cpu_request: jnp.ndarray   # millicores (scheduling request)
    cpu_demand: jnp.ndarray    # millicores actually burned while running
    mem_request: jnp.ndarray   # MiB
    mem_demand: jnp.ndarray    # MiB
    # accelerators the pod asks for in ``limits`` (int32); ``None`` asks for
    # none.  Asking for any on a state without accelerators is an error.
    accel_request: Optional[jnp.ndarray] = None


# ---------------------------------------------------------------------------
# scenario description (heterogeneous node pools × pod catalogs × arrivals)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NodeClass:
    """A homogeneous slice of a heterogeneous node pool.

    ``base_cpu_frac`` / ``requested_frac`` are uniform ranges *as fractions of
    this class's capacity*, so a big node and a small node with the same
    fraction carry proportionate pre-existing load.

    ``idle_watts`` / ``peak_watts`` parameterize the energy model: a node the
    experiment workload keeps alive draws ``idle + (peak - idle) * cpu_util``
    watts; nodes hosting none of our pods are releasable (could be powered
    down), so they bill nothing to the workload.
    """

    name: str
    count: int
    cpu_capacity: float               # millicores
    mem_capacity: float               # MiB
    max_pods: int = 110
    unhealthy_prob: float = 0.0       # spot / flaky pools set this > 0
    base_cpu_frac: tuple = (0.02, 0.2)
    requested_frac: tuple = (0.05, 0.5)
    image_cached_prob: float = 0.0    # chance the experiment image is pre-pulled
    idle_watts: float = 120.0         # draw of a powered-on but idle node
    peak_watts: float = 350.0         # draw at 100% CPU utilization
    # mid-episode chaos: mean time between failures / to recovery (seconds),
    # exponentially distributed per node (a Poisson fail/recover process).
    # ``inf`` (the default) = the node never fails mid-episode, which keeps
    # every pre-chaos scenario bit-identical (see env.sample_failure_trace).
    mtbf_s: float = float("inf")
    mttr_s: float = 60.0
    # accelerators a node of this class carries (an extended resource); a
    # pool where every class and pod type says 0 has no accelerator column
    accel_capacity: int = 0


@dataclasses.dataclass(frozen=True)
class PodType:
    """One entry of the workload catalog (mixture component of the stream).

    ``lifetime_mean_s`` / ``lifetime_cv`` give the pod's running-duration
    distribution (lognormal with that mean and coefficient of variation;
    ``cv=0`` is deterministic).  The default ``inf`` never completes, which
    reproduces the static-table episodes exactly (see ``env.retire_expired``).
    """

    name: str
    weight: float                     # mixture weight in the arrival stream
    cpu_request: float                # millicores (scheduling request)
    cpu_demand: float                 # millicores actually burned
    mem_request: float                # MiB
    mem_demand: float                 # MiB
    lifetime_mean_s: float = float("inf")  # mean running duration; inf = forever
    lifetime_cv: float = 0.0          # lognormal coefficient of variation
    accel_request: int = 0            # accelerators asked for in limits


@dataclasses.dataclass(frozen=True)
class ArrivalConfig:
    """Pod arrival process.

    * ``burst``   — fixed inter-arrival gap (the paper's 50-pod burst);
    * ``poisson`` — exponential inter-arrival times at ``rate_per_s``;
    * ``diurnal`` — Poisson stream whose rate is modulated by a sine wave of
      ``period_s`` and relative amplitude ``depth`` (daily traffic wave).
    """

    kind: str = "burst"               # "burst" | "poisson" | "diurnal"
    rate_per_s: float = 0.5           # mean arrival rate (poisson / diurnal)
    period_s: float = 1200.0          # diurnal wave period
    depth: float = 0.8                # diurnal modulation depth in [0, 1)


@dataclasses.dataclass(frozen=True)
class ScenarioConfig:
    """Declarative scenario: node pool + pod catalog + arrival process.

    Static (hashable) so an ``EnvConfig`` carrying it can stay a jit static
    argument; all sampled quantities (which pod type arrives when, per-node
    jitter) are drawn inside jit from explicit PRNG keys.
    """

    name: str
    node_classes: tuple               # tuple[NodeClass, ...]
    pod_types: tuple                  # tuple[PodType, ...]
    arrival: ArrivalConfig = ArrivalConfig()
    n_pods: int = 50                  # default arrivals per episode
    settle_steps: Optional[int] = None  # post-arrival drain window override
    #   (churn scenarios need a longer settle so pods actually finish and
    #   the consolidation/energy story becomes measurable)

    @property
    def n_nodes(self) -> int:
        return sum(c.count for c in self.node_classes)


class PodTable(NamedTuple):
    """Pre-sampled arrival stream: everything ``lax.scan`` needs per step.

    ``specs`` holds one ``PodSpec`` per arrival (leading dim n_pods);
    ``dt_s`` is the wall-clock gap *after* each placement; ``type_idx``
    indexes the scenario's pod catalog (for logging / per-type metrics);
    ``lifetime_s`` is each pod's sampled running duration (``inf`` = the
    pod never completes — the pre-lifecycle static table).
    """

    specs: PodSpec                    # each field (n_pods,)
    dt_s: jnp.ndarray                 # (n_pods,) float32
    type_idx: jnp.ndarray             # (n_pods,) int32
    lifetime_s: jnp.ndarray           # (n_pods,) float32, inf = runs forever


class PodLedger(NamedTuple):
    """Fixed-shape expiry ledger: one slot per episode arrival.

    The jit/vmap-safe lifecycle bookkeeping: slot ``t`` is written when
    arrival ``t`` binds (``node`` = chosen node, ``expiry_s`` = absolute
    episode time the pod completes, ``spec`` = the exact resources to hand
    back), and ``env.retire_expired`` scatter-releases every due slot per
    step.  ``node == -1`` marks empty, dropped, or already-retired slots.
    All arrays have leading dim K = arrivals per episode (a static shape),
    so episodes batch under ``vmap`` / ``lax.scan`` unchanged.
    """

    node: jnp.ndarray                 # (K,) int32; -1 = empty / retired
    expiry_s: jnp.ndarray             # (K,) float32 absolute completion time
    spec: PodSpec                     # each field (K,): resources to release


class FailureTrace(NamedTuple):
    """Fixed-shape mid-episode node fail/recover schedule (jit/vmap-safe).

    ``fail_s[c, n]`` / ``recover_s[c, n]`` bound node ``n``'s ``c``-th outage
    window: the node is down whenever ``fail_s <= t < recover_s``.  ``inf``
    marks an unused cycle, so node health at any time ``t`` is a pure
    function of the trace — no event queue, no dynamic shapes.  Sampled per
    node from each ``NodeClass``'s Poisson MTBF/MTTR
    (``env.sample_failure_trace``); an all-``inf`` trace
    (``env.empty_failure_trace``) injects nothing and episodes reproduce the
    chaos-free trajectories (parity pinned in tests/test_chaos.py).
    """

    fail_s: jnp.ndarray               # (C, N) float32 outage start times
    recover_s: jnp.ndarray            # (C, N) float32 matching recovery times


class EpisodeStats(NamedTuple):
    """Time-resolved lifecycle metrics of one episode (all scalars).

    ``nodes_active`` counts nodes hosting >= 1 experiment pod — the nodes the
    workload prevents from being drained/powered down (the paper's SDQN-n
    green-consolidation objective, §1 contribution 2 / §6).

    The chaos counters account for mid-episode node failures (see
    ``env.sample_failure_trace``): every eviction resolves to exactly one of
    rescheduled or lost, so ``evicted == rescheduled + lost`` at episode end
    and a reward can charge failures (e.g. penalize ``lost``).  All three are
    zero for episodes without an active failure trace.
    """

    nodes_active_mean: jnp.ndarray    # time-averaged active-node count
    nodes_active_final: jnp.ndarray   # int32, active nodes at episode end
    nodes_active_peak: jnp.ndarray    # int32, max active nodes over the episode
    node_seconds: jnp.ndarray         # integral of nodes_active over wall-clock
    energy_wh: jnp.ndarray            # integral of active-node power draw
    retired: jnp.ndarray              # int32, pods that completed + released
    evicted: jnp.ndarray = jnp.int32(0)      # pods killed by node failures
    rescheduled: jnp.ndarray = jnp.int32(0)  # evicted pods re-placed in-episode
    lost: jnp.ndarray = jnp.int32(0)         # evicted pods never re-placed


class EpisodeResult(NamedTuple):
    """The public return value of ``env.run_episode``.

    Replaces the positional 5-tuple the function historically returned.  The
    field order is exactly the old positional order, so legacy
    ``state, dist, metric, dropped, stats = run_episode(...)`` unpacking
    keeps working through the NamedTuple (the one-release deprecation shim);
    new code should use the named fields.
    """

    state: "ClusterState"             # final cluster state after settle
    placements: jnp.ndarray           # (N,) final pods per node (the paper's
    #                                   "pod distribution"; tenant + ours)
    metric: jnp.ndarray               # dt-weighted cluster-average CPU% — the
    #                                   paper's objective (its reward signal)
    dropped: jnp.ndarray              # int32, arrivals with no feasible node
    stats: "EpisodeStats"             # time-resolved lifecycle metrics


@dataclasses.dataclass(frozen=True)
class EnvConfig:
    """Cluster simulation constants (calibrated against the paper's Tables 8–10).

    Mechanisms follow the paper §4.3.2: image caching and shared I/O reduce
    startup overhead for co-located pods; active nodes carry a base system
    overhead; overloading a node (>70% CPU) costs super-linear contention.
    """

    n_nodes: int = 4
    cpu_capacity: float = 4000.0       # millicores (4 vCPU slaves)
    mem_capacity: float = 16384.0      # MiB
    max_pods: int = 110                # k8s default
    # pod workload (no-op CPU burner)
    pod_cpu_request: float = 140.0
    pod_cpu_demand: float = 20.0       # no-op pods burn less than they request
    pod_mem_request: float = 128.0
    pod_mem_demand: float = 100.0
    # overhead model
    node_active_overhead: float = 500.0   # kubelet/cadvisor/runtime while pods run
    image_pull_cost: float = 4200.0       # transient CPU of a cold image pull (docker
    #                                       pull+unpack saturates small nodes for ~30s)
    warm_start_cost: float = 40.0         # transient CPU of a warm (cached) start
    startup_decay: float = 0.88           # per-step geometric decay of transients
    pull_concurrency_coeff: float = 0.7   # extra pull cost per concurrent pull
    contention_knee: float = 0.68         # utilization where contention kicks in
    #                                       (aligned with the paper's 70% threshold)
    contention_coeff: float = 120.0       # super-linear contention multiplier
    crowd_knee: int = 26                  # pods per node before CFS crowding costs
    crowd_coeff: float = 8.0              # millicores per (pods - knee)^2
    # episode
    schedule_dt_s: float = 2.0            # seconds between pod arrivals
    settle_steps: int = 20                # post-placement steps in the metric window
    # energy model (homogeneous pools; scenario node classes override per class)
    idle_watts: float = 120.0             # powered-on idle draw per node
    peak_watts: float = 350.0             # draw at 100% CPU utilization
    # in-episode SDQN-n consolidation cadence: every `consolidate_every_s`
    # seconds of episode time, run the jit-safe consolidation pass (see
    # sched.elastic.make_consolidator) passed to env.run_episode.  0 = off.
    consolidate_every_s: float = 0.0
    # initial conditions.  Per-trial, the per-node *usage* profile and the
    # per-node *requests* profile are independently permuted + jittered: the
    # cluster-wide totals stay stable (paper CVs are 1.6–5.4%) while which
    # node is busy/booked varies.  Pre-existing usage (system daemons,
    # co-located services) is NOT reflected in pre-existing requests — that
    # is exactly the blindness of request-based kube-scheduler scoring that
    # the RL schedulers exploit.
    # one "busy" node (co-located services / control-plane components) whose
    # load is invisible to request-based scoring — the paper's cluster shows
    # exactly this asymmetry in its default-scheduler distributions.
    base_cpu_profile: tuple = (720.0, 200.0, 120.0, 70.0)
    base_cpu_jitter: float = 40.0
    requested_frac_profile: tuple = (0.05, 0.12, 0.45, 0.80)
    requested_frac_jitter: float = 0.03
    init_uptime_range_h: tuple = (1.0, 200.0)
    unhealthy_prob: float = 0.0           # paper cluster: all Ready; tests override
    # domain randomization for TRAINING resets only (decorrelates node state
    # from episode time so the Q-net learns the actual reward structure, not
    # the on-policy time correlation).  Evaluation uses the clean cluster.
    randomize_workload: bool = False
    randomize_max_pods: int = 26
    randomize_empty_prob: float = 0.45    # chance a node starts with no pods
    randomize_cached_prob: float = 0.3    # chance an empty node has the image
    # chaos (mid-episode node failures, see env.sample_failure_trace): the
    # fixed capacity of the in-episode reschedule ring evicted pods re-enter
    # the arrival stream through, and how many fail/recover cycles per node
    # a sampled FailureTrace can hold.  Both are static shape parameters.
    chaos_requeue_cap: int = 32
    chaos_cycles: int = 4
    # scenario mode: when set, reset() builds the heterogeneous node pool from
    # scenario.node_classes (n_nodes/capacity fields above are overridden) and
    # episodes draw per-arrival PodSpecs from the scenario's pod catalog.
    scenario: Optional[ScenarioConfig] = None


def training_cluster() -> "EnvConfig":
    """Domain-randomized variant of the paper cluster for policy training."""
    return dataclasses.replace(paper_cluster(), randomize_workload=True)


def paper_cluster() -> EnvConfig:
    """The paper's experimental cluster: 4 slave nodes, 50-pod batches."""
    return EnvConfig()


def fleet_cluster(n_nodes: int = 1024) -> EnvConfig:
    """A fleet-scale cluster for the 1000+-node scheduling benchmarks."""
    return dataclasses.replace(paper_cluster(), n_nodes=n_nodes, max_pods=110)


def scenario_env(scn: ScenarioConfig, randomize: bool = False, **overrides) -> EnvConfig:
    """EnvConfig for a scenario: n_nodes tracks the node pool; capacity and
    pod fields become per-class / per-arrival at reset/episode time."""
    if scn.settle_steps is not None:
        overrides.setdefault("settle_steps", scn.settle_steps)
    return dataclasses.replace(
        paper_cluster(),
        n_nodes=scn.n_nodes,
        scenario=scn,
        randomize_workload=randomize,
        **overrides,
    )
