"""Production placement daemon: continuously-serving, batched, optimistic.

The paper's SDQN scheduler is only useful in production if it can serve
placement decisions under load.  This daemon is that serving loop:

  * **Batched one-launch scoring.**  Pending pod requests accumulate into
    batches (cut by size OR by the oldest request's wait time) and the whole
    batch is scored through the shared fused dispatch
    (``schedulers.score_afterstates_batch`` / ``ops.sdqn_score_delta`` via
    ``repro.sched.api``) in ONE device launch — one jitted call per batch,
    padded to a static batch shape so every fill level reuses one
    compilation.
  * **Double-buffered fleet state.**  Admission (``submit`` + committed
    binds) writes the *live* buffer — a mutable host-side (numpy) mirror —
    while scoring reads a frozen device *snapshot* published at batch cut.
    Request intake is a queue append plus numpy writes and never blocks on a
    device launch.  ``ClusterSubstrate`` keeps the snapshot on the device
    between batches and publishes only the rows that changed since the last
    publish: one packed transfer and one jitted scatter, whatever the fleet
    size (``_DeviceSnapshot``).
  * **Optimistic concurrency.**  Scores are computed against the snapshot,
    but by bind time the live buffer may have moved (earlier binds in the
    same batch, external churn applied through ``substrate.live``).  Every
    bind re-validates feasibility against the live buffer; a conflicted
    request loses the race and is re-queued to be re-scored against fresh
    state (``conflict_policy="requeue"``, mirroring the real kube binding
    race where an optimistic bind fails admission and the pod returns to the
    scheduling queue) or falls through to its next-best snapshot candidate
    (``conflict_policy="next-best"``).

Two substrates plug into the same loop: ``ClusterSubstrate`` (the paper's
pod->node cluster, ``core.env`` physics) and ``FleetSubstrate`` (job->host
placement over ``sched.placement.FleetState``, used by the serving driver in
``launch/serve.py``).  Both keep their live buffer as numpy mirrors whose
bind/feasibility arithmetic is pinned against the jnp reference
(``env.place`` / ``env.feasible``) in tests/test_daemon.py.

    sub = ClusterSubstrate(kenv.reset(key, cfg), cfg)
    d = PlacementDaemon(sub, qparams, DaemonConfig(batch_size=32))
    d.submit(pod); ...; d.poll(); decisions = d.decisions

Offered load comes from the scenario engine's arrival streams
(``scenarios.arrivals.arrival_trace``) replayed through ``replay_trace`` —
see ``benchmarks/placement_serve.py`` for the sustained placements/sec and
p50/p99 decision-latency bench.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import env as kenv, schedulers
from repro.core.types import NO_PLACEMENT, ClusterState, EnvConfig, PodSpec
from repro.sched import placement as _pl
from repro.sched.api import DIVERGENCE_LIMIT as _DIVERGENCE_LIMIT

__all__ = [
    "ClusterSubstrate", "DaemonConfig", "DaemonMetrics", "DaemonStats",
    "Decision", "FleetSubstrate", "LatencyReservoir", "PlacementDaemon",
    "replay_trace",
]


@dataclasses.dataclass(frozen=True)
class DaemonConfig:
    """Serving-loop knobs.

    A batch is cut when ``batch_size`` requests are pending OR the oldest
    pending request has waited ``max_wait_s`` — the standard
    throughput/latency trade of a batching server.  ``max_retries`` bounds
    how many times a conflicted request re-queues before it is dropped;
    ``conflict_policy`` picks what happens when an optimistic bind loses the
    race (see module docstring).  ``fused`` threads through to the scoring
    dispatch (``repro.sched.api.score``).

    Robustness knobs (all default to the legacy fail-open behavior):

    * ``queue_cap`` — admission backpressure: with more than this many
      requests pending, each new ``submit`` sheds the OLDEST pending request
      (decided as ``shed``, counted in ``DaemonStats.shed``) rather than
      growing the queue without bound.  ``0`` = unbounded.
    * ``backoff_base_s`` — a request that loses its optimistic bind re-queues
      with exponential backoff: attempt ``k`` waits
      ``backoff_base_s * 2**(k-1)`` before it is eligible for another batch
      (``poll`` honors the hold; ``flush``/``drain`` force it through so
      shutdown always terminates).  ``0`` = immediate re-queue.
    * ``score_deadline_s`` — per-batch scoring deadline.  A Q-net launch
      exceeding it (or returning NaN/diverged scores — always checked)
      degrades the daemon: the breached batch is re-scored with the closed-
      form kube heuristic (``sched.api.heuristic_score`` arithmetic, numpy,
      no device launch) and the next ``degrade_batches`` batches skip the
      Q-net entirely before probing it again.  ``None`` = no deadline.
    * ``heuristic_only`` — serve every batch with the kube heuristic (the
      degraded mode pinned on; the chaos bench's kube arm).
    """

    batch_size: int = 32
    max_wait_s: float = 0.02
    max_retries: int = 4
    conflict_policy: str = "requeue"     # "requeue" | "next-best"
    fused: object = "auto"
    queue_cap: int = 0                   # 0 = unbounded admission queue
    backoff_base_s: float = 0.0          # 0 = immediate conflict re-queue
    score_deadline_s: Optional[float] = None
    degrade_batches: int = 8
    heuristic_only: bool = False

    def __post_init__(self):
        if self.batch_size < 1:
            raise ValueError("batch_size must be >= 1")
        if self.conflict_policy not in ("requeue", "next-best"):
            raise ValueError(f"unknown conflict_policy "
                             f"{self.conflict_policy!r}")
        if self.queue_cap < 0:
            raise ValueError("queue_cap must be >= 0 (0 = unbounded)")
        if self.backoff_base_s < 0:
            raise ValueError("backoff_base_s must be >= 0")
        if self.degrade_batches < 0:
            raise ValueError("degrade_batches must be >= 0")


class Decision(NamedTuple):
    """One served placement decision (``node == NO_PLACEMENT`` = dropped)."""

    req_id: int
    node: int
    latency_s: float       # decision time - submission time
    attempts: int          # 1 + times the request lost an optimistic bind
    shed: bool = False     # evicted from the admission queue (backpressure)


class LatencyReservoir:
    """Fixed-memory uniform sample of the decision-latency stream.

    Algorithm R over a numpy buffer: every latency ever appended has equal
    probability of being in the sample, so p50/p99 stay unbiased while a
    days-long ``replay_trace`` run holds ``capacity`` floats instead of an
    unbounded python list.  Deterministically seeded — two daemons fed the
    same stream report the same percentiles.  Keeps the list surface the
    bench relies on (``append``, ``len``, iteration, ``np.asarray``).
    """

    __slots__ = ("_buf", "_filled", "_seen", "_rng")

    def __init__(self, capacity: int = 4096, seed: int = 0):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self._buf = np.zeros((capacity,), np.float64)
        self._filled = 0      # live entries in the buffer
        self._seen = 0        # total appends ever
        self._rng = np.random.default_rng(seed)

    def append(self, x: float) -> None:
        cap = self._buf.shape[0]
        if self._filled < cap:
            self._buf[self._filled] = x
            self._filled += 1
        else:
            j = int(self._rng.integers(0, self._seen + 1))
            if j < cap:
                self._buf[j] = x
        self._seen += 1

    @property
    def seen(self) -> int:
        """Total latencies observed (not just the retained sample)."""
        return self._seen

    def __len__(self) -> int:
        return self._filled

    def __iter__(self):
        return iter(self._buf[:self._filled])

    def __array__(self, dtype=None, copy=None):
        arr = self._buf[:self._filled]
        return arr.astype(dtype) if dtype is not None else arr.copy()

    def percentile(self, q: float) -> float:
        if self._filled == 0:
            return float("nan")
        return float(np.percentile(self._buf[:self._filled], q))

    def p50(self) -> float:
        return self.percentile(50.0)

    def p99(self) -> float:
        return self.percentile(99.0)


@dataclasses.dataclass
class DaemonMetrics:
    submitted: int = 0
    bound: int = 0
    dropped: int = 0
    shed: int = 0           # evicted from the admission queue (backpressure)
    conflicts: int = 0      # optimistic binds that failed live re-validation
    requeued: int = 0       # conflicted requests sent back to the queue
    evictions: int = 0      # bound pods auto-requeued off a failed node
    batches: int = 0
    device_launches: int = 0  # jitted scoring calls (degraded batches skip)
    fallback_batches: int = 0  # batches served by the kube heuristic
    commit_calls: int = 0   # requests through the commit loop (per attempt)
    walk_steps: int = 0     # next-best candidates re-validated after the first
    taken: int = 0          # requests taken into a batch (per attempt)
    queue_wait_s: float = 0.0  # summed over taken: take - (re)enqueue time
    upload_bytes: int = 0   # host-to-device bytes: snapshot publishes + pods
    full_publishes: int = 0   # snapshots published whole
    delta_publishes: int = 0  # snapshots published as their changed rows
    publish_rows: int = 0     # rows the delta publishes sent
    readback_bytes: int = 0  # bytes of scorer outputs copied to the host
    accel_bound: int = 0      # accelerators the bound pods asked for
    accel_conflicts: int = 0  # failed re-validations the accelerators failed
    # per batch-loop stage (``sched.batch``, ``sched.snapshot``, ...; see
    # ``_Span``): summed wall seconds and the number of times it ran
    stage_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    stage_n: Dict[str, int] = dataclasses.field(default_factory=dict)
    # decision latency of SERVED requests (bound or dropped) — the p50/p99
    # the placement_serve gate measures.  Shed requests live in shed_wait_s:
    # mixing the two meant that under backpressure the p99 gate measured
    # time-to-shed, not decision latency.
    bind_latencies_s: LatencyReservoir = dataclasses.field(
        default_factory=LatencyReservoir)
    shed_wait_s: LatencyReservoir = dataclasses.field(
        default_factory=LatencyReservoir)


# the public name the ops surface documents; the dataclass predates it
DaemonStats = DaemonMetrics


class _Request:
    __slots__ = ("req_id", "pod", "t_submit", "t_enqueued", "attempts",
                 "not_before", "accel")

    def __init__(self, req_id, pod, t_submit, accel=0):
        self.req_id = req_id
        self.pod = pod
        self.accel = accel           # accelerators the pod asks for
        self.t_submit = t_submit
        self.t_enqueued = t_submit   # last (re)entry into the queue
        self.attempts = 0
        self.not_before = t_submit   # conflict-backoff hold (poll honors it)


class _Span:
    """One stage of the batch loop, as a profiler span and a counter.

    The span is a ``jax.profiler.TraceAnnotation``: while a profiler session
    runs it lands on the profiler's host clock, the clock of the device
    trace; otherwise it is a no-op.  Its ``perf_counter`` duration is always
    added to ``metrics.stage_s[name]`` and 1 to ``metrics.stage_n[name]`` —
    the operator's per-stage time, on or off the profiler.  ``meta`` (ints)
    is attached to the trace event.
    """

    __slots__ = ("_metrics", "_name", "_ann", "_t0")

    def __init__(self, metrics: DaemonMetrics, name: str, **meta):
        self._metrics, self._name = metrics, name
        self._ann = jax.profiler.TraceAnnotation(name, **meta)

    def __enter__(self):
        self._ann.__enter__()
        self._t0 = time.perf_counter()

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        m, name = self._metrics, self._name
        m.stage_s[name] = m.stage_s.get(name, 0.0) + dt
        m.stage_n[name] = m.stage_n.get(name, 0) + 1


# ---------------------------------------------------------------------------
# substrates: live-buffer mirror + batched snapshot scorer
# ---------------------------------------------------------------------------

# rows a delta publish carries (capped at N): a batch's binds plus the writes
# made between batches (retirements, churn) fit; a larger change falls back
# to publishing the whole state
DELTA_ROWS = 256


class _Publish(NamedTuple):
    """What one snapshot publish sent to the device."""

    full: bool     # the whole state; else only the rows changed since the last
    rows: int      # rows a delta publish sent (0 for a full one)
    nbytes: int    # host-to-device bytes


def _accel_count(pod: PodSpec) -> int:
    """Accelerators a pod asks for (0 where its spec leaves it at None)."""
    return 0 if pod.accel_request is None else int(pod.accel_request)


def _bit_view(a: np.ndarray) -> np.ndarray:
    """``a`` as unsigned integers of its width, so ``!=`` compares bits: a
    NaN equals itself and ``-0.0`` differs from ``0.0``, as on the device."""
    return a.view(f"u{a.itemsize}")


@functools.partial(jax.jit, static_argnums=(2,))
def _scatter_rows(cols, buf, layout):
    """The node columns ``cols`` with the packed rows of ``buf`` written in.

    ``buf`` is (C, 1 + columns) int32: column 0 the row indices, column
    ``1 + j`` the values of ``cols[j]`` as 32-bit words (bools as 0/1).
    Padding rows carry an out-of-range index, which ``mode="drop"`` skips.
    In a sharded layout row ``r`` lands at ``(r // shard_size, r %
    shard_size)``.  Nothing is donated: the previous snapshot stays valid
    for whoever still holds it.
    """
    idx = buf[:, 0]
    at = (idx,) if layout is None else (idx // layout.shard_size,
                                        idx % layout.shard_size)
    out = []
    for col, w in zip(cols, buf[:, 1:].T):
        vals = (w != 0 if col.dtype == jnp.bool_
                else jax.lax.bitcast_convert_type(w, col.dtype))
        col = col.at[at].set(vals, mode="drop")
        if layout is not None and layout.mesh is not None:
            col = jax.lax.with_sharding_constraint(
                col, jax.sharding.NamedSharding(
                    layout.mesh, jax.sharding.PartitionSpec("data", None)))
        out.append(col)
    return out


class _DeviceSnapshot:
    """The scoring snapshot, kept on the device between publishes.

    ``dev`` is the device state the scorer reads: flat ``(N,)`` columns, or
    ``shard_cluster``'s padded ``(shards, shard_size)`` columns with a
    ``layout``.  ``_mirror`` is a host copy of what ``dev`` was made from.
    ``publish(live)`` compares every column of ``live`` with the mirror
    bitwise and sends only the rows that differ, packed with their indices
    into one (C, 1 + columns) buffer: one transfer, one jitted scatter
    (``_scatter_rows``).  It compares rather than tracks writes because
    callers may write ``substrate.live`` directly.

    It publishes the whole state instead (``jnp.asarray`` of every column,
    then ``shard_cluster``) on the first publish, when ``live`` was replaced,
    when a shape, a dtype or a scalar field (``time_s``) changed, when more
    than C rows changed, and always when a column is not 1-D of N rows with
    a 32-bit or bool device dtype.
    """

    def __init__(self, layout=None):
        self.layout = layout
        self.dev = None
        self._live = None      # the live tree the mirror copies
        self._mirror: list = []
        self._cap = 0          # C: rows a delta carries; 0 = whole only
        self._pad = 0          # row index past every layout's last row
        self._dtypes: list = []  # each node column's device dtype

    def publish(self, live) -> _Publish:
        if live is self._live and self._cap:
            pub = self._delta(jax.tree.leaves(live))
            if pub is not None:
                return pub
        return self._full(live)

    def warm(self) -> None:
        """Compile the delta publish's scatter (one program: C is fixed)."""
        if self._cap:
            jax.block_until_ready(self._scatter(self._buffer()))

    def _scatter(self, buf):
        """``dev`` with ``buf``'s rows written into its node columns; the
        scalar fields stay the arrays they are, on the devices they are."""
        leaves, tree = jax.tree.flatten(self.dev)
        cols = iter(_scatter_rows([x for x in leaves if x.ndim], buf,
                                  self.layout))
        return tree.unflatten([next(cols) if x.ndim else x for x in leaves])

    def _buffer(self) -> np.ndarray:
        buf = np.zeros((self._cap, 1 + len(self._dtypes)), np.int32)
        buf[:, 0] = self._pad
        return buf

    def _full(self, live) -> _Publish:
        # from a fresh host copy: on the CPU backend a device array may
        # alias the numpy buffer it was made from, and ``live`` moves on
        snap = jax.tree.map(lambda x: jnp.asarray(np.array(x)), live)
        nbytes = sum(x.nbytes for x in jax.tree.leaves(snap))
        dtypes = [x.dtype for x in jax.tree.leaves(snap)]
        if self.layout is not None:
            from repro.sched import shard as _shard

            snap = _shard.shard_cluster(snap, self.layout)
        self.dev = snap
        leaves = [np.asarray(x) for x in jax.tree.leaves(live)]
        if live is self._live and all(
                m.shape == a.shape and m.dtype == a.dtype
                for m, a in zip(self._mirror, leaves)):
            for m, a in zip(self._mirror, leaves):
                np.copyto(m, a)
        else:
            self._live = live
            self._mirror = [np.array(a) for a in leaves]
            cols = [a for a in leaves if a.ndim]
            n = cols[0].shape[0] if cols else 0
            ok = n > 0 and all(
                a.ndim == 0 or (a.ndim == 1 and a.shape[0] == n
                                and (dt == np.bool_ or dt.itemsize == 4))
                for a, dt in zip(leaves, dtypes))
            self._cap = min(DELTA_ROWS, n) if ok else 0
            self._pad = n if self.layout is None else self.layout.padded
            self._dtypes = [dt for a, dt in zip(leaves, dtypes) if a.ndim]
        return _Publish(True, 0, nbytes)

    def _delta(self, leaves) -> Optional[_Publish]:
        """The delta publish, or None where it must publish whole."""
        changed = None
        for a, m in zip(leaves, self._mirror):
            a = np.asarray(a)
            if a.shape != m.shape or a.dtype != m.dtype:
                return None
            if a.ndim == 0:
                if a.tobytes() != m.tobytes():
                    return None
            elif changed is None:
                changed = _bit_view(a) != _bit_view(m)
            else:
                changed |= _bit_view(a) != _bit_view(m)
        rows = np.flatnonzero(changed)
        k = rows.size
        if k > self._cap:
            return None
        if k == 0:
            return _Publish(False, 0, 0)
        buf = self._buffer()
        buf[:k, 0] = rows
        cols = (am for am in zip(leaves, self._mirror) if am[1].ndim)
        for j, ((a, m), dt) in enumerate(zip(cols, self._dtypes), 1):
            vals = np.asarray(a)[rows]
            m[rows] = vals
            vals = vals.astype(dt, copy=False)
            buf[:k, j] = vals if dt == np.bool_ else vals.view(np.int32)
        self.dev = self._scatter(buf)
        return _Publish(False, k, buf.nbytes)


class ClusterSubstrate:
    """The paper's pod->node cluster as a daemon substrate.

    ``live`` is a ``ClusterState`` of *mutable numpy* arrays — the admission
    buffer, and the single source of truth; callers may write it directly.
    ``snapshot`` publishes it as device arrays for the scoring launch.  The
    device copy stays resident between publishes, and a publish sends only
    the rows that differ from what the device holds: at most ``DELTA_ROWS``
    rows with their indices, as one (C, 1 + columns) int32 buffer applied
    by one jitted scatter.  It sends the whole state on the first publish,
    after ``live`` is replaced, when ``time_s`` or a shape or dtype changed,
    or when more than C rows changed (``_DeviceSnapshot``).
    ``last_publish`` says what the latest publish sent.
    ``bind``/``feasible_one`` mirror ``env.place``/``env.feasible``
    restricted to the touched row (parity pinned in tests/test_daemon.py).
    Where the state carries accelerators (``accel_capacity``), every filter
    and every bind and unbind here counts them too, and the delta publish
    carries their two int32 columns.
    """

    def __init__(self, state: ClusterState, cfg: EnvConfig,
                 score_fn: Optional[Callable] = None, policy=None,
                 layout=None, topk: int = 8):
        if score_fn is not None and policy is not None:
            raise ValueError("pass either score_fn or policy, not both")
        self.cfg = cfg
        self.score_fn = score_fn
        self.policy = policy
        # a launch.mesh.FleetLayout switches the substrate to two-stage
        # sharded scoring (sched.shard): the snapshot is published PRE-SHARDED
        # — (shards, shard_size) columns, device-distributed when the layout
        # carries a mesh — and stays that way between batches; the scorer
        # returns per-request candidate lists (topk per shard, merged)
        # instead of full (B, N) score rows
        self.layout = layout
        self.topk = topk
        self.live = jax.tree.map(lambda x: np.array(x), state)
        self._snap = _DeviceSnapshot(layout)
        self.last_publish: Optional[_Publish] = None

    def snapshot(self) -> ClusterState:
        self.last_publish = self._snap.publish(self.live)
        return self._snap.dev

    def warm_publish(self) -> None:
        """Compile the delta publish outside any timing window (publishing
        first if nothing has been published yet)."""
        if self._snap.dev is None:
            self.snapshot()
        self._snap.warm()

    def init_carry(self, params: dict):
        """The daemon-lifetime arrival-history carry: the policy's encoder
        state over the submitted request stream (() for stateless specs)."""
        if self.policy is not None and self.policy.embed_dim > 0:
            return self.policy.carry_init(params)
        return ()

    def pack(self, pods: Sequence[PodSpec], size: int) -> PodSpec:
        """Stack + pad a request batch to the static (size,) scoring shape."""
        pad = size - len(pods)
        pods = list(pods) + [pods[-1]] * pad

        def col(get):
            return jnp.asarray([float(get(p)) for p in pods], jnp.float32)

        accel = None
        if self.has_accel:
            accel = jnp.asarray([_accel_count(p) for p in pods], jnp.int32)
        return PodSpec(cpu_request=col(lambda p: p.cpu_request),
                       cpu_demand=col(lambda p: p.cpu_demand),
                       mem_request=col(lambda p: p.mem_request),
                       mem_demand=col(lambda p: p.mem_demand),
                       accel_request=accel)

    @property
    def has_accel(self) -> bool:
        """The live state carries accelerator columns."""
        return self.live.accel_capacity is not None

    def accel_of(self, pod) -> int:
        """Accelerators ``pod`` asks for; asking for any on a state without
        accelerators is an error (admission's check)."""
        a = _accel_count(pod)
        if a and not self.has_accel:
            raise ValueError(f"the pod requests {a} accelerators but the "
                             f"cluster state has no accelerator column")
        return a

    def accel_fits(self, node: int, pod) -> bool:
        """The accelerator predicate alone, against the LIVE buffer."""
        lv = self.live
        return bool(lv.accel_requested[node] + _accel_count(pod)
                    <= lv.accel_capacity[node])

    def make_scorer(self, fused) -> Callable:
        """Jitted ``(params, snapshot, pod_batch, carry, n_real) ->
        (scores, feasible, carry)``, scores/feasible (B, N): the whole batch
        in ONE device launch.

        The signature is uniform across policy classes so the daemon loop
        never branches: stateless specs thread ``carry = ()`` untouched,
        sequence specs advance their encoder carry *inside* the launch via a
        ``lax.scan`` over the batch (requests encode in submission order).
        ``n_real`` is a traced scalar — the ``< n_real`` pad mask means pad
        rows are scored (static shape, one compilation at every fill level)
        but never advance the history.  A conflicted request that re-queues
        re-encodes on its next batch — the history sees it twice, which is
        faithful to a kube scheduling queue (the pod really does arrive at
        the scheduler again).

        With a ``layout`` the contract becomes ``(params, snap, pods, carry,
        n_real) -> (cand_vals, cand_idx, carry)``, both (B, C) with
        ``C = shards * topk``: each request's two-stage candidate merge
        (sorted descending, ``-inf``/``-1`` past the feasible set) — the full
        (B, N) score matrix is never materialized on one device.  The
        ``pull_cost_now`` global reduction is computed once per batch from
        the sharded snapshot and threaded into every per-shard call.
        """
        cfg, score_fn, policy = self.cfg, self.score_fn, self.policy

        if self.layout is not None:
            from repro.core import policy as policy_mod
            from repro.sched import shard as _shard

            layout, k = self.layout, self.topk

            if policy is None or policy.embed_dim == 0:

                @jax.jit
                def score(params, snap, pods, carry, n_real):
                    pull = kenv.pull_cost_now(snap, cfg)
                    cv, ci = jax.vmap(
                        lambda p: _shard.cluster_topk(
                            params, snap, p, cfg, layout, k=k, fused=fused,
                            score_fn=score_fn, policy=policy,
                            pull_cost=pull))(pods)
                    return cv, ci, carry

                return score

            @jax.jit
            def score(params, snap, pods, carry, n_real):
                pull = kenv.pull_cost_now(snap, cfg)

                def step(c, xs):
                    pod, is_real = xs
                    c2, emb = policy.encode_step(
                        params, c, policy_mod.pod_workload_features(pod))
                    c2 = jax.tree.map(lambda a, b: jnp.where(is_real, a, b),
                                      c2, c)
                    cv, ci = _shard.cluster_topk(
                        params, snap, pod, cfg, layout, k=k, fused=fused,
                        policy=policy, embed=emb, pull_cost=pull)
                    return c2, (cv, ci)

                n_b = jax.tree.leaves(pods)[0].shape[0]
                is_real = jnp.arange(n_b) < n_real
                carry2, (cv, ci) = jax.lax.scan(step, carry, (pods, is_real))
                return cv, ci, carry2

            return score

        if policy is None or policy.embed_dim == 0:

            @jax.jit
            def score(params, snap, pods, carry, n_real):
                q = schedulers.score_afterstates_batch(params, snap, pods,
                                                       cfg, score_fn, fused,
                                                       policy=policy)
                ok = jax.vmap(lambda p: kenv.feasible(snap, p, cfg))(pods)
                return q, ok, carry

            return score

        from repro.core import policy as policy_mod

        @jax.jit
        def score(params, snap, pods, carry, n_real):
            def step(c, xs):
                pod, is_real = xs
                c2, emb = policy.encode_step(
                    params, c, policy_mod.pod_workload_features(pod))
                c2 = jax.tree.map(lambda a, b: jnp.where(is_real, a, b),
                                  c2, c)
                q = schedulers.score_afterstates(params, snap, pod, cfg,
                                                 fused=fused, policy=policy,
                                                 embed=emb)
                return c2, (q, kenv.feasible(snap, pod, cfg))

            n_b = jax.tree.leaves(pods)[0].shape[0]
            is_real = jnp.arange(n_b) < n_real
            carry2, (q, ok) = jax.lax.scan(step, carry, (pods, is_real))
            return q, ok, carry2

        return score

    def feasible_one(self, node: int, pod: PodSpec) -> bool:
        """``env.feasible`` row ``node`` against the LIVE buffer (bind-time
        re-validation)."""
        lv = self.live
        return bool(
            lv.healthy[node]
            and lv.cpu_requested[node] + float(pod.cpu_request)
            <= lv.cpu_capacity[node]
            and lv.mem_requested[node] + float(pod.mem_request)
            <= lv.mem_capacity[node]
            and lv.num_pods[node] < lv.max_pods[node]
            and (lv.accel_capacity is None or self.accel_fits(node, pod))
        )

    def bind(self, node: int, pod: PodSpec) -> None:
        """Commit one bind to the live buffer: ``env.place`` restricted to
        the chosen row, in numpy (no device op on the serving hot path)."""
        lv, cfg = self.live, self.cfg
        in_flight = float(np.sum(lv.startup_cpu > 0.25 * cfg.image_pull_cost))
        pull = cfg.image_pull_cost * (1.0 + cfg.pull_concurrency_coeff
                                      * in_flight)
        start = cfg.warm_start_cost if lv.image_cached[node] else pull
        lv.num_pods[node] += 1
        lv.exp_pods[node] += 1
        lv.cpu_requested[node] += float(pod.cpu_request)
        lv.mem_requested[node] += float(pod.mem_request)
        lv.pods_cpu[node] += float(pod.cpu_demand)
        lv.mem_used[node] += float(pod.mem_demand)
        lv.startup_cpu[node] += start
        lv.image_cached[node] = True
        if lv.accel_capacity is not None:
            lv.accel_requested[node] += _accel_count(pod)

    def unbind(self, node: int, pod: PodSpec) -> None:
        """Release one bound pod from the live buffer: ``env.remove_pod``
        restricted to the touched row (startup transients and the cached
        image stay, exactly like the env's arithmetic)."""
        lv = self.live
        lv.num_pods[node] -= 1
        lv.exp_pods[node] -= 1
        lv.cpu_requested[node] -= float(pod.cpu_request)
        lv.mem_requested[node] -= float(pod.mem_request)
        lv.pods_cpu[node] -= float(pod.cpu_demand)
        lv.mem_used[node] -= float(pod.mem_demand)
        if lv.accel_capacity is not None:
            lv.accel_requested[node] -= _accel_count(pod)

    def set_health(self, node: int, healthy: bool) -> None:
        """Flip one node's Ready condition in the live buffer (the health
        watchdog's write; ``feasible_one`` and the next snapshot see it)."""
        self.live.healthy[node] = bool(healthy)

    def heuristic_batch(self, pods: Sequence[PodSpec]):
        """(B, N) kube LeastRequested+Balanced scores + feasibility against
        the LIVE buffer, pure numpy — the degraded-mode scorer (same formula
        as ``sched.api.heuristic_score``, no device launch)."""
        lv = self.live
        creq = np.asarray([float(p.cpu_request) for p in pods])[:, None]
        mreq = np.asarray([float(p.mem_request) for p in pods])[:, None]
        cpu_free = (lv.cpu_capacity[None, :] - lv.cpu_requested[None, :]
                    - creq) / lv.cpu_capacity[None, :]
        mem_free = (lv.mem_capacity[None, :] - lv.mem_requested[None, :]
                    - mreq) / lv.mem_capacity[None, :]
        q = 10.0 * (cpu_free + mem_free) / 2.0 \
            + 10.0 * (1.0 - np.abs(cpu_free - mem_free))
        ok = (lv.healthy[None, :]
              & (lv.cpu_requested[None, :] + creq <= lv.cpu_capacity[None, :])
              & (lv.mem_requested[None, :] + mreq <= lv.mem_capacity[None, :])
              & (lv.num_pods[None, :] < lv.max_pods[None, :]))
        if lv.accel_capacity is not None:
            areq = np.asarray([_accel_count(p) for p in pods])[:, None]
            ok &= (lv.accel_requested[None, :] + areq
                   <= lv.accel_capacity[None, :])
        return q, ok


class FleetSubstrate:
    """Job->host placement (``sched.placement``) as a daemon substrate.

    Jobs are packed as (B, 6) afterstate-delta rows (``placement.job_delta``)
    and scored through the fused column kernel — the same dispatch
    ``PlacementEngine.select`` uses, batched.
    """

    def __init__(self, fleet: _pl.FleetState,
                 max_host_cpu_pct: float = 88.0, policy=None,
                 layout=None, topk: int = 8):
        self.live = jax.tree.map(lambda x: np.array(x, np.float64), fleet)
        self.max_host_cpu_pct = max_host_cpu_pct
        self.policy = policy
        # same sharded-substrate switch as ClusterSubstrate: pre-sharded
        # snapshot, candidate-list scorer contract (see there)
        self.layout = layout
        self.topk = topk

    def snapshot(self) -> _pl.FleetState:
        snap = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), self.live)
        if self.layout is not None:
            from repro.sched import shard as _shard

            snap = _shard.shard_fleet(snap, self.layout)
        return snap

    def pack(self, jobs: Sequence[_pl.JobSpec], size: int) -> jnp.ndarray:
        jobs = list(jobs) + [jobs[-1]] * (size - len(jobs))
        return jnp.stack([_pl.job_delta(j) for j in jobs])

    def init_carry(self, params: dict):
        if self.policy is not None and self.policy.embed_dim > 0:
            return self.policy.carry_init(params)
        return ()

    def make_scorer(self, fused) -> Callable:
        """Same uniform ``(params, snap, deltas, carry, n_real) ->
        (q, ok, carry)`` contract as ``ClusterSubstrate.make_scorer``.

        Fused-capable specs (and the default ``policy=None``) keep the fused
        column kernel; other policy classes score the assembled (N, 6) rows
        through ``PolicySpec.score_set``.  Sequence specs feed their encoder
        the job's normalized demand delta (the first ``ENCODER_IN`` entries
        of ``delta / FEATURE_SCALE`` — the job-stream analogue of
        ``pod_workload_features``).
        """
        max_cpu = self.max_host_cpu_pct
        policy = self.policy
        if policy is not None and policy.fused_kernel:
            policy = None          # "mlp": the column kernel IS its score_set

        from repro.kernels import ops
        from repro.sched.api import _fleet_mode

        mode = _fleet_mode(fused)

        if self.layout is not None:
            from repro.core.policy import ENCODER_IN
            from repro.sched import shard as _shard

            layout, k = self.layout, self.topk

            def shard_topk(params, snap, d, emb=None):
                return _shard.fleet_topk(params, snap, None, layout, k=k,
                                         fused=fused, policy=policy,
                                         embed=emb, delta=d,
                                         max_host_cpu_pct=max_cpu)

            if policy is None or policy.embed_dim == 0:

                @jax.jit
                def score(params, snap, deltas, carry, n_real):
                    cv, ci = jax.vmap(
                        lambda d: shard_topk(params, snap, d))(deltas)
                    return cv, ci, carry

                return score

            @jax.jit
            def score(params, snap, deltas, carry, n_real):
                def step(c, xs):
                    d, is_real = xs
                    wf = (d / kenv.FEATURE_SCALE)[:ENCODER_IN]
                    c2, emb = policy.encode_step(params, c, wf)
                    c2 = jax.tree.map(lambda a, b: jnp.where(is_real, a, b),
                                      c2, c)
                    return c2, shard_topk(params, snap, d, emb)

                is_real = jnp.arange(deltas.shape[0]) < n_real
                carry2, (cv, ci) = jax.lax.scan(step, carry, (deltas, is_real))
                return cv, ci, carry2

            return score

        def feasible(snap, deltas):
            return (
                (snap.healthy > 0.5)[None, :]
                & (snap.cpu_pct[None, :] + deltas[:, 0:1] <= max_cpu)
                & (snap.mem_pct[None, :] + deltas[:, 1:2] <= 95.0)
                & (snap.job_util_pct[None, :] + deltas[:, 2:3]
                   <= 100.0 + 1e-6)
            )

        def afterstate_rows(snap, delta, embed=None):
            feats = (jnp.stack(_pl.fleet_cols(snap), axis=-1)
                     + delta[None, :]) / kenv.FEATURE_SCALE
            if embed is not None:
                feats = jnp.concatenate(
                    [feats,
                     jnp.broadcast_to(embed, feats.shape[:-1] + embed.shape)],
                    axis=-1)
            return feats

        if policy is None:

            @jax.jit
            def score(params, snap, deltas, carry, n_real):
                cols = _pl.fleet_cols(snap)
                q = jax.vmap(lambda d: ops.sdqn_score_delta(
                    cols, d, params, mode=mode))(deltas)
                return q, feasible(snap, deltas), carry

            return score

        if policy.embed_dim == 0:

            @jax.jit
            def score(params, snap, deltas, carry, n_real):
                q = jax.vmap(lambda d: policy.score_set(
                    params, afterstate_rows(snap, d)))(deltas)
                return q, feasible(snap, deltas), carry

            return score

        from repro.core.policy import ENCODER_IN

        @jax.jit
        def score(params, snap, deltas, carry, n_real):
            def step(c, xs):
                d, is_real = xs
                wf = (d / kenv.FEATURE_SCALE)[:ENCODER_IN]
                c2, emb = policy.encode_step(params, c, wf)
                c2 = jax.tree.map(lambda a, b: jnp.where(is_real, a, b),
                                  c2, c)
                return c2, policy.score_set(
                    params, afterstate_rows(snap, d, embed=emb))

            is_real = jnp.arange(deltas.shape[0]) < n_real
            carry2, q = jax.lax.scan(step, carry, (deltas, is_real))
            return q, feasible(snap, deltas), carry2

        return score

    def feasible_one(self, node: int, job: _pl.JobSpec) -> bool:
        lv = self.live
        return bool(
            lv.healthy[node] > 0.5
            and lv.cpu_pct[node] + job.cpu_pct_demand <= self.max_host_cpu_pct
            and lv.mem_pct[node] + job.mem_pct_demand <= 95.0
            and lv.job_util_pct[node] + _pl.JOB_UTIL_DELTA_PCT
            <= 100.0 + 1e-6
        )

    def bind(self, node: int, job: _pl.JobSpec) -> None:
        lv = self.live
        lv.cpu_pct[node] += job.cpu_pct_demand
        lv.mem_pct[node] += job.mem_pct_demand
        lv.job_util_pct[node] += _pl.JOB_UTIL_DELTA_PCT
        lv.num_jobs[node] += 1

    def unbind(self, node: int, job: _pl.JobSpec) -> None:
        lv = self.live
        lv.cpu_pct[node] -= job.cpu_pct_demand
        lv.mem_pct[node] -= job.mem_pct_demand
        lv.job_util_pct[node] -= _pl.JOB_UTIL_DELTA_PCT
        lv.num_jobs[node] -= 1

    def set_health(self, node: int, healthy: bool) -> None:
        self.live.healthy[node] = 1.0 if healthy else 0.0

    def heuristic_batch(self, jobs: Sequence[_pl.JobSpec]):
        """(B, N) percent-utilization LeastRequested+Balanced scores against
        the LIVE buffer (``sched.api.heuristic_score``'s FleetState arm)."""
        lv = self.live
        dc = np.asarray([j.cpu_pct_demand for j in jobs])[:, None]
        dm = np.asarray([j.mem_pct_demand for j in jobs])[:, None]
        cpu_free = (100.0 - lv.cpu_pct[None, :] - dc) / 100.0
        mem_free = (100.0 - lv.mem_pct[None, :] - dm) / 100.0
        q = 10.0 * (cpu_free + mem_free) / 2.0 \
            + 10.0 * (1.0 - np.abs(cpu_free - mem_free))
        ok = ((lv.healthy[None, :] > 0.5)
              & (lv.cpu_pct[None, :] + dc <= self.max_host_cpu_pct)
              & (lv.mem_pct[None, :] + dm <= 95.0)
              & (lv.job_util_pct[None, :] + _pl.JOB_UTIL_DELTA_PCT
                 <= 100.0 + 1e-6))
        return q, ok


# ---------------------------------------------------------------------------
# the daemon
# ---------------------------------------------------------------------------


class PlacementDaemon:
    """Continuously-serving placement loop over a substrate.

    ``submit`` is admission: O(1) queue append, never blocks on the device.
    ``poll`` cuts at most one batch when ready (size or max-wait), publishes
    the live buffer as the scoring snapshot, scores the whole batch in one
    jitted launch, and commits binds with bind-time re-validation.
    ``flush``/``drain`` force remaining work through.  ``clock`` is
    injectable for deterministic tests (defaults to ``time.monotonic``).

    Each stage of a batch (``sched.snapshot``, ``sched.pack``,
    ``sched.launch``, ``sched.readback``, ``sched.commit``, inside
    ``sched.batch``) is a profiler span and a per-stage counter in
    ``metrics.stage_s`` / ``stage_n`` (``_Span``).
    """

    def __init__(self, substrate, params: dict,
                 config: DaemonConfig = DaemonConfig(),
                 clock: Callable[[], float] = time.monotonic,
                 timer: Callable[[], float] = time.monotonic,
                 decision_hook: Optional[Callable] = None):
        self._sub = substrate
        # ``decision_hook(pod, node)`` observes every SERVED decision (bound
        # or dropped; shed requests are never scored, so they produce no
        # transition) — the online-learning recorder attaches here
        self.decision_hook = decision_hook
        self._params = params
        self.config = config
        self._clock = clock
        # the deadline stopwatch: separate from ``clock`` so tests can pin
        # the logical clock while still faking launch durations
        self._timer = timer
        self._pending: collections.deque = collections.deque()
        self._scorer = substrate.make_scorer(config.fused)
        # admission's accelerator check (substrates without accelerators
        # have none); the commit loop's accelerator predicate, set a batch
        self._accel_of = getattr(substrate, "accel_of", None)
        self._accel_fits = None
        # sharded substrates score to (B, C) candidate lists (two-stage
        # top-k merge) instead of full (B, N) rows — the commit path reads
        # candidates in merged order and never sees a fleet-length vector
        self._cand_mode = getattr(substrate, "layout", None) is not None
        # sequence policy classes carry their arrival-history encoder state
        # across batches; stateless substrates (incl. ones predating
        # init_carry) thread an empty pytree
        self._carry = getattr(substrate, "init_carry", lambda p: ())(params)
        self._next_id = 0
        # req_id -> (node, pod) of every currently-bound placement: the
        # health watchdog's index for evicting pods off a failed node
        self._bound: dict = {}
        # > 0: this many upcoming batches skip the Q-net launch and serve
        # from the kube heuristic (set on a deadline breach / NaN scores)
        self._degraded = 0
        self._pods_nbytes = None     # a packed pod batch's device bytes
        self._whole_nbytes = None    # a snapshot's, without ``last_publish``
        self.metrics = DaemonMetrics()
        self.decisions: List[Decision] = []

    # -- admission (writes the live buffer side only) -----------------------

    def submit(self, pod, now: Optional[float] = None) -> int:
        """Enqueue one placement request; returns its request id.  A pod
        that asks for accelerators on a state without them raises here.

        With ``queue_cap`` set, admission applies backpressure: a full queue
        sheds its OLDEST pending request (decided as ``shed=True``, node
        ``NO_PLACEMENT``) to make room — the newest work is the most likely
        to still matter, and the shed count is the overload signal.
        """
        now = self._clock() if now is None else now
        cap = self.config.queue_cap
        if cap > 0:
            while len(self._pending) >= cap:
                old = self._pending.popleft()
                lat = max(now - old.t_submit, 0.0)
                self.decisions.append(Decision(old.req_id, NO_PLACEMENT, lat,
                                               old.attempts, shed=True))
                self.metrics.shed_wait_s.append(lat)
                self.metrics.shed += 1
        accel = 0 if self._accel_of is None else self._accel_of(pod)
        req = _Request(self._next_id, pod, now, accel)
        self._next_id += 1
        self._pending.append(req)
        self.metrics.submitted += 1
        return req.req_id

    # -- health watchdog (fail/recover events from the node controller) -----

    def fail_node(self, node: int, now: Optional[float] = None) -> int:
        """Mark ``node`` NotReady and auto-requeue every pod bound there.

        The self-healing path: each evicted pod re-enters the admission
        queue as a NEW submission (fresh request id, so the
        bound+dropped+shed == submitted accounting stays exact per request)
        and will be re-scored against the updated fleet — never against the
        dead node, whose ``healthy`` is now false in both the live buffer
        and the next snapshot.  Returns the number of evicted pods.
        """
        now = self._clock() if now is None else now
        self._sub.set_health(node, False)
        evicted = [(rid, pod) for rid, (n, pod) in self._bound.items()
                   if n == node]
        for rid, pod in evicted:
            del self._bound[rid]
            self._sub.unbind(node, pod)
            self.metrics.evictions += 1
            self.submit(pod, now=now)
        return len(evicted)

    def recover_node(self, node: int) -> None:
        """Mark ``node`` Ready again — it rejoins the feasible set at the
        next snapshot/bind re-validation."""
        self._sub.set_health(node, True)

    def set_params(self, params: dict) -> None:
        """Hot-swap policy params (same pytree structure: no recompile) —
        the online-learning refresh hook."""
        self._params = params

    @property
    def pending(self) -> int:
        return len(self._pending)

    # -- serving loop -------------------------------------------------------

    def _cut_ready(self, now: float) -> bool:
        if not self._pending:
            return False
        if len(self._pending) >= self.config.batch_size:
            return True
        return now - self._pending[0].t_submit >= self.config.max_wait_s

    def poll(self, now: Optional[float] = None) -> int:
        """Process at most one batch if the cut condition holds.  Returns
        the number of requests decided (bound or dropped) this call."""
        now = self._clock() if now is None else now
        if not self._cut_ready(now):
            return 0
        return self._process_batch(now)

    def flush(self, now: Optional[float] = None) -> int:
        """Process one batch regardless of the cut condition (0 if idle).
        Backoff holds are overridden — flush means *now*."""
        now = self._clock() if now is None else now
        if not self._pending:
            return 0
        return self._process_batch(now, force=True)

    def drain(self, now: Optional[float] = None) -> int:
        """Flush until the queue is empty (conflict re-queues included)."""
        done = 0
        while self._pending:
            done += self.flush(now)
        return done

    def warmup(self) -> None:
        """Prime the scoring compilation, and the substrate's delta publish
        where it has one, outside any timing window.

        ``n_real = 0``: every warmup row is a pad row, so a sequence
        policy's history carry is untouched by warming up.
        """
        jax.block_until_ready(self._scorer(*self._warm_args()))
        warm_publish = getattr(self._sub, "warm_publish", None)
        if warm_publish is not None:
            warm_publish()

    def scorer_cache_size(self) -> int:
        """Compilations of the batched scorer (1 == every batch, at every
        fill level, reused one executable)."""
        return self._scorer._cache_size()

    def scorer_text(self) -> str:
        """The batched scorer's compiled program text at the serving shapes
        (e.g. to check which kernels the backend runs)."""
        return self._scorer.lower(*self._warm_args()).compile().as_text()

    # -- internals ----------------------------------------------------------

    def _warm_args(self):
        """Scorer arguments at the serving shapes, all rows padding."""
        pods = self._sub.pack([self._dummy_pod()], self.config.batch_size)
        return self._params, self._sub.snapshot(), pods, self._carry, 0

    def _dummy_pod(self):
        if isinstance(self._sub, ClusterSubstrate):
            return kenv.default_pod(self._sub.cfg)
        return _pl.JobSpec()

    def _take_batch(self, now: float, force: bool) -> List[_Request]:
        """Pop up to one batch of eligible requests (backoff holds honored
        unless forced; held requests keep their queue order)."""
        b = self.config.batch_size
        take: List[_Request] = []
        held: List[_Request] = []
        while self._pending and len(take) < b:
            req = self._pending.popleft()
            if force or req.not_before <= now:
                take.append(req)
            else:
                held.append(req)
        for req in reversed(held):
            self._pending.appendleft(req)
        self.metrics.taken += len(take)
        self.metrics.queue_wait_s += sum(now - r.t_enqueued for r in take)
        return take

    def _process_batch(self, now: float, force: bool = False) -> int:
        reqs = self._take_batch(now, force)
        if not reqs:
            return 0
        m = self.metrics
        # one span a stage a batch (never a request); the batch's id and
        # size ride on the enclosing span
        with _Span(m, "sched.batch", batch=m.batches, n=len(reqs)):
            return self._score_and_commit(reqs, now)

    def _score_and_commit(self, reqs: List[_Request], now: float) -> int:
        m = self.metrics
        self._accel_fits = (self._sub.accel_fits
                            if getattr(self._sub, "has_accel", False)
                            else None)
        scores = ok = cand_idx = None
        degraded = self.config.heuristic_only or self._degraded > 0
        if not degraded:
            # publish the admission buffer as the read (scoring) snapshot;
            # the live buffer keeps taking writes from here on
            with _Span(m, "sched.snapshot"):
                snap = self._sub.snapshot()
            with _Span(m, "sched.pack"):
                pods = self._sub.pack([r.pod for r in reqs],
                                      self.config.batch_size)
            self._count_upload(snap, pods)
            t0 = self._timer()
            with _Span(m, "sched.launch"):
                q, okq, carry2 = self._scorer(
                    self._params, snap, pods, self._carry,
                    len(reqs))  # 1 launch
            with _Span(m, "sched.readback"):
                q, okq = np.asarray(q), np.asarray(okq)
                elapsed = self._timer() - t0
                bad = self._diverged(q[:len(reqs)])
            m.device_launches += 1
            m.readback_bytes += q.nbytes + okq.nbytes
            deadline = self.config.score_deadline_s
            if bad or (deadline is not None and elapsed > deadline):
                # degrade: discard the launch (scores AND its history-carry
                # advance) and serve this + the next degrade_batches batches
                # from the closed-form heuristic, no device round-trips
                self._degraded = self.config.degrade_batches
                degraded = True
            else:
                self._carry = carry2
                if self._cand_mode:
                    scores, cand_idx = q, okq
                else:
                    scores, ok = q, okq
        if degraded:
            if not self.config.heuristic_only and self._degraded > 0:
                self._degraded -= 1
            m.fallback_batches += 1
            scores, ok = self._sub.heuristic_batch([r.pod for r in reqs])
            if self._cand_mode:
                # degraded mode is host-side numpy by design (no device
                # launches while degraded), so the full-N heuristic rows are
                # sorted here into the same candidate contract; the stable
                # sort keeps the lowest-index-first tie rule of the merge
                masked = np.where(ok, scores, -np.inf)
                cand_idx = np.argsort(-masked, axis=1, kind="stable")
                scores = np.take_along_axis(masked, cand_idx, axis=1)
        m.batches += 1
        m.commit_calls += len(reqs)
        decided = 0
        with _Span(m, "sched.commit"):
            for i, req in enumerate(reqs):
                if self._cand_mode:
                    decided += self._commit_candidates(req, scores[i],
                                                       cand_idx[i], now)
                else:
                    decided += self._commit(req, scores[i], ok[i], now)
        return decided

    def _count_upload(self, snap, pods) -> None:
        """Count the batch's host-to-device bytes and its publish's kind.

        A substrate without ``last_publish`` publishes its whole snapshot.
        Shapes are static (one compilation), so the pod batch's bytes, and
        such a snapshot's, are taken once: ``nbytes`` of a device array
        costs microseconds."""
        m = self.metrics
        if self._pods_nbytes is None:
            self._pods_nbytes = sum(x.nbytes for x in jax.tree.leaves(pods))
        pub = getattr(self._sub, "last_publish", None)
        if pub is None:
            if self._whole_nbytes is None:
                self._whole_nbytes = sum(x.nbytes
                                         for x in jax.tree.leaves(snap))
            pub = _Publish(True, 0, self._whole_nbytes)
        m.upload_bytes += self._pods_nbytes + pub.nbytes
        if pub.full:
            m.full_publishes += 1
        else:
            m.delta_publishes += 1
            m.publish_rows += pub.rows

    def _diverged(self, real: np.ndarray) -> bool:
        """NaN or out-of-limit scores in the batch's real rows."""
        if self._cand_mode:
            # candidate lists legitimately carry -inf (infeasible /
            # exhausted slots) — divergence means NaN, or a FINITE
            # candidate outside the limit
            finite = np.isfinite(real)
            return bool(np.isnan(real).any()
                        or (np.where(finite, np.abs(real), 0.0)
                            > _DIVERGENCE_LIMIT).any())
        return (not np.all(np.isfinite(real))
                or float(np.max(np.abs(real))) > _DIVERGENCE_LIMIT)

    def _decide(self, req: _Request, node: int) -> None:
        lat = max(self._clock() - req.t_submit, 0.0)
        self.decisions.append(Decision(req.req_id, node, lat, req.attempts))
        self.metrics.bind_latencies_s.append(lat)
        if node == NO_PLACEMENT:
            self.metrics.dropped += 1
        else:
            self.metrics.bound += 1
            self.metrics.accel_bound += req.accel
            self._bound[req.req_id] = (node, req.pod)
        if self.decision_hook is not None:
            # O(1) host-side append inside the hook (sched.online's
            # TransitionRecorder): no device work on the serving hot path,
            # so enabling online learning adds zero scoring launches
            self.decision_hook(req.pod, node)

    def _commit(self, req: _Request, row: np.ndarray, ok: np.ndarray,
                now: float) -> int:
        """Optimistic bind of one scored request; returns 1 if decided."""
        req.attempts += 1
        masked = np.where(ok, row, -np.inf)
        if not ok.any():
            # the snapshot offered no feasible node at all: a genuine drop,
            # exactly env.run_episode's NO_NODE accounting
            self._decide(req, NO_PLACEMENT)
            return 1
        choice = int(np.argmax(masked))
        if self._sub.feasible_one(choice, req.pod):
            self._sub.bind(choice, req.pod)
            self._decide(req, choice)
            return 1
        # optimistic bind lost the race: the snapshot's winner was taken by
        # an earlier bind (or external churn) before this request's turn
        self.metrics.conflicts += 1
        self._count_accel_conflict(choice, req)
        if self.config.conflict_policy == "next-best":
            walked = 0
            for cand in np.argsort(-masked)[1:]:
                if not np.isfinite(masked[cand]):
                    break
                walked += 1
                if self._sub.feasible_one(int(cand), req.pod):
                    self.metrics.walk_steps += walked
                    self._sub.bind(int(cand), req.pod)
                    self._decide(req, int(cand))
                    return 1
                self._count_accel_conflict(int(cand), req)
            self.metrics.walk_steps += walked
        return self._requeue_or_drop(req, now)

    def _commit_candidates(self, req: _Request, vals: np.ndarray,
                           idx: np.ndarray, now: float) -> int:
        """Optimistic bind from a merged candidate list (sharded substrates).

        ``vals``/``idx`` are the two-stage merge output: descending scores
        with global node indices, ``-inf`` past the feasible set.  Same
        semantics as ``_commit`` — element 0 is exactly the full argmax
        winner; ``next-best`` walks the remaining candidates (depth
        ``shards * topk`` instead of N, the price of never materializing the
        fleet)."""
        req.attempts += 1
        if not np.isfinite(vals[0]):
            self._decide(req, NO_PLACEMENT)
            return 1
        choice = int(idx[0])
        if self._sub.feasible_one(choice, req.pod):
            self._sub.bind(choice, req.pod)
            self._decide(req, choice)
            return 1
        self.metrics.conflicts += 1
        self._count_accel_conflict(choice, req)
        if self.config.conflict_policy == "next-best":
            walked = 0
            for v, cand in zip(vals[1:], idx[1:]):
                if not np.isfinite(v):
                    break
                walked += 1
                if self._sub.feasible_one(int(cand), req.pod):
                    self.metrics.walk_steps += walked
                    self._sub.bind(int(cand), req.pod)
                    self._decide(req, int(cand))
                    return 1
                self._count_accel_conflict(int(cand), req)
            self.metrics.walk_steps += walked
        return self._requeue_or_drop(req, now)

    def _count_accel_conflict(self, node: int, req: _Request) -> None:
        """After a failed re-validation of ``node``: count it in
        ``accel_conflicts`` where the accelerator predicate failed too."""
        if (self._accel_fits is not None
                and not self._accel_fits(node, req.pod)):
            self.metrics.accel_conflicts += 1

    def _requeue_or_drop(self, req: _Request, now: float) -> int:
        if req.attempts > self.config.max_retries:
            self._decide(req, NO_PLACEMENT)
            return 1
        # back to the queue head (with exponential backoff when configured):
        # re-scored against fresh state next eligible batch
        self.metrics.requeued += 1
        if self.config.backoff_base_s > 0:
            req.not_before = now + (self.config.backoff_base_s
                                    * 2.0 ** (req.attempts - 1))
        req.t_enqueued = self._clock()
        self._pending.appendleft(req)
        return 0


def replay_trace(daemon: PlacementDaemon, t_s: Sequence[float],
                 pods: Sequence, speed: float = 1.0,
                 events: Optional[Sequence] = None) -> float:
    """Replay an arrival trace in real time through the daemon.

    ``t_s`` are arrival offsets (seconds) from the replay start, ``pods``
    the matching workload specs (see ``scenarios.arrivals.arrival_trace``).
    Each request's submission time is its *scheduled* arrival, so when the
    daemon cannot keep up, queueing delay shows up in decision latency —
    the offered-load curve the placement_serve bench measures.  ``speed``
    compresses the trace (2.0 = twice the offered rate).  Polls between
    arrivals, drains at the end; returns the wall-clock serving duration.

    ``events`` injects node chaos into the replay: an optional sequence of
    ``(t_off, kind, node)`` tuples (``kind`` in ``{"fail", "recover"}``,
    offsets on the same clock as ``t_s``), applied in order as the replay
    clock passes each offset — ``fail`` evicts and auto-requeues the node's
    bound pods through the health watchdog.  Events left over when the
    arrivals end are applied before the final drain.
    """
    clock = daemon._clock
    ev = sorted(events or [], key=lambda e: e[0])
    ev_i = 0

    def apply_events(up_to: float):
        nonlocal ev_i
        while ev_i < len(ev) and ev[ev_i][0] / speed <= up_to:
            _, kind, node = ev[ev_i]
            if kind == "fail":
                daemon.fail_node(int(node))
            elif kind == "recover":
                daemon.recover_node(int(node))
            else:
                raise ValueError(f"unknown chaos event kind {kind!r}")
            ev_i += 1

    t0 = clock()
    for t_off, pod in zip(t_s, pods):
        due = t0 + t_off / speed
        apply_events(due - t0)
        while clock() < due:
            if not daemon.poll():
                time.sleep(0)        # yield; arrival gaps are sub-ms anyway
        daemon.submit(pod, now=due)
        daemon.poll()
    apply_events(float("inf"))
    daemon.drain()
    return clock() - t0
