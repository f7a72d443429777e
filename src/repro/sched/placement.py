"""SDQN-driven job→host placement for the training/serving runtime.

This is the framework-integration of the paper's technique: the same
Q-network that schedules pods in the reproduction schedules *jobs* (training
replicas, serving replicas, data workers) onto fleet hosts.  Host state maps
onto the six Table-2 features 1:1; scoring runs through the fused Pallas
column kernel (``repro.kernels.ops.sdqn_score_delta``) so a 10^5-host fleet
is scored in one kernel launch.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import env as kenv, schedulers
from repro.core.types import NO_PLACEMENT

# Job-slot ceiling per host: the Table-2 "pod utilization" analogue for the
# fleet.  ``job_util_pct`` advances by JOB_UTIL_DELTA_PCT per bound job, and
# ``select`` assumes the same delta when scoring afterstates, so the third
# feature stays consistent with ``num_jobs`` across a placement session.
MAX_JOBS_PER_HOST = 25.0
JOB_UTIL_DELTA_PCT = 100.0 / MAX_JOBS_PER_HOST

# The filter's default ceiling on a host's post-placement CPU utilization.
MAX_HOST_CPU_PCT = 88.0

# select() sentinel: no feasible host, the job is not bound.  Re-export of
# the unified ``core.types.NO_PLACEMENT`` constant (old spelling kept).
NO_HOST = NO_PLACEMENT


class FleetState(NamedTuple):
    """Host fleet, vectorized (same layout as the cluster env)."""

    cpu_pct: jnp.ndarray       # (N,) current host utilization %
    mem_pct: jnp.ndarray       # (N,)
    job_util_pct: jnp.ndarray  # (N,) jobs / max_jobs * 100
    healthy: jnp.ndarray       # (N,) {0, 1}
    uptime_hours: jnp.ndarray  # (N,)
    num_jobs: jnp.ndarray      # (N,)

    def features(self) -> jnp.ndarray:
        return jnp.stack(
            [self.cpu_pct, self.mem_pct, self.job_util_pct,
             self.healthy.astype(jnp.float32), self.uptime_hours,
             self.num_jobs.astype(jnp.float32)], axis=-1)


@dataclasses.dataclass(frozen=True)
class JobSpec:
    cpu_pct_demand: float = 5.0     # host-% one job replica adds
    mem_pct_demand: float = 2.0
    kind: str = "train"             # train | serve | data


def fleet_cols(fleet: FleetState) -> tuple:
    """The six raw Table-2 feature columns of a fleet, for the column kernel."""
    return (fleet.cpu_pct, fleet.mem_pct, fleet.job_util_pct,
            fleet.healthy.astype(jnp.float32), fleet.uptime_hours,
            fleet.num_jobs.astype(jnp.float32))


def job_delta(job: JobSpec) -> jnp.ndarray:
    """The afterstate delta one job adds to the six columns (matches ``place``
    exactly — including the JOB_UTIL_DELTA_PCT advance of the third feature)."""
    return jnp.array([job.cpu_pct_demand, job.mem_pct_demand,
                      JOB_UTIL_DELTA_PCT, 0.0, 0.0, 1.0])


def fleet_ceilings(max_host_cpu_pct: float) -> tuple:
    """The filter's ceilings on post-placement cpu %, mem % and job-util %.
    The job-util one keeps ``job_util_pct <= 100`` (the k8s max-pods
    analogue), so the third feature stays in the trained range."""
    return (max_host_cpu_pct, 95.0, 100.0 + 1e-6)


def fleet_feasible(fleet: FleetState, delta: jnp.ndarray,
                   max_host_cpu_pct: float = MAX_HOST_CPU_PCT) -> jnp.ndarray:
    """(N,) the filtering phase: a host is feasible when it is healthy and
    its cpu, mem and job-util columns plus the job's afterstate ``delta``
    stay under ``fleet_ceilings``.  The host's bind-time re-validation is
    its numpy twin (``daemon.FleetSubstrate.feasible_one``)."""
    cpu, mem, util = fleet_ceilings(max_host_cpu_pct)
    return ((fleet.healthy > 0.5)
            & (fleet.cpu_pct + delta[0] <= cpu)
            & (fleet.mem_pct + delta[1] <= mem)
            & (fleet.job_util_pct + delta[2] <= util))


def afterstate_rows(fleet: FleetState, delta: jnp.ndarray,
                    embed=None) -> jnp.ndarray:
    """(N, 6 [+ E]) normalized afterstate rows of placing the job whose
    afterstate ``delta`` is given on each host, with a sequence policy's
    ``embed`` appended: what the column kernel builds in-kernel, for the
    policy classes that score the whole set (``PolicySpec.score_set``)."""
    feats = (jnp.stack(fleet_cols(fleet), axis=-1)
             + delta[None, :]) / kenv.FEATURE_SCALE
    return schedulers.with_embedding(feats, embed)


class PlacementEngine:
    """Scores afterstates with a trained SDQN and binds jobs to hosts.

    ``consolidate=True`` uses an SDQN-n-trained network: placements pack
    onto the busiest feasible hosts, which feeds ``elastic.consolidation_plan``
    with shut-down candidates (the paper's green-datacenter §6 narrative).
    """

    def __init__(self, qparams: dict, consolidate: bool = False,
                 max_host_cpu_pct: float = MAX_HOST_CPU_PCT,
                 use_kernel: Optional[bool] = None):
        self.qparams = qparams
        self.consolidate = consolidate
        self.max_host_cpu_pct = max_host_cpu_pct
        self.use_kernel = use_kernel

    def feasible(self, fleet: FleetState, job: JobSpec) -> jnp.ndarray:
        return fleet_feasible(fleet, job_delta(job), self.max_host_cpu_pct)

    def select(self, fleet: FleetState, job: JobSpec) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Pick the host for one job. Returns (host index, scores).

        Afterstate scoring streams the six fleet columns through the fused
        column kernel (``ops.sdqn_score_delta``, via the unified
        ``repro.sched.api.score`` entry point): features + job delta +
        normalization + Q-net in one pass, never materializing the (N, 6)
        feature matrix in HBM.  The delta matches ``place`` exactly —
        including the ``job_util_pct`` advance of JOB_UTIL_DELTA_PCT, which
        previously stayed stale at its reset value.

        The host index comes back as a 0-d int32 device array, not a Python
        int: ``int(argmax)``/``bool(any)`` here forced a device sync on
        EVERY decision, serializing ``place_batch`` on dispatch latency.
        ``place`` consumes the device scalar as-is; callers that need a
        Python int sync once at their own API boundary.
        """
        from repro.sched import api  # lazy: api imports this module

        fused = ("auto" if self.use_kernel is None
                 else ("interpret" if self.use_kernel else False))
        scores = api.score(fleet, job, params=self.qparams, fused=fused)
        ok = self.feasible(fleet, job)
        scores = jnp.where(ok, scores, -jnp.inf)
        # all-infeasible fleet: argmax over all -inf would bind host 0 —
        # return the NO_HOST sentinel instead (place() ignores it)
        choice = jnp.where(jnp.any(ok), jnp.argmax(scores),
                           NO_HOST).astype(jnp.int32)
        return choice, scores

    def place(self, fleet: FleetState, host: int, job: JobSpec) -> FleetState:
        onehot = (jnp.arange(fleet.cpu_pct.shape[0]) == host)
        return fleet._replace(
            cpu_pct=fleet.cpu_pct + onehot * job.cpu_pct_demand,
            mem_pct=fleet.mem_pct + onehot * job.mem_pct_demand,
            # keep the third Table-2 feature live: without this the serving
            # path scores every post-first-binding afterstate on stale data
            job_util_pct=fleet.job_util_pct + onehot * JOB_UTIL_DELTA_PCT,
            num_jobs=fleet.num_jobs + onehot.astype(jnp.int32),
        )

    def place_batch(self, fleet: FleetState, jobs: int, job: JobSpec) -> Tuple[FleetState, np.ndarray]:
        hosts = []
        for _ in range(jobs):
            h, _ = self.select(fleet, job)
            fleet = self.place(fleet, h, job)
            hosts.append(h)
        return fleet, np.asarray(hosts)


def fresh_fleet(n_hosts: int, key: Optional[jax.Array] = None) -> FleetState:
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    return FleetState(
        cpu_pct=2.0 + 8.0 * jax.random.uniform(k1, (n_hosts,)),
        mem_pct=jnp.full((n_hosts,), 5.0),
        job_util_pct=jnp.zeros((n_hosts,)),
        healthy=jnp.ones((n_hosts,)),
        uptime_hours=5.0 + 100.0 * jax.random.uniform(k2, (n_hosts,)),
        num_jobs=jnp.zeros((n_hosts,), jnp.int32),
    )
