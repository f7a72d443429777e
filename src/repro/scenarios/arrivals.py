"""Arrival-stream adapter: scenario pod tables -> daemon request traces.

The scenario engine's arrival processes (burst / Poisson / diurnal, sampled
by ``env.sample_pod_table``) drive episode *simulations*.  The placement
daemon (``sched.daemon``) serves the same streams in *wall-clock* time: this
module converts a sampled ``PodTable`` into an ``ArrivalTrace`` — absolute
arrival offsets plus per-request ``PodSpec``s — optionally rescaled to a
target offered rate, ready for ``daemon.replay_trace`` and the
``placement_serve`` benchmark.

    trace = arrival_trace(key, cfg, n_pods=500, rate_per_s=2000.0)
    replay_trace(daemon, trace.t_s, trace.pods)

Traces are reproducible: same key + config + n_pods = same trace (the pod
table sampling is the exact episode-stream code path).
"""
from __future__ import annotations

from typing import List, NamedTuple

import jax
import numpy as np

from repro.core import env as kenv
from repro.core.types import EnvConfig, PodSpec

__all__ = ["ArrivalTrace", "arrival_trace", "trace_from_table"]


class ArrivalTrace(NamedTuple):
    """A serving request trace: request i arrives ``t_s[i]`` seconds after
    the trace starts and asks to place ``pods[i]``."""

    t_s: np.ndarray          # (n,) float64, non-decreasing, t_s[0] == 0
    pods: List[PodSpec]      # n scalar PodSpecs (python floats)

    @property
    def offered_rate_per_s(self) -> float:
        """Mean offered arrival rate over the trace (requests/sec)."""
        span = float(self.t_s[-1]) if len(self.t_s) > 1 else 0.0
        return float(len(self.t_s) - 1) / span if span > 0 else float("inf")


def trace_from_table(table, rate_per_s: float | None = None) -> ArrivalTrace:
    """Turn a sampled ``PodTable`` into an ``ArrivalTrace``.

    Inter-arrival gaps become absolute offsets (first arrival at t=0 — the
    leading gap is episode lead-in, not serving latency).  ``rate_per_s``
    rescales the time axis to that mean offered rate, preserving the arrival
    process's *shape* (burstiness, diurnal modulation) while sweeping load —
    how the placement_serve bench produces its offered-rate curve.
    """
    dt = np.asarray(table.dt_s, np.float64)
    t = np.cumsum(dt) - float(dt[0])
    if rate_per_s is not None:
        if rate_per_s <= 0:
            raise ValueError("rate_per_s must be positive")
        span = float(t[-1])
        if span > 0:
            t = t * ((len(t) - 1) / (span * rate_per_s))
        else:  # pure burst: spread at exactly the offered rate
            t = np.arange(len(t), dtype=np.float64) / rate_per_s
    specs = jax.tree.map(np.asarray, table.specs)
    accel = specs.accel_request
    pods = [
        PodSpec(cpu_request=float(specs.cpu_request[i]),
                cpu_demand=float(specs.cpu_demand[i]),
                mem_request=float(specs.mem_request[i]),
                mem_demand=float(specs.mem_demand[i]),
                accel_request=None if accel is None else int(accel[i]))
        for i in range(len(t))
    ]
    return ArrivalTrace(t_s=t, pods=pods)


def arrival_trace(key: jax.Array, cfg: EnvConfig, n_pods: int,
                  rate_per_s: float | None = None) -> ArrivalTrace:
    """Sample a scenario arrival stream as a daemon request trace.

    Uses the exact episode-stream sampler (``env.sample_pod_table``), so the
    daemon serves the same workload mixture + arrival process the scenario
    engine simulates; ``rate_per_s`` rescales to a target offered rate.
    """
    return trace_from_table(kenv.sample_pod_table(key, cfg, n_pods),
                            rate_per_s=rate_per_s)
