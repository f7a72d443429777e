"""The plain reference: the scheduler's semantics written out in numpy.

It imports nothing of the program under test and takes nothing the program
made.  Its inputs are the benchmark's own: the seeded node columns, the
seeded weights, the pod mix, and the order of the decisions the run made.

* ``Replay`` re-applies every bind and retirement in order to its own copy
  of the columns, checks each bind against the state at that moment, and at
  the end must equal the program's live buffer exactly.
* ``afterstate_q`` scores every node's afterstate through the 6 -> 32 -> 1
  Q-net in float64 (``precision="bf16"`` is the control: bfloat16 operands,
  float32 accumulation, the step a faster kernel would be tempted to take).
* ``candidates`` is the two-stage sharded path's contract: per shard the
  ``topk`` best feasible nodes, merged and sorted.
"""
from __future__ import annotations

from typing import Dict, Sequence

import numpy as np

from bench.lib.cluster import STATE_FIELDS, PodType


def to_bf16(x) -> np.ndarray:
    """Round float32 values to bfloat16 (round to nearest even), kept in a
    float32 container."""
    x = np.ascontiguousarray(np.asarray(x, np.float32))
    b = x.view(np.uint32).astype(np.uint64)
    b = (b + 0x7FFF + ((b >> 16) & 1)) & 0xFFFF0000
    return b.astype(np.uint32).view(np.float32).reshape(x.shape)


def in_flight(startup_cpu: np.ndarray, phys: dict) -> int:
    return int(np.sum(startup_cpu > 0.25 * phys["image_pull_cost"]))


def pull_cost(n_in_flight: int, phys: dict) -> float:
    return phys["image_pull_cost"] * (1.0 + phys["pull_concurrency_coeff"]
                                      * n_in_flight)


def feasible(cols: Dict[str, np.ndarray], pod: PodType) -> np.ndarray:
    """The k8s filtering phase, in the columns' own float32."""
    return (cols["healthy"]
            & (cols["cpu_requested"] + np.float32(pod.cpu_request)
               <= cols["cpu_capacity"])
            & (cols["mem_requested"] + np.float32(pod.mem_request)
               <= cols["mem_capacity"])
            & (cols["num_pods"] < cols["max_pods"]))


def afterstate_features(cols: Dict[str, np.ndarray], pod: PodType,
                        phys: dict) -> np.ndarray:
    """(N, 6) normalized Table-2 features of every node as if ``pod`` were
    placed there, in float64."""
    c = {k: np.asarray(v, np.float64) for k, v in cols.items()}
    pull = pull_cost(in_flight(cols["startup_cpu"], phys), phys)
    start = np.where(cols["image_cached"], phys["warm_start_cost"], pull)
    num1 = c["num_pods"] + 1.0
    exp1 = c["exp_pods"] + 1.0
    crowd = np.maximum(num1 - phys["crowd_knee"], 0.0)
    raw = (c["base_cpu"] + phys["node_active_overhead"] + c["pods_cpu"]
           + pod.cpu_demand + c["startup_cpu"] + start
           + phys["crowd_coeff"] * crowd * crowd)
    cap = c["cpu_capacity"]
    over = np.maximum(raw / cap - phys["contention_knee"], 0.0)
    used = np.minimum(raw + phys["contention_coeff"] * over * over * cap, cap)
    feats = np.stack([
        100.0 * used / cap,
        100.0 * (c["mem_used"] + pod.mem_demand) / c["mem_capacity"],
        100.0 * num1 / c["max_pods"],
        c["healthy"],
        c["uptime_hours"],
        exp1,
    ], axis=-1)
    return feats / np.asarray(phys["feature_scale"], np.float64)


def qnet(feats: np.ndarray, w: Dict[str, np.ndarray],
         precision: str = "f64") -> np.ndarray:
    if precision == "f64":
        h = np.maximum(feats @ w["w1"].astype(np.float64) + w["b1"], 0.0)
        return (h @ w["w2"].astype(np.float64) + w["b2"])[..., 0]
    if precision != "bf16":
        raise ValueError(f"unknown precision {precision!r}")
    f32 = np.float32
    h = to_bf16(feats.astype(f32)) @ to_bf16(w["w1"]) + w["b1"]
    h = np.maximum(h, f32(0.0))
    return (to_bf16(h) @ to_bf16(w["w2"]) + w["b2"])[..., 0].astype(
        np.float64)


def afterstate_q(cols, pod, phys, w, precision: str = "f64") -> np.ndarray:
    return qnet(afterstate_features(cols, pod, phys), w, precision)


def candidates(q: np.ndarray, ok: np.ndarray, shards: int, topk: int):
    """(values, node indices) of the two-stage path for one request: the
    ``topk`` best feasible nodes of each of ``shards`` contiguous shards,
    merged in descending order (ties by ascending index), ``-inf`` / ``-1``
    past each shard's feasible set."""
    n = q.shape[0]
    size = -(-n // shards)
    vals, idx = [], []
    for s in range(shards):
        lo, hi = s * size, min((s + 1) * size, n)
        qs = np.where(ok[lo:hi], q[lo:hi], -np.inf)
        order = np.argsort(-qs, kind="stable")[:topk]
        vals.append(qs[order])
        idx.append(np.where(np.isfinite(qs[order]), order + lo, -1))
    vals, idx = np.concatenate(vals), np.concatenate(idx)
    order = np.argsort(-vals, kind="stable")
    return vals[order], idx[order]


class Replay:
    """The live buffer, re-derived from the benchmark's own start state and
    the run's ordered binds and retirements.

    ``bind`` reports whether the bind was feasible at that moment: the
    optimistic commit loop must never bind to a node that had no room.
    """

    def __init__(self, cols: Dict[str, np.ndarray], types: Sequence[PodType],
                 phys: dict):
        self.cols = {k: np.array(cols[k]) for k in STATE_FIELDS}
        self.types = list(types)
        self.phys = phys
        self._thresh = 0.25 * phys["image_pull_cost"]
        self._in_flight = in_flight(self.cols["startup_cpu"], phys)

    def feasible_one(self, node: int, kind: int) -> bool:
        c, t = self.cols, self.types[kind]
        return bool(c["healthy"][node]
                    and c["cpu_requested"][node] + t.cpu_request
                    <= c["cpu_capacity"][node]
                    and c["mem_requested"][node] + t.mem_request
                    <= c["mem_capacity"][node]
                    and c["num_pods"][node] < c["max_pods"][node])

    def bind(self, node: int, kind: int) -> bool:
        ok = self.feasible_one(node, kind)
        c, t, p = self.cols, self.types[kind], self.phys
        if c["image_cached"][node]:
            start = p["warm_start_cost"]
        else:
            start = pull_cost(self._in_flight, p)
        was = c["startup_cpu"][node] > self._thresh
        c["num_pods"][node] += 1
        c["exp_pods"][node] += 1
        c["cpu_requested"][node] += t.cpu_request
        c["mem_requested"][node] += t.mem_request
        c["pods_cpu"][node] += t.cpu_demand
        c["mem_used"][node] += t.mem_demand
        c["startup_cpu"][node] += start
        c["image_cached"][node] = True
        self._in_flight += int(c["startup_cpu"][node] > self._thresh) - int(was)
        return ok

    def unbind(self, node: int, kind: int) -> None:
        c, t = self.cols, self.types[kind]
        c["num_pods"][node] -= 1
        c["exp_pods"][node] -= 1
        c["cpu_requested"][node] -= t.cpu_request
        c["mem_requested"][node] -= t.mem_request
        c["pods_cpu"][node] -= t.cpu_demand
        c["mem_used"][node] -= t.mem_demand

    def copy_cols(self) -> Dict[str, np.ndarray]:
        return {k: v.copy() for k, v in self.cols.items()}


def state_diff(a: Dict[str, np.ndarray], b: Dict[str, np.ndarray]) -> float:
    """Largest absolute difference over every column (0 = identical)."""
    return max(float(np.max(np.abs(np.asarray(a[k], np.float64)
                                   - np.asarray(b[k], np.float64))))
               for k in STATE_FIELDS)
