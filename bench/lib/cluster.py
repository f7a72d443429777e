"""A cluster configuration made into node columns, from the seed, in numpy.

The configuration file (``bench/configs/<name>.json``) states the node pool,
the pod mix, the per-node physics constants, the pre-fill and the policy's
weight seed.  The cluster is drawn from ``--seed`` with numpy, in bulk, so
that a 100,000-node cluster and its ~866,000 resident pods take well under a
second, and the program under test only ever receives the finished columns.

Column names and dtypes follow the scheduler's ``ClusterState``: float32
resources, int32 counts, bool flags.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import numpy as np

FLOAT_COLS = ("cpu_capacity", "mem_capacity", "uptime_hours", "cpu_requested",
              "mem_requested", "pods_cpu", "mem_used", "base_cpu",
              "startup_cpu")
INT_COLS = ("max_pods", "num_pods", "exp_pods")
# ClusterState's field order (time_s is the scalar clock, always 0 here)
STATE_FIELDS = ("cpu_capacity", "mem_capacity", "max_pods", "healthy",
                "uptime_hours", "num_pods", "exp_pods", "cpu_requested",
                "mem_requested", "pods_cpu", "mem_used", "base_cpu",
                "startup_cpu", "image_cached")


@dataclasses.dataclass(frozen=True)
class PodType:
    name: str
    weight: float
    cpu_request: float
    cpu_demand: float
    mem_request: float
    mem_demand: float


def pod_types(config: dict) -> List[PodType]:
    return [PodType(**p) for p in config["pods"]]


def mean_cpu_request(types: Sequence[PodType]) -> float:
    w = np.asarray([t.weight for t in types], np.float64)
    return float(np.sum(w / w.sum() * [t.cpu_request for t in types]))


def _uniform_or_profile(rng, spec: dict, n: int) -> np.ndarray:
    """A per-node fraction or load: ``{"frac": [lo, hi]}`` draws uniformly;
    ``{"profile": [...], "jitter": j}`` tiles the profile over the nodes,
    permutes it and adds uniform jitter (stable totals, varied layout)."""
    if "frac" in spec:
        lo, hi = spec["frac"]
        return rng.uniform(lo, hi, n)
    prof = np.asarray(spec["profile"], np.float64)
    vals = np.tile(prof, -(-n // len(prof)))[:n]
    vals = rng.permutation(vals)
    return vals + rng.uniform(-spec["jitter"], spec["jitter"], n)


def reset(config: dict, rng: np.random.Generator) -> Dict[str, np.ndarray]:
    """Node columns of the configured pool before any experiment pod runs.

    Per class: base load and tenant bookings either as fractions of the
    class's capacity (``frac``) or as an absolute profile (``profile``);
    tenant pods are bookings over the mix's mean CPU request; uptime is
    uniform over ``uptime_h``; every node starts Ready with no cached image.
    ``repeat`` lays the list of classes out that many times in a row.
    """
    mean_req = mean_cpu_request(pod_types(config))
    cols: Dict[str, list] = {k: [] for k in STATE_FIELDS}
    lo_h, hi_h = config["nodes"]["uptime_h"]
    for cls in config["nodes"]["classes"] * config["nodes"].get("repeat", 1):
        n, cap = cls["count"], float(cls["cpu_capacity"])
        base = _uniform_or_profile(rng, cls["base_cpu"], n)
        if "frac" in cls["base_cpu"]:
            base = cap * base
        base = np.maximum(base, 0.0)
        req = _uniform_or_profile(rng, cls["requested_frac"], n)
        req0 = cap * np.clip(req, 0.0, 0.95)
        cols["cpu_capacity"].append(np.full(n, cap))
        cols["mem_capacity"].append(np.full(n, float(cls["mem_capacity"])))
        cols["max_pods"].append(np.full(n, cls["max_pods"]))
        cols["healthy"].append(np.ones(n, bool))
        cols["uptime_hours"].append(rng.uniform(lo_h, hi_h, n))
        cols["num_pods"].append(np.floor(req0 / mean_req))
        cols["exp_pods"].append(np.zeros(n))
        cols["cpu_requested"].append(np.minimum(req0, 0.98 * cap))
        for k in ("mem_requested", "pods_cpu", "mem_used", "startup_cpu"):
            cols[k].append(np.zeros(n))
        cols["base_cpu"].append(base)
        cols["image_cached"].append(np.zeros(n, bool))
    out = {}
    for k, parts in cols.items():
        v = np.concatenate(parts)
        if k in FLOAT_COLS:
            out[k] = v.astype(np.float32)
        elif k in INT_COLS:
            out[k] = v.astype(np.int32)
        else:
            out[k] = v.astype(bool)
    return out


class PodStream:
    """Pod type indices in blocks of exact mix proportions, each block in a
    fresh seeded order: every seed sends the same mix, in another order."""

    def __init__(self, types: Sequence[PodType], rng: np.random.Generator,
                 block: int = 1000):
        w = np.asarray([t.weight for t in types], np.float64)
        counts = np.round(w / w.sum() * block).astype(np.int64)
        self._block = np.repeat(np.arange(len(types)), counts)
        self._rng = rng
        self._buf = np.empty(0, np.int64)
        self._at = 0

    def take(self, n: int) -> np.ndarray:
        while len(self._buf) - self._at < n:
            self._buf = np.concatenate(
                [self._buf[self._at:], self._rng.permutation(self._block)])
            self._at = 0
        out = self._buf[self._at:self._at + n]
        self._at += n
        return out

    def next(self) -> int:
        return int(self.take(1)[0])


def prefill(cols: Dict[str, np.ndarray], types: Sequence[PodType],
            fill_frac: float, stream: PodStream,
            rng: np.random.Generator) -> np.ndarray:
    """Bind resident experiment pods directly (scheduler_perf's ``initPods``),
    in place, and return them as an (R, 2) array of (node, type) in the
    seeded FIFO order in which they will retire.

    Each node takes ``floor(fill_frac * fit)`` pods, where ``fit`` is how
    many pods of the mix's mean size its free CPU, memory and pod slots
    hold; the types come from ``stream``.  A node that the drawn types would
    overbook gives back pods until it fits.  Startup transients are left at
    0: resident pods started long ago.
    """
    w = np.asarray([t.weight for t in types], np.float64)
    w = w / w.sum()
    creq = np.asarray([t.cpu_request for t in types], np.float64)
    mreq = np.asarray([t.mem_request for t in types], np.float64)
    cdem = np.asarray([t.cpu_demand for t in types], np.float64)
    mdem = np.asarray([t.mem_demand for t in types], np.float64)
    cap = cols["cpu_capacity"].astype(np.float64)
    mcap = cols["mem_capacity"].astype(np.float64)
    free_c = cap - cols["cpu_requested"]
    free_m = mcap - cols["mem_requested"]
    fit = np.minimum.reduce([
        np.floor(free_c / float(w @ creq)), np.floor(free_m / float(w @ mreq)),
        (cols["max_pods"] - cols["num_pods"]).astype(np.float64)])
    count = np.floor(fill_frac * np.maximum(fit, 0.0)).astype(np.int64)
    nodes = np.repeat(np.arange(len(cap)), count)
    kinds = stream.take(len(nodes))

    def sums(nodes, kinds, per_type):
        return np.bincount(nodes, weights=per_type[kinds], minlength=len(cap))

    keep = np.ones(len(nodes), bool)
    over = ((cols["cpu_requested"] + sums(nodes, kinds, creq) > cap)
            | (cols["mem_requested"] + sums(nodes, kinds, mreq) > mcap))
    first = np.cumsum(count) - count          # nodes[] is grouped by node
    for node in np.flatnonzero(over):
        idx = np.arange(first[node], first[node] + count[node])
        c = cols["cpu_requested"][node] + creq[kinds[idx]].cumsum()
        m = cols["mem_requested"][node] + mreq[kinds[idx]].cumsum()
        keep[idx[(c > cap[node]) | (m > mcap[node])]] = False
    nodes, kinds = nodes[keep], kinds[keep]
    per_node = np.bincount(nodes, minlength=len(cap))
    for name, per_type in (("cpu_requested", creq), ("mem_requested", mreq),
                           ("pods_cpu", cdem), ("mem_used", mdem)):
        cols[name] = (cols[name] + sums(nodes, kinds, per_type)).astype(
            np.float32)
    cols["num_pods"] = (cols["num_pods"] + per_node).astype(np.int32)
    cols["exp_pods"] = (cols["exp_pods"] + per_node).astype(np.int32)
    cols["image_cached"] = cols["image_cached"] | (per_node > 0)
    order = rng.permutation(len(nodes))
    return np.stack([nodes[order], kinds[order]], axis=1)


def config_weights(config: dict) -> Dict[str, np.ndarray]:
    """The configuration's policy: Q-net weights drawn from its own stated
    ``weights.seed``, the same for every ``--seed``, as a deployment serves
    one policy while its cluster and traffic vary."""
    return make_weights(np.random.default_rng(int(config["weights"]["seed"])))


def make_weights(rng: np.random.Generator, hidden: int = 32,
                 n_features: int = 6) -> Dict[str, np.ndarray]:
    """Random Table-4 Q-net weights (6 -> 32 -> 1), float32.  Random, not
    trained: speed and agreement with the reference need no trained policy.
    Biases are drawn too, so no part of the net is trivially zero."""
    return {
        "w1": (rng.standard_normal((n_features, hidden))
               * np.sqrt(2.0 / n_features)).astype(np.float32),
        "b1": (0.1 * rng.standard_normal(hidden)).astype(np.float32),
        "w2": (rng.standard_normal((hidden, 1))
               * np.sqrt(1.0 / hidden)).astype(np.float32),
        "b2": (0.1 * rng.standard_normal(1)).astype(np.float32),
    }
