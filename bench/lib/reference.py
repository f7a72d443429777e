"""The plain reference: the scheduler's semantics written out in numpy.

It imports nothing of the program under test and takes nothing the program
made.  Its inputs are the benchmark's own: the seeded node columns, the
seeded weights, the pod mix, and the order of the decisions the run made.

Each configuration brings its reference.  The configuration file names a
module with the key ``reference`` (a path relative to the checkout); without
the key it is this module.  The harness takes the module's ``Reference``
(``for_config``), once, at set-up, and every reader of the semantics --
the cluster, the run, the check, the control and the work count --
asks it.  A configuration with another resource or rule brings a module
that imports this one, subclasses ``Reference`` and overrides only what
differs.  What a ``Reference`` states:

* ``COLUMNS``: the node columns with their dtypes, in the order of the
  program's ``ClusterState``; ``POD_FIELDS``: the program's ``PodSpec``
  fields, in the order of a packed pod row; ``PodType``: a pod type of the
  file.  The harness refuses a program that lacks any of them.
* how a node class and a pod type of the file become columns and rows
  (``reset``, ``class_columns``, ``pod_types``), and the pre-fill's fit,
  trim and bulk bind (``prefill_fit``, ``prefill_keep``, ``book``);
* the policy's weights (``make_weights``, ``config_weights``);
* ``feasible``, ``afterstate_q`` (float64; ``precision="bf16"`` is the
  control: bfloat16 operands, float32 accumulation, the step a faster
  kernel would be tempted to take), ``candidates`` (the two-stage sharded
  path's contract: per shard the ``topk`` best feasible nodes, merged and
  sorted), ``Replay`` (re-applies every bind and retirement in order to its
  own copy of the columns, checks each bind against the state at that
  moment, and at the end must equal the program's live buffer exactly) and
  ``state_diff``;
* the work a decision needs: ``NODE_BYTES``, ``POD_ROW_BYTES`` and the
  operations a (request, node) pair (``pair_flops``).

The module-level names (``feasible``, ``Replay``, ``reset``, ...)
are those of this module's own ``Reference``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import re
from typing import Dict, List, Sequence

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
DEFAULT = "bench/lib/reference.py"


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


def uniform_or_profile(rng: np.random.Generator, spec: dict,
                       n: int) -> np.ndarray:
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


@dataclasses.dataclass(frozen=True)
class PodType:
    name: str
    weight: float
    cpu_request: float
    cpu_demand: float
    mem_request: float
    mem_demand: float


def _per_type(types: Sequence, field: str) -> np.ndarray:
    return np.asarray([getattr(t, field) for t in types], np.float64)


class Replay:
    """The live buffer, re-derived from the benchmark's own start state and
    the run's ordered binds and retirements.

    ``bind`` reports whether the bind was feasible at that moment: the
    optimistic commit loop must never bind to a node that had no room.
    """

    def __init__(self, cols: Dict[str, np.ndarray], types: Sequence[PodType],
                 phys: dict):
        self.cols = {k: np.array(v) for k, v in cols.items()}
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


class Reference:
    """The semantics of a configuration of CPU and memory pods on nodes
    with pod slots, scored by the 6 -> 32 -> 1 Table-4 Q-net."""

    # ClusterState's field order (time_s is the scalar clock, always 0 here)
    COLUMNS = {
        "cpu_capacity": np.float32, "mem_capacity": np.float32,
        "max_pods": np.int32, "healthy": np.bool_,
        "uptime_hours": np.float32, "num_pods": np.int32,
        "exp_pods": np.int32, "cpu_requested": np.float32,
        "mem_requested": np.float32, "pods_cpu": np.float32,
        "mem_used": np.float32, "base_cpu": np.float32,
        "startup_cpu": np.float32, "image_cached": np.bool_,
    }
    POD_FIELDS = ("cpu_request", "cpu_demand", "mem_request", "mem_demand")
    PodType = PodType
    Replay = Replay

    HIDDEN, FEATURES = 32, 6
    # the work count (``bench/lib/work.py``): the node columns scoring
    # (afterstate features) and filtering read, with their widths in the
    # snapshot: float32 and int32 columns 4 bytes, bools 1
    NODE_BYTES = {
        "base_cpu": 4, "pods_cpu": 4, "startup_cpu": 4, "num_pods": 4,
        "exp_pods": 4, "mem_used": 4, "image_cached": 1, "healthy": 1,
        "uptime_hours": 4, "cpu_capacity": 4, "mem_capacity": 4,
        "max_pods": 4, "cpu_requested": 4, "mem_requested": 4,
    }
    POD_ROW_BYTES = 4 * 4        # cpu/mem request and demand, float32
    # one afterstate-feature build: start cost select, the pod/experiment
    # increments, crowding (2), the raw CPU sum (7), utilization, contention
    # knee (2), contention (4), the cap, and six normalized features (6)
    FEATURE_FLOPS = 26
    FILTER_FLOPS = 5             # two request sums, three comparisons (+ Ready)
    # 6 -> 32 multiply-adds, bias, ReLU, 32 -> 1 multiply-adds, bias (a
    # subclass that changes the widths restates it)
    MLP_FLOPS = 2 * FEATURES * HIDDEN + HIDDEN + HIDDEN + 2 * HIDDEN + 1

    # -- the work a decision needs -----------------------------------------

    def node_bytes(self) -> int:
        return sum(self.NODE_BYTES.values())

    def pair_flops(self) -> int:
        """Operations a (request, node) pair: one afterstate-feature build,
        one filter and one evaluation of the Q-net."""
        return self.FEATURE_FLOPS + self.FILTER_FLOPS + self.MLP_FLOPS

    # -- the configuration file made into columns and rows -----------------

    def pod_types(self, config: dict) -> List[PodType]:
        return [self.PodType(**p) for p in config["pods"]]

    def mean_cpu_request(self, types: Sequence[PodType]) -> float:
        w = np.asarray([t.weight for t in types], np.float64)
        return float(np.sum(w / w.sum() * [t.cpu_request for t in types]))

    def class_columns(self, cls: dict, uptime_h: Sequence[float],
                      mean_req: float, rng: np.random.Generator
                      ) -> Dict[str, np.ndarray]:
        """One node class's columns, before any experiment pod runs.

        Base load and tenant bookings either as fractions of the class's
        capacity (``frac``) or as an absolute profile (``profile``); tenant
        pods are bookings over the mix's mean CPU request; uptime is
        uniform over ``uptime_h``; every node starts Ready with no cached
        image."""
        n, cap = cls["count"], float(cls["cpu_capacity"])
        base = uniform_or_profile(rng, cls["base_cpu"], n)
        if "frac" in cls["base_cpu"]:
            base = cap * base
        base = np.maximum(base, 0.0)
        req = uniform_or_profile(rng, cls["requested_frac"], n)
        req0 = cap * np.clip(req, 0.0, 0.95)
        return {
            "cpu_capacity": np.full(n, cap),
            "mem_capacity": np.full(n, float(cls["mem_capacity"])),
            "max_pods": np.full(n, cls["max_pods"]),
            "healthy": np.ones(n, bool),
            "uptime_hours": rng.uniform(uptime_h[0], uptime_h[1], n),
            "num_pods": np.floor(req0 / mean_req),
            "exp_pods": np.zeros(n),
            "cpu_requested": np.minimum(req0, 0.98 * cap),
            "mem_requested": np.zeros(n), "pods_cpu": np.zeros(n),
            "mem_used": np.zeros(n), "base_cpu": base,
            "startup_cpu": np.zeros(n), "image_cached": np.zeros(n, bool),
        }

    def reset(self, config: dict, rng: np.random.Generator
              ) -> Dict[str, np.ndarray]:
        """Node columns of the configured pool before any experiment pod
        runs, in ``COLUMNS``' order and dtypes.  ``repeat`` lays the list of
        classes out that many times in a row."""
        mean_req = self.mean_cpu_request(self.pod_types(config))
        nodes = config["nodes"]
        parts = [self.class_columns(cls, nodes["uptime_h"], mean_req, rng)
                 for cls in nodes["classes"] * nodes.get("repeat", 1)]
        return {k: np.concatenate([p[k] for p in parts]).astype(dtype)
                for k, dtype in self.COLUMNS.items()}

    def prefill_fit(self, cols: Dict[str, np.ndarray],
                    types: Sequence[PodType]) -> np.ndarray:
        """How many pods of the mix's mean size each node's free CPU,
        memory and pod slots hold."""
        w = _per_type(types, "weight")
        w = w / w.sum()
        free_c = cols["cpu_capacity"].astype(np.float64) - cols[
            "cpu_requested"]
        free_m = cols["mem_capacity"].astype(np.float64) - cols[
            "mem_requested"]
        return np.minimum.reduce([
            np.floor(free_c / float(w @ _per_type(types, "cpu_request"))),
            np.floor(free_m / float(w @ _per_type(types, "mem_request"))),
            (cols["max_pods"] - cols["num_pods"]).astype(np.float64)])

    def prefill_keep(self, cols: Dict[str, np.ndarray],
                     types: Sequence[PodType], nodes: np.ndarray,
                     kinds: np.ndarray) -> np.ndarray:
        """Which of the pods drawn for the nodes (``nodes`` grouped by
        node, ``kinds`` their types) the pre-fill keeps: a node that the
        drawn types would overbook gives back, in order, every pod past
        its CPU or memory."""
        n = len(cols["cpu_capacity"])
        creq = _per_type(types, "cpu_request")
        mreq = _per_type(types, "mem_request")
        cap = cols["cpu_capacity"].astype(np.float64)
        mcap = cols["mem_capacity"].astype(np.float64)

        def sums(per_type):
            return np.bincount(nodes, weights=per_type[kinds], minlength=n)

        keep = np.ones(len(nodes), bool)
        over = ((cols["cpu_requested"] + sums(creq) > cap)
                | (cols["mem_requested"] + sums(mreq) > mcap))
        count = np.bincount(nodes, minlength=n)
        first = np.cumsum(count) - count
        for node in np.flatnonzero(over):
            idx = np.arange(first[node], first[node] + count[node])
            c = cols["cpu_requested"][node] + creq[kinds[idx]].cumsum()
            m = cols["mem_requested"][node] + mreq[kinds[idx]].cumsum()
            keep[idx[(c > cap[node]) | (m > mcap[node])]] = False
        return keep

    def book(self, cols: Dict[str, np.ndarray], types: Sequence[PodType],
             nodes: np.ndarray, kinds: np.ndarray) -> None:
        """Bind the pre-fill's pods (``kinds[i]`` on ``nodes[i]``) in bulk,
        in place.  Startup transients are left at 0: resident pods started
        long ago."""
        n = len(cols["cpu_capacity"])
        per_node = np.bincount(nodes, minlength=n)
        for name, field in (("cpu_requested", "cpu_request"),
                            ("mem_requested", "mem_request"),
                            ("pods_cpu", "cpu_demand"),
                            ("mem_used", "mem_demand")):
            add = np.bincount(nodes, weights=_per_type(types, field)[kinds],
                              minlength=n)
            cols[name] = (cols[name] + add).astype(np.float32)
        cols["num_pods"] = (cols["num_pods"] + per_node).astype(np.int32)
        cols["exp_pods"] = (cols["exp_pods"] + per_node).astype(np.int32)
        cols["image_cached"] = cols["image_cached"] | (per_node > 0)

    # -- the policy ---------------------------------------------------------

    def config_weights(self, config: dict) -> Dict[str, np.ndarray]:
        """The configuration's policy: Q-net weights drawn from its own
        stated ``weights.seed``, the same for every ``--seed``, as a
        deployment serves one policy while its cluster and traffic vary."""
        return self.make_weights(
            np.random.default_rng(int(config["weights"]["seed"])))

    def make_weights(self, rng: np.random.Generator
                     ) -> Dict[str, np.ndarray]:
        """Random Table-4 Q-net weights (6 -> 32 -> 1), float32.  Random,
        not trained: speed and agreement with the reference need no trained
        policy.  Biases are drawn too, so no part of the net is trivially
        zero."""
        n, hidden = self.FEATURES, self.HIDDEN
        return {
            "w1": (rng.standard_normal((n, hidden))
                   * np.sqrt(2.0 / n)).astype(np.float32),
            "b1": (0.1 * rng.standard_normal(hidden)).astype(np.float32),
            "w2": (rng.standard_normal((hidden, 1))
                   * np.sqrt(1.0 / hidden)).astype(np.float32),
            "b2": (0.1 * rng.standard_normal(1)).astype(np.float32),
        }

    # -- the scheduler's semantics ------------------------------------------

    def feasible(self, cols: Dict[str, np.ndarray], pod) -> np.ndarray:
        """The k8s filtering phase, in the columns' own float32."""
        return (cols["healthy"]
                & (cols["cpu_requested"] + np.float32(pod.cpu_request)
                   <= cols["cpu_capacity"])
                & (cols["mem_requested"] + np.float32(pod.mem_request)
                   <= cols["mem_capacity"])
                & (cols["num_pods"] < cols["max_pods"]))

    def afterstate_features(self, cols: Dict[str, np.ndarray], pod,
                            phys: dict) -> np.ndarray:
        """(N, 6) normalized Table-2 features of every node as if ``pod``
        were placed there, in float64."""
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
        used = np.minimum(raw + phys["contention_coeff"] * over * over * cap,
                          cap)
        feats = np.stack([
            100.0 * used / cap,
            100.0 * (c["mem_used"] + pod.mem_demand) / c["mem_capacity"],
            100.0 * num1 / c["max_pods"],
            c["healthy"],
            c["uptime_hours"],
            exp1,
        ], axis=-1)
        return feats / np.asarray(phys["feature_scale"], np.float64)

    def qnet(self, feats: np.ndarray, w: Dict[str, np.ndarray],
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

    def afterstate_q(self, cols, pod, phys, w,
                     precision: str = "f64") -> np.ndarray:
        return self.qnet(self.afterstate_features(cols, pod, phys), w,
                         precision)

    def candidates(self, q: np.ndarray, ok: np.ndarray, shards: int,
                   topk: int):
        """(values, node indices) of the two-stage path for one request:
        the ``topk`` best feasible nodes of each of ``shards`` contiguous
        shards, merged in descending order (ties by ascending index),
        ``-inf`` / ``-1`` past each shard's feasible set."""
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

    def state_diff(self, a: Dict[str, np.ndarray],
                   b: Dict[str, np.ndarray]) -> float:
        """Largest absolute difference over every column (0 = identical)."""
        return max(float(np.max(np.abs(np.asarray(a[k], np.float64)
                                       - np.asarray(b[k], np.float64))))
                   for k in self.COLUMNS)


def for_config(config: dict) -> Reference:
    """The ``Reference`` of the module the configuration names under
    ``reference`` (a path relative to the checkout), or of this module where
    it names none."""
    rel = config.get("reference", DEFAULT)
    path = os.path.join(ROOT, rel)
    if os.path.realpath(path) == os.path.realpath(__file__):
        return BASE
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + re.sub(r"\W", "_", rel), path)
    if spec is None:
        raise ValueError(f"reference {rel!r} is not a Python module")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    ref = getattr(mod, "Reference", None)
    if not (isinstance(ref, type) and issubclass(ref, Reference)):
        raise ValueError(f"reference {rel!r} defines no subclass of "
                         f"bench.lib.reference.Reference")
    return ref()


BASE = Reference()
pod_types = BASE.pod_types
reset = BASE.reset
make_weights = BASE.make_weights
config_weights = BASE.config_weights
feasible = BASE.feasible
afterstate_q = BASE.afterstate_q
candidates = BASE.candidates
state_diff = BASE.state_diff
