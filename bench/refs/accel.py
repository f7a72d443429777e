"""The plain reference of a cluster whose nodes carry accelerators.

Kubernetes schedules accelerators (``nvidia.com/gpu``,
``aws.amazon.com/neuron``, ``google.com/tpu``) as an *extended resource*:
an integer count a node, asked for in a pod's ``limits``, never
overcommitted and never shared between pods; the filter is the same
"requested + asked <= capacity" test as for CPU
(kubernetes.io/docs/tasks/manage-gpus/scheduling-gpus/).  This module is
the base reference (``bench/lib/reference.py``) with that resource added,
and nothing else changed: the Q-net does not see accelerators.

It imports nothing of the program under test.  (No ``from __future__
import annotations``: the harness loads this file as a module of no
package, and ``dataclasses`` cannot resolve string annotations there.)
"""
import dataclasses
from typing import Dict, Sequence

import numpy as np

from bench.lib import reference as base
from bench.lib.reference import _per_type


@dataclasses.dataclass(frozen=True)
class PodType(base.PodType):
    accel_request: int = 0       # accelerators asked for in limits


class Replay(base.Replay):
    """The base replay, with every bind checked against and every bind and
    retirement applied to the accelerators too."""

    def feasible_one(self, node: int, kind: int) -> bool:
        c = self.cols
        return bool(super().feasible_one(node, kind)
                    and int(c["accel_requested"][node])
                    + self.types[kind].accel_request
                    <= int(c["accel_capacity"][node]))

    def bind(self, node: int, kind: int) -> bool:
        ok = super().bind(node, kind)
        self.cols["accel_requested"][node] += self.types[kind].accel_request
        return ok

    def unbind(self, node: int, kind: int) -> None:
        super().unbind(node, kind)
        self.cols["accel_requested"][node] -= self.types[kind].accel_request


class Reference(base.Reference):
    """Pods on nodes with CPU, memory, pod slots and accelerators, scored by
    the base's 6 -> 32 -> 1 Table-4 Q-net."""

    COLUMNS = dict(base.Reference.COLUMNS, accel_capacity=np.int32,
                   accel_requested=np.int32)
    POD_FIELDS = base.Reference.POD_FIELDS + ("accel_request",)
    PodType = PodType
    Replay = Replay

    # the filter reads two more int32 columns, the pod row one more int32
    NODE_BYTES = dict(base.Reference.NODE_BYTES, accel_capacity=4,
                      accel_requested=4)
    POD_ROW_BYTES = base.Reference.POD_ROW_BYTES + 4
    FILTER_FLOPS = base.Reference.FILTER_FLOPS + 2   # one sum, one comparison

    def class_columns(self, cls: dict, uptime_h: Sequence[float],
                      mean_req: float, rng: np.random.Generator
                      ) -> Dict[str, np.ndarray]:
        """The base's columns, and the class's accelerators, none of them
        requested before the pre-fill."""
        cols = super().class_columns(cls, uptime_h, mean_req, rng)
        n = cls["count"]
        cols["accel_capacity"] = np.full(n, int(cls["accel_capacity"]))
        cols["accel_requested"] = np.zeros(n, np.int64)
        return cols

    def prefill_fit(self, cols: Dict[str, np.ndarray],
                    types: Sequence[PodType]) -> np.ndarray:
        """The base's fit, and how many pods of the mix's mean accelerator
        request each node's free accelerators hold."""
        w = _per_type(types, "weight")
        mean = float(w / w.sum() @ _per_type(types, "accel_request"))
        free = (cols["accel_capacity"].astype(np.float64)
                - cols["accel_requested"])
        return np.minimum(super().prefill_fit(cols, types),
                          np.floor(free / mean))

    def prefill_keep(self, cols: Dict[str, np.ndarray],
                     types: Sequence[PodType], nodes: np.ndarray,
                     kinds: np.ndarray) -> np.ndarray:
        """A node that the drawn types would overbook gives back, in order,
        every pod past its CPU, memory or accelerators."""
        n = len(cols["cpu_capacity"])
        need = [(_per_type(types, f)[kinds], cols[used].astype(np.float64),
                 cols[cap].astype(np.float64))
                for f, used, cap in (
                    ("cpu_request", "cpu_requested", "cpu_capacity"),
                    ("mem_request", "mem_requested", "mem_capacity"),
                    ("accel_request", "accel_requested", "accel_capacity"))]
        keep = np.ones(len(nodes), bool)
        over = np.zeros(n, bool)
        for req, used, cap in need:
            over |= used + np.bincount(nodes, weights=req, minlength=n) > cap
        count = np.bincount(nodes, minlength=n)
        first = np.cumsum(count) - count
        for node in np.flatnonzero(over):
            idx = np.arange(first[node], first[node] + count[node])
            for req, used, cap in need:
                keep[idx[used[node] + req[idx].cumsum() > cap[node]]] = False
        return keep

    def book(self, cols: Dict[str, np.ndarray], types: Sequence[PodType],
             nodes: np.ndarray, kinds: np.ndarray) -> None:
        super().book(cols, types, nodes, kinds)
        add = np.bincount(nodes, weights=_per_type(types, "accel_request")[
            kinds], minlength=len(cols["accel_requested"]))
        cols["accel_requested"] = (cols["accel_requested"]
                                   + add).astype(np.int32)

    def feasible(self, cols: Dict[str, np.ndarray], pod) -> np.ndarray:
        """The base's filter, and the accelerators not yet requested cover
        the pod's request (integers, exact)."""
        return (super().feasible(cols, pod)
                & (cols["accel_requested"].astype(np.int64)
                   + int(pod.accel_request) <= cols["accel_capacity"]))
