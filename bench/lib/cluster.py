"""A cluster configuration made into node columns, from the seed, in numpy.

The configuration file (``bench/configs/<name>.json``) states the node pool,
the pod mix, the per-node physics constants, the pre-fill and the policy's
weight seed.  The cluster is drawn from ``--seed`` with numpy, in bulk, so
that a 100,000-node cluster and its ~866,000 resident pods take well under a
second, and the program under test only ever receives the finished columns.

What the columns are, and how a node class, a pod type and the pre-fill's
binds become them, is the configuration's reference's (``reference.py``);
this module keeps what every configuration shares: the pod stream and the
pre-fill's draw and FIFO order.  The names the base reference states
(``reset``, ``make_weights``, ...) are also kept here.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np

from bench.lib import reference
from bench.lib.reference import (  # noqa: F401  (the base's, kept here)
    PodType, config_weights, make_weights, pod_types, reset)


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


def prefill(cols, types: Sequence[PodType], fill_frac: float,
            stream: PodStream, rng: np.random.Generator,
            ref: reference.Reference = reference.BASE) -> np.ndarray:
    """Bind resident experiment pods directly (scheduler_perf's ``initPods``),
    in place, and return them as an (R, 2) array of (node, type) in the
    seeded FIFO order in which they will retire.

    Each node takes ``floor(fill_frac * fit)`` pods, where ``fit`` is the
    reference's ``prefill_fit``; the types come from ``stream``.  The
    reference's ``prefill_keep`` gives back the pods that would overbook a
    node, and its ``book`` binds the rest.
    """
    fit = ref.prefill_fit(cols, types)
    count = np.floor(fill_frac * np.maximum(fit, 0.0)).astype(np.int64)
    nodes = np.repeat(np.arange(len(count)), count)
    kinds = stream.take(len(nodes))
    keep = ref.prefill_keep(cols, types, nodes, kinds)
    nodes, kinds = nodes[keep], kinds[keep]
    ref.book(cols, types, nodes, kinds)
    order = rng.permutation(len(nodes))
    return np.stack([nodes[order], kinds[order]], axis=1)
