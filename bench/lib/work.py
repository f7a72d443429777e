"""The work a placement decision needs, and the chip's published peaks.

The counts are of the work the decision needs, whatever implements it: every
node column that scoring and filtering read, once per batch; the batch's pod
rows; the candidate lists the commit loop reads back; and for every (request,
node) pair one afterstate-feature build, one filter and one evaluation of the
6 -> 32 -> 1 Q-net.  They are not the bytes today's kernel happens to move.
A multiply-add counts as two operations.  The node columns, the pod row and
the operations a pair are the configuration's reference's (``reference.py``);
this module keeps the readback, the peaks and the least time.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

from bench.lib import reference

# the base configuration's counts; a configuration's reference states its own
FEATURE_FLOPS = reference.Reference.FEATURE_FLOPS
FILTER_FLOPS = reference.Reference.FILTER_FLOPS
MLP_FLOPS = reference.Reference.MLP_FLOPS

PEAKS_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def node_bytes(ref: reference.Reference = reference.BASE) -> int:
    return ref.node_bytes()


def serve_batch(n_nodes: int, n_real: int, candidates: int = 0,
                ref: reference.Reference = reference.BASE
                ) -> Tuple[float, float]:
    """(operations, bytes) one scoring launch needs for ``n_real`` requests
    over ``n_nodes`` nodes, by the configuration's reference ``ref``: its
    node columns, pod row and operations a (request, node) pair.
    ``candidates`` > 0 is the two-stage path, whose commit loop reads
    ``candidates`` (score, index) pairs a request; 0 is the flat path, whose
    commit loop reads a score and a feasibility flag for every node."""
    flops = float(n_real) * n_nodes * ref.pair_flops()
    per_req = candidates * (4 + 4) if candidates else n_nodes * (4 + 1)
    nbytes = float(n_nodes * ref.node_bytes() + n_real * ref.POD_ROW_BYTES
                   + n_real * per_req)
    return flops, nbytes


def load_peaks(path: str = PEAKS_PATH) -> Dict[str, dict]:
    with open(path) as f:
        return json.load(f)["devices"]


def peak_for(device_kind: str, peaks: Dict[str, dict] = None) -> dict:
    """The published peaks of ``device_kind``; a device missing from the
    table is an error, never a default."""
    peaks = load_peaks() if peaks is None else peaks
    if device_kind not in peaks:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json (have: {sorted(peaks)})")
    return peaks[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> Tuple[float, str]:
    """The least time the chip could take, and which term bounds it."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
