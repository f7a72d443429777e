"""The work a placement decision needs, and the chip's published peaks.

The counts are of the work the decision needs, whatever implements it: every
node column that scoring and filtering read, once per batch; the batch's pod
rows; the candidate lists the commit loop reads back; and for every (request,
node) pair one afterstate-feature build, one filter and one evaluation of the
6 -> 32 -> 1 Q-net.  They are not the bytes today's kernel happens to move.
A multiply-add counts as two operations.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Tuple

# the node columns scoring (afterstate features) and filtering read, with
# their widths in the snapshot: float32 and int32 columns 4 bytes, bools 1
NODE_COLUMNS = {
    "base_cpu": 4, "pods_cpu": 4, "startup_cpu": 4, "num_pods": 4,
    "exp_pods": 4, "mem_used": 4, "image_cached": 1, "healthy": 1,
    "uptime_hours": 4, "cpu_capacity": 4, "mem_capacity": 4, "max_pods": 4,
    "cpu_requested": 4, "mem_requested": 4,
}
POD_ROW_BYTES = 4 * 4            # cpu/mem request and demand, float32
HIDDEN, FEATURES = 32, 6
# one afterstate-feature build: start cost select, the pod/experiment
# increments, crowding (2), the raw CPU sum (7), utilization, contention knee
# (2), contention (4), the cap, and six normalized features (6)
FEATURE_FLOPS = 26
FILTER_FLOPS = 5                 # two request sums, three comparisons (+ Ready)
# 6 -> 32 multiply-adds, bias, ReLU, 32 -> 1 multiply-adds, bias
MLP_FLOPS = 2 * FEATURES * HIDDEN + HIDDEN + HIDDEN + 2 * HIDDEN + 1

PEAKS_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def node_bytes() -> int:
    return sum(NODE_COLUMNS.values())


def serve_batch(n_nodes: int, n_real: int, candidates: int = 0
                ) -> Tuple[float, float]:
    """(operations, bytes) one scoring launch needs for ``n_real`` requests
    over ``n_nodes`` nodes.  ``candidates`` > 0 is the two-stage path, whose
    commit loop reads ``candidates`` (score, index) pairs a request; 0 is
    the flat path, whose commit loop reads a score and a feasibility flag
    for every node."""
    flops = float(n_real) * n_nodes * (FEATURE_FLOPS + FILTER_FLOPS + MLP_FLOPS)
    per_req = candidates * (4 + 4) if candidates else n_nodes * (4 + 1)
    nbytes = float(n_nodes * node_bytes() + n_real * POD_ROW_BYTES
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
