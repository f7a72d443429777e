"""snapshot_ms.tput: batch loop, snapshot publish -- mean time per batch of
``ClusterSubstrate.snapshot`` (live buffer to device, sharded on the two-stage path),
ended by ``block_until_ready``."""
import numpy as np


def read(run):
    t = run["spans"]["snapshot_s"]
    return 1e3 * float(np.mean(t)) if t else None
