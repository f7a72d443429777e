"""score_ms.tput: scoring launch -- mean time per batch from the scorer call
to its outputs being ready (``block_until_ready``)."""
import numpy as np


def read(run):
    t = run["spans"]["score_s"]
    return 1e3 * float(np.mean(t)) if t else None
