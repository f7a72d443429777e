"""commit_ms.tput: batch loop, the rest -- mean batch time minus snapshot
publish minus scoring launch: the pack, the readback copy, and the
re-validate / bind / next-best walk of the commit loop."""
import numpy as np


def read(run):
    s = run["spans"]
    if not (s["poll_s"] and s["snapshot_s"] and s["score_s"]):
        return None
    return 1e3 * (float(np.mean(s["poll_s"])) - float(np.mean(s["snapshot_s"]))
                  - float(np.mean(s["score_s"])))
