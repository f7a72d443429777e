"""accel_stranded_share.tput: commit loop -- at the run's end, the free
accelerators on nodes whose free count is below the mix's largest request,
as a share of all free accelerators: the fragmentation that turns
whole-node pods away.  Nothing to read where the program's live buffer has
no accelerator columns."""
import numpy as np


def read(run):
    cols = run["live_cols"]
    cap, used = cols.get("accel_capacity"), cols.get("accel_requested")
    # a program without the columns leaves them out, or (a field left at
    # None) leaves a 0-d array of None
    if cap is None or used is None or np.ndim(cap) != 1:
        return None
    largest = max(int(getattr(t, "accel_request", 0)) for t in run["types"])
    free = np.asarray(cap, np.int64) - np.asarray(used, np.int64)
    total = int(free.sum())
    if largest <= 0 or total <= 0:
        return None
    return float(free[free < largest].sum()) / total
