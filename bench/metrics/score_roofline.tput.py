"""score_roofline.tput: scoring launch -- the least time the chip needs for
the scoring work (``bench/lib/work.py``: the decision's bytes and
operations, by the configuration's reference, over the published peaks) /
the device time of the scorer's module in the trace, in percent."""
import numpy as np

from bench.lib import work
from bench.lib.serve import SCORER_MODULE


def read(run):
    tr = run["trace"]
    sizes = run["spans"]["batch_sizes"]
    if tr is None or not sizes:
        return None
    mod = tr["modules"].get(SCORER_MODULE)
    if not mod or mod["launches"] == 0 or mod["device_s"] <= 0:
        return None
    scoring = run["config"]["scoring"]
    cand = scoring.get("shards", 0) * scoring.get("topk", 0)
    least = np.mean([work.least_time(*work.serve_batch(
        run["n_nodes"], b, cand, run["ref"]), run["peak"])[0] for b in sizes])
    return 100.0 * least * mod["launches"] / mod["device_s"]
