"""placements_per_s: pods bound in the window / window seconds."""
from bench.lib.traffic import rate


def read(run):
    return rate(run["window_bound"], run["window_s"])
