"""Device idle share: 1 - (union of the device's op intervals / traced
window), from the profiler trace of the window."""


def read(run):
    tr = run["trace"]
    return None if tr is None else tr["idle_share"]
