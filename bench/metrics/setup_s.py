"""setup_s: process start to window start -- imports, device start-up, the
cluster, pre-fill and weights from the seed, compilation or the compile
cache, and warm-up."""


def read(run):
    return run["setup_s"]
