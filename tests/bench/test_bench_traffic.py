"""The benchmark's traffic generators and metric arithmetic (CPU, tiny)."""
import json
import math
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import cluster, traffic  # noqa: E402

BIG_SEED = 2**31 + 977


def test_rate_over_the_window():
    assert traffic.rate(3000, 10.0) == 300.0
    with pytest.raises(ValueError):
        traffic.rate(1, 0.0)


@pytest.mark.parametrize("mix,ok", [
    ({"kind": "backlog", "depth": 64}, True),
    ({"kind": "backlog", "depth": 1}, True),
    ({"kind": "backlog", "depth": 0}, False),
    ({"kind": "poisson", "rate_per_s": 10.0}, False),
    ({"kind": "diurnal"}, False),
])
def test_check_mix(mix, ok):
    if ok:
        assert traffic.check_mix(mix) is mix
    else:
        with pytest.raises(ValueError):
            traffic.check_mix(mix)


def test_every_traffic_file_is_a_valid_mix():
    tdir = os.path.join(ROOT, "bench", "traffic")
    for name in os.listdir(tdir):
        with open(os.path.join(tdir, name)) as f:
            traffic.check_mix(json.load(f))


def test_pod_stream_sends_the_exact_mix():
    types = cluster.pod_types(json.load(open(os.path.join(
        ROOT, "tests", "bench", "data", "tiny-sharded.json"))))
    s = cluster.PodStream(types, np.random.default_rng(BIG_SEED))
    kinds = np.concatenate([s.take(700), s.take(1300)])
    np.testing.assert_array_equal(np.bincount(kinds), [400, 1200, 400])
    other = cluster.PodStream(types, np.random.default_rng(5)).take(2000)
    assert not np.array_equal(kinds, other)


@pytest.mark.parametrize("name", ["k8s-5k", "eks-100k"])
def test_cluster_and_prefill_from_the_seed(name):
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      f"{name}.json")))
    types = cluster.pod_types(cfg)

    def build(seed):
        ss = np.random.SeedSequence(seed).spawn(3)
        rngs = [np.random.default_rng(x) for x in ss]
        cols = cluster.reset(cfg, rngs[0])
        fifo = cluster.prefill(cols, types, cfg["prefill"]["fill_frac"],
                               cluster.PodStream(types, rngs[1]), rngs[2])
        return cols, fifo

    cols, fifo = build(BIG_SEED)
    n = sum(c["count"] for c in cfg["nodes"]["classes"]) * cfg["nodes"].get(
        "repeat", 1)
    assert len(cols["cpu_capacity"]) == n
    assert np.all(cols["cpu_requested"] <= cols["cpu_capacity"])
    assert np.all(cols["mem_requested"] <= cols["mem_capacity"])
    assert np.all(cols["num_pods"] <= cols["max_pods"])
    assert np.all(cols["healthy"])
    np.testing.assert_array_equal(np.bincount(fifo[:, 0], minlength=n),
                                  cols["exp_pods"])
    # every node keeps room: half of each node's fit is left free
    free = (cols["cpu_capacity"] - cols["cpu_requested"]) / max(
        t.cpu_request for t in types)
    assert np.mean(free >= 1) > 0.5
    again, fifo2 = build(BIG_SEED)
    np.testing.assert_array_equal(fifo, fifo2)
    for k in cols:
        np.testing.assert_array_equal(cols[k], again[k])
    # the reckoning in PERF.md: about 18.4 pods of free CPU a node, half kept
    assert 8.0 * n < len(fifo) < 9.4 * n
    assert not math.isnan(float(np.sum(cols["base_cpu"])))


@pytest.mark.parametrize("name", ["k8s-5k", "eks-100k"])
def test_the_policy_is_the_configurations_not_the_seeds(name):
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs",
                                      f"{name}.json")))
    w = cluster.config_weights(cfg)
    again = cluster.make_weights(np.random.default_rng(cfg["weights"]["seed"]))
    for k in ("w1", "b1", "w2", "b2"):
        np.testing.assert_array_equal(w[k], again[k])
        assert w[k].dtype == np.float32
    assert w["w1"].shape == (6, 32) and w["w2"].shape == (32, 1)
