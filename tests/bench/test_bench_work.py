"""Work counts and the table of peaks."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import work  # noqa: E402

V5E = "TPU v5 lite"


def test_per_pair_operations():
    # 26 feature ops + 5 filter ops + (2*6*32 + 32 + 32 + 2*32 + 1) MLP ops
    assert work.FEATURE_FLOPS + work.FILTER_FLOPS + work.MLP_FLOPS == 544
    assert work.node_bytes() == 50          # 12 four-byte, 2 one-byte columns


@pytest.mark.parametrize("n_nodes,n_real,cand,flops,nbytes", [
    # k8s-5k: flat path, the commit loop reads a score and a flag a node
    (5000, 32, 0, 32 * 5000 * 544, 5000 * 50 + 32 * 16 + 32 * 5000 * 5),
    # eks-100k: 8 shards x top 8 candidates of (score, index) a request
    (100000, 32, 64, 32 * 100000 * 544, 100000 * 50 + 32 * 16 + 32 * 64 * 8),
])
def test_counts_at_each_cells_sizes(n_nodes, n_real, cand, flops, nbytes):
    got = work.serve_batch(n_nodes, n_real, cand)
    assert got == (float(flops), float(nbytes))


def test_least_time_names_its_bound():
    peak = work.peak_for(V5E)
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    t, bound = work.least_time(*work.serve_batch(5000, 32, 0), peak)
    assert bound == "bytes" and t == pytest.approx(1_050_512 / 819e9)
    t, bound = work.least_time(*work.serve_batch(100000, 32, 64), peak)
    assert bound == "flops"
    assert t == pytest.approx(32 * 100000 * 544 / 197e12)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        work.peak_for("cpu")
    with pytest.raises(KeyError):
        work.peak_for("TPU v4", {V5E: {}})
