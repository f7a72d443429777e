"""The plain reference against the scheduler it stands for, on the CPU.

The reference imports nothing of the program; these tests hold the two
together at a small size, so that a disagreement on the chip points at the
program and not at the yardstick."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import cluster, reference as ref  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _cluster(name, seed=2**31 + 5):
    cfg = json.load(open(os.path.join(DATA, f"{name}.json")))
    rngs = [np.random.default_rng(s)
            for s in np.random.SeedSequence(seed).spawn(4)]
    types = cluster.pod_types(cfg)
    cols = cluster.reset(cfg, rngs[0])
    cluster.prefill(cols, types, 0.5, cluster.PodStream(types, rngs[1]),
                    rngs[2])
    # some startup transients and cached images, as a running cluster has
    cols["startup_cpu"] = rngs[3].uniform(0, 3000, len(cols["startup_cpu"])
                                          ).astype(np.float32)
    cols["image_cached"] = rngs[3].uniform(size=len(cols["startup_cpu"])) < .5
    cols["healthy"][::7] = False
    return cfg, types, cols, cluster.make_weights(rngs[3])


def _program(cfg, types, cols):
    from repro.core.types import ClusterState
    from repro.sched.daemon import ClusterSubstrate

    from bench.lib.serve import env_config

    state = ClusterState(time_s=np.float32(0), **cols)
    return state, env_config(cfg, len(cols["cpu_capacity"]), types[0]), \
        ClusterSubstrate


def test_bf16_rounding():
    x = np.float32([1.0, 1.0 + 2**-8, 1.0 + 3 * 2**-9, -2.5, 0.0])
    np.testing.assert_array_equal(
        ref.to_bf16(x), np.float32([1.0, 1.0, 1.0 + 2**-7, -2.5, 0.0]))


@pytest.mark.parametrize("name", ["tiny-flat", "tiny-sharded"])
def test_scores_and_filter_match_the_scheduler(name):
    import jax.numpy as jnp

    from repro.core import env as kenv, schedulers
    from repro.core.types import PodSpec

    cfg, types, cols, w = _cluster(name)
    state, ecfg, _ = _program(cfg, types, cols)
    params = {k: jnp.asarray(v) for k, v in w.items()}
    for t in types:
        pod = PodSpec(*(np.float32(getattr(t, f)) for f in PodSpec._fields))
        want = np.asarray(schedulers.score_afterstates(params, state, pod,
                                                       ecfg, fused=False))
        ok = np.asarray(kenv.feasible(state, pod, ecfg))
        got = ref.afterstate_q(cols, t, cfg["physics"], w)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(ref.feasible(cols, t), ok)
        ctrl = ref.afterstate_q(cols, t, cfg["physics"], w, "bf16")
        assert np.max(np.abs(ctrl - got)) > 1e-3


def test_candidates_match_the_two_stage_path():
    import jax.numpy as jnp

    from repro.core.types import PodSpec
    from repro.launch.mesh import plan_fleet_layout
    from repro.sched import shard

    cfg, types, cols, w = _cluster("tiny-sharded")
    state, ecfg, _ = _program(cfg, types, cols)
    sc = cfg["scoring"]
    layout = plan_fleet_layout(len(cols["cpu_capacity"]), shards=sc["shards"])
    params = {k: jnp.asarray(v) for k, v in w.items()}
    for t in types:
        pod = PodSpec(*(np.float32(getattr(t, f)) for f in PodSpec._fields))
        pv, pi = shard.cluster_topk(params, state, pod, ecfg, layout,
                                    k=sc["topk"])
        q = ref.afterstate_q(cols, t, cfg["physics"], w)
        rv, ri = ref.candidates(q, ref.feasible(cols, t), sc["shards"],
                                sc["topk"])
        fin = np.isfinite(rv)
        np.testing.assert_array_equal(np.isfinite(np.asarray(pv)), fin)
        np.testing.assert_allclose(np.asarray(pv)[fin], rv[fin], rtol=2e-5,
                                   atol=2e-5)
        assert int(np.asarray(pi)[0]) == int(ri[0])


def test_replay_matches_the_live_buffer_exactly():
    cfg, types, cols, _ = _cluster("tiny-sharded")
    state, ecfg, Sub = _program(cfg, types, cols)
    sub = Sub(state, ecfg)
    rp = ref.Replay(cols, types, cfg["physics"])
    from repro.core.types import PodSpec

    pods = [PodSpec(t.cpu_request, t.cpu_demand, t.mem_request, t.mem_demand)
            for t in types]
    rng = np.random.default_rng(3)
    bound = []
    for i in range(3000):
        t = int(rng.integers(len(types)))
        node = int(rng.integers(len(cols["cpu_capacity"])))
        if bound and rng.uniform() < 0.4:
            n2, t2 = bound.pop(int(rng.integers(len(bound))))
            sub.unbind(n2, pods[t2])
            rp.unbind(n2, t2)
        assert rp.feasible_one(node, t) == sub.feasible_one(node, pods[t])
        sub.bind(node, pods[t])
        rp.bind(node, t)
        bound.append((node, t))
    live = {k: np.asarray(v) for k, v in sub.live._asdict().items()}
    assert ref.state_diff(rp.cols, live) == 0.0
    assert ref.in_flight(rp.cols["startup_cpu"], cfg["physics"]) \
        == rp._in_flight
