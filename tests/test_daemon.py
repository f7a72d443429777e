"""Placement-daemon suite: batching, one-launch scoring, optimistic binds.

Covers the serving loop's contracts (``repro.sched.daemon``): batches cut by
size AND by max-wait; the whole batch scores in ONE device launch with ONE
compilation across fill levels; racing binds to the same node resolve with
exactly one winner and the loser re-validating against fresh state; the
numpy live-buffer mirrors (``bind``/``feasible_one``) stay bit-close to the
jnp references (``env.place``/``env.feasible``, ``PlacementEngine``); the
resident snapshot's delta publish equals a whole publish bit for bit and
compiles nothing after warm-up; plus
the unified ``repro.sched.api`` dispatch, the arrival-trace adapter, the
``EpisodeResult`` shim, and ``serve.load_qnet`` checkpoint loading.
"""
import dataclasses
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dqn, env as kenv, policy as policy_mod, schedulers
from repro.core.types import (
    NO_PLACEMENT,
    EpisodeResult,
    paper_cluster,
)
from repro.launch.mesh import plan_fleet_layout
from repro.scenarios import arrival_trace, trace_from_table
from repro.sched import api, placement, shard
from repro.sched.daemon import (
    DELTA_ROWS,
    ClusterSubstrate,
    DaemonConfig,
    FleetSubstrate,
    PlacementDaemon,
)

CFG = paper_cluster()


@pytest.fixture(scope="module")
def qparams():
    return dqn.init_qnet(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def state():
    return kenv.reset(jax.random.PRNGKey(1), CFG)


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def make_daemon(state, qparams, score_fn=None, **cfg_kw):
    clock = FakeClock()
    sub = ClusterSubstrate(state, CFG, score_fn=score_fn)
    d = PlacementDaemon(sub, qparams, DaemonConfig(**cfg_kw), clock=clock)
    return d, sub, clock


# ---------------------------------------------------------------------------
# batching semantics
# ---------------------------------------------------------------------------


class TestBatching:
    def test_batch_cut_by_size(self, state, qparams):
        d, _, clock = make_daemon(state, qparams, batch_size=4,
                                  max_wait_s=1e9)
        pod = kenv.default_pod(CFG)
        for _ in range(3):
            d.submit(pod)
            assert d.poll() == 0          # below size, wait unbounded
        d.submit(pod)
        assert d.poll() == 4              # 4th request cuts the batch
        assert d.metrics.batches == 1
        assert d.pending == 0

    def test_batch_cut_by_max_wait(self, state, qparams):
        d, _, clock = make_daemon(state, qparams, batch_size=64,
                                  max_wait_s=0.5)
        pod = kenv.default_pod(CFG)
        d.submit(pod)
        d.submit(pod)
        assert d.poll() == 0              # neither condition holds yet
        clock.t = 0.499
        assert d.poll() == 0
        clock.t = 0.5                     # oldest waited max_wait_s
        assert d.poll() == 2              # partial batch ships
        assert d.metrics.batches == 1

    def test_drain_finishes_everything(self, state, qparams):
        d, _, _ = make_daemon(state, qparams, batch_size=8, max_wait_s=1e9)
        pod = kenv.default_pod(CFG)
        for _ in range(11):
            d.submit(pod)
        assert d.drain() == 11
        assert len(d.decisions) == 11
        assert d.metrics.bound + d.metrics.dropped == 11

    def test_latency_measured_from_submission(self, state, qparams):
        d, _, clock = make_daemon(state, qparams, batch_size=64,
                                  max_wait_s=0.1)
        d.submit(kenv.default_pod(CFG))   # t=0
        clock.t = 0.25
        assert d.poll() == 1
        assert d.decisions[0].latency_s == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# one device launch per batch
# ---------------------------------------------------------------------------


class TestOneLaunch:
    def test_one_launch_one_compile_across_fills(self, state, qparams):
        d, _, _ = make_daemon(state, qparams, batch_size=4, max_wait_s=1e9)
        d.warmup()
        pod = kenv.default_pod(CFG)
        # full batch, then two partial fills (3, 1) via drain
        for _ in range(4):
            d.submit(pod)
        d.poll()
        for _ in range(3):
            d.submit(pod)
        d.flush()
        d.submit(pod)
        d.flush()
        assert d.metrics.batches == 3
        # ONE jitted call per batch...
        assert d.metrics.device_launches == d.metrics.batches
        # ...and ONE compilation total: partial fills pad to the static
        # batch shape instead of recompiling
        assert d.scorer_cache_size() == 1

    def test_fleet_substrate_one_compile(self, qparams):
        sub = FleetSubstrate(placement.fresh_fleet(8))
        d = PlacementDaemon(sub, qparams,
                            DaemonConfig(batch_size=4, max_wait_s=1e9),
                            clock=FakeClock())
        d.warmup()
        for _ in range(6):
            d.submit(placement.JobSpec())
        d.drain()
        assert d.metrics.device_launches == d.metrics.batches == 2
        assert d.scorer_cache_size() == 1

    def test_scorer_text_leaves_the_serving_compile_alone(self, state,
                                                          qparams):
        d, _, _ = make_daemon(state, qparams, batch_size=4, max_wait_s=1e9)
        d.warmup()
        text = d.scorer_text()
        assert text.startswith("HloModule")
        # reading the program is not a second serving compilation
        assert d.scorer_cache_size() == 1

    @pytest.mark.parametrize("policy", sorted(policy_mod.names()))
    def test_cluster_one_launch_one_compile_per_policy_class(
            self, state, policy):
        """The one-launch / one-compile invariant must hold for EVERY
        registered policy class: sequence specs advance their history carry
        inside the single jitted launch, and the traced ``n_real`` pad mask
        means fill levels 4/3/1 all reuse one executable."""
        spec = policy_mod.get(policy)
        params = spec.init(jax.random.PRNGKey(0))
        sub = ClusterSubstrate(state, CFG, policy=spec)
        d = PlacementDaemon(sub, params,
                            DaemonConfig(batch_size=4, max_wait_s=1e9),
                            clock=FakeClock())
        d.warmup()
        pod = kenv.default_pod(CFG)
        for fill in (4, 3, 1):
            for _ in range(fill):
                d.submit(pod)
            d.flush()
        assert d.metrics.batches == 3
        assert d.metrics.device_launches == d.metrics.batches
        assert d.scorer_cache_size() == 1
        assert d.metrics.bound + d.metrics.dropped == 8

    @pytest.mark.parametrize("policy", sorted(policy_mod.names()))
    def test_fleet_one_launch_one_compile_per_policy_class(self, policy):
        spec = policy_mod.get(policy)
        params = spec.init(jax.random.PRNGKey(0))
        sub = FleetSubstrate(placement.fresh_fleet(8), policy=spec)
        d = PlacementDaemon(sub, params,
                            DaemonConfig(batch_size=4, max_wait_s=1e9),
                            clock=FakeClock())
        d.warmup()
        for _ in range(6):
            d.submit(placement.JobSpec())
        d.drain()
        assert d.metrics.device_launches == d.metrics.batches == 2
        assert d.scorer_cache_size() == 1


# ---------------------------------------------------------------------------
# optimistic concurrency
# ---------------------------------------------------------------------------


def _two_node_race(qparams, conflict_policy="requeue", max_retries=4):
    """Two requests, one batch, both scored against the same snapshot and
    both preferring node 0 — which only has room for ONE more pod."""
    cfg = dataclasses.replace(paper_cluster(), n_nodes=2)
    state = kenv.reset(jax.random.PRNGKey(2), cfg)
    # prefer the lowest-CPU afterstate, deterministically
    score_fn = lambda params, feats: -feats[:, 0]
    clock = FakeClock()
    sub = ClusterSubstrate(state, cfg, score_fn=score_fn)
    lv = sub.live
    lv.healthy[:] = True
    lv.base_cpu[:] = (1.0, 30.0)          # node 0 is the attractive one
    lv.cpu_requested[:] = 0.0
    lv.mem_requested[:] = 0.0
    lv.max_pods[0] = lv.num_pods[0] + 1   # ...but fits exactly one more pod
    lv.max_pods[1] = lv.num_pods[1] + 10
    d = PlacementDaemon(
        sub, qparams,
        DaemonConfig(batch_size=2, max_wait_s=1e9, max_retries=max_retries,
                     conflict_policy=conflict_policy),
        clock=clock)
    pod = kenv.default_pod(cfg)
    d.submit(pod)
    d.submit(pod)
    return d


class TestOptimisticConcurrency:
    def test_racing_binds_one_winner_loser_requeues(self, qparams):
        d = _two_node_race(qparams)
        assert d.poll() == 1              # winner bound; loser re-queued
        assert d.metrics.conflicts == 1
        assert d.metrics.requeued == 1
        assert d.pending == 1
        assert d.decisions[0].node == 0
        # the re-queued loser re-validates against FRESH state next batch:
        # node 0 is now full in the new snapshot, so it lands on node 1
        assert d.drain() == 1
        assert d.decisions[1].node == 1
        assert d.decisions[1].attempts == 2
        assert d.metrics.bound == 2

    def test_next_best_policy_resolves_in_one_batch(self, qparams):
        d = _two_node_race(qparams, conflict_policy="next-best")
        assert d.poll() == 2              # loser falls through to node 1
        assert d.metrics.conflicts == 1
        assert d.metrics.requeued == 0
        assert sorted(dec.node for dec in d.decisions) == [0, 1]

    def test_max_retries_drops_conflicted_request(self, qparams):
        d = _two_node_race(qparams, max_retries=1)
        # make node 1 infeasible too, AFTER the snapshot preference is set:
        # the loser's only alternative vanishes and retries run out
        d.poll()
        d._sub.live.max_pods[1] = d._sub.live.num_pods[1]
        d.drain()
        assert d.decisions[1].node == NO_PLACEMENT
        assert d.metrics.dropped == 1

    def test_infeasible_batch_drops_with_sentinel(self, state, qparams):
        d, sub, _ = make_daemon(state, qparams, batch_size=1)
        sub.live.healthy[:] = False       # nothing passes the filter phase
        d.submit(kenv.default_pod(CFG))
        assert d.flush() == 1
        assert d.decisions[0].node == NO_PLACEMENT
        assert d.metrics.dropped == 1
        assert d.metrics.conflicts == 0   # a drop, not a lost race


# ---------------------------------------------------------------------------
# live-buffer mirrors vs the jnp references
# ---------------------------------------------------------------------------


class TestMirrorParity:
    def test_cluster_bind_matches_env_place(self, state, qparams):
        sub = ClusterSubstrate(state, CFG)
        pod = kenv.default_pod(CFG)
        for node in (0, 3, 0):            # includes a warm re-bind
            ref = kenv.place(
                jax.tree.map(jnp.asarray, sub.live), jnp.int32(node), pod,
                CFG)
            sub.bind(node, pod)
            for name, a, b in zip(ref._fields, jax.tree.map(
                    np.asarray, sub.live), ref):
                if a is None:        # no accelerator column on either side
                    assert b is None, name
                    continue
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5,
                    err_msg=f"{name} after bind({node})")

    def test_cluster_feasible_one_matches_env_feasible(self, state, qparams):
        sub = ClusterSubstrate(state, CFG)
        lv = sub.live
        lv.healthy[1] = False
        lv.cpu_requested[2] = lv.cpu_capacity[2]          # CPU-full
        lv.num_pods[3] = lv.max_pods[3]                   # at max-pods
        pod = kenv.default_pod(CFG)
        ref = np.asarray(kenv.feasible(
            jax.tree.map(jnp.asarray, lv), pod, CFG))
        got = np.array([sub.feasible_one(i, pod)
                        for i in range(CFG.n_nodes)])
        np.testing.assert_array_equal(got, ref)

    def test_fleet_bind_matches_engine_place(self, qparams):
        fleet = placement.fresh_fleet(6)
        sub = FleetSubstrate(fleet)
        eng = placement.PlacementEngine(qparams)
        job = placement.JobSpec()
        ref = eng.place(eng.place(fleet, 2, job), 4, job)
        sub.bind(2, job)
        sub.bind(4, job)
        for name, a, b in zip(ref._fields, jax.tree.map(
                np.asarray, sub.live), ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, err_msg=name)

    def test_fleet_feasible_one_matches_engine(self, qparams):
        fleet = placement.fresh_fleet(6)._replace(
            cpu_pct=jnp.asarray([10.0, 90.0, 10.0, 10.0, 10.0, 10.0]),
            mem_pct=jnp.asarray([5.0, 5.0, 96.0, 5.0, 5.0, 5.0]),
            healthy=jnp.asarray([1.0, 1.0, 1.0, 0.0, 1.0, 1.0]),
            job_util_pct=jnp.asarray([0.0, 0.0, 0.0, 0.0, 100.0, 0.0]),
        )
        sub = FleetSubstrate(fleet)
        eng = placement.PlacementEngine(qparams)
        job = placement.JobSpec()
        ref = np.asarray(eng.feasible(fleet, job))
        got = np.array([sub.feasible_one(i, job) for i in range(6)])
        np.testing.assert_array_equal(got, ref)


# ---------------------------------------------------------------------------
# the unified public scheduling API
# ---------------------------------------------------------------------------


class TestApi:
    def test_cluster_dispatch_matches_schedulers(self, state, qparams):
        pod = kenv.default_pod(CFG)
        got = api.score(state, pod, params=qparams, cfg=CFG)
        ref = schedulers.score_afterstates(qparams, state, pod, CFG)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref))

    def test_cluster_requires_cfg(self, state, qparams):
        with pytest.raises(ValueError, match="cfg"):
            api.score(state, kenv.default_pod(CFG), params=qparams)

    def test_fleet_dispatch_matches_engine_select_scores(self, qparams):
        fleet = placement.fresh_fleet(16)
        job = placement.JobSpec()
        got = api.score(fleet, job, params=qparams, fused=False)
        eng = placement.PlacementEngine(qparams, use_kernel=False)
        _, ref = eng.select(fleet, job)
        ok = np.asarray(eng.feasible(fleet, job))
        np.testing.assert_allclose(np.asarray(got)[ok],
                                   np.asarray(ref)[ok], rtol=1e-5)

    def test_score_batch_rows_match_score(self, state, qparams):
        pods = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (3,)), kenv.default_pod(CFG))
        qb = api.score_batch(state, pods, params=qparams, cfg=CFG)
        q1 = api.score(state, kenv.default_pod(CFG), params=qparams, cfg=CFG)
        assert qb.shape == (3, CFG.n_nodes)
        np.testing.assert_allclose(np.asarray(qb[0]), np.asarray(q1),
                                   rtol=1e-5)

    def test_select_returns_sentinel_when_fleet_full(self, qparams):
        fleet = placement.fresh_fleet(4)._replace(
            healthy=jnp.zeros((4,)))
        assert int(api.select(fleet, placement.JobSpec(),
                              params=qparams)) == NO_PLACEMENT

    def test_bad_fused_value_rejected(self, qparams):
        with pytest.raises(ValueError, match="fused"):
            api.score(placement.fresh_fleet(4), placement.JobSpec(),
                      params=qparams, fused="bogus")

    def test_sentinels_are_unified(self):
        assert kenv.NO_NODE is NO_PLACEMENT
        assert placement.NO_HOST is NO_PLACEMENT
        assert api.NO_PLACEMENT is NO_PLACEMENT


# ---------------------------------------------------------------------------
# arrival traces + EpisodeResult shim + checkpoint loading
# ---------------------------------------------------------------------------


class TestArrivals:
    def test_trace_reproducible_and_monotone(self):
        a = arrival_trace(jax.random.PRNGKey(5), CFG, 40)
        b = arrival_trace(jax.random.PRNGKey(5), CFG, 40)
        np.testing.assert_array_equal(a.t_s, b.t_s)
        assert a.t_s[0] == 0.0
        assert np.all(np.diff(a.t_s) >= 0)
        assert len(a.pods) == 40

    def test_rate_rescaling(self):
        tr = arrival_trace(jax.random.PRNGKey(6), CFG, 50,
                           rate_per_s=2000.0)
        assert tr.offered_rate_per_s == pytest.approx(2000.0, rel=1e-6)

    def test_burst_table_spreads_at_offered_rate(self):
        table = kenv.sample_pod_table(jax.random.PRNGKey(7), CFG, 10)
        zero = table._replace(dt_s=jnp.zeros_like(table.dt_s))
        tr = trace_from_table(zero, rate_per_s=100.0)
        np.testing.assert_allclose(np.diff(tr.t_s), 0.01)


class TestEpisodeResultShim:
    def test_tuple_unpacking_still_works(self):
        sel = schedulers.make_kube_selector(CFG)
        res = kenv.run_episode(jax.random.PRNGKey(0), CFG, sel, 10)
        assert isinstance(res, EpisodeResult)
        # the deprecation shim: legacy positional order is preserved
        state, placements, metric, dropped, stats = res
        assert state is res.state
        assert placements is res.placements
        assert metric is res.metric
        assert dropped is res.dropped
        assert stats is res.stats
        assert res._fields == ("state", "placements", "metric", "dropped",
                               "stats")


# ---------------------------------------------------------------------------
# self-healing: health watchdog, backpressure, backoff, degradation
# ---------------------------------------------------------------------------


class TickTimer:
    """Fake deadline stopwatch: every read advances by ``step`` seconds, so
    a scoring launch appears to take exactly ``step`` regardless of the
    (pinned) logical clock."""

    def __init__(self, step):
        self.t = 0.0
        self.step = step

    def __call__(self):
        self.t += self.step
        return self.t


class TestHealthWatchdog:
    def test_fail_node_evicts_and_requeues(self, state, qparams):
        d, sub, _ = make_daemon(state, qparams, batch_size=4, max_wait_s=1e9)
        pod = kenv.default_pod(CFG)
        for _ in range(4):
            d.submit(pod)
        d.poll()
        bound = [x for x in d.decisions if x.node != NO_PLACEMENT]
        assert bound, "setup: nothing bound"
        victim = bound[0].node
        n_on_victim = sum(1 for x in bound if x.node == victim)
        pods_before = int(sub.live.num_pods[victim])
        evicted = d.fail_node(victim)
        assert evicted == n_on_victim
        assert d.metrics.evictions == n_on_victim
        assert not sub.live.healthy[victim]
        # evicted pods released their live-buffer resources...
        assert int(sub.live.num_pods[victim]) == pods_before - n_on_victim
        # ...and re-entered the queue as fresh submissions
        assert d.pending == n_on_victim
        assert d.metrics.submitted == 4 + n_on_victim
        d.drain()
        # rebound decisions never land on the failed node
        for dec in d.decisions[len(bound):]:
            assert dec.node != victim
        m = d.metrics
        assert m.bound + m.dropped + m.shed == m.submitted
        assert len(d.decisions) == m.submitted

    def test_recover_node_rejoins_feasible_set(self, state, qparams):
        d, sub, _ = make_daemon(state, qparams, batch_size=1)
        pod = kenv.default_pod(CFG)
        for n in range(CFG.n_nodes):
            if n != 2:
                d.fail_node(n)
        d.submit(pod)
        d.flush()
        assert d.decisions[-1].node == 2      # only node left standing
        d.fail_node(2)
        d.recover_node(3)
        assert sub.live.healthy[3]
        d.drain()                              # the evictee rebinds onto 3
        rebound = d.decisions[-1]
        assert rebound.node == 3

    def test_fail_empty_node_is_noop_eviction(self, state, qparams):
        d, sub, _ = make_daemon(state, qparams)
        assert d.fail_node(3) == 0
        assert d.metrics.evictions == 0
        assert not sub.live.healthy[3]


class TestBackpressure:
    def test_full_queue_sheds_oldest(self, state, qparams):
        d, _, _ = make_daemon(state, qparams, batch_size=64, max_wait_s=1e9,
                              queue_cap=2)
        pod = kenv.default_pod(CFG)
        first = d.submit(pod)
        d.submit(pod)
        d.submit(pod)                          # cap hit: oldest shed
        assert d.metrics.shed == 1
        assert d.pending == 2
        shed = d.decisions[0]
        assert shed.req_id == first
        assert shed.shed and shed.node == NO_PLACEMENT
        d.drain()
        m = d.metrics
        assert m.bound + m.dropped + m.shed == m.submitted == 3
        assert len(d.decisions) == 3

    def test_unbounded_by_default(self, state, qparams):
        d, _, _ = make_daemon(state, qparams, batch_size=64, max_wait_s=1e9)
        pod = kenv.default_pod(CFG)
        for _ in range(100):
            d.submit(pod)
        assert d.metrics.shed == 0
        assert d.pending == 100


class TestConflictBackoff:
    def _conflicted(self, state, qparams, **cfg_kw):
        d, sub, clock = make_daemon(state, qparams, batch_size=1,
                                    max_wait_s=0.0, **cfg_kw)
        real = sub.feasible_one
        sub.feasible_one = lambda node, pod: False   # every bind loses
        d.submit(kenv.default_pod(CFG))
        assert d.poll() == 0                   # conflicted; re-queued
        sub.feasible_one = real
        return d, clock

    def test_poll_honors_backoff_hold(self, state, qparams):
        d, clock = self._conflicted(state, qparams, backoff_base_s=5.0)
        assert d.pending == 1
        clock.t = 4.9
        assert d.poll() == 0                   # still inside the hold
        clock.t = 5.0
        assert d.poll() == 1                   # hold expired: re-scored
        assert d.decisions[0].attempts == 2

    def test_flush_overrides_hold(self, state, qparams):
        d, clock = self._conflicted(state, qparams, backoff_base_s=1e9)
        assert d.flush() == 1                  # force: shutdown terminates
        assert d.metrics.bound == 1

    def test_backoff_doubles_per_attempt(self, state, qparams):
        d, sub, clock = make_daemon(state, qparams, batch_size=1,
                                    max_wait_s=0.0, max_retries=3,
                                    backoff_base_s=1.0)
        sub.feasible_one = lambda node, pod: False
        d.submit(kenv.default_pod(CFG))
        d.poll()                               # attempt 1 -> hold 1s
        assert d._pending[0].not_before == pytest.approx(1.0)
        clock.t = 1.0
        d.poll()                               # attempt 2 -> hold 2s
        assert d._pending[0].not_before == pytest.approx(3.0)
        clock.t = 3.0
        d.poll()                               # attempt 3 -> hold 4s
        assert d._pending[0].not_before == pytest.approx(7.0)


class TestRetryExhaustion:
    @pytest.mark.parametrize("policy", ["requeue", "next-best"])
    def test_exhausted_retries_drop_under_both_policies(
            self, state, qparams, policy):
        d, sub, _ = make_daemon(state, qparams, batch_size=1, max_wait_s=0.0,
                                max_retries=2, conflict_policy=policy)
        sub.feasible_one = lambda node, pod: False   # permanent bind race
        d.submit(kenv.default_pod(CFG))
        d.drain()
        assert d.metrics.dropped == 1
        assert d.metrics.conflicts == 3        # initial + 2 retries
        assert d.metrics.requeued == 2
        dec = d.decisions[0]
        assert dec.node == NO_PLACEMENT
        assert dec.attempts == 3
        m = d.metrics
        assert m.bound + m.dropped + m.shed == m.submitted == 1


class TestGracefulDegradation:
    def test_deadline_breach_degrades_to_heuristic(self, state, qparams):
        clock = FakeClock()
        sub = ClusterSubstrate(state, CFG)
        d = PlacementDaemon(
            sub, qparams,
            DaemonConfig(batch_size=2, max_wait_s=1e9, score_deadline_s=0.5,
                         degrade_batches=2),
            clock=clock, timer=TickTimer(1.0))   # every launch "takes" 1s
        pod = kenv.default_pod(CFG)
        for batch in range(4):
            d.submit(pod)
            d.submit(pod)
            d.flush()
        m = d.metrics
        assert m.batches == 4
        # batch 1 probes the net (breach), 2-3 skip it, 4 probes again
        assert m.device_launches == 2
        assert m.fallback_batches == 4
        assert m.bound + m.dropped + m.shed == m.submitted == 8

    def test_nan_scores_fall_back_same_batch(self, state, qparams):
        bad_fn = lambda params, feats: jnp.full((feats.shape[0],), jnp.nan)
        d, _, _ = make_daemon(state, qparams, score_fn=bad_fn, batch_size=2,
                              max_wait_s=1e9)
        pod = kenv.default_pod(CFG)
        d.submit(pod)
        d.submit(pod)
        assert d.flush() == 2
        assert d.metrics.fallback_batches == 1
        # NaN scores still place pods: the heuristic served the batch
        assert d.metrics.bound == 2

    def test_diverged_scores_fall_back(self, state, qparams):
        hot_fn = lambda params, feats: jnp.full((feats.shape[0],), 1e9)
        d, _, _ = make_daemon(state, qparams, score_fn=hot_fn, batch_size=1)
        d.submit(kenv.default_pod(CFG))
        assert d.flush() == 1
        assert d.metrics.fallback_batches == 1
        assert d.metrics.bound == 1

    def test_heuristic_only_never_launches(self, state, qparams):
        d, _, _ = make_daemon(state, qparams, heuristic_only=True,
                              batch_size=4, max_wait_s=1e9)
        pod = kenv.default_pod(CFG)
        for _ in range(9):
            d.submit(pod)
        d.drain()
        m = d.metrics
        assert m.device_launches == 0
        assert m.fallback_batches == m.batches == 3
        assert m.bound + m.dropped == 9

    def test_healthy_scores_never_degrade(self, state, qparams):
        d, _, _ = make_daemon(state, qparams, batch_size=2, max_wait_s=1e9,
                              score_deadline_s=1e9)
        pod = kenv.default_pod(CFG)
        d.submit(pod)
        d.submit(pod)
        d.flush()
        assert d.metrics.fallback_batches == 0
        assert d.metrics.device_launches == d.metrics.batches == 1


class TestLatencyReservoir:
    def test_memory_stays_bounded(self):
        from repro.sched.daemon import LatencyReservoir

        r = LatencyReservoir(capacity=8, seed=1)
        for i in range(1000):
            r.append(float(i))
        assert len(r) == 8
        assert r.seen == 1000
        assert np.asarray(r).shape == (8,)

    def test_percentiles_exact_below_capacity(self):
        from repro.sched.daemon import LatencyReservoir

        r = LatencyReservoir(capacity=256)
        vals = np.arange(100, dtype=np.float64)
        for v in vals:
            r.append(float(v))
        assert r.p50() == pytest.approx(np.percentile(vals, 50))
        assert r.p99() == pytest.approx(np.percentile(vals, 99))
        assert r.percentile(0.0) == 0.0

    def test_empty_reservoir_is_nan(self):
        from repro.sched.daemon import LatencyReservoir

        r = LatencyReservoir()
        assert np.isnan(r.p99())

    def test_sample_stays_representative(self):
        from repro.sched.daemon import LatencyReservoir

        r = LatencyReservoir(capacity=512, seed=7)
        for v in np.linspace(0.0, 1.0, 20_000):
            r.append(float(v))
        # uniform stream: the retained sample's median stays near 0.5
        assert abs(r.p50() - 0.5) < 0.1

    def test_daemon_metrics_use_reservoir(self, state, qparams):
        from repro.sched.daemon import LatencyReservoir

        d, _, _ = make_daemon(state, qparams)
        assert isinstance(d.metrics.bind_latencies_s, LatencyReservoir)


# ---------------------------------------------------------------------------
# batch-loop spans and counters
# ---------------------------------------------------------------------------

STAGES = ("sched.snapshot", "sched.pack", "sched.launch", "sched.readback",
          "sched.commit")
LAYOUTS = {"flat": None, "sharded": plan_fleet_layout(CFG.n_nodes, shards=2)}


def _sched_events(log_dir):
    """``(name, start_ns, end_ns, stats)`` of every ``sched.*`` host event in
    the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    out = []
    for plane in jax.profiler.ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                            dict(e.stats)) for e in line.events
                           if e.name.startswith("sched."))
    return out


def _walk_race(qparams, layout):
    """Four requests in one batch, all preferring node 0 < 1 < 2 < 3 by
    snapshot score.  Nodes 0-2 fit one pod each and node 3 none, so request
    k binds node k after re-validating k candidates past the first, and the
    last walks nodes 1 and 2, stops at node 3 (infeasible in the snapshot)
    and is re-queued, to be dropped on its next batch."""
    state = kenv.reset(jax.random.PRNGKey(2), CFG)
    sub = ClusterSubstrate(state, CFG, score_fn=lambda p, f: -f[:, 0],
                           layout=layout, topk=2)
    lv = sub.live
    lv.healthy[:] = True
    lv.image_cached[:] = True
    lv.base_cpu[:] = (100.0, 200.0, 300.0, 400.0)
    for col in (lv.pods_cpu, lv.startup_cpu, lv.cpu_requested,
                lv.mem_requested, lv.mem_used):
        col[:] = 0
    lv.max_pods[:] = lv.num_pods + np.array([1, 1, 1, 0])
    d = PlacementDaemon(sub, qparams,
                        DaemonConfig(batch_size=4, max_wait_s=1e9,
                                     conflict_policy="next-best"),
                        clock=FakeClock())
    pod = kenv.default_pod(CFG)
    for _ in range(4):
        d.submit(pod)
    return d


class TestBatchLoopSpans:
    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_stage_spans_nest_in_batch_on_the_profiler(self, state, qparams,
                                                       tmp_path, layout):
        sub = ClusterSubstrate(state, CFG, layout=LAYOUTS[layout], topk=2)
        d = PlacementDaemon(sub, qparams,
                            DaemonConfig(batch_size=4, max_wait_s=1e9),
                            clock=FakeClock())
        d.warmup()
        pod = kenv.default_pod(CFG)
        jax.profiler.start_trace(str(tmp_path))
        try:
            for fill in (4, 2):
                for _ in range(fill):
                    d.submit(pod)
                d.flush()
        finally:
            jax.profiler.stop_trace()
        events = _sched_events(str(tmp_path))
        batches = sorted((e for e in events if e[0] == "sched.batch"),
                         key=lambda e: e[1])
        # one span a stage a batch: never one a request
        assert [(b[3]["batch"], b[3]["n"]) for b in batches] == [(0, 4),
                                                                 (1, 2)]
        for name in STAGES:
            spans = [e for e in events if e[0] == name]
            assert len(spans) == 2, name
            for (_, s, e, _), b in zip(sorted(spans, key=lambda e: e[1]),
                                       batches):
                assert b[1] <= s and e <= b[2], name
        # the always-on counters saw the same spans
        assert d.metrics.stage_n == {n: 2 for n in ("sched.batch",) + STAGES}
        assert all(v > 0 for v in d.metrics.stage_s.values())
        assert d.metrics.stage_s["sched.batch"] >= sum(
            d.metrics.stage_s[n] for n in STAGES)

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_walk_and_commit_counters_exact(self, qparams, layout):
        d = _walk_race(qparams, LAYOUTS[layout])
        assert d.flush() == 3
        assert [dec.node for dec in d.decisions] == [0, 1, 2]
        m = d.metrics
        assert (m.conflicts, m.requeued) == (3, 1)
        assert m.walk_steps == 0 + 1 + 2 + 2
        assert (m.commit_calls, m.taken) == (4, 4)
        assert d.flush() == 1                 # nothing left: dropped
        assert d.decisions[-1].node == NO_PLACEMENT
        assert (m.walk_steps, m.commit_calls, m.taken) == (5, 5, 5)

    def test_queue_wait_exact_with_requeue(self, qparams):
        d = _two_node_race(qparams)           # both submitted at t = 0
        clock = d._clock
        clock.t = 1.0
        assert d.poll(clock.t) == 1           # the loser re-queued at 1.0
        assert (d.metrics.taken, d.metrics.queue_wait_s) == (2, 2.0)
        clock.t = 3.5
        assert d.flush() == 1
        assert (d.metrics.taken, d.metrics.queue_wait_s) == (3, 4.5)
        assert d.metrics.commit_calls == 3

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_upload_and_readback_bytes(self, state, qparams, layout):
        sub = ClusterSubstrate(state, CFG, layout=LAYOUTS[layout], topk=2)
        d = PlacementDaemon(sub, qparams,
                            DaemonConfig(batch_size=4, max_wait_s=1e9),
                            clock=FakeClock())
        pod = kenv.default_pod(CFG)
        for _ in range(6):
            d.submit(pod)
        d.drain()
        m = d.metrics
        assert m.batches == 2
        # the first batch publishes the whole (unpadded) state, the second
        # only the rows the first one's binds changed, in one C-row buffer
        assert (m.full_publishes, m.delta_publishes) == (1, 1)
        assert 1 <= m.publish_rows <= 4
        leaves = jax.tree.leaves(sub.live)
        whole = sum(x.nbytes for x in leaves)
        delta = min(DELTA_ROWS, CFG.n_nodes) * 4 * (
            1 + sum(x.ndim for x in leaves))
        snap, pods = sub.snapshot(), sub.pack([pod], 4)
        up = whole + delta + 2 * sum(x.nbytes for x in jax.tree.leaves(pods))
        out0, out1, _ = d._scorer(qparams, snap, pods, (), 1)
        back = np.asarray(out0).nbytes + np.asarray(out1).nbytes
        if layout == "flat":                  # (B, N) float32 scores + bools
            assert back == 4 * CFG.n_nodes * (4 + 1)
        else:                                 # (B, C) float32 + int32 lists
            assert back == 4 * 2 * 2 * (4 + 4)
        assert m.upload_bytes == up
        assert m.readback_bytes == 2 * back


# C and C + 1 changed rows both fit; odd, so the 2-shard layout pads a row
N_RESIDENT = DELTA_ROWS + 45


def _resident_sub(layout):
    cfg = dataclasses.replace(CFG, n_nodes=N_RESIDENT)
    lay = None if layout == "flat" else plan_fleet_layout(N_RESIDENT,
                                                          shards=2)
    sub = ClusterSubstrate(kenv.reset(jax.random.PRNGKey(3), cfg), cfg,
                           layout=lay, topk=2)
    return sub, cfg


def _bits(tree):
    """Every leaf as a host copy of its bit pattern."""
    out = []
    for x in jax.tree.leaves(tree):
        a = np.asarray(x)
        out.append(a.view(f"u{a.itemsize}").copy())
    return out


def _same_bits(a, b):
    return len(a) == len(b) and all(
        x.shape == y.shape and x.dtype == y.dtype and np.array_equal(x, y)
        for x, y in zip(a, b))


class TestResidentSnapshot:
    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_publish_equals_a_fresh_whole_publish(self, layout):
        sub, cfg = _resident_sub(layout)
        n, pod = cfg.n_nodes, kenv.default_pod(cfg)
        rng = np.random.default_rng(14)

        def rows(k):
            return rng.choice(n, size=k, replace=False)

        def binds():
            for node in rows(5):
                sub.bind(int(node), pod)

        def unbinds():
            for node in rows(3):
                sub.unbind(int(node), pod)

        def health():
            sub.set_health(int(rng.integers(n)), bool(rng.integers(2)))

        def direct():
            lv = sub.live
            r = rows(6)
            lv.cpu_requested[r[:2]] = rng.normal(size=2)
            lv.mem_used[r[2]] = np.nan
            lv.pods_cpu[r[3]] = -0.0
            lv.num_pods[r[4]] += 1
            lv.image_cached[r[5]] = ~lv.image_cached[r[5]]

        def replace():
            sub.live = jax.tree.map(np.array, sub.live)
            sub.live.startup_cpu[rows(2)] += 1.0

        def many():
            sub.live.uptime_hours[rows(DELTA_ROWS + 1)] += 1.0

        def clock():
            sub.live.time_s[()] += 0.5

        def nothing():
            pass

        ops = [binds, unbinds, health, direct, replace, many, clock, nothing]
        script = [nothing, binds, direct, nothing, many, binds, clock,
                  unbinds, replace, health, binds]
        script += [ops[i] for i in rng.integers(len(ops), size=40)]
        kinds, held = set(), None
        for op in script:
            op()
            snap = sub.snapshot()
            pub = sub.last_publish
            kinds.add("full" if pub.full else
                      "delta" if pub.rows else "unchanged")
            if op is nothing and held is not None:   # the mirror kept up
                assert pub == (False, 0, 0)
            whole = jax.tree.map(jnp.asarray, sub.live)
            if sub.layout is not None:
                whole = shard.shard_cluster(whole, sub.layout)
            assert _same_bits(_bits(snap), _bits(whole)), op.__name__
            # the previous snapshot is not changed by this publish
            if held is not None:
                assert _same_bits(_bits(held[0]), held[1]), op.__name__
            held = (snap, _bits(snap))
        assert kinds == {"full", "delta", "unchanged"}

    def test_bitwise_change_detection(self):
        sub, _ = _resident_sub("flat")
        lv = sub.live
        lv.mem_used[7] = 0.0
        lv.mem_used[8] = np.nan
        sub.snapshot()
        lv.mem_used[7] = -0.0                 # == 0.0, but other bits
        lv.mem_used[8] = np.nan               # same bits: no change
        sub.snapshot()
        assert sub.last_publish == (False, 1, sub.last_publish.nbytes)
        assert np.signbit(np.asarray(sub.snapshot().mem_used)[7])

    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    def test_warm_publishes_compile_nothing_and_count(self, qparams, layout):
        sub, cfg = _resident_sub(layout)
        d = PlacementDaemon(sub, qparams,
                            DaemonConfig(batch_size=2, max_wait_s=1e9),
                            clock=FakeClock())
        d.warmup()
        # no node fits this pod: every request is dropped, nothing binds,
        # and each publish sends exactly the rows written below
        big = kenv.default_pod(cfg)._replace(cpu_request=jnp.float32(1e9))
        events = []

        def on_event(event, duration, **kw):
            if "backend_compile" in event:
                events.append(event)

        jax.monitoring.register_event_duration_secs_listener(on_event)
        try:
            for k in (0, 1, DELTA_ROWS, DELTA_ROWS + 1):
                sub.live.uptime_hours[:k] += 1.0
                d.submit(big)
                d.flush()
            compiled = len(events)
            jax.jit(lambda x: x * 3)(np.zeros(7, np.float32))
        finally:
            jax.monitoring.unregister_event_duration_listener(on_event)
        assert compiled == 0 and len(events) == 1   # the listener listens
        assert d.scorer_cache_size() == 1
        assert [dec.node for dec in d.decisions] == [NO_PLACEMENT] * 4
        m = d.metrics
        assert (m.delta_publishes, m.full_publishes) == (3, 1)
        assert m.publish_rows == 0 + 1 + DELTA_ROWS
        leaves = jax.tree.leaves(sub.live)
        whole = sum(x.nbytes for x in leaves)
        buf = DELTA_ROWS * 4 * (1 + sum(x.ndim for x in leaves))
        pods = sum(x.nbytes for x in jax.tree.leaves(sub.pack([big], 2)))
        assert m.upload_bytes == 4 * pods + 2 * buf + whole


def _scorer_variants():
    """(name, substrate factory) for every ``make_scorer`` branch."""
    fleet_layout = plan_fleet_layout(8, shards=2)
    out = []
    for policy in [None] + sorted(policy_mod.names()):
        for layout in sorted(LAYOUTS):
            out.append((f"cluster-{layout}-{policy}",
                        lambda p=policy, lay=LAYOUTS[layout]: ClusterSubstrate(
                            kenv.reset(jax.random.PRNGKey(1), CFG), CFG,
                            policy=p and policy_mod.get(p), layout=lay,
                            topk=2)))
            out.append((f"fleet-{layout}-{policy}",
                        lambda p=policy, lay=layout: FleetSubstrate(
                            placement.fresh_fleet(8),
                            policy=p and policy_mod.get(p),
                            layout=fleet_layout if lay == "sharded" else None,
                            topk=2)))
    return out


@pytest.mark.parametrize("make_sub", [f for _, f in _scorer_variants()],
                         ids=[n for n, _ in _scorer_variants()])
def test_every_scorer_is_the_jit_score_module(make_sub):
    """The device trace names the scorer's program ``jit_score(...)``; the
    benchmark's roofline reader finds the scorer by that name, so every
    ``make_scorer`` branch must keep it."""
    sub = make_sub()
    spec = sub.policy
    params = (spec.init(jax.random.PRNGKey(0)) if spec is not None
              else dqn.init_qnet(jax.random.PRNGKey(0)))
    d = PlacementDaemon(sub, params, DaemonConfig(batch_size=2))
    text = d._scorer.lower(*d._warm_args()).as_text()
    assert text.startswith("module @jit_score ")


class TestServeCheckpointLoading:
    def test_load_qnet_roundtrips_through_ckpt(self, tmp_path, qparams):
        from repro.checkpoint import ckpt
        from repro.launch import serve

        ckpt.save(str(tmp_path), 7, qparams)
        loaded = serve.load_qnet(str(tmp_path), jax.random.PRNGKey(9))
        for name in qparams:
            np.testing.assert_array_equal(np.asarray(loaded[name]),
                                          np.asarray(qparams[name]))

    def test_load_qnet_npz_legacy(self, tmp_path, qparams):
        from repro.launch import serve

        path = tmp_path / "q.npz"
        np.savez(path, **{k: np.asarray(v) for k, v in qparams.items()})
        loaded = serve.load_qnet(str(path), jax.random.PRNGKey(9))
        np.testing.assert_array_equal(
            np.asarray(loaded["w1"]), np.asarray(qparams["w1"]))

    def test_load_qnet_empty_is_fresh_init(self):
        from repro.launch import serve

        a = serve.load_qnet("", jax.random.PRNGKey(3))
        b = dqn.init_qnet(jax.random.PRNGKey(3))
        np.testing.assert_array_equal(np.asarray(a["w1"]),
                                      np.asarray(b["w1"]))
