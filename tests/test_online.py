"""Online-learning loop suite: recorder ring parity, double-buffered param
swaps, and the serving-path invariants with a refresher in the loop.

Pins the contracts of ``repro.sched.online`` (+ its satellites from the same
change): the daemon-recorded transition stream is bit-identical to the
offline ``train_rl.realized_transition`` fold; a mid-batch ``set_params``
publish never mixes into an in-flight batch (one params read per batch cut);
attaching a recorder is invisible to the decision stream; the
bound+dropped+shed == submitted ledger holds with refresh cycles interleaved
at arbitrary points; ``replay_add(n_valid=...)`` masked adds match sequential
one-row adds bit-for-bit; the TOPSIS scorer's closeness/selector contracts;
``make_reward_fn``'s energy_weight validation; and the split
bind-vs-shed latency metrics.
"""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import strategies as strat
from repro.core import dqn, env as kenv, policy as policy_mod, rewards, train_rl
from repro.core.replay import replay_add, replay_init
from repro.core.types import FEATURE_DIM, NO_PLACEMENT, PodSpec, paper_cluster
from repro.sched import api, topsis
from repro.sched.daemon import (
    ClusterSubstrate,
    DaemonConfig,
    LatencyReservoir,
    PlacementDaemon,
)
from repro.sched.online import OnlineRefresher, TransitionRecorder
from repro.sched.placement import JobSpec, fresh_fleet

CFG = paper_cluster()


@pytest.fixture(scope="module")
def qparams():
    return dqn.init_qnet(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def state():
    return kenv.reset(jax.random.PRNGKey(1), CFG)


def _pods(n, seed=7):
    table = kenv.sample_pod_table(jax.random.PRNGKey(seed), CFG, n)
    return [jax.tree.map(lambda x: x[i], table.specs) for i in range(n)]


OVERSIZED = PodSpec(cpu_request=1e9, cpu_demand=1e9,
                    mem_request=1e9, mem_demand=1e9)


# ---------------------------------------------------------------------------
# tentpole: recorder ring parity with the offline transition arithmetic
# ---------------------------------------------------------------------------


def test_recorder_ring_parity_bit_for_bit(state, qparams):
    """The ring a served daemon's recorder produces == the ring the offline
    transition body produces from the same (pod, action) stream, bitwise —
    including a weight-0 row for the dropped (infeasible) arrival and a
    partial final drain chunk."""
    rfn = rewards.make_reward_fn("sdqn_n", efficiency_weight=50.0)
    stream = []
    rec = TransitionRecorder(state, CFG, capacity=64, reward_fn=rfn, chunk=8)

    def hook(pod, action):
        stream.append((pod, action))
        rec.record(pod, action)

    sub = ClusterSubstrate(state, CFG)
    d = PlacementDaemon(sub, qparams,
                        DaemonConfig(batch_size=4, max_wait_s=0.0),
                        decision_hook=hook)
    pods = _pods(20)
    pods.insert(5, OVERSIZED)            # guaranteed drop -> weight-0 row
    for pod in pods:
        d.submit(pod)
    d.drain()
    assert len(stream) == rec.pending == 21   # 21 = partial 8-chunk tail
    assert any(a == NO_PLACEMENT for _, a in stream)
    rec.drain()

    @jax.jit
    def fold(shadow, buf, pod, a):
        shadow, stored, r = train_rl.realized_transition(shadow, pod, a,
                                                         CFG, rfn)
        w = (a >= 0).astype(jnp.float32)
        return shadow, replay_add(buf, stored[None], r[None], w[None])

    shadow = jax.tree.map(jnp.asarray, state)
    buf = replay_init(64, n_features=FEATURE_DIM, lane=1)
    for pod, a in stream:
        shadow, buf = fold(shadow, buf, pod, jnp.asarray(a, jnp.int32))

    assert int(rec.buffer.size) == int(buf.size) == 21
    assert int(rec.buffer.ptr) == int(buf.ptr)
    np.testing.assert_array_equal(np.asarray(rec.buffer.data),
                                  np.asarray(buf.data))
    # the shadow tracked the same trajectory the offline fold walked
    for a, b in zip(jax.tree.leaves(rec._shadow), jax.tree.leaves(shadow)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_recorder_warmup_is_a_bitwise_noop(state):
    rec = TransitionRecorder(state, CFG, capacity=32, chunk=8)
    rec.record(kenv.default_pod(CFG), 1)
    rec.drain()
    before = jax.tree.map(np.asarray, (rec._shadow, rec.buffer))
    rec.warmup()
    after = jax.tree.map(np.asarray, (rec._shadow, rec.buffer))
    for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_array_equal(a, b)


def test_recorder_bounded_drain(state):
    rec = TransitionRecorder(state, CFG, capacity=64, chunk=4)
    pod = kenv.default_pod(CFG)
    for _ in range(11):
        rec.record(pod, 0)
    assert rec.drain(max_chunks=2) == 8       # two chunks of 4
    assert rec.pending == 3
    assert rec.drain() == 3                   # the tail on the next cycle
    assert rec.drained == 11


def test_resync_rebases_shadow_on_live(state, qparams):
    sub = ClusterSubstrate(state, CFG)
    rec = TransitionRecorder(state, CFG)
    d = PlacementDaemon(sub, qparams,
                        DaemonConfig(batch_size=2, max_wait_s=0.0),
                        decision_hook=rec.record)
    for pod in _pods(4):
        d.submit(pod)
    d.drain()
    sub.live.healthy[2] = False               # churn the stream never carried
    rec.resync(sub.live)
    assert rec.pending == 0                   # resync drains first
    for a, b in zip(jax.tree.leaves(rec._shadow),
                    jax.tree.leaves(jax.tree.map(jnp.asarray, sub.live))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# tentpole: double-buffered params, atomic per-batch swap
# ---------------------------------------------------------------------------


def test_param_swap_is_atomic_at_batch_cuts(state, qparams):
    """A publish from inside a batch (decision hook fires between a batch's
    decisions) must not mix into that batch: params are read ONCE per batch
    cut, so batch 1 scores entirely under the old pytree and the swap takes
    effect exactly at the next cut."""
    p2 = dqn.init_qnet(jax.random.PRNGKey(9))
    sub = ClusterSubstrate(state, CFG)
    d = PlacementDaemon(sub, qparams,
                        DaemonConfig(batch_size=4, max_wait_s=0.0),
                        decision_hook=lambda pod, node: d.set_params(p2))
    real, seen = d._scorer, []

    def spy(params, snap, pods, carry, n):
        seen.append(params)
        return real(params, snap, pods, carry, n)

    d._scorer = spy
    pod = kenv.default_pod(CFG)
    for _ in range(4):
        d.submit(pod)
    d.flush()        # hook publishes p2 four times DURING this batch
    for _ in range(4):
        d.submit(pod)
    d.flush()
    assert len(seen) == 2, "one params read per batch"
    assert seen[0] is qparams, "mid-batch publish leaked into its own batch"
    assert seen[1] is p2, "publish missed the next batch cut"


def test_refresher_publishes_back_buffer(state, qparams):
    sub = ClusterSubstrate(state, CFG)
    rec = TransitionRecorder(state, CFG)
    d = PlacementDaemon(sub, qparams,
                        DaemonConfig(batch_size=2, max_wait_s=0.0),
                        decision_hook=rec.record)
    ref = OnlineRefresher(d, rec, batch_size=8, seed=3)
    assert ref.step() is None                 # empty ring: nothing to learn
    assert (ref.steps, ref.swaps) == (0, 0)
    for pod in _pods(4):
        d.submit(pod)
    d.drain()
    loss = ref.step()
    assert loss is not None and np.isfinite(loss)
    assert (ref.steps, ref.swaps) == (1, 1)
    assert d._params is ref.params            # the atomic reference flip
    assert d._params is not qparams


def test_refresher_warmup_publishes_nothing(state, qparams):
    sub = ClusterSubstrate(state, CFG)
    rec = TransitionRecorder(state, CFG)
    d = PlacementDaemon(sub, qparams,
                        DaemonConfig(batch_size=2, max_wait_s=0.0),
                        decision_hook=rec.record)
    ref = OnlineRefresher(d, rec)
    back, key = ref._back, ref._key
    ref.warmup()
    assert d._params is qparams               # nothing published
    assert ref._back is back                  # back buffer untouched
    np.testing.assert_array_equal(np.asarray(ref._key), np.asarray(key))
    assert ref.steps == 0


def test_refresher_disabled_is_bit_identical(state, qparams):
    """A daemon with the full online plumbing attached but the refresher
    never stepped serves the EXACT decision stream of a bare daemon."""

    def run(online):
        sub = ClusterSubstrate(state, CFG)
        rec = TransitionRecorder(state, CFG) if online else None
        d = PlacementDaemon(sub, qparams,
                            DaemonConfig(batch_size=4, max_wait_s=0.0),
                            decision_hook=rec.record if online else None)
        if online:
            OnlineRefresher(d, rec).warmup()  # construct + warm, never step
        for pod in _pods(16, seed=11):
            d.submit(pod)
        d.drain()
        return ([(dec.req_id, dec.node) for dec in d.decisions], sub.live)

    bare_dec, bare_live = run(False)
    online_dec, online_live = run(True)
    assert bare_dec == online_dec
    for a, b in zip(jax.tree.leaves(bare_live), jax.tree.leaves(online_live)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# ledger conservation with refresh cycles interleaved (fixed + hypothesis)
# ---------------------------------------------------------------------------


def _check_online_ledger_conservation(seed, ops):
    """bound + dropped + shed == submitted through arbitrary interleavings
    of submits/advances/polls/flushes with a refresh cycle (drain + train +
    publish) injected between ops — and shed requests, which are never
    scored, never reach the recorder."""
    state = kenv.reset(jax.random.PRNGKey(seed), CFG)
    sub = ClusterSubstrate(state, CFG)
    rec = TransitionRecorder(state, CFG, capacity=64, chunk=4)
    t = [0.0]
    d = PlacementDaemon(
        sub, dqn.init_qnet(jax.random.PRNGKey(0)),
        DaemonConfig(batch_size=3, max_wait_s=0.05, max_retries=2,
                     queue_cap=5),
        clock=lambda: t[0], decision_hook=rec.record)
    ref = OnlineRefresher(d, rec, batch_size=8, drain_chunks_per_step=1)
    cap = float(np.min(np.asarray(sub.live.cpu_capacity)))
    mem_cap = float(np.min(np.asarray(sub.live.mem_capacity)))
    for i, (op, arg) in enumerate(ops):
        if op == "submit":
            d.submit(PodSpec(cpu_request=arg * cap,
                             cpu_demand=0.5 * arg * cap,
                             mem_request=arg * mem_cap,
                             mem_demand=0.2 * arg * mem_cap))
        elif op == "advance":
            t[0] += arg
            d.poll()
        elif op == "poll":
            d.poll()
        elif op == "flush":
            d.flush()
        if i % 2 == 1:
            ref.step()                        # refresh mid-stream
    d.drain()
    ref.step()
    m = d.metrics
    assert m.bound + m.dropped + m.shed == m.submitted
    assert len(d.decisions) == m.submitted
    assert rec.recorded == m.bound + m.dropped, \
        "shed requests must never produce transitions"
    rec.drain()
    assert rec.drained == rec.recorded
    assert int(rec.buffer.size) == min(rec.recorded, 64)


def test_online_ledger_conservation_fixed_cases():
    _check_online_ledger_conservation(
        0, [("submit", 0.2), ("submit", 1.4), ("flush", 0.0),
            ("submit", 0.3), ("advance", 0.06), ("flush", 0.0)])
    # backpressure: shed requests while refresh cycles run between ops
    _check_online_ledger_conservation(
        3, [("submit", 0.2)] * 9 + [("flush", 0.0), ("submit", 0.4),
                                    ("flush", 0.0)])
    _check_online_ledger_conservation(
        7, [("submit", 0.25), ("advance", 0.06)] * 6)


if strat.HAVE_HYPOTHESIS:
    from hypothesis import given

    @given(seed=strat.seeds(), ops=strat.daemon_ops())
    def test_property_online_ledger_conservation(seed, ops):
        _check_online_ledger_conservation(seed, ops)
else:  # pragma: no cover - the [test] extra is installed in CI
    def test_property_online_ledger_conservation():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# satellites: replay masked adds, opt-state warm start
# ---------------------------------------------------------------------------


def test_replay_masked_add_matches_sequential_adds():
    """replay_add(n_valid=k) over a padded chunk == k sequential one-row
    adds, bit-for-bit, including across the ring wrap."""
    rng = np.random.default_rng(0)
    a = replay_init(8, n_features=3, lane=1)
    b = replay_init(8, n_features=3, lane=1)
    for n_valid in (3, 0, 4, 2, 4):           # 13 rows through a cap-8 ring
        feats = jnp.asarray(rng.normal(size=(4, 3)), jnp.float32)
        targets = jnp.asarray(rng.normal(size=(4,)), jnp.float32)
        weights = jnp.asarray(rng.random(size=(4,)), jnp.float32)
        a = replay_add(a, feats, targets, weights, n_valid=n_valid)
        for i in range(n_valid):
            b = replay_add(b, feats[i:i + 1], targets[i:i + 1],
                           weights[i:i + 1])
        assert int(a.ptr) == int(b.ptr) and int(a.size) == int(b.size)
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))


def test_replay_masked_add_rejects_bad_shapes():
    with pytest.raises(ValueError, match="lane-1"):
        replay_add(replay_init(8, n_features=3, lane=4),
                   jnp.zeros((4, 3)), jnp.zeros((4,)), n_valid=2)
    with pytest.raises(ValueError, match="exceeds capacity"):
        replay_add(replay_init(4, n_features=3, lane=1),
                   jnp.zeros((8, 3)), jnp.zeros((8,)), n_valid=2)


def test_make_opt_state_warm_starts_existing_params(qparams):
    opt = policy_mod.make_opt_state(qparams)
    spec = policy_mod.get("mlp")
    step = policy_mod.make_train_step(spec)
    feats = jnp.ones((4, FEATURE_DIM), jnp.float32)
    p2, opt2, loss, _ = step(qparams, opt, feats, jnp.ones((4,)),
                             jnp.ones((4,)))
    assert np.isfinite(float(loss))
    # fresh moments for the SAME pytree: structure matches, params moved
    assert jax.tree.structure(p2) == jax.tree.structure(qparams)
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(p2), jax.tree.leaves(qparams)))


# ---------------------------------------------------------------------------
# satellites: TOPSIS scorer
# ---------------------------------------------------------------------------


class TestTopsis:
    def test_closeness_range_and_ranking(self):
        # row 0 strictly dominates (lower on every cost column) -> top score
        crit = jnp.asarray([[0.1, 0.1, 0.0, 0.1],
                            [0.5, 0.4, 1.0, 0.3],
                            [0.9, 0.8, 1.0, 0.6]])
        c = topsis.closeness(crit)
        assert c.shape == (3,)
        assert np.all(np.asarray(c) >= 0.0) and np.all(np.asarray(c) <= 1.0)
        assert int(np.argmax(np.asarray(c))) == 0
        assert float(c[1]) > float(c[2])

    def test_closeness_degenerate_uniform(self):
        # all candidates identical: no preference, and NO NaNs
        c = topsis.closeness(jnp.ones((5, 4)))
        assert np.all(np.isfinite(np.asarray(c)))
        np.testing.assert_allclose(np.asarray(c), np.asarray(c)[0])

    def test_cluster_scores_and_selector(self, state):
        pod = kenv.default_pod(CFG)
        q = topsis.topsis_scores(state, pod, cfg=CFG)
        assert q.shape == (CFG.n_nodes,)
        assert np.all(np.isfinite(np.asarray(q)))
        sel = topsis.make_topsis_selector(CFG)
        node = int(sel(jax.random.PRNGKey(0), state, pod))
        assert 0 <= node < CFG.n_nodes
        assert bool(kenv.feasible(state, pod, CFG)[node])
        # infeasible everywhere -> NO_PLACEMENT, like every selector
        assert int(sel(jax.random.PRNGKey(0), state, OVERSIZED)) == \
            NO_PLACEMENT

    def test_fleet_dispatch_and_api_parity(self, state):
        fleet = fresh_fleet(6, jax.random.PRNGKey(2))
        job = JobSpec(cpu_pct_demand=10.0)
        qf = topsis.topsis_scores(fleet, job)
        assert qf.shape == (6,) and np.all(np.isfinite(np.asarray(qf)))
        np.testing.assert_array_equal(
            np.asarray(api.topsis_score(fleet, job)), np.asarray(qf))
        pod = kenv.default_pod(CFG)
        np.testing.assert_array_equal(
            np.asarray(api.topsis_score(state, pod, cfg=CFG)),
            np.asarray(topsis.topsis_scores(state, pod, cfg=CFG)))

    def test_cluster_requires_cfg(self, state):
        with pytest.raises(ValueError, match="cfg"):
            topsis.topsis_scores(state, kenv.default_pod(CFG))

    def test_energy_weight_prefers_warm_nodes(self, state):
        """Scaling the wake-cost column steers placement away from idle
        nodes — the knob the Pareto sweep turns."""
        live = jax.tree.map(np.array, state)
        live.exp_pods[:] = 0
        live.exp_pods[1] = 3                  # one warm node
        st = jax.tree.map(jnp.asarray, live)
        pod = kenv.default_pod(CFG)
        green = topsis.topsis_scores(st, pod, cfg=CFG,
                                     weights=(0.05, 0.05, 0.9, 0.0))
        assert int(np.argmax(np.asarray(green))) == 1


# ---------------------------------------------------------------------------
# satellites: energy_weight validation, latency split, empty reservoir
# ---------------------------------------------------------------------------


class TestRewardValidation:
    def test_rejects_bool(self):
        with pytest.raises(TypeError, match="plain Python number"):
            rewards.make_reward_fn("sdqn", energy_weight=True)

    def test_rejects_arrays(self):
        with pytest.raises(TypeError, match="plain Python number"):
            rewards.make_reward_fn("sdqn", energy_weight=jnp.float32(1.0))
        with pytest.raises(TypeError, match="plain Python number"):
            rewards.make_reward_fn("sdqn", energy_weight=np.asarray(1.0))
        # np.float64 IS a Python float subclass: accepted by design
        assert callable(rewards.make_reward_fn("sdqn",
                                               energy_weight=np.float64(1.0)))

    def test_rejects_negative(self):
        with pytest.raises(ValueError, match=">= 0"):
            rewards.make_reward_fn("sdqn", energy_weight=-0.5)

    def test_zero_is_exactly_off(self, state):
        base = rewards.make_reward_fn("sdqn")
        z = rewards.make_reward_fn("sdqn", energy_weight=0.0)
        assert z is base or z.__code__ is base.__code__
        assert rewards.make_reward_fn("sdqn", energy_weight=0) is not None
        assert callable(rewards.make_reward_fn("sdqn", energy_weight=1.5))


class TestLatencySplit:
    def test_bind_and_shed_streams_are_separate(self, state, qparams):
        t = [0.0]
        sub = ClusterSubstrate(state, CFG)
        d = PlacementDaemon(sub, qparams,
                            DaemonConfig(batch_size=8, max_wait_s=10.0,
                                         queue_cap=2),
                            clock=lambda: t[0])
        pod = kenv.default_pod(CFG)
        d.submit(pod)
        t[0] = 0.5
        d.submit(pod)
        d.submit(pod)                         # cap hit: oldest shed at 0.5s
        d.drain()
        m = d.metrics
        assert m.shed == 1 and m.bound == 2
        assert len(m.shed_wait_s) == 1 and len(m.bind_latencies_s) == 2
        assert m.shed_wait_s.percentile(50) == pytest.approx(0.5)

    def test_empty_reservoir_percentile_is_nan(self):
        r = LatencyReservoir()
        assert np.isnan(r.percentile(99.0))
        assert np.isnan(r.p50()) and np.isnan(r.p99())
        r.append(1.0)
        assert r.p99() == pytest.approx(1.0)
