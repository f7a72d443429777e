"""Seed-parallel training-engine tests: equivalence with the sequential seed
loop, joint seed×env layout planning + mesh-constraint parity, fused in-loop
afterstate scoring, NaN-guarded candidate selection, and replay-sampling
regressions."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dqn, env as kenv, policy as policy_mod, rewards, \
    schedulers, train_rl
from repro.core.replay import replay_add, replay_init, replay_sample
from repro.core.types import fleet_cluster, paper_cluster, training_cluster
from repro.eval import engine as eval_engine
from repro.launch import mesh as meshmod
from repro.train import engine

TCFG = training_cluster()
# tiny but complete: bootstrap on, replay wraps (cap 64 < 2*3*5 stores... it
# does not wrap here, wraparound is covered by TestReplaySampling directly)
RL = train_rl.RLConfig(variant="sdqn", episodes=3, pods_per_episode=5,
                       n_envs=2, batch_size=16, buffer_capacity=64)


def _train_sequential(key, n_seeds, rl=RL, cfg=TCFG):
    train_fn = jax.jit(lambda k: train_rl.train(k, cfg, rl))
    return [train_fn(jax.random.fold_in(key, s)) for s in range(n_seeds)]


class TestSeedParallel:
    def test_matches_sequential_per_seed(self):
        """One vmapped launch == the per-seed sequential loop, seed by seed.

        Same ``fold_in(key, s)`` ladder, same PRNG streams; values agree to
        float-reassociation tolerance (vmap batches the learner's matmul and
        reduction accumulations, which drifts ~1e-9/step — there is no
        semantic divergence, pinned here at 1e-6).
        """
        key = jax.random.PRNGKey(0)
        seqs = _train_sequential(key, 3)
        stacked, metrics = engine.train_seeds(key, TCFG, RL, 3)
        for s in range(3):
            for name, leaf in seqs[s][0].items():
                np.testing.assert_allclose(np.asarray(stacked[name][s]),
                                           np.asarray(leaf),
                                           atol=1e-6, rtol=1e-5, err_msg=name)
            for m in ("loss", "reward", "avg_cpu"):
                np.testing.assert_allclose(np.asarray(metrics[m][s]),
                                           np.asarray(seqs[s][1][m]),
                                           atol=1e-6, rtol=1e-5, err_msg=m)

    @pytest.mark.parametrize("policy", sorted(policy_mod.names()))
    def test_matches_sequential_per_seed_all_policy_classes(self, policy):
        """Every registered policy class trains through the UNCHANGED
        seed-parallel engine: one vmapped launch == the per-seed sequential
        loop, whatever the params pytree looks like (nested for mamba)."""
        rl = dataclasses.replace(RL, policy=policy, episodes=2)
        key = jax.random.PRNGKey(5)
        seqs = _train_sequential(key, 2, rl=rl)
        stacked, metrics = engine.train_seeds(key, TCFG, rl, 2)
        stacked_leaves, treedef = jax.tree.flatten(stacked)
        for s in range(2):
            seq_leaves, seq_def = jax.tree.flatten(seqs[s][0])
            assert seq_def == treedef
            for got, want in zip(stacked_leaves, seq_leaves):
                np.testing.assert_allclose(np.asarray(got[s]),
                                           np.asarray(want),
                                           atol=1e-6, rtol=1e-5)
            for m in ("loss", "reward", "avg_cpu"):
                np.testing.assert_allclose(np.asarray(metrics[m][s]),
                                           np.asarray(seqs[s][1][m]),
                                           atol=1e-6, rtol=1e-5, err_msg=m)

    def test_seed_keys_match_fold_in_ladder(self):
        keys = engine.seed_fold_keys(jax.random.PRNGKey(3), 4)
        for s in range(4):
            np.testing.assert_array_equal(
                np.asarray(keys[s]),
                np.asarray(jax.random.fold_in(jax.random.PRNGKey(3), s)))

    def test_host_mesh_parity(self):
        """The seed-axis sharding constraint must not change results (here on
        the 1-device host mesh — the CPU fallback the tests always take)."""
        key = jax.random.PRNGKey(1)
        plain, _ = engine.train_seeds(key, TCFG, RL, 2)
        sharded, _ = engine.train_seeds(key, TCFG, RL, 2,
                                        mesh=meshmod.make_host_mesh())
        for name in plain:
            np.testing.assert_allclose(np.asarray(sharded[name]),
                                       np.asarray(plain[name]),
                                       atol=1e-6, rtol=1e-5, err_msg=name)

    def test_train_env_mesh_parity(self):
        """``train(mesh=...)``'s n_envs ``data`` constraint is numerics-
        neutral; an indivisible batch falls back to the identity program."""
        key = jax.random.PRNGKey(2)
        ref, _ = jax.jit(lambda k: train_rl.train(k, TCFG, RL))(key)
        mesh = meshmod.make_train_mesh()
        got, _ = train_rl.train(key, TCFG, RL, mesh=mesh)
        for name in ref:
            np.testing.assert_allclose(np.asarray(got[name]),
                                       np.asarray(ref[name]),
                                       atol=1e-6, rtol=1e-5, err_msg=name)

    def test_train_and_select_matches_sequential_selection(self):
        """The engine must pick the same candidate the old Python loop did
        and return that candidate's params."""
        key = jax.random.PRNGKey(4)
        n_seeds, val_trials, val_pods = 2, 2, 8
        # the pre-engine path: sequential train + per-seed batched validation
        evaluator = eval_engine.make_param_evaluator(
            TCFG, lambda p: schedulers.make_sdqn_selector(p, TCFG), val_pods)
        val_keys = eval_engine.fixed_trial_keys(5000, val_trials)
        best_params, best_metric = None, jnp.inf
        for params, _ in _train_sequential(key, n_seeds):
            metric = jnp.mean(evaluator(params, val_keys).metric)
            if metric < best_metric:
                best_params, best_metric = params, metric
        got_params, got_metric = train_rl.train_and_select(
            key, TCFG, TCFG, RL, n_seeds=n_seeds, val_trials=val_trials,
            val_pods=val_pods)
        assert got_params is not None
        np.testing.assert_allclose(got_metric, float(best_metric), rtol=1e-4)
        for name in best_params:
            np.testing.assert_allclose(np.asarray(got_params[name]),
                                       np.asarray(best_params[name]),
                                       atol=1e-6, rtol=1e-5, err_msg=name)


class TestSelectBest:
    def _stack(self):
        return {"w": jnp.arange(3.0).reshape(3, 1)}

    def test_picks_min(self):
        p, v, diverged = engine.select_best(self._stack(),
                                            jnp.array([3.0, 1.0, 2.0]))
        assert float(v) == 1.0 and float(p["w"][0]) == 1.0
        assert not bool(diverged)

    def test_nan_never_wins(self):
        """NaN validation metrics must not beat finite ones (every NaN
        comparison is False, so the old running-min returned (None, inf))."""
        p, v, diverged = engine.select_best(self._stack(),
                                            jnp.array([jnp.nan, 2.0, jnp.nan]))
        assert float(v) == 2.0 and float(p["w"][0]) == 1.0
        assert not bool(diverged)  # one finite seed is a real selection

    def test_all_nan_falls_back_to_seed0_and_warns(self):
        """All-NaN still returns real params (seed 0), but the ``diverged``
        flag must distinguish that fallback from seed 0 *winning* — the
        metric alone cannot (callers see inf either way)."""
        p, v, diverged = engine.select_best(self._stack(),
                                            jnp.full((3,), jnp.nan))
        assert np.isinf(float(v)) and float(p["w"][0]) == 0.0
        assert bool(diverged)

    def test_train_and_select_warns_on_divergence(self, monkeypatch):
        """The engine surfaces the all-NaN case as a RuntimeWarning instead
        of silently handing back seed 0."""
        import pytest

        def fake_train_seeds(key, cfg, rl, n_seeds, mesh=None):
            return {"w": jnp.zeros((n_seeds, 1))}, {}

        class FakeEval:
            def __call__(self, stacked, keys):
                class R:
                    metric = jnp.full((2, 3), jnp.nan)
                return R()

        monkeypatch.setattr(engine, "train_seeds", fake_train_seeds)
        monkeypatch.setattr(engine.eval_engine, "make_multi_param_evaluator",
                            lambda *a, **k: FakeEval())
        with pytest.warns(RuntimeWarning, match="NaN"):
            params, metric = engine.train_and_select(
                jax.random.PRNGKey(0), TCFG, TCFG, RL, n_seeds=2,
                val_trials=3)
        assert np.isinf(metric) and params is not None


class TestLayoutPlanner:
    """``plan_seed_env_layout``: the joint seed×env device split."""

    def test_split_prefers_seed_axis(self):
        assert meshmod._split_seed_env(4, 16, 4) == (4, 1)
        assert meshmod._split_seed_env(8, 16, 4) == (4, 1)

    def test_split_joint_when_seeds_short(self):
        assert meshmod._split_seed_env(2, 16, 4) == (2, 2)
        assert meshmod._split_seed_env(2, 16, 8) == (2, 4)
        assert meshmod._split_seed_env(6, 10, 4) == (2, 2)
        assert meshmod._split_seed_env(9, 8, 6) == (3, 2)

    def test_split_env_only(self):
        assert meshmod._split_seed_env(3, 16, 4) == (1, 4)
        assert meshmod._split_seed_env(1, 8, 2) == (1, 2)

    def test_split_indivisible(self):
        assert meshmod._split_seed_env(3, 5, 4) is None
        assert meshmod._split_seed_env(2, 2, 8) is None  # batch < devices
        assert meshmod._split_seed_env(2, 16, 0) is None

    def test_split_always_exists_when_product_divides(self):
        """Number theory pin: the greedy prime split never misses a valid
        factorization when n_seeds * n_envs % n_dev == 0."""
        for n_seeds in range(1, 13):
            for n_envs in range(1, 17):
                for n_dev in range(1, 17):
                    got = meshmod._split_seed_env(n_seeds, n_envs, n_dev)
                    if (n_seeds * n_envs) % n_dev == 0:
                        s, e = got
                        assert s * e == n_dev
                        assert n_seeds % s == 0 and n_envs % e == 0
                    else:
                        assert got is None

    def test_single_device_and_no_mesh_plan_none(self):
        assert meshmod.plan_seed_env_layout(4, 16, None) is None
        assert meshmod.plan_seed_env_layout(
            4, 16, meshmod.make_host_mesh()) is None

    def test_layout_is_hashable_jit_static(self):
        lay = meshmod.SeedEnvLayout(meshmod.make_host_mesh(), 1, 1)
        assert hash(lay) == hash(
            meshmod.SeedEnvLayout(meshmod.make_host_mesh(), 1, 1))


class TestJointShardingParity:
    """Multi-device parity for the joint layouts, in a child process (the
    host platform can only be split into >1 device before jax initializes).

    One child covers the layout paths on a forced 4-device host: joint
    (2, 2) at n_seeds=2, env-only (1, 4) at n_seeds=3 with the seed axis
    indivisible, seed-only (4, 1) at n_seeds=4, and the full fallback at an
    indivisible batch — each pinned <= 1e-6 against the unsharded program
    with the identical ``fold_in`` PRNG ladder."""

    _CHILD = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import train_rl
        from repro.core.types import training_cluster
        from repro.launch import mesh as meshmod
        from repro.train import engine

        cfg = training_cluster()
        key = jax.random.PRNGKey(0)
        mesh4 = meshmod.make_train_mesh(4)
        checks = {}

        def parity(tag, rl, n_seeds):
            ref, rm = engine.train_seeds(key, cfg, rl, n_seeds)
            got, gm = engine.train_seeds(key, cfg, rl, n_seeds, mesh=mesh4)
            # the repo-wide parity pin: atol 1e-6 with rtol 1e-5 headroom for
            # float reassociation on O(10-100) metrics (see
            # TestSeedParallel.test_matches_sequential_per_seed)
            for name in ref:
                np.testing.assert_allclose(np.asarray(got[name]),
                                           np.asarray(ref[name]),
                                           atol=1e-6, rtol=1e-5,
                                           err_msg=f"{tag}:{name}")
            for k in rm:
                np.testing.assert_allclose(np.asarray(gm[k]),
                                           np.asarray(rm[k]),
                                           atol=1e-6, rtol=1e-5,
                                           err_msg=f"{tag}:{k}")
            checks[tag] = "ok"

        rl4 = train_rl.RLConfig(episodes=2, pods_per_episode=5, n_envs=4,
                                batch_size=16, buffer_capacity=64)
        lay = meshmod.plan_seed_env_layout(2, 4, mesh4)
        assert (lay.seed_shards, lay.env_shards) == (2, 2), lay
        parity("joint_2x2", rl4, 2)

        lay = meshmod.plan_seed_env_layout(3, 4, mesh4)
        assert (lay.seed_shards, lay.env_shards) == (1, 4), lay
        parity("env_only_1x4", rl4, 3)

        lay = meshmod.plan_seed_env_layout(4, 4, mesh4)
        assert (lay.seed_shards, lay.env_shards) == (4, 1), lay
        parity("seed_only_4x1", rl4, 4)

        rl5 = train_rl.RLConfig(episodes=1, pods_per_episode=4, n_envs=5,
                                batch_size=16, buffer_capacity=60)
        assert meshmod.plan_seed_env_layout(3, 5, mesh4) is None
        parity("fallback_unsharded", rl5, 3)

        print("PARITY" + json.dumps(checks))
    """)

    def test_joint_and_fallback_match_unsharded(self):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4").strip()
        env["JAX_PLATFORM_NAME"] = "cpu"
        # the child must resolve the same repro tree whether the suite runs
        # from PYTHONPATH=src or an editable install
        import repro

        # __path__ (not __file__): repro is a namespace package
        src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        env["PYTHONPATH"] = os.pathsep.join(
            [src_dir] + [p for p in (env.get("PYTHONPATH"),) if p])
        out = subprocess.run([sys.executable, "-c", self._CHILD], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("PARITY")][-1]
        checks = json.loads(line[len("PARITY"):])
        assert set(checks) == {"joint_2x2", "env_only_1x4", "seed_only_4x1",
                               "fallback_unsharded"}
        assert all(v == "ok" for v in checks.values()), checks


class TestFusedInLoopScoring:
    def _setup(self, cfg):
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        return state, kenv.default_pod(cfg)

    def test_hypothetical_place_one_matches_matrix_paper(self):
        cfg = paper_cluster()
        state, pod = self._setup(cfg)
        full = kenv.hypothetical_place(state, pod, cfg)
        for i in range(cfg.n_nodes):
            np.testing.assert_array_equal(
                np.asarray(kenv.hypothetical_place_one(state, pod, cfg,
                                                       jnp.int32(i))),
                np.asarray(full[i]))

    def test_hypothetical_place_one_matches_matrix_fleet(self):
        cfg = fleet_cluster(4096)
        state, pod = self._setup(cfg)
        full = kenv.hypothetical_place(state, pod, cfg)
        for i in (0, 1, 2047, 4095):
            np.testing.assert_allclose(
                np.asarray(kenv.hypothetical_place_one(state, pod, cfg,
                                                       jnp.int32(i))),
                np.asarray(full[i]), atol=1e-5)

    def test_training_scoring_matches_reference_paper_cluster(self):
        """In-loop scoring == hypothetical_place + qvalues on the 4-node
        paper cluster (N < FUSED_SCORE_MIN_NODES: the identical jnp path)."""
        cfg = paper_cluster()
        state, pod = self._setup(cfg)
        qp = dqn.init_qnet(jax.random.PRNGKey(1))
        ref = dqn.qvalues(qp, kenv.normalize_features(
            kenv.hypothetical_place(state, pod, cfg)))
        got = schedulers.score_afterstates(qp, state, pod, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))

    def test_training_scoring_matches_reference_fleet(self):
        """At 4096 nodes the training loop's scoring dispatches to the fused
        kernel path; it must agree with the unfused reference to <=1e-5."""
        cfg = fleet_cluster(4096)
        state, pod = self._setup(cfg)
        qp = dqn.init_qnet(jax.random.PRNGKey(1))
        ref = dqn.qvalues(qp, kenv.normalize_features(
            kenv.hypothetical_place(state, pod, cfg)))
        got = schedulers.score_afterstates(qp, state, pod, cfg)
        assert got.shape == (4096,)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref), atol=1e-5)

    def test_transition_matches_unfused_reference(self):
        """`_transition` (shared helper + fused dispatch) reproduces the old
        inline body: same action, same stored afterstate, same reward."""
        cfg = TCFG
        state, pod = self._setup(cfg)
        qp = dqn.init_qnet(jax.random.PRNGKey(1))
        rl = RL
        reward_fn = rewards.make_reward_fn(rl.variant, rl.consolidation_n,
                                           rl.efficiency_weight)
        key = jax.random.PRNGKey(7)

        # the pre-refactor transition body, verbatim
        ok = kenv.feasible(state, pod, cfg)
        after_all = kenv.hypothetical_place(state, pod, cfg)
        q = dqn.qvalues(qp, kenv.normalize_features(after_all))
        action = schedulers.masked_argmax(key, q, ok, 0.1)
        ref_state = kenv.place(state, action, pod, cfg)
        ref_r = reward_fn(kenv.features(ref_state, cfg),
                          kenv.features(state, cfg), ok, action,
                          state.exp_pods, ref_state.exp_pods)
        ref_stored = kenv.normalize_features(after_all[jnp.maximum(action, 0)])

        new_state, stored, r, got_action = train_rl._transition(
            key, qp, state, pod, cfg.schedule_dt_s, cfg, 0.1, reward_fn)
        assert int(got_action) == int(action)
        np.testing.assert_array_equal(np.asarray(stored), np.asarray(ref_stored))
        np.testing.assert_allclose(float(r), float(ref_r) * train_rl.REWARD_SCALE,
                                   rtol=1e-6)


class TestMultiParamEvaluator:
    def test_matches_per_seed_evaluator(self):
        cfg = paper_cluster()
        stacked = jax.vmap(dqn.init_qnet)(engine.seed_fold_keys(
            jax.random.PRNGKey(0), 2))
        keys = eval_engine.fixed_trial_keys(5000, 3)
        multi = eval_engine.make_multi_param_evaluator(
            cfg, lambda p: schedulers.make_sdqn_selector(p, cfg), 10)
        res = multi(stacked, keys)
        assert res.metric.shape == (2, 3)
        single = eval_engine.make_param_evaluator(
            cfg, lambda p: schedulers.make_sdqn_selector(p, cfg), 10)
        for s in range(2):
            params = jax.tree.map(lambda x: x[s], stacked)
            np.testing.assert_allclose(np.asarray(res.metric[s]),
                                       np.asarray(single(params, keys).metric),
                                       rtol=1e-6)


class TestReplaySampling:
    """`replay_sample` draws from [0, size): indices are in-range by
    construction — these regressions pin it across fill levels."""

    def _buf(self, cap, n):
        buf = replay_init(cap)
        feats = jnp.tile(jnp.arange(n, dtype=jnp.float32)[:, None], (1, 6))
        return replay_add(buf, feats, jnp.arange(n, dtype=jnp.float32))

    def _assert_samples_live(self, buf, live_targets, batch=64):
        for t in range(5):
            feats, targets, w = replay_sample(buf, jax.random.PRNGKey(t), batch)
            assert set(np.asarray(targets).tolist()) <= live_targets
            np.testing.assert_array_equal(np.asarray(w), np.ones((batch,)))
            # stored rows are (target, target, ..., target): sampling must
            # return rows aligned with their targets
            np.testing.assert_array_equal(np.asarray(feats[:, 0]),
                                          np.asarray(targets))

    def test_partial_fill(self):
        buf = self._buf(8, 3)
        assert int(buf.size) == 3
        self._assert_samples_live(buf, {0.0, 1.0, 2.0})

    def test_exact_fill(self):
        buf = self._buf(8, 8)
        assert int(buf.size) == 8 and int(buf.ptr) == 0
        self._assert_samples_live(buf, set(float(i) for i in range(8)))

    def test_wraparound_overwrite(self):
        """12 adds into cap=8: slots 0-3 now hold entries 8-11; every sample
        must come from the live set {4..11}, never a stale overwritten row."""
        buf = self._buf(8, 12)
        assert int(buf.size) == 8 and int(buf.ptr) == 4
        self._assert_samples_live(buf, set(float(i) for i in range(4, 12)))

    def test_empty_buffer_zero_weights(self):
        buf = replay_init(8)
        _, _, w = replay_sample(buf, jax.random.PRNGKey(0), 16)
        np.testing.assert_array_equal(np.asarray(w), np.zeros((16,)))


class TestReplayLaneLayout:
    """The training loop's ring is lane-structured by ``n_envs``."""

    def test_init_carry_lane_matches_env_batch(self):
        carry = train_rl._init_carry(jax.random.PRNGKey(0), RL)
        assert carry.buffer.lane == RL.n_envs
        assert carry.buffer.capacity == RL.buffer_capacity

    def test_init_carry_lane_falls_back_when_indivisible(self):
        rl = train_rl.RLConfig(n_envs=3, buffer_capacity=64)
        carry = train_rl._init_carry(jax.random.PRNGKey(0), rl)
        assert carry.buffer.lane == 1
        assert carry.buffer.capacity == 64


class TestSupervisedSharedTransition:
    def test_lstm_scorer_trains_through_shared_helper(self):
        from repro.core import baselines

        params = train_rl.train_supervised_scorer(
            jax.random.PRNGKey(0), TCFG, baselines.init_lstm,
            baselines.lstm_score, episodes=2, pods_per_episode=4, n_envs=2)
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(params))

    def test_transformer_scorer_trains_through_shared_helper(self):
        from repro.core import baselines

        params = train_rl.train_supervised_scorer(
            jax.random.PRNGKey(0), TCFG, baselines.init_transformer,
            baselines.transformer_score, episodes=2, pods_per_episode=4,
            n_envs=2)
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(params))
