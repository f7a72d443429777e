"""Pod-lifecycle tests: lifetime sampling, expiry-ledger conservation,
static-table parity (lifetime = inf reproduces the pre-lifecycle episodes
bit-for-bit), churn metrics, the jit-safe consolidation pass, and the
lifecycle CI gate."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.core import dqn, env as kenv, rewards, schedulers
from repro.core.types import paper_cluster

class TestLifetimeSampling:
    def test_default_pod_runs_forever(self):
        table = kenv.sample_pod_table(jax.random.PRNGKey(0), paper_cluster(), 16)
        assert bool(np.all(np.isinf(np.asarray(table.lifetime_s))))

    def test_static_scenarios_run_forever(self):
        cfg = scenarios.make_env("hetero-bigsmall")
        table = kenv.sample_pod_table(jax.random.PRNGKey(0), cfg, 32)
        assert bool(np.all(np.isinf(np.asarray(table.lifetime_s))))

    def test_lifetime_mean_matches_pod_type(self):
        cfg = scenarios.make_env("short-job-burst")  # single 45s-mean type
        table = kenv.sample_pod_table(jax.random.PRNGKey(1), cfg, 4000)
        life = np.asarray(table.lifetime_s)
        assert np.all(np.isfinite(life)) and np.all(life > 0)
        assert np.mean(life) == pytest.approx(45.0, rel=0.1)

    def test_lifetimes_decorrelated_from_types_and_gaps(self):
        """The lifetime stream draws from fold_in(key, 3): the type/gap draws
        of pre-lifecycle tables must be unchanged by its addition."""
        cfg = scenarios.make_env("longrun-train-mix")
        t1 = kenv.sample_pod_table(jax.random.PRNGKey(5), cfg, 64)
        t2 = kenv.sample_pod_table(jax.random.PRNGKey(5), cfg, 64)
        np.testing.assert_array_equal(np.asarray(t1.lifetime_s),
                                      np.asarray(t2.lifetime_s))
        # per-type means follow each catalog entry
        life = np.asarray(t1.lifetime_s)
        idx = np.asarray(t1.type_idx)
        means = [p.lifetime_mean_s for p in cfg.scenario.pod_types]
        assert means[0] > means[1]  # long-train outlives serve-churn
        assert life[idx == 0].mean() > life[idx == 1].mean()


class TestRetireExpired:
    def _place_two(self):
        cfg = paper_cluster()
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        pod = kenv.default_pod(cfg)
        ledger = kenv.ledger_init(4)
        st = kenv.place(state, jnp.int32(0), pod, cfg)
        ledger = kenv.ledger_record(ledger, 0, jnp.int32(0),
                                    st.time_s + 10.0, pod)
        st = kenv.place(st, jnp.int32(1), pod, cfg)
        ledger = kenv.ledger_record(ledger, 1, jnp.int32(1),
                                    st.time_s + 100.0, pod)
        return cfg, state, st, ledger, pod

    def test_releases_exactly_what_was_acquired(self):
        cfg, before, st, ledger, pod = self._place_two()
        st = kenv.tick(st, cfg, 20.0)  # pod 0 expires, pod 1 lives
        st, ledger, n = kenv.retire_expired(st, ledger)
        assert int(n) == 1
        np.testing.assert_allclose(np.asarray(st.exp_pods),
                                   np.asarray(before.exp_pods) + [0, 1, 0, 0])
        np.testing.assert_allclose(
            np.asarray(st.cpu_requested),
            np.asarray(before.cpu_requested) + [0, float(pod.cpu_request), 0, 0],
            rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(st.mem_used),
            np.asarray(before.mem_used) + [0, float(pod.mem_demand), 0, 0],
            rtol=1e-6)
        # retiring again is a no-op: the slot was freed
        st2, ledger2, n2 = kenv.retire_expired(st, ledger)
        assert int(n2) == 0
        np.testing.assert_array_equal(np.asarray(st2.exp_pods),
                                      np.asarray(st.exp_pods))

    def test_dropped_arrivals_never_retire(self):
        cfg = paper_cluster()
        pod = kenv.default_pod(cfg)
        ledger = kenv.ledger_record(kenv.ledger_init(2), 0,
                                    jnp.int32(kenv.NO_NODE), 5.0, pod)
        state = kenv.tick(kenv.reset(jax.random.PRNGKey(0), cfg), cfg, 100.0)
        st, ledger, n = kenv.retire_expired(state, ledger)
        assert int(n) == 0


class TestConservation:
    @pytest.mark.parametrize("name", ["short-job-burst", "consolidation-stress"])
    def test_fleet_returns_to_reset_utilization(self, name):
        """Every resource a pod acquires is released on expiry: after a long
        settle window all experiment pods are dead and the pod-accounting
        columns are back at their reset values."""
        cfg = scenarios.make_env(name, settle_steps=400)
        sel = schedulers.make_kube_selector(cfg)
        key = jax.random.PRNGKey(3)
        n = cfg.scenario.n_pods
        res = jax.jit(lambda k: kenv.run_episode(k, cfg, sel, n))(key)
        final, dropped, stats = res.state, res.dropped, res.stats
        assert int(stats.retired) == n - int(dropped)
        assert int(stats.nodes_active_final) == 0
        reset_state = kenv.reset(jax.random.split(key, 3)[0], cfg)
        np.testing.assert_array_equal(np.asarray(final.exp_pods), 0)
        np.testing.assert_array_equal(np.asarray(final.num_pods),
                                      np.asarray(reset_state.num_pods))
        for col in ("cpu_requested", "mem_requested", "pods_cpu", "mem_used"):
            np.testing.assert_allclose(
                np.asarray(getattr(final, col)),
                np.asarray(getattr(reset_state, col)),
                rtol=1e-4, atol=0.5, err_msg=col)


def _static_reference_episode(key, cfg, select, n_pods, table):
    """The pre-lifecycle ``run_episode`` loop (place/tick/integrate only):
    the parity ground truth the ledgered episode must reproduce when no pod
    ever expires."""
    k_reset, _, k_act = jax.random.split(key, 3)
    state = kenv.reset(k_reset, cfg)

    def sched_step(carry, xs):
        st, acc, cnt = carry
        k, pod, dt = xs
        a = select(k, st, pod)
        st = kenv.place(st, a, pod, cfg)
        st = kenv.tick(st, cfg, dt)
        m = kenv.average_cpu_utilization(st, cfg)
        return (st, acc + m * dt, cnt + dt), a

    keys = jax.random.split(k_act, n_pods)
    (state, acc, cnt), actions = jax.lax.scan(
        sched_step, (state, jnp.float32(0.0), jnp.float32(0.0)),
        (keys, table.specs, table.dt_s))

    def settle_step(carry, _):
        st, acc, cnt = carry
        st = kenv.tick(st, cfg, cfg.schedule_dt_s)
        m = kenv.average_cpu_utilization(st, cfg)
        return (st, acc + m * cfg.schedule_dt_s, cnt + cfg.schedule_dt_s), None

    (state, acc, cnt), _ = jax.lax.scan(
        settle_step, (state, acc, cnt), None, length=cfg.settle_steps)
    return state, acc / cnt, actions


class TestStaticParity:
    @pytest.mark.parametrize("cfg_name", [None, "hetero-bigsmall", "spot-flaky"])
    def test_inf_lifetime_reproduces_static_trajectories(self, cfg_name):
        """lifetime = inf must pin old-vs-new trajectories to <= 1e-6 (they
        are the same program: retirement masks are identically false)."""
        cfg = paper_cluster() if cfg_name is None else scenarios.make_env(cfg_name)
        sel = schedulers.make_kube_selector(cfg)
        key = jax.random.PRNGKey(11)
        n = 25
        table = kenv.sample_pod_table(jax.random.split(key, 3)[1], cfg, n)
        assert bool(np.all(np.isinf(np.asarray(table.lifetime_s))))
        ref_state, ref_metric, _ = jax.jit(
            lambda k: _static_reference_episode(k, cfg, sel, n, table))(key)
        res = jax.jit(
            lambda k: kenv.run_episode(k, cfg, sel, n, pod_table=table))(key)
        new_state, new_metric, stats = res.state, res.metric, res.stats
        assert int(stats.retired) == 0
        np.testing.assert_allclose(float(ref_metric), float(new_metric),
                                   rtol=1e-6)
        for name, a, b in zip(ref_state._fields, ref_state, new_state):
            if a is None:            # no accelerator column on either side
                assert b is None, name
                continue
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6, err_msg=name)

    def test_finite_lifetimes_diverge_from_static(self):
        """Sanity: with real churn the ledgered episode is NOT the static one
        (pods die, the metric window sees the drain)."""
        cfg = scenarios.make_env("short-job-burst")
        sel = schedulers.make_kube_selector(cfg)
        key = jax.random.PRNGKey(11)
        n = 25
        table = kenv.sample_pod_table(jax.random.split(key, 3)[1], cfg, n)
        _, ref_metric, _ = jax.jit(
            lambda k: _static_reference_episode(k, cfg, sel, n, table))(key)
        res = jax.jit(
            lambda k: kenv.run_episode(k, cfg, sel, n, pod_table=table))(key)
        new_metric, stats = res.metric, res.stats
        assert int(stats.retired) > 0
        assert float(new_metric) < float(ref_metric)  # drained cluster is idler


class TestChurnEpisodes:
    def test_nodes_active_falls_after_arrival_wave(self):
        cfg = scenarios.make_env("short-job-burst")
        sel = schedulers.make_kube_selector(cfg)
        stats = jax.jit(
            lambda k: kenv.run_episode(k, cfg, sel, cfg.scenario.n_pods))(
                jax.random.PRNGKey(0)).stats
        assert int(stats.retired) > 0
        assert int(stats.nodes_active_final) < int(stats.nodes_active_peak)
        assert float(stats.nodes_active_mean) < float(stats.nodes_active_peak)

    def test_stats_are_consistent_integrals(self):
        cfg = scenarios.make_env("diurnal-churn")
        sel = schedulers.make_kube_selector(cfg)
        stats = jax.jit(
            lambda k: kenv.run_episode(k, cfg, sel, 40))(
                jax.random.PRNGKey(1)).stats
        assert float(stats.node_seconds) > 0.0
        assert float(stats.energy_wh) > 0.0
        assert 0.0 < float(stats.nodes_active_mean) <= float(stats.nodes_active_peak)
        assert int(stats.nodes_active_peak) <= cfg.n_nodes

    def test_settle_override_materializes(self):
        cfg = scenarios.make_env("short-job-burst")
        assert cfg.settle_steps == 60
        cfg2 = scenarios.make_env("short-job-burst", settle_steps=5)
        assert cfg2.settle_steps == 5  # explicit override wins


class TestConsolidator:
    def _loaded_state(self, cfg, pods_per_node):
        """A cluster with `pods_per_node[i]` experiment pods on node i, all
        ledgered with long lifetimes."""
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        pod = kenv.default_pod(cfg)
        ledger = kenv.ledger_init(int(sum(pods_per_node)))
        slot = 0
        for node, k in enumerate(pods_per_node):
            for _ in range(k):
                state = kenv.place(state, jnp.int32(node), pod, cfg)
                ledger = kenv.ledger_record(ledger, slot, jnp.int32(node),
                                            state.time_s + 1e6, pod)
                slot += 1
        return state, ledger, pod

    def test_drains_low_occupancy_nodes(self):
        from repro.sched import elastic

        cfg = paper_cluster()
        qp = dqn.init_qnet(jax.random.PRNGKey(2))
        state, ledger, pod = self._loaded_state(cfg, (1, 6, 1, 0))
        cons = jax.jit(elastic.make_consolidator(qp, cfg, max_migrations=4,
                                                 idle_threshold=2))
        new_state, new_ledger, moved = cons(state, ledger)
        assert int(moved) >= 1
        # conservation: nothing created or destroyed, just moved
        assert int(new_state.exp_pods.sum()) == int(state.exp_pods.sum())
        np.testing.assert_allclose(float(new_state.pods_cpu.sum()),
                                   float(state.pods_cpu.sum()), rtol=1e-6)
        assert int(kenv.nodes_active(new_state)) <= int(kenv.nodes_active(state))
        # the ledger tracks the migrations: rows live on the new hosts
        live = np.asarray(new_ledger.node)
        counts = np.bincount(live[live >= 0], minlength=cfg.n_nodes)
        np.testing.assert_array_equal(counts, np.asarray(new_state.exp_pods))

    def test_noop_on_empty_and_saturated_clusters(self):
        from repro.sched import elastic

        cfg = paper_cluster()
        qp = dqn.init_qnet(jax.random.PRNGKey(2))
        cons = jax.jit(elastic.make_consolidator(qp, cfg))
        # empty: nothing to drain
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        ledger = kenv.ledger_init(4)
        new_state, _, moved = cons(state, ledger)
        assert int(moved) == 0
        np.testing.assert_array_equal(np.asarray(new_state.exp_pods),
                                      np.asarray(state.exp_pods))
        # every node above the idle threshold: no drain source
        state, ledger, _ = self._loaded_state(cfg, (5, 5, 5, 5))
        _, _, moved = cons(state, ledger)
        assert int(moved) == 0

    def test_already_packed_cluster_is_a_fixed_point(self):
        """A lone pod (maximally packed already) must stay put — targets must
        be at least as loaded as the source was BEFORE removal, else the pass
        ping-pongs the pod between empty nodes paying pull costs."""
        from repro.sched import elastic

        cfg = paper_cluster()
        qp = dqn.init_qnet(jax.random.PRNGKey(2))
        cons = jax.jit(elastic.make_consolidator(qp, cfg, max_migrations=4))
        state, ledger, _ = self._loaded_state(cfg, (1, 0, 0, 0))
        new_state, _, moved = cons(state, ledger)
        assert int(moved) == 0
        np.testing.assert_array_equal(np.asarray(new_state.exp_pods),
                                      np.asarray(state.exp_pods))
        np.testing.assert_allclose(np.asarray(new_state.startup_cpu),
                                   np.asarray(state.startup_cpu))

    def test_consolidated_episode_keeps_fewer_nodes_awake(self):
        """The in-episode pass must not *increase* active nodes, and the
        episode must stay conservation-clean under it."""
        from repro.sched import elastic

        base = scenarios.make_env("consolidation-stress", settle_steps=400)
        qp = dqn.init_qnet(jax.random.PRNGKey(4))
        cfg = dataclasses.replace(base, consolidate_every_s=30.0)
        sel = schedulers.make_sdqn_selector(qp, cfg)
        cons = elastic.make_consolidator(qp, cfg)
        n = 40
        key = jax.random.PRNGKey(6)
        plain = jax.jit(lambda k: kenv.run_episode(k, base, sel, n))(key)
        packed = jax.jit(lambda k: kenv.run_episode(
            k, cfg, sel, n, consolidate=cons))(key)
        assert (float(packed.stats.node_seconds)
                <= float(plain.stats.node_seconds) * 1.05)
        # all pods still die and release everything
        assert int(packed.stats.nodes_active_final) == 0
        np.testing.assert_array_equal(np.asarray(packed.state.exp_pods), 0)


class TestEnergyReward:
    def test_energy_term_counts_newly_active_nodes(self):
        before = jnp.array([2, 0, 1, 0])
        assert float(rewards.energy_term(before, jnp.array([2, 1, 1, 0]))) == 1.0
        assert float(rewards.energy_term(before, jnp.array([3, 0, 1, 0]))) == 0.0

    def test_reward_fn_prefers_packing_under_energy_weight(self):
        feats = jnp.zeros((4, 6))
        action = jnp.int32(1)
        ok = jnp.ones((4,), bool)
        before = jnp.array([3, 0, 0, 0])
        packed_after = jnp.array([4, 0, 0, 0])
        spread_after = jnp.array([3, 1, 0, 0])
        for variant in ("sdqn", "sdqn_n"):
            fn = rewards.make_reward_fn(variant, energy_weight=15.0)
            fn0 = rewards.make_reward_fn(variant, energy_weight=0.0)
            gap = float(fn(feats, feats, ok, action, before, packed_after)
                        - fn(feats, feats, ok, action, before, spread_after))
            gap0 = float(fn0(feats, feats, ok, action, before, packed_after)
                         - fn0(feats, feats, ok, action, before, spread_after))
            assert gap - gap0 == pytest.approx(15.0), variant


class TestLifecycleGate:
    def _payload(self, ratios, throughput=250.0):
        rows = []
        for scn, (kube, sdqnn) in ratios.items():
            rows.append({"name": f"lifecycle_{scn}_kube", "us_per_call": 0.0,
                         "derived": kube})
            rows.append({"name": f"lifecycle_{scn}_sdqn", "us_per_call": 0.0,
                         "derived": (kube + sdqnn) / 2})
            rows.append({"name": f"lifecycle_{scn}_sdqnn", "us_per_call": 0.0,
                         "derived": sdqnn})
            rows.append({"name": f"lifecycle_{scn}_sdqnn_energy_wh",
                         "us_per_call": 0.0, "derived": 1.0})
        rows.append({"name": "lifecycle_episode_throughput", "us_per_call": 0.0,
                     "derived": throughput})
        return {"rows": rows}

    def test_gate_passes_within_tolerance(self):
        from benchmarks import check_smoke

        base = self._payload({"short-job-burst": (4.0, 2.0)})
        cur = self._payload({"short-job-burst": (4.0, 2.1)})
        rc = check_smoke.compare(cur, base, 0.10, lifecycle=True,
                                 throughput_rows=["lifecycle_episode_throughput"],
                                 throughput_tolerance=0.5)
        assert rc == 0

    def test_gate_fails_on_consolidation_regression(self):
        from benchmarks import check_smoke

        base = self._payload({"short-job-burst": (4.0, 2.0)})
        cur = self._payload({"short-job-burst": (4.0, 3.5)})  # ratio 0.5 -> 0.875
        assert check_smoke.compare(cur, base, 0.10, lifecycle=True) == 1

    def test_gate_fails_on_throughput_collapse(self):
        from benchmarks import check_smoke

        base = self._payload({"short-job-burst": (4.0, 2.0)}, throughput=250.0)
        cur = self._payload({"short-job-burst": (4.0, 2.0)}, throughput=50.0)
        rc = check_smoke.compare(cur, base, 0.10, lifecycle=True,
                                 throughput_rows=["lifecycle_episode_throughput"],
                                 throughput_tolerance=0.5)
        assert rc == 1

    def test_gate_fails_on_missing_scenario(self):
        from benchmarks import check_smoke

        base = self._payload({"short-job-burst": (4.0, 2.0),
                              "diurnal-churn": (5.0, 2.0)})
        cur = self._payload({"short-job-burst": (4.0, 2.0)})
        assert check_smoke.compare(cur, base, 0.10, lifecycle=True) == 1


class TestEvalEngineLifecycle:
    def test_batched_trials_surface_lifecycle_stats(self):
        from repro.eval import engine as eval_engine

        cfg = scenarios.make_env("short-job-burst")
        sel = schedulers.make_kube_selector(cfg)
        res = eval_engine.make_batch_episode(cfg, sel, 20)(
            eval_engine.trial_keys(jax.random.PRNGKey(0), 3))
        assert res.nodes_active.shape == (3,)
        assert bool(np.all(np.asarray(res.retired) > 0))
        out = eval_engine.summarize(res)
        for k in ("nodes_active_mean", "nodes_active_final_mean",
                  "node_seconds_mean", "energy_wh_mean", "retired_mean"):
            assert k in out, k
        assert out["retired_mean"] > 0
