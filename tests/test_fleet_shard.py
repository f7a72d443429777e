"""Two-stage hierarchical sharded fleet scoring (``sched.shard``).

Everything here runs at N=97, shards=5 on purpose: 97 % 5 != 0 exercises the
infeasible-pad lanes (padded slots must never win a merge), and the parity
assertions pin the module's core contract — the two-stage candidate merge
selects exactly the node the flat masked argmax would, ties included.
"""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import baselines, dqn, env as kenv, policy as pol
from repro.core.types import NO_PLACEMENT, fleet_cluster
from repro.launch.mesh import FleetLayout, plan_fleet_layout
from repro.sched import api, placement, shard
from repro.sched.daemon import ClusterSubstrate, DaemonConfig, PlacementDaemon

N = 97          # deliberately not divisible by SHARDS: forces padded lanes
SHARDS = 5
CFG = fleet_cluster(N)
STATE = kenv.reset(jax.random.PRNGKey(0), CFG)
POD = kenv.default_pod(CFG)
PARAMS = dqn.init_qnet(jax.random.PRNGKey(0))
LAYOUT = plan_fleet_layout(N, shards=SHARDS)


def _flat_choice(state=STATE, **kw):
    return int(api.select(state, POD, params=PARAMS, cfg=CFG, shard=False, **kw))


def _policy_kit(name):
    """(spec, params, embed) for a registry policy — sequence specs get one
    encoder step over the test pod's workload features."""
    spec = pol.get(name)
    params = spec.init(jax.random.PRNGKey(2))
    embed = None
    if spec.embed_dim:
        carry = spec.carry_init(params)
        _, embed = spec.encode_step(params, carry,
                                    pol.pod_workload_features(POD))
    return spec, params, embed


class TestLayoutResolution:
    def test_knob_mapping(self):
        assert shard.resolve_layout(None, N) is None
        assert shard.resolve_layout(False, N) is None
        lay = shard.resolve_layout(SHARDS, N)
        assert isinstance(lay, FleetLayout) and lay.shards == SHARDS
        assert shard.resolve_layout(lay, N) is lay
        # "auto" on a single device is the bit-identical flat fallback
        if len(jax.devices()) <= 1:
            assert shard.resolve_layout("auto", N) is None

    def test_rejects_bogus_knobs(self):
        with pytest.raises(ValueError):
            shard.resolve_layout(True, N)
        with pytest.raises(ValueError):
            shard.resolve_layout("bogus", N)

    def test_plan_geometry(self):
        assert LAYOUT.shards == SHARDS
        assert LAYOUT.padded == SHARDS * LAYOUT.shard_size
        assert 0 <= LAYOUT.padded - N < LAYOUT.shard_size
        # degenerate plans collapse to no layout at all
        assert plan_fleet_layout(3, shards=5) is None
        assert plan_fleet_layout(N, shards=1) is None


class TestShardedSelection:
    @pytest.mark.parametrize("shards", [2, 5, 8])
    def test_matches_flat_argmax(self, shards):
        lay = plan_fleet_layout(N, shards=shards)
        got = int(api.select(STATE, POD, params=PARAMS, cfg=CFG, shard=lay))
        assert got == _flat_choice()

    def test_topk_candidates_match_flat_scores(self):
        vals, idx = api.topk(STATE, POD, params=PARAMS, cfg=CFG, shard=LAYOUT)
        q = np.asarray(api.score(STATE, POD, params=PARAMS, cfg=CFG,
                                 shard=False))
        ok = np.asarray(kenv.feasible(STATE, POD, CFG))
        masked = np.where(ok, q, -np.inf)
        vals, idx = np.asarray(vals), np.asarray(idx)
        # winner == flat argmax; merged list is descending with -inf/-1 tails
        assert idx[0] == int(np.argmax(masked))
        assert np.all(np.diff(vals) <= 1e-6)
        finite = np.isfinite(vals)
        assert np.all(idx[finite] >= 0) and np.all(idx[~finite] == -1)
        # no node appears twice, and each candidate carries its flat score
        assert len(np.unique(idx[finite])) == finite.sum()
        np.testing.assert_allclose(vals[finite], masked[idx[finite]],
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("k", [1, 4])
    def test_k_does_not_change_winner(self, k):
        got = int(shard.select_candidates(STATE, POD, params=PARAMS, cfg=CFG,
                                          layout=LAYOUT, k=k))
        assert got == _flat_choice()

    @pytest.mark.parametrize("fused", ["interpret", True])
    def test_in_kernel_topk_matches_unfused(self, fused):
        # the fused per-shard top-k (Pallas interpret body AND its XLA twin)
        # must emit the same candidates as the unfused lax.top_k reduction
        vref, iref = shard.cluster_topk(PARAMS, STATE, POD, CFG, LAYOUT,
                                        fused=False)
        v, i = shard.cluster_topk(PARAMS, STATE, POD, CFG, LAYOUT, fused=fused)
        np.testing.assert_array_equal(np.asarray(i), np.asarray(iref))
        np.testing.assert_allclose(np.asarray(v), np.asarray(vref),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("k", [1, 3])
    def test_tie_breaks_to_lowest_feasible_index(self, k):
        # constant scores tie every node: first-occurrence argmax semantics
        # must survive the per-shard top-k AND the global merge
        const = lambda p, feats: jnp.zeros(feats.shape[0])
        state = STATE._replace(
            healthy=STATE.healthy.at[:3].set(False))
        got = int(shard.select_candidates(state, POD, params=PARAMS, cfg=CFG,
                                          layout=LAYOUT, k=k, score_fn=const))
        want = _flat_choice(state, score_fn=const)
        assert got == want
        ok = np.asarray(kenv.feasible(state, POD, CFG))
        assert got == int(np.argmax(ok))        # the lowest feasible index

    def test_all_infeasible_is_no_placement(self):
        state = STATE._replace(healthy=jnp.zeros(N, bool))
        got = shard.select_candidates(state, POD, params=PARAMS, cfg=CFG,
                                      layout=LAYOUT)
        assert int(got) == NO_PLACEMENT
        vals, idx = api.topk(state, POD, params=PARAMS, cfg=CFG, shard=LAYOUT)
        assert not np.isfinite(np.asarray(vals)).any()
        assert np.all(np.asarray(idx) == -1)

    def test_single_device_auto_is_bit_identical(self):
        if len(jax.devices()) > 1:
            pytest.skip("multi-device: 'auto' legitimately shards")
        qa = api.score(STATE, POD, params=PARAMS, cfg=CFG, shard="auto")
        qf = api.score(STATE, POD, params=PARAMS, cfg=CFG, shard=False)
        np.testing.assert_array_equal(np.asarray(qa), np.asarray(qf))
        assert int(api.select(STATE, POD, params=PARAMS, cfg=CFG,
                              shard="auto")) == _flat_choice()

    def test_guard_degrades_to_heuristic_candidates(self):
        bad = jax.tree.map(lambda x: jnp.full_like(x, jnp.nan), PARAMS)
        got = shard.select_candidates(STATE, POD, params=bad, cfg=CFG,
                                      layout=LAYOUT, guard=True)
        q = np.asarray(baselines.kube_scores(STATE, POD, CFG))
        ok = np.asarray(kenv.feasible(STATE, POD, CFG))
        assert int(got) == int(np.argmax(np.where(ok, q, -np.inf)))


class TestShardedScores:
    def test_matches_flat_within_tolerance(self):
        q = np.asarray(api.score(STATE, POD, params=PARAMS, cfg=CFG,
                                 shard=False))
        qs = np.asarray(api.score(STATE, POD, params=PARAMS, cfg=CFG,
                                  shard=LAYOUT))
        assert qs.shape == (N,)
        np.testing.assert_allclose(qs, q, rtol=1e-5, atol=1e-5)

    def test_pull_cost_is_global_not_per_shard(self):
        # in-flight startups concentrated in ONE shard must inflate every
        # shard's scores identically — pull_cost_now is a global reduction
        startup = jnp.zeros(N).at[:4].set(0.9 * CFG.image_pull_cost)
        state = STATE._replace(startup_cpu=startup)
        assert float(kenv.pull_cost_now(state, CFG)) > float(
            kenv.pull_cost_now(STATE, CFG))
        q = np.asarray(api.score(state, POD, params=PARAMS, cfg=CFG,
                                 shard=False))
        qs = np.asarray(api.score(state, POD, params=PARAMS, cfg=CFG,
                                  shard=LAYOUT))
        np.testing.assert_allclose(qs, q, rtol=1e-5, atol=1e-5)


class TestPolicyClasses:
    @pytest.mark.parametrize("name", pol.names())
    def test_sharded_selection_consistent(self, name):
        spec, params, embed = _policy_kit(name)
        got = int(shard.select_candidates(STATE, POD, params=params, cfg=CFG,
                                          layout=LAYOUT, policy=spec,
                                          embed=embed))
        # the two-stage merge must agree with the argmax of its OWN sharded
        # score vector (for "attention" that vector is block-local by
        # construction, so this — not flat parity — is the contract)
        qs = np.asarray(api.score(STATE, POD, params=params, cfg=CFG,
                                  shard=LAYOUT, policy=spec, embed=embed))
        ok = np.asarray(kenv.feasible(STATE, POD, CFG))
        assert got == int(np.argmax(np.where(ok, qs, -np.inf)))
        if name != "attention":  # pointwise classes: exact flat parity too
            qf = np.asarray(api.score(STATE, POD, params=params, cfg=CFG,
                                      shard=False, policy=spec, embed=embed))
            assert got == int(np.argmax(np.where(ok, qf, -np.inf)))


class TestFleetSubstrate:
    def test_sharded_select_matches_engine(self):
        fleet = placement.fresh_fleet(N)
        job = placement.JobSpec(cpu_pct_demand=4.0)
        lay = plan_fleet_layout(N, shards=SHARDS)
        got = int(shard.select_candidates(fleet, job, params=PARAMS,
                                          layout=lay))
        eng = placement.PlacementEngine(PARAMS)
        choice, _ = eng.select(fleet, job)
        assert got == int(choice)

    def test_engine_select_stays_on_device(self):
        # the serving-path bugfix: select must not force a host sync — it
        # returns a 0-d device array, callers sync at their own boundary
        eng = placement.PlacementEngine(PARAMS)
        fleet = placement.fresh_fleet(8)
        choice, scores = eng.select(fleet, placement.JobSpec())
        assert isinstance(choice, jnp.ndarray) and choice.shape == ()
        assert choice.dtype == jnp.int32
        assert scores.shape == (8,)
        dead = fleet._replace(healthy=jnp.zeros(8))
        choice, _ = eng.select(dead, placement.JobSpec())
        assert int(choice) == placement.NO_HOST


class TestDaemonSharded:
    def test_decisions_match_unsharded_daemon(self):
        cfgd = DaemonConfig(batch_size=3, max_wait_s=1e9)
        pods = [kenv.default_pod(CFG) for _ in range(6)]
        nodes = {}
        for label, layout in (("flat", None), ("sharded", LAYOUT)):
            sub = ClusterSubstrate(STATE, CFG, layout=layout)
            d = PlacementDaemon(sub, PARAMS, cfgd, clock=lambda: 0.0)
            for p in pods:
                d.submit(p)
            d.drain()
            nodes[label] = [dec.node for dec in d.decisions]
        assert len(nodes["sharded"]) == 6
        assert nodes["sharded"] == nodes["flat"]


class TestDeviceMeshParity:
    """A 4-device ``FleetLayout`` (CPU virtual devices, in a child process:
    the device count is fixed when JAX starts) must select exactly what the
    flat program selects.  On a mesh the per-shard stage runs under
    ``shard_map``, one shard per device."""

    _CHILD = textwrap.dedent("""
        import json
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.core import dqn, env as kenv
        from repro.core.types import fleet_cluster
        from repro.launch.mesh import plan_fleet_layout
        from repro.sched import api, placement, shard
        from repro.sched.daemon import (ClusterSubstrate, DaemonConfig,
                                        PlacementDaemon)

        N = 97
        cfg = fleet_cluster(N)
        state = kenv.reset(jax.random.PRNGKey(0), cfg)
        pod = kenv.default_pod(cfg)
        params = dqn.init_qnet(jax.random.PRNGKey(0))
        mesh = jax.sharding.Mesh(np.array(jax.devices()), ("data",))
        layout = plan_fleet_layout(N, mesh)
        assert layout.shards == 4 and layout.mesh is not None, layout
        checks = {}
        flat = int(api.select(state, pod, params=params, cfg=cfg, shard=False))
        for fused in (True, "interpret", False):
            got = int(jax.jit(lambda s: api.select(
                s, pod, params=params, cfg=cfg, shard=layout,
                fused=fused))(state))
            checks[f"select_{fused}"] = got == flat
        checks["select_auto"] = int(api.select(
            state, pod, params=params, cfg=cfg, shard="auto")) == flat
        q = api.score(state, pod, params=params, cfg=cfg, shard=False)
        qs = api.score(state, pod, params=params, cfg=cfg, shard=layout)
        checks["scores"] = bool(np.allclose(qs, q, rtol=1e-5, atol=1e-5))
        fleet = placement.fresh_fleet(N)
        job = placement.JobSpec(cpu_pct_demand=4.0)
        checks["fleet_select"] = int(api.select(
            fleet, job, params=params, shard=layout)) == int(api.select(
            fleet, job, params=params, shard=False))
        nodes = {}
        for label, lay in (("flat", None), ("mesh", layout)):
            sub = ClusterSubstrate(state, cfg, layout=lay)
            d = PlacementDaemon(sub, params, DaemonConfig(batch_size=3,
                                                          max_wait_s=1e9),
                                clock=lambda: 0.0)
            d.warmup()
            for _ in range(6):
                d.submit(pod)
            d.drain()
            nodes[label] = [dec.node for dec in d.decisions]
        checks["daemon"] = nodes["mesh"] == nodes["flat"]
        # on the mesh too, each batch publishes only its changed rows, the
        # result equals a whole publish, and the scorer compiled once
        whole = shard.shard_cluster(jax.tree.map(jnp.asarray, sub.live),
                                    layout)
        checks["daemon_resident"] = (
            d.metrics.delta_publishes == 2 and d.scorer_cache_size() == 1
            and all(np.array_equal(a, b) and a.sharding == b.sharding
                    for a, b in zip(jax.tree.leaves(sub.snapshot()),
                                    jax.tree.leaves(whole))))
        print("PARITY" + json.dumps(checks))
    """)

    def test_four_device_layout_matches_flat(self):
        import repro

        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=4").strip()
        env["JAX_PLATFORMS"] = "cpu"
        src_dir = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
        env["PYTHONPATH"] = os.pathsep.join(
            [src_dir] + [p for p in (env.get("PYTHONPATH"),) if p])
        out = subprocess.run([sys.executable, "-c", self._CHILD], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        line = [ln for ln in out.stdout.splitlines()
                if ln.startswith("PARITY")][-1]
        checks = json.loads(line[len("PARITY"):])
        assert len(checks) == 8 and all(checks.values()), checks


class TestGatesManifest:
    ROOT = pathlib.Path(__file__).resolve().parents[1]

    def _manifest(self):
        with open(self.ROOT / "benchmarks" / "gates.json") as f:
            return json.load(f)

    def test_schema_and_suites(self):
        m = self._manifest()
        assert m["schema"] == "repro-gates-v1"
        names = [s["name"] for s in m["suites"]]
        assert len(names) == len(set(names))
        assert "fleet_scale" in names            # the new suite is gated...
        assert "fleet_scale" in [s["name"] for s in m["nightly"]]  # ...and swept
        for suite in m["suites"] + m["nightly"]:
            assert suite["run_args"], f"{suite['name']}: empty run_args"
            assert all(a.startswith("--") or not a.startswith("-")
                       for a in suite["run_args"])

    def test_baselines_exist_and_contain_gated_rows(self):
        for suite in self._manifest()["suites"]:
            base = self.ROOT / suite["baseline"]
            assert base.exists(), f"{suite['name']}: missing {suite['baseline']}"
            with open(base) as f:
                rows = {r["name"] for r in json.load(f)["rows"]}
            for key in ("throughput_rows", "latency_rows"):
                for row in suite.get(key, ()):
                    assert row in rows, (
                        f"{suite['name']}: gated row {row!r} absent from "
                        f"{suite['baseline']}")

    def test_run_flags_are_real(self):
        src = (self.ROOT / "benchmarks" / "run.py").read_text()
        for suite in self._manifest()["suites"] + self._manifest()["nightly"]:
            flag = suite["run_args"][0]
            assert f'"{flag}"' in src, f"unknown bench flag {flag}"
