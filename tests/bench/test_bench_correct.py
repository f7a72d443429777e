"""What decides ``correct``: sound runs pass, and the control and every fault
a serving cell can have fail.

The harness's look for a chip is skipped: ``serve.run_cell`` drives a whole
run on the CPU at a tiny size (``data/tiny-*.json``, the cells' own shapes
cut to hundreds of nodes), with the timed path broken underneath the
benchmark's recorder where a test says so.  The control is the reference
put in the program's place in bfloat16; on the chip it is read at the
cells' own sizes (PERF.md gives those readings)."""
import dataclasses
import json
import os
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench.lib import check, serve  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**31 + 4242
MIXES = {"tiny-flat": {"kind": "backlog", "depth": 16},
         "tiny-sharded": {"kind": "backlog", "depth": 16}}


def _config(name):
    with open(os.path.join(DATA, f"{name}.json")) as f:
        return json.load(f)


def _run(name, faults=None, seed=SEED):
    cfg = _config(name)
    run = serve.run_cell(cfg, MIXES[name], seed, 0.6, False,
                         time.perf_counter(), faults=faults)
    return run, dict((n, (v, lim)) for n, v, lim in
                     check.serving_checks(run, cfg["limits"]))


def _failed(nums):
    return sorted(n for n, (v, lim) in nums.items()
                  if not (np.isfinite(v) and v <= lim))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_sound_run_is_correct_and_the_control_is_not(name):
    run, nums = _run(name)
    assert run["window_bound"] > 0 and len(run["samples"]) > 0
    assert _failed(nums) == [], nums
    ctrl = check.control_numbers(run)
    limit = nums["score_err"][1]
    assert ctrl["score_err"] > 3 * limit > 3 * nums["score_err"][0]


def _shifted_scores(scorer):
    """An answer altered where it is produced: every score off by 0.01."""
    def f(*a):
        v, o, c = scorer(*a)
        return v + 0.01, o, c
    return f


def _half_batch(scorer):
    """Half of the batch left out: the second half's rows repeat the first
    half's."""
    def f(params, snap, pods, carry, n_real):
        v, o, c = scorer(params, snap, pods, carry, n_real)
        h = v.shape[0] // 2
        return v.at[h:].set(v[:h]), o.at[h:].set(o[:h]), c
    return f


def _swap_choice(scorer):
    """A decision altered: the best candidate and the worst trade places."""
    def f(*a):
        v, o, c = scorer(*a)
        return v[:, ::-1], o[:, ::-1], c
    return f


def _frozen_state(sub, daemon):
    """A step that returns its state unchanged: binds leave no trace."""
    sub.bind = lambda node, pod: None


def _wrong_node(sub, daemon):
    """A bind lands on the next node, not the one decided."""
    bind = sub.bind
    n = len(sub.live.cpu_capacity)
    sub.bind = lambda node, pod: bind((node + 1) % n, pod)


def _worst_first(sub, daemon):
    """The commit loop binds the last feasible candidate: it walks the
    snapshot's candidates from the worst up."""
    commit, commit_cands = daemon._commit, daemon._commit_candidates

    def walk_up(req, vals, idx, now):
        fin = np.isfinite(vals)
        order = np.concatenate([np.flatnonzero(fin)[::-1],
                                np.flatnonzero(~fin)])
        return commit_cands(req, vals[order], idx[order], now)

    daemon._commit = lambda req, row, ok, now: commit(req, -row, ok, now)
    daemon._commit_candidates = walk_up


def _drop_on_conflict(sub, daemon):
    """A request whose first choice was taken is dropped, not walked on to
    the next best."""
    daemon.config = dataclasses.replace(daemon.config, max_retries=0,
                                        conflict_policy="requeue")


def _requeue_on_conflict(sub, daemon):
    """The next-best walk skipped: a request whose first choice was taken
    goes back to the queue."""
    daemon.config = dataclasses.replace(daemon.config,
                                        conflict_policy="requeue")


@pytest.mark.parametrize("name,faults,expect", [
    ("tiny-flat", {"scorer": _shifted_scores}, "score_err"),
    ("tiny-sharded", {"scorer": _shifted_scores}, "score_err"),
    ("tiny-sharded", {"scorer": _half_batch}, "score_err"),
    ("tiny-sharded", {"scorer": _swap_choice}, "choice_gap"),
    ("tiny-flat", {"substrate": _frozen_state}, "state_diff"),
    ("tiny-sharded", {"substrate": _wrong_node}, "state_diff"),
    ("tiny-flat", {"substrate": _worst_first}, "bind_gap"),
    ("tiny-sharded", {"substrate": _worst_first}, "bind_gap"),
    ("tiny-flat", {"substrate": _drop_on_conflict}, "decision_errors"),
    ("tiny-sharded", {"substrate": _drop_on_conflict}, "decision_errors"),
    ("tiny-flat", {"substrate": _requeue_on_conflict}, "decision_errors"),
    ("tiny-sharded", {"substrate": _requeue_on_conflict}, "decision_errors"),
])
def test_a_broken_timed_path_is_not_correct(name, faults, expect):
    run, nums = _run(name, faults)
    bad = _failed(nums)
    assert expect in bad, nums
    assert not check.passed([(n, v, lim) for n, (v, lim) in nums.items()])


def _one_batch(decisions, n_real):
    """A sampled batch of ``n_real`` identical pods over eight nodes whose
    best node has room for one pod only, and the reference's ranking."""
    from bench.lib import cluster, reference as ref

    cfg = _config("tiny-flat")
    cfg["nodes"]["classes"][0]["count"] = 8
    types = cluster.pod_types(cfg)
    cols = cluster.reset(cfg, np.random.default_rng(SEED))
    weights = cluster.config_weights(cfg)
    q = ref.afterstate_q(cols, types[0], cfg["physics"], weights)
    ok = ref.feasible(cols, types[0])
    order = np.argsort(-np.where(ok, q, -np.inf), kind="stable")
    # requested CPU is no feature: the scores stay, the room shrinks
    cols["cpu_requested"][order[0]] = (cols["cpu_capacity"][order[0]]
                                       - np.float32(types[0].cpu_request))
    ok = ref.feasible(cols, types[0])
    t = types[0]
    sample = {"pos": 0, "n_real": n_real,
              "pods": np.float32([[t.cpu_request, t.cpu_demand,
                                   t.mem_request, t.mem_demand]] * n_real),
              "out0": np.float32([q] * n_real), "out1": np.array([ok] * n_real),
              "decisions": [(0, -1 if r < 0 else int(order[r]))
                            for r in decisions]}
    return check.sample_numbers([sample], {0: cols}, types, cfg["physics"],
                                weights, cfg["scoring"])


@pytest.mark.parametrize("ranks,n_real,bad", [
    ([0, 1], 2, []),                       # the best, then the next best
    ([0, 2], 2, ["bind_gap"]),             # the walk skips a feasible node
    ([1, 0], 2, ["bind_gap"]),             # the first choice is not the best
    ([0, -1], 2, ["decision_errors"]),     # dropped with a node free
    ([0], 2, ["decision_errors"]),         # re-queued with a node free
])
def test_the_commit_loop_is_held_to_its_rule(ranks, n_real, bad):
    nums = _one_batch(ranks, n_real)
    assert nums["score_err"] < 1e-6 and nums["feasible_mismatch"] == 0
    failed = sorted(n for n in ("bind_gap", "decision_errors")
                    if nums[n] > (1e-4 if n == "bind_gap" else 0))
    assert failed == bad, nums
