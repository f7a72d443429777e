"""The comparison that decides ``correct`` for a serving cell.

Inputs are the benchmark's own start columns, weights and pod mix, the
ordered binds and retirements of the run, the scorer outputs and the
commit loop's decisions of a seeded sample of the window's batches, and the
program's final live buffer.  The configuration's plain reference
(``reference.py``, or the module its file names) re-derives everything else.

Numbers compared, each against the limit the configuration file states:

* ``score_err``     -- largest gap between a score the program returned and
  the float64 reference's score of that node, over feasible nodes (flat
  path) or returned candidates (two-stage path), relative to the row's
  largest reference score magnitude (at least ``SCALE_FLOOR``).  The two-stage path is also held
  to the reference's own per-shard top-k values.
* ``choice_gap``    -- largest gap by which the program's first choice lies
  below the reference's best feasible node, on the same scale.
* ``bind_gap``      -- largest gap by which a node the commit loop bound
  lies below the best of the snapshot's candidates that still had room at
  that bind, by the reference's scores, on the same scale: the loop binds
  the snapshot's first choice, or walks to the next best still feasible
  (``conflict_policy`` next-best).
* ``feasible_mismatch`` -- rows or nodes where the program's filtering, its
  candidate set or its packed request disagrees with the reference.
* ``decision_errors`` -- requests dropped or sent back to the queue while
  one of their candidates still had room.
* ``bind_violations``   -- binds to a node that had no room at that moment.
* ``state_diff``    -- largest difference between the final live buffer and
  the reference's replay of every bind and retirement.
* ``accounting_errors`` -- requests not decided exactly once, or
  bound + dropped + shed != submitted.
* ``fallback_batches``  -- batches served by the host heuristic instead of
  the scoring launch.
"""
from __future__ import annotations

import collections
from typing import Dict, List, Sequence, Tuple

import numpy as np

from bench.lib import reference

BIND, UNBIND = 0, 1
# the smallest row scale a relative gap is taken against
SCALE_FLOOR = 1e-3
EXACT = ("feasible_mismatch", "decision_errors", "bind_violations",
         "state_diff", "accounting_errors", "fallback_batches")
ORDER = ("score_err", "choice_gap", "bind_gap") + EXACT


def _pod_rows(types: Sequence, ref: reference.Reference) -> np.ndarray:
    """(T, F) float32: each pod type as the program packs it, in the order
    of ``ref.POD_FIELDS``."""
    return np.float32([[getattr(t, f) for f in ref.POD_FIELDS]
                       for t in types])


def _type_of(row: Sequence[float], rows: np.ndarray) -> int:
    for i, want in enumerate(rows):
        if np.array_equal(np.float32(row), want):
            return i
    return -1


def replay(start_cols, types, phys, events, positions,
           ref: reference.Reference):
    """Replay the run; returns (replay, bind violations, columns at each
    requested event position)."""
    rp = ref.Replay(start_cols, types, phys)
    want = sorted(set(positions))
    at: Dict[int, dict] = {}
    w = 0
    violations = 0
    for i, (kind, node, t) in enumerate(events):
        while w < len(want) and want[w] == i:
            at[i] = rp.copy_cols()
            w += 1
        if kind == BIND:
            violations += not rp.bind(node, t)
        else:
            rp.unbind(node, t)
    while w < len(want):
        at[want[w]] = rp.copy_cols()
        w += 1
    return rp, violations, at


class _Type:
    """The reference's view of one pod type at a batch's snapshot: scores,
    filter, the candidates the commit loop may walk, and the control's."""

    def __init__(self, cols, pod, phys, weights, scoring, precision,
                 control, ref: reference.Reference):
        self.q = ref.afterstate_q(cols, pod, phys, weights)
        self.ok = ref.feasible(cols, pod)
        self.scale = (max(float(np.max(np.abs(self.q[self.ok]))), SCALE_FLOOR)
                      if self.ok.any() else 1.0)
        self.best = float(np.max(self.q[self.ok])) if self.ok.any() else -np.inf
        shards = scoring.get("shards")
        if shards:
            self.cand_vals, idx = ref.candidates(self.q, self.ok, shards,
                                                 scoring["topk"])
            self.cand = idx[np.isfinite(self.cand_vals)]
        else:
            self.cand = np.flatnonzero(self.ok)
        self.ctrl_row = self.ctrl_cand = None
        if control:
            qc = ref.afterstate_q(cols, pod, phys, weights, precision)
            if shards:
                self.ctrl_row = ref.candidates(qc, self.ok, shards,
                                               scoring["topk"])
                self.ctrl_cand = self.ctrl_row[1][np.isfinite(
                    self.ctrl_row[0])]
            else:
                self.ctrl_row = (qc, self.ok)
                self.ctrl_cand = self.cand[np.argsort(-qc[self.cand],
                                                      kind="stable")]


def _row_numbers(out_row, ty: _Type, scoring: dict) -> Tuple[float, float, int]:
    """(score_err, choice_gap, mismatches) of one request's scorer output
    against the reference's view ``ty`` of its pod type."""
    qr, okr, scale, best = ty.q, ty.ok, ty.scale, ty.best
    if scoring.get("shards"):
        cv, ci = (np.asarray(x) for x in out_row)
        rv = ty.cand_vals
        fin, rfin = np.isfinite(cv), np.isfinite(rv)
        if fin.sum() != rfin.sum():
            return np.inf, np.inf, 1
        idx = ci[fin]
        if idx.size and (idx.min() < 0 or idx.max() >= qr.shape[0]
                         or not okr[idx].all()):
            return np.inf, np.inf, 1
        if not idx.size:
            return 0.0, 0.0, int(okr.any())
        err = max(float(np.max(np.abs(cv[fin] - qr[idx]))),
                  float(np.max(np.abs(cv[fin] - rv[rfin]))))
        return err / scale, (best - float(qr[idx[0]])) / scale, 0
    q, ok = (np.asarray(x) for x in out_row)
    mism = int(np.sum(ok != okr))
    if not okr.any():
        return 0.0, 0.0, mism
    err = float(np.max(np.abs(q[okr] - qr[okr])))
    choice = int(np.argmax(np.where(ok, q, -np.inf)))
    return err / scale, (best - float(qr[choice])) / scale, mism


def _commit_numbers(s, cols, types, phys, get, control: bool,
                    ref: reference.Reference, rows: np.ndarray
                    ) -> Tuple[float, int]:
    """(bind_gap, decision_errors) of one sampled batch's commit loop.

    The replay starts from the batch's snapshot and applies the batch's
    binds in order.  At each decision, the candidates with room are those
    of the snapshot's candidates that the replayed state can still take
    the pod on: the commit loop binds the best of them (the snapshot's
    first choice, or the next best still feasible), and drops or re-queues
    a request only when there is none.  With ``control`` the gap is that of
    the control's choice at the same moments."""
    rp = ref.Replay(cols, types, phys)
    gap, errors = 0.0, 0
    left = collections.Counter(
        _type_of(s["pods"][r], rows) for r in range(s["n_real"]))
    for t, node in s["decisions"]:
        left[t] -= 1
        ty = get(t)
        room = ty.cand[ref.feasible(rp.cols, types[t])[ty.cand]]
        if control:
            pick = ty.ctrl_cand[ref.feasible(rp.cols, types[t])[
                ty.ctrl_cand]]
            choice = int(pick[0]) if pick.size else -1
        else:
            choice = node
        if choice < 0:
            errors += int(room.size > 0)
        elif room.size:
            gap = max(gap, (float(np.max(ty.q[room])) - float(ty.q[choice]))
                      / ty.scale)
        if node >= 0:
            rp.bind(node, t)
    # rows with no decision went back to the queue: rightly only when none
    # of their candidates had room, which binds can only take away, so the
    # batch's end state decides
    for t, n in left.items():
        if n > 0 and t >= 0:
            ty = get(t)
            room = ref.feasible(rp.cols, types[t])[ty.cand]
            errors += n * int(room.any())
        elif n < 0 or t < 0:
            errors += abs(n)
    return gap, errors


def sample_numbers(samples, at, types, phys, weights, scoring: dict,
                   precision: str = "bf16", control: bool = False,
                   ref: reference.Reference = reference.BASE) -> dict:
    """score_err, choice_gap, bind_gap, feasible_mismatch and
    decision_errors over the sampled batches, by the configuration's
    reference ``ref``.

    With ``control=True`` the scorer outputs and the commit loop's choices
    are replaced by the reference computed at ``precision`` (the control
    put in the program's place)."""
    err = gap = bgap = 0.0
    mism = derr = 0
    rows = _pod_rows(types, ref)
    for s in samples:
        cols = at[s["pos"]]
        cache: Dict[int, _Type] = {}

        def get(t):
            if t not in cache:
                cache[t] = _Type(cols, types[t], phys, weights, scoring,
                                 precision, control, ref)
            return cache[t]

        for r in range(s["n_real"]):
            t = _type_of(s["pods"][r], rows)
            if t < 0:
                mism += 1
                continue
            ty = get(t)
            row = ty.ctrl_row if control else (s["out0"][r], s["out1"][r])
            e, g, m = _row_numbers(row, ty, scoring)
            err, gap, mism = max(err, e), max(gap, g), mism + m
        b, d = _commit_numbers(s, cols, types, phys, get, control, ref,
                               rows)
        bgap, derr = max(bgap, b), derr + d
    return {"score_err": err, "choice_gap": gap, "bind_gap": bgap,
            "feasible_mismatch": mism, "decision_errors": derr}


def accounting(decisions, n_submitted: int, metrics: dict) -> int:
    ids = [d.req_id for d in decisions]
    errors = abs(len(ids) - len(set(ids)))
    errors += abs(len(set(ids)) - n_submitted)
    errors += abs(metrics["bound"] + metrics["dropped"] + metrics["shed"]
                  - n_submitted)
    return errors


def serving_checks(run: dict, limits: Dict[str, float]
                   ) -> List[Tuple[str, float, float]]:
    """Every number compared, with its limit, in the order they print."""
    cfg, ref = run["config"], run["ref"]
    positions = [s["pos"] for s in run["samples"]]
    rp, violations, at = replay(run["start_cols"], run["types"],
                                cfg["physics"], run["events"], positions, ref)
    numbers = sample_numbers(run["samples"], at, run["types"],
                             cfg["physics"], run["weights"], cfg["scoring"],
                             ref=ref)
    numbers.update({
        "bind_violations": violations,
        "state_diff": ref.state_diff(rp.cols, run["live_cols"]),
        "accounting_errors": accounting(run["decisions"], run["submitted"],
                                        run["counters"]),
        "fallback_batches": run["counters"]["fallback_batches"],
    })
    out = []
    for name in ORDER:
        limit = 0.0 if name in EXACT else float(limits[name])
        out.append((name, float(numbers[name]), limit))
    return out


def control_numbers(run: dict, precision: str = "bf16") -> Dict[str, float]:
    """score_err, choice_gap and bind_gap of the reference at ``precision``
    put in the program's place, on the run's own sampled batches."""
    cfg, ref = run["config"], run["ref"]
    _, _, at = replay(run["start_cols"], run["types"], cfg["physics"],
                      run["events"], [s["pos"] for s in run["samples"]], ref)
    nums = sample_numbers(run["samples"], at, run["types"], cfg["physics"],
                          run["weights"], cfg["scoring"], precision,
                          control=True, ref=ref)
    return {k: nums[k] for k in ("score_err", "choice_gap", "bind_gap")}


def passed(numbers: Sequence[Tuple[str, float, float]]) -> bool:
    return all(np.isfinite(v) and v <= lim for _, v, lim in numbers)
