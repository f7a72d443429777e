"""One run of a serving cell: the placement daemon under the cell's traffic.

The window drives ``PlacementDaemon.submit`` / ``poll`` over a
``ClusterSubstrate`` -- flat, or the two-stage sharded path when the
configuration names shards -- exactly as a deployment would.  The
benchmark's own code sits only around it:

* a ``ClusterSubstrate`` subclass that, in ``--trace 1`` runs, times the
  snapshot publish and the scoring launch (each ended by
  ``block_until_ready``) and marks them for the profiler, and in every run
  keeps a seeded sample of the window's batches for the check: each one's
  scorer outputs and the decisions its commit loop made;
* the load generator (``traffic.py``) and the FIFO retirement that holds the
  resident pod count at the pre-filled ``R``;
* the check (``check.py``), run after the window has closed and the
  device's peak memory has been read.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import shutil
import tempfile
import time
from typing import Dict, Optional

import numpy as np

from bench.lib import cluster, reference, trace as tracemod, traffic
from bench.lib.check import BIND, UNBIND

# the scorer's jitted module, as the device trace names it
SCORER_MODULE = "jit_score("


class _Recorder:
    """Seeded reservoir of the window's scorer calls (for the check), and the
    trace runs' timings of the snapshot publish and the scoring launch."""

    def __init__(self, rng: np.random.Generator, size: int, timed: bool,
                 events: list, fault=None):
        self.rng, self.size, self.timed, self.events = rng, size, timed, events
        self.fault = fault
        self.active = False
        self.calls = 0
        self.samples: list = []
        # the daemon's decision count at each window batch's scoring launch:
        # a batch's decisions are those made before the next launch
        self.n_decisions = None
        self.first_decision: list = []
        self.snapshot_s: list = []
        self.score_s: list = []
        # (name, start, seconds) on the host clock, for the trace's labels
        self.spans: list = []

    def timed_call(self, name: str, times: list, fn, *args):
        import jax

        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        times.append(dt)
        self.spans.append((name, t0, dt))
        return out

    def wrap(self, program):
        inner = program if self.fault is None else self.fault(program)

        def scorer(params, snap, pods, carry, n_real):
            if not self.active:
                return inner(params, snap, pods, carry, n_real)
            if self.timed:
                out = self.timed_call("bench.score", self.score_s, inner,
                                      params, snap, pods, carry, n_real)
            else:
                out = inner(params, snap, pods, carry, n_real)
            keep = {"pos": len(self.events), "call": self.calls,
                    "n_real": int(n_real), "pods": pods, "out0": out[0],
                    "out1": out[1]}
            self.first_decision.append(self.n_decisions())
            self.calls += 1
            if len(self.samples) < self.size:
                self.samples.append(keep)
            else:
                j = int(self.rng.integers(self.calls))
                if j < self.size:
                    self.samples[j] = keep
            return out

        # the daemon's compile-count and program-text probes
        scorer._cache_size = program._cache_size
        scorer.lower = program.lower
        return scorer


def _substrate_class():
    from repro.sched.daemon import ClusterSubstrate

    class BenchSubstrate(ClusterSubstrate):
        recorder: Optional[_Recorder] = None

        def snapshot(self):
            rec = self.recorder
            if rec is None or not (rec.active and rec.timed):
                return super().snapshot()
            return rec.timed_call("bench.snapshot", rec.snapshot_s,
                                  super().snapshot)

        def make_scorer(self, fused):
            return self.recorder.wrap(super().make_scorer(fused))

    return BenchSubstrate


def program_lacks(ref: reference.Reference) -> str:
    """What the program lacks of the node columns and pod fields the
    configuration's reference states (``"ClusterState lacks a, b; PodSpec
    lacks c"``), or ``""``.  It imports the program's types only: no device
    work, no compile."""
    from repro.core.types import ClusterState, PodSpec

    out = []
    for kind, have, want in (("ClusterState", ClusterState._fields,
                              ref.COLUMNS),
                             ("PodSpec", PodSpec._fields, ref.POD_FIELDS)):
        lacks = [f for f in want if f not in have]
        if lacks:
            out.append(f"{kind} lacks {', '.join(lacks)}")
    return "; ".join(out)


def env_config(config: dict, n_nodes: int, first: reference.PodType):
    from repro.core.types import EnvConfig

    phys = {k: v for k, v in config["physics"].items()
            if k != "feature_scale"}
    return EnvConfig(n_nodes=n_nodes, pod_cpu_request=first.cpu_request,
                     pod_cpu_demand=first.cpu_demand,
                     pod_mem_request=first.mem_request,
                     pod_mem_demand=first.mem_demand, **phys)


class _CompileCounter:
    """Counts backend compilations while ``active`` (none may happen inside
    the measured window)."""

    def __init__(self):
        import jax

        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and "backend_compile" in event:
            self.count += 1

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


def run_cell(config: dict, mix: dict, seed: int, seconds: float,
             trace: bool, t_process: float, faults=None,
             ref: Optional[reference.Reference] = None) -> Dict:
    """Set up, warm up, measure for ``seconds``, drain, check.  Returns the
    run's record; the caller turns it into metrics.  ``ref`` is the
    configuration's reference (``reference.for_config(config)`` where
    None): the node columns, pod rows, weights and pre-fill are its, and
    the record carries it to the check.  ``faults`` (tests
    only) patches the substrate or daemon after set-up, to show the check
    catches a broken timed path: ``{"scorer": f}`` wraps the program's
    scorer as ``f(scorer)`` underneath the benchmark's recorder, and
    ``{"substrate": g}`` calls ``g(substrate, daemon)`` after set-up."""
    import jax
    import jax.numpy as jnp

    from repro.core.types import ClusterState, PodSpec
    from repro.launch.mesh import plan_fleet_layout
    from repro.sched.daemon import DaemonConfig, PlacementDaemon

    t_enter = time.perf_counter()
    traffic.check_mix(mix)
    ref = reference.for_config(config) if ref is None else ref
    ss = np.random.SeedSequence(seed)
    r_cluster, r_prefill, r_order, r_stream, r_sample = (
        np.random.default_rng(s) for s in ss.spawn(5))
    types = ref.pod_types(config)
    cols = ref.reset(config, r_cluster)
    fifo_init = cluster.prefill(cols, types, config["prefill"]["fill_frac"],
                                cluster.PodStream(types, r_prefill), r_order,
                                ref)
    resident = len(fifo_init)
    weights = ref.config_weights(config)
    start_cols = {k: v.copy() for k, v in cols.items()}
    n_nodes = len(cols["cpu_capacity"])
    stream = cluster.PodStream(types, r_stream)
    pods = [PodSpec(**{f: getattr(t, f) for f in ref.POD_FIELDS})
            for t in types]

    t_cluster = time.perf_counter()
    scoring = config["scoring"]
    layout = (plan_fleet_layout(n_nodes, shards=scoring["shards"])
              if scoring.get("shards") else None)
    events: list = []
    faults = faults or {}
    rec = _Recorder(r_sample, int(config["check"]["samples"]), trace, events,
                    faults.get("scorer"))
    Sub = _substrate_class()
    Sub.recorder = rec
    state = ClusterState(time_s=np.float32(0.0),
                         **{k: cols[k] for k in ref.COLUMNS})
    sub = Sub(state, env_config(config, n_nodes, types[0]), layout=layout,
              topk=scoring.get("topk", 8))
    params = {k: jnp.asarray(v) for k, v in weights.items()}
    daemon = PlacementDaemon(sub, params, DaemonConfig(**config["daemon"]),
                             clock=time.perf_counter)
    rec.n_decisions = lambda: len(daemon.decisions)
    if "substrate" in faults:
        faults["substrate"](sub, daemon)

    fifo = collections.deque(map(tuple, fifo_init.tolist()))
    kind_of: list = []

    def submit(now: float) -> None:
        t = stream.next()
        rid = daemon.submit(pods[t], now=now)
        if rid != len(kind_of):
            raise RuntimeError(f"request id {rid}, expected {len(kind_of)}")
        kind_of.append(t)

    def settle(n_before: int) -> None:
        """Log the poll's decisions and retire the oldest pods above R."""
        for d in daemon.decisions[n_before:]:
            if d.node >= 0:
                t = kind_of[d.req_id]
                events.append((BIND, d.node, t))
                fifo.append((d.node, t))
        while len(fifo) > resident:
            node, t = fifo.popleft()
            sub.unbind(node, pods[t])
            events.append((UNBIND, node, t))

    t_daemon = time.perf_counter()
    # warm-up: compile the scorer at the serving shapes, then two real
    # batches through the whole path (snapshot, pack, launch, commit)
    compiles = _CompileCounter()
    daemon.warmup()
    for _ in range(2):
        now = time.perf_counter()
        for _ in range(daemon.config.batch_size):
            submit(now)
        n0 = len(daemon.decisions)
        daemon.flush(now)
        settle(n0)
    while daemon.pending:
        n0 = len(daemon.decisions)
        daemon.flush()
        settle(n0)
    jax.block_until_ready(params)
    warm_requests = len(kind_of)
    # what set-up made (the pre-filled FIFO alone holds a tuple a resident
    # pod) is kept out of the window's garbage collections, so they scan
    # only what the window makes
    gc.collect()
    gc.freeze()

    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # the window is the one profiler annotation; the other spans are kept on
    # the host clock only when they did work, and moved onto the trace's
    # clock afterwards, so a spinning generator adds no trace events
    window_mark = (jax.profiler.TraceAnnotation("bench.window") if trace
                   else contextlib.nullcontext())

    spans = {"poll_s": [], "batch_sizes": []}
    depth = int(mix["depth"])
    n_dec0 = len(daemon.decisions)
    conflicts0 = daemon.metrics.conflicts
    rec.active = True
    compiles.active = True
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    t_end = t0 + seconds
    with window_mark:
        t_anchor = time.perf_counter()
        while True:
            now = time.perf_counter()
            if now >= t_end:
                break
            n_sub = len(kind_of)
            while daemon.pending < depth:
                submit(now)
            n0, b0 = len(daemon.decisions), daemon.metrics.batches
            t_poll = time.perf_counter()
            if trace and len(kind_of) > n_sub:
                rec.spans.append(("bench.submit", now, t_poll - now))
            daemon.poll(t_poll)
            if daemon.metrics.batches > b0:
                t_done = time.perf_counter()
                spans["poll_s"].append(t_done - t_poll)
                if trace:
                    rec.spans.append(("bench.poll", t_poll, t_done - t_poll))
                spans["batch_sizes"].append(len(daemon.decisions) - n0)
            settle(n0)
    t_close = time.perf_counter()
    compiles.active = False
    compiles.close()
    rec.active = False
    window_s = t_close - t0
    n_dec_window = len(daemon.decisions)
    window_conflicts = daemon.metrics.conflicts - conflicts0
    if trace:
        jax.profiler.stop_trace()
    # everything still pending is decided
    while daemon.pending:
        n0 = len(daemon.decisions)
        daemon.flush()
        settle(n0)

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    m = daemon.metrics
    counters = {k: getattr(m, k) for k in (
        "submitted", "bound", "dropped", "shed", "conflicts", "requeued",
        "batches", "device_launches", "fallback_batches")}
    window_decisions = daemon.decisions[n_dec0:n_dec_window]
    window_ids = range(warm_requests, len(kind_of))
    by_id = {d.req_id: d for d in daemon.decisions}
    ends = rec.first_decision[1:] + [n_dec_window]

    def batch_decisions(call: int) -> list:
        """(pod type, node) of each decision the batch's commit loop made,
        in order; -1 for a drop."""
        return [(kind_of[d.req_id], int(d.node)) for d in
                daemon.decisions[rec.first_decision[call]:ends[call]]]

    samples = [{"pos": s["pos"], "n_real": s["n_real"],
                "pods": np.stack([np.asarray(getattr(s["pods"], f))
                                  for f in ref.POD_FIELDS], axis=1),
                "out0": np.asarray(s["out0"]), "out1": np.asarray(s["out1"]),
                "decisions": batch_decisions(s["call"])}
               for s in rec.samples]
    run = {
        "config": config, "ref": ref, "types": types, "weights": weights,
        "start_cols": start_cols, "events": events, "samples": samples,
        "live_cols": {k: np.array(v) for k, v in sub.live._asdict().items()},
        "decisions": list(daemon.decisions), "submitted": len(kind_of),
        "counters": counters, "resident": resident, "n_nodes": n_nodes,
        "setup_s": setup_s, "window_s": window_s,
        # where set-up went: imports and device start-up, the cluster and
        # pre-fill in numpy, the substrate and daemon, the warm-up
        "setup_phases_s": {"start": t_enter - t_process,
                           "cluster": t_cluster - t_enter,
                           "daemon": t_daemon - t_cluster,
                           "warm_up": t0 - t_daemon},
        "window_bound": sum(d.node >= 0 for d in window_decisions),
        "window_requests": [by_id[i] for i in window_ids],
        "commit_attempts": sum(d.attempts for d in window_decisions),
        "window_conflicts": window_conflicts,
        "spans": dict(spans, snapshot_s=rec.snapshot_s, score_s=rec.score_s),
        "compiles_in_window": compiles.count,
        "scorer_compiles": daemon.scorer_cache_size(),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count(),
                   "memory_peak_bytes": int(stats.get("peak_bytes_in_use",
                                                      0))},
        "trace": None,
    }
    host_spans = rec.spans
    del daemon, sub, rec, params
    gc.unfreeze()
    gc.collect()
    if trace:
        try:
            raw = tracemod.load_xplane(tracemod.find_xplane(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        tracemod.add_host_spans(raw, host_spans, t_anchor)
        run["trace"] = tracemod.reduce_trace(raw, (SCORER_MODULE,))
    return run
