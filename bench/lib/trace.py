"""Reduction of a profiler trace to device busy time, module time and the
host's activity during idle gaps.

Two stages, so that the arithmetic can be tested on a small recorded trace:

1. ``load_xplane`` reads the ``.xplane.pb`` the JAX profiler wrote and keeps
   only what the metrics need, as plain lists: the device's ``XLA Ops`` and
   ``XLA Modules`` events and the host's ``bench.*`` annotations (the
   window), each as ``[name, start_ns, duration_ns]`` on the profiler's
   common clock; ``add_host_spans`` adds the benchmark's own host spans.
2. ``reduce_trace`` works on those lists alone.

Busy time is the union of the device's op intervals inside the window, not
their sum, so overlapping ops count once.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# what the host was doing while the device sat idle, by the innermost
# benchmark span covering the gap
HOST_LABELS = {
    "bench.snapshot": "snapshot publish (host)",
    "bench.score": "scoring launch and readback wait (host)",
    "bench.poll": "batch loop: pack, readback copy, commit (host)",
    "bench.submit": "load generator submitting (host)",
    "bench.window": "between polls: generator waiting or looping (host)",
}


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def load_xplane(path: str) -> Dict[str, list]:
    """The device and host events the reduction reads, from an xplane file."""
    from jax.profiler import ProfileData

    out = {"device_ops": [], "device_modules": [], "host": [], "chips": 0}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            out["chips"] += 1
            for line in plane.lines:
                key = {"XLA Ops": "device_ops",
                       "XLA Modules": "device_modules"}.get(line.name)
                if key is not None:
                    out[key].extend([e.name, e.start_ns, e.duration_ns]
                                    for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [e.name, e.start_ns, e.duration_ns] for e in line.events
                    if e.name.startswith(HOST_PREFIX))
    return out


def add_host_spans(trace: Dict[str, list], spans, anchor_s: float) -> None:
    """Put spans kept on the host's ``perf_counter`` clock, as ``(name,
    start_s, seconds)``, onto the trace's clock, anchored at the start of
    the window annotation, which began at ``anchor_s`` on the host clock."""
    lo, _ = _window(trace)
    offset = lo - anchor_s * 1e9
    trace["host"].extend([name, offset + s * 1e9, d * 1e9]
                         for name, s, d in spans)


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals: Sequence[Tuple[float, float]], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The ``[start, end)`` stretches of ``[lo, hi)`` no interval covers."""
    gaps, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            gaps.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(s, e) for s, e in gaps if e > s]


def short_op_name(hlo: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``; a Pallas kernel
    keeps its name (``vmap_jit_sdqn_score_afterstate__``)."""
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)


def _window(trace: Dict[str, list]) -> Tuple[float, float]:
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if len(spans) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, found "
                           f"{len(spans)}")
    return spans[0]


def _label(host: Sequence[Tuple[str, float, float]], starts: Sequence[float],
           window: Tuple[float, float], t: float) -> str:
    """Label of the innermost ``bench.*`` span covering ``t``.  Spans nest
    (window > poll > snapshot, score) and a poll holds at most three
    children, so the innermost one is among the last few spans that started
    before ``t``; failing those, the window itself."""
    inner, inner_len = None, None
    at = bisect.bisect_right(starts, t)
    for name, s, d in host[max(at - 6, 0):at]:
        if s <= t < s + d and (inner_len is None or d < inner_len):
            inner, inner_len = name, d
    if inner is None and window[0] <= t < window[1]:
        inner = WINDOW_SPAN
    return HOST_LABELS.get(inner, "outside the benchmark's spans (host)")


def reduce_trace(trace: Dict[str, list], module_prefixes: Sequence[str] = (),
                 top: int = 10) -> dict:
    """Busy and idle time of the device over the benchmark window, device
    time per module prefix, the ops that took most time, and the idle time
    by what the host was doing.  Averages over the traced chips."""
    lo, hi = _window(trace)
    chips = max(int(trace.get("chips", 1)), 1)
    ops = [(n, s, s + d) for n, s, d in trace["device_ops"]
           if s < hi and s + d > lo]
    busy = union_ns(((s, e) for _, s, e in ops), lo, hi) / chips
    window = hi - lo
    by_op: Dict[str, float] = {}
    for n, s, e in ops:
        k = short_op_name(n)
        by_op[k] = by_op.get(k, 0.0) + (min(e, hi) - max(s, lo))
    modules = {}
    for prefix in module_prefixes:
        evs = [(s, s + d) for n, s, d in trace["device_modules"]
               if n.startswith(prefix) and s < hi and s + d > lo]
        modules[prefix] = {
            "device_s": sum(min(e, hi) - max(s, lo) for s, e in evs) / 1e9
            / chips,
            "launches": len(evs),
        }
    host = sorted(((n, s, d) for n, s, d in trace["host"]
                   if n != WINDOW_SPAN), key=lambda h: h[1])
    starts = [s for _, s, _ in host]
    by_label: Dict[str, float] = {}
    for s, e in idle_gaps([(s, e) for _, s, e in ops], lo, hi):
        k = _label(host, starts, (lo, hi), 0.5 * (s + e))
        by_label[k] = by_label.get(k, 0.0) + (e - s)
    return {
        "busy_s": busy / 1e9,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy / window,
        "modules": modules,
        "device_ops": [[k, v / 1e9 / chips] for k, v in sorted(
            by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
    }
