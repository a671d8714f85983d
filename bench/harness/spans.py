"""The program's own spans in a profiler trace of the window.

The program opens `jax.profiler.TraceAnnotation` spans at its layer
boundaries (`runtime.*`, `master.*`, `optimizer.*`, `backend.*`; see
`repro.core.telemetry.Spans`), and the benchmark adds `dorm.pass` and
`backend.<program>` around the master and the backend. This module sweeps
those host spans once:

* at each instant of the window the innermost open span is what the host
  was doing (`runtime.event_loop` where none is open), which gives each
  span's self time;
* each idle gap of the device is split over those instants, so a gap that
  starts in one pass, runs through the event loop and ends in the next is
  charged to each piece in proportion, not whole to the span at its
  midpoint as `trace.reduce_planes` charges it.

The metric readers (`bench/metrics/*.py`) are handed only `ctx`.
`report.run` does not put the trace's path there, so `window(ctx)` takes
it from `ctx["trace_path"]` where a caller has set it, else from the
frame of the caller that built `ctx` (`report.run`, whose `files` lists
the trace); a traced run whose trace it cannot find raises rather than
read nothing. Run as a script on an `.xplane.pb` to print the split.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import trace as trace_mod

PROGRAM_PREFIXES = ("runtime.", "master.", "optimizer.", "backend.")
LOOP = "runtime.event_loop"


def is_label(name: str) -> bool:
    return name == "dorm.pass" or name.startswith(PROGRAM_PREFIXES)


def innermost(spans: Sequence[Tuple[float, float, str]], w0: float,
              w1: float) -> Tuple[np.ndarray, np.ndarray, List[str]]:
    """Properly nested host spans (start, end, name) -> the window cut
    into contiguous segments, each labelled with the innermost span open
    over it (`LOOP` where none is). A child that outlives its parent is cut
    at the parent's end. -> (starts, ends, labels)."""
    starts: List[float] = []
    ends: List[float] = []
    labels: List[str] = []

    def emit(a: float, b: float, label: str) -> None:
        a, b = max(a, w0), min(b, w1)
        if b > a:
            starts.append(a)
            ends.append(b)
            labels.append(label)

    stack: List[Tuple[float, str]] = []
    cur = w0
    for s, e, name in sorted(spans, key=lambda v: (v[0], -v[1])):
        while stack and stack[-1][0] <= s:
            end, label = stack.pop()
            emit(cur, end, label)
            cur = max(cur, end)
        emit(cur, s, stack[-1][1] if stack else LOOP)
        cur = max(cur, s)
        stack.append((min(e, stack[-1][0]) if stack else e, name))
    while stack:
        end, label = stack.pop()
        emit(cur, end, label)
        cur = max(cur, end)
    emit(cur, w1, LOOP)
    return np.asarray(starts), np.asarray(ends), labels


def split_idle(busy: Sequence[Sequence[float]], seg_starts: np.ndarray,
               seg_ends: np.ndarray, labels: List[str], w0: float,
               w1: float) -> Dict[str, float]:
    """Seconds of the window in which the device ran nothing, per label of
    the segment the host was in. `busy`: merged [start, end) ns intervals;
    the segments cover the window contiguously."""
    edges = [w0] + [min(max(v, w0), w1) for iv in busy for v in iv] + [w1]
    g0 = np.asarray(edges[0::2], np.float64)
    g1 = np.asarray(edges[1::2], np.float64)
    keep = g1 > g0
    g0, g1 = g0[keep], g1[keep]
    cuts = np.unique(np.concatenate([g0, g1, seg_starts, seg_ends]))
    if len(cuts) < 2:
        return {}
    mid = 0.5 * (cuts[:-1] + cuts[1:])
    length = np.diff(cuts)
    gi = np.searchsorted(g0, mid, side="right") - 1
    idle = (gi >= 0) & (mid < g1[np.maximum(gi, 0)])
    si = np.searchsorted(seg_starts, mid, side="right") - 1
    inside = (si >= 0) & (mid < seg_ends[np.maximum(si, 0)])
    names = sorted(set(labels))
    code = {n: i for i, n in enumerate(names)}
    seg_code = np.asarray([code[n] for n in labels], np.int64)
    pick = idle & inside
    by = np.bincount(seg_code[si[pick]], weights=length[pick],
                     minlength=len(names))
    out = {n: float(by[i]) * 1e-9 for i, n in enumerate(names) if by[i]}
    rest = float(length[idle & ~inside].sum()) * 1e-9
    if rest:
        out[LOOP] = out.get(LOOP, 0.0) + rest
    return out


def reduce_planes(planes) -> dict:
    """-> the window's host spans: `total_s` and `count` per span name
    (clipped to the window), `self_s` per innermost label, and `idle_s`,
    the device's idle time split over the same labels."""
    host: List[Tuple[float, float, str]] = []
    window = None
    busy_iv: List[Tuple[float, float]] = []
    for plane in planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != "XLA Ops":
                continue
            for ev in line.events:
                s, e = ev.start_ns, ev.start_ns + ev.duration_ns
                if device:
                    busy_iv.append((s, e))
                elif ev.name == "bench.window":
                    window = (s, e)
                elif is_label(ev.name):
                    host.append((s, e, ev.name))
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = window
    total: Dict[str, float] = {}
    count: Dict[str, int] = {}
    for s, e, name in host:
        a, b = max(s, w0), min(e, w1)
        if b > a:
            total[name] = total.get(name, 0.0) + (b - a) * 1e-9
            count[name] = count.get(name, 0) + 1
    seg_s, seg_e, labels = innermost(host, w0, w1)
    self_s: Dict[str, float] = {}
    for a, b, label in zip(seg_s.tolist(), seg_e.tolist(), labels):
        self_s[label] = self_s.get(label, 0.0) + (b - a) * 1e-9
    clipped = [(max(s, w0), min(e, w1)) for s, e in busy_iv
               if min(e, w1) > max(s, w0)]
    busy_s, merged = trace_mod.union_length(clipped)
    return {"window_s": (w1 - w0) * 1e-9, "busy_s": busy_s * 1e-9,
            "total_s": total, "count": count, "self_s": self_s,
            "idle_s": split_idle(merged, seg_s, seg_e, labels, w0, w1)}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)


_LAST: List[object] = [None, None]          # [path, its reduction]


def trace_path(ctx: dict) -> Optional[str]:
    """The `.xplane.pb` behind `ctx`: `ctx["trace_path"]` where the caller
    put it there, else the first of `files` in the frame of the caller
    that built `ctx` (`report.run`). None for an untraced run; a traced
    run (`ctx["trace"]` set) whose trace cannot be found raises."""
    path = ctx.get("trace_path")
    frame = sys._getframe(1)
    while path is None and frame is not None:
        loc = frame.f_locals
        if loc.get("ctx") is ctx and loc.get("files"):
            path = loc["files"][0]
        frame = frame.f_back
    if path is None and ctx.get("trace") is not None:
        raise LookupError("a traced run's ctx, but no caller holds its "
                          "trace files: pass ctx['trace_path']")
    return path


def window(ctx: dict) -> Optional[dict]:
    """The reduction of the trace behind `ctx` (`trace_path`), or None
    when the run was not traced."""
    path = trace_path(ctx)
    if path is None:
        return None
    if _LAST[0] != path:
        _LAST[:] = [path, reduce_file(path)]
    return _LAST[1]


def per_pass_ms(ctx: dict, seconds: Optional[float]) -> Optional[float]:
    """`seconds` of the window over its passes, in ms (None stays None)."""
    passes = len(ctx["run"]["rec"].pass_wall)
    if seconds is None or not passes:
        return None
    return 1e3 * seconds / passes


def main(argv=None) -> int:
    paths = (argv if argv is not None else sys.argv[1:])
    for path in paths:
        out = reduce_file(path)
        print(json.dumps({
            "trace": path, "window_s": out["window_s"],
            "busy_s": out["busy_s"],
            "idle_s": sorted(out["idle_s"].items(), key=lambda kv: -kv[1]),
            "self_s": sorted(out["self_s"].items(), key=lambda kv: -kv[1]),
            "count": out["count"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
