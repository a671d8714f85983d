"""From a profiler trace of the window to device numbers.

Reads the `.xplane.pb` that `jax.profiler` writes, with nothing but jax:

* the window is the host span `bench.window` that `harness/driver.py`
  opens and closes around the measured passes;
* busy time is the union of the intervals in which an XLA op ran on a
  device plane (`/device:...`, line `XLA Ops`), clipped to the window and
  averaged over the devices that ran anything;
* a program's device time is the sum of its executions on the
  `XLA Modules` line (`jit_place_run(...)` counts for `place_run`);
* each idle gap between busy intervals is charged to what the host was
  doing at its midpoint: the innermost benchmark span (`backend.*` inside
  `dorm.pass`), or the runtime's event loop outside any pass.
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Tuple

import numpy as np

_BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MODULE_NAME = re.compile(r"^(?:jit_)?([^(]+?)(?:\(.*\))?$")


def peaks_for(device_kind: str, path: Optional[str] = None) -> dict:
    """The chip's published peaks. A device missing from the table is an
    error, never a default."""
    with open(path or os.path.join(_BENCH_DIR, "peaks.json")) as fh:
        table = json.load(fh)["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[device_kind]


def op_name(event: str) -> str:
    """A device op's short name: TPU trace events carry the whole HLO
    instruction text; keep its name and a custom call's target."""
    name = event.split(" = ", 1)[0]
    m = re.search(r'custom_call_target="([^"]+)"', event)
    return f"{name} ({m.group(1)})" if m else name


def program_name(module_event: str) -> str:
    m = _MODULE_NAME.match(module_event)
    return m.group(1) if m else module_event


def union_length(intervals: List[Tuple[float, float]]) -> Tuple[float, list]:
    """-> (total length, merged intervals) of [start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def reduce_planes(planes) -> dict:
    """`planes`: objects with `.name` and `.lines`, lines with `.name` and
    `.events`, events with `.name`, `.start_ns`, `.duration_ns` (what
    `jax.profiler.ProfileData` gives). -> the window's device numbers."""
    host_spans: List[Tuple[float, float, str]] = []
    window = None
    devices = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name
                if name == "bench.window":
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif name == "dorm.pass" or name.startswith("backend."):
                    host_spans.append((ev.start_ns,
                                       ev.start_ns + ev.duration_ns, name))
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = window
    programs: Dict[str, Dict[str, float]] = {}
    ops: Dict[str, float] = {}
    busy_total, n_busy = 0.0, 0
    merged_all: List[list] = []
    for plane in devices:
        intervals = []
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                s = max(ev.start_ns, w0)
                e = min(ev.start_ns + ev.duration_ns, w1)
                if e <= s:
                    continue
                if line.name == "XLA Modules":
                    p = programs.setdefault(program_name(ev.name),
                                            {"device_s": 0.0, "count": 0})
                    p["device_s"] += (e - s) * 1e-9
                    p["count"] += 1
                else:
                    intervals.append((s, e))
                    op = op_name(ev.name)
                    ops[op] = ops.get(op, 0.0) + (e - s) * 1e-9
        if intervals:
            length, merged = union_length(intervals)
            busy_total += length
            n_busy += 1
            merged_all.extend(merged)
    window_s = (w1 - w0) * 1e-9
    busy_s = busy_total * 1e-9 / max(n_busy, 1)

    # Idle gaps of the (first) busy device, charged to the host's activity.
    gaps: Dict[str, float] = {}
    _, merged = union_length([tuple(iv) for iv in merged_all])
    edges = [w0] + [v for iv in merged for v in iv] + [w1]
    spans = sorted(host_spans)
    starts = np.asarray([s for s, _, _ in spans], np.float64)
    for k in range(0, len(edges), 2):
        g0, g1 = edges[k], edges[k + 1]
        if g1 <= g0:
            continue
        mid = 0.5 * (g0 + g1)
        label = "runtime.event_loop"
        best = None
        hi = int(np.searchsorted(starts, mid, side="right"))
        for s, e, name in spans[max(0, hi - 64):hi]:
            if s <= mid < e and (best is None or e - s < best):
                best, label = e - s, name
        gaps[label] = gaps.get(label, 0.0) + (g1 - g0) * 1e-9
    breakdown = {
        "device_ops": [[n, s] for n, s in sorted(
            ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[n, s] for n, s in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:10]],
    }
    return {"window_s": window_s, "busy_s": busy_s, "programs": programs,
            "devices": n_busy, "breakdown": breakdown}


def reduce_file(path: str) -> dict:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
