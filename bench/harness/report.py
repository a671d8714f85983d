"""From one run to its result line: end-to-end metrics, the check, the
trace reduction and the per-layer metric readers."""
from __future__ import annotations

import glob
import os
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from . import cells, check, driver, trace as trace_mod


def end_to_end(run: dict) -> Dict[str, float]:
    """Every event of the window is charged the full wall time of the pass
    that decided it; the rate is all events over all the window's time."""
    rec = run["rec"]
    wall = np.asarray(rec.pass_wall, np.float64)
    k = np.asarray(rec.pass_k, np.int64)
    per_event = np.repeat(wall, k)
    return {
        "events_per_s": float(k.sum()) / run["window_s"],
        "decision_p50_ms": 1e3 * float(np.percentile(per_event, 50)),
        "decision_p95_ms": 1e3 * float(np.percentile(per_event, 95)),
        "setup_s": float(run["setup_s"]),
    }


def device_info(devices) -> dict:
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    stats = dev.memory_stats() if hasattr(dev, "memory_stats") else None
    info["memory_peak_bytes"] = int((stats or {}).get("peak_bytes_in_use", 0))
    return info


def run(cell: dict, seed: int, seconds: float, trace_dir: Optional[str],
        t_start: float, devices, log: Callable[[str], None],
        hooks=None) -> Tuple[dict, Dict[str, tuple]]:
    """-> (the result line, {number compared: (value, limit)})."""
    wl = cell["workload"]
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; cell {wl['name']} ({wl['chips']} chip(s)), seed "
        f"{seed}, window {seconds:g} s, trace {int(trace_dir is not None)}")
    r = driver.run_window(cell, seed, seconds, trace_dir, t_start, log, hooks)
    dev = device_info(devices)
    rec = r["rec"]
    events = int(sum(rec.pass_k))
    passes = len(rec.pass_wall)
    log(f"window: {r['window_s']:.3f} s, {passes} passes, {events} events "
        f"(latency samples), compiles in the window: {r['compiles']} "
        f"(traces {r['traces']}), set-up {r['setup_s']:.3f} s")
    log(f"full DRF refills in the window: {r['full_refills']} of {passes} "
        f"passes (share {r['full_refills'] / max(passes, 1):.4f})")

    g = cell["config"]["guarantees"]
    limits = cells.load_json("limits.json")
    values = check.numbers(r, g)
    correct = check.judge(values, limits)
    checks = {k: (values[k], limits[k]) for k in limits}
    log(f"compared: {values['passes_compared']} passes, "
        f"{values['calls_compared']} place_run calls, "
        f"{len(rec.tape)} passes on the timeline")

    log(f"wrong decisions by layer: timeline {values['timeline_wrong']}, "
        f"passes {values['passes_wrong']}, place_run calls "
        f"{values['backend_wrong']}")
    wrong = int(values["wrong_decisions"])
    line = {"correct": bool(correct), "attempted": events,
            "failed": 0 if correct else max(wrong, 1), "metrics": {},
            "device": dev}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    if trace_dir is None:
        e2e = end_to_end(r)
        line["metrics"] = {n: {"value": e2e[n], "unit": u}
                           for n, u in units.items()}
    else:
        files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        reduced = trace_mod.reduce_file(files[0]) if files else None
        ctx = {"run": r, "trace": reduced,
               "peaks": trace_mod.peaks_for(dev["kind"])}
        for m in cell["per_layer"]:
            v = cells.metric_reader(m["name"])(ctx)
            if v is not None:
                line["metrics"][m["name"]] = {"value": v, "unit": m["unit"]}
        if reduced is not None and reduced["devices"]:
            line["device"]["busy_s"] = reduced["busy_s"]
            line["device"]["window_s"] = reduced["window_s"]
            line["breakdown"] = reduced["breakdown"]
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    return line, checks
