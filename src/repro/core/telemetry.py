"""Telemetry: structured metric logging, spans and compile counts.

Production CMSs stream scheduler state for dashboards and postmortems; Dorm's
equivalent is a JSONL metrics log. `MetricsLogger` is accepted by the
simulator (timeline export) and usable by ElasticTrainers (per-step rows).

`Spans` times the scheduler's own layers (runtime, master, optimizer,
backend) on the profiler's clock: every span is also a
`jax.profiler.TraceAnnotation`, so a profiler trace holds it next to the
device's ops. `compile_counter()` counts jax's compiles per program name.
"""
from __future__ import annotations

import json
import os
import re
import threading
from time import perf_counter
from typing import Any, Dict, List, Optional


class MetricsLogger:
    """Append-only JSONL metrics sink with an in-memory mirror."""

    def __init__(self, path: Optional[str] = None):
        self.path = path
        self.rows: List[Dict[str, Any]] = []
        self._fh = None
        if path:
            os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
            self._fh = open(path, "a")

    def log(self, kind: str, **fields: Any) -> None:
        row = {"kind": kind, **fields}
        self.rows.append(row)
        if self._fh:
            self._fh.write(json.dumps(row) + "\n")
            self._fh.flush()

    def attach(self, bus) -> None:
        """Subscribe to a `runtime.EventBus`: every cluster event becomes a
        kind="event" row (the samples already flow in via the runtime's
        `logger=` hook; this adds the event stream itself -- arrivals with
        app ids, completions, resizes, ticks)."""
        from .runtime import (Arrival, Completion, Migrate, Reallocated,
                              Resize, ScaleDecision, Tick)

        bus.subscribe(Arrival, lambda e: self.log(
            "event", event="arrival", t=e.t,
            apps=[s.app_id for s in e.specs]))
        bus.subscribe(Completion, lambda e: self.log(
            "event", event="completion", t=e.t, app=e.app_id))
        bus.subscribe(Resize, lambda e: self.log(
            "event", event="resize", t=e.t, app=e.app_id,
            n_min=e.n_min, n_max=e.n_max))
        bus.subscribe(Tick, lambda e: self.log(
            "event", event="tick", t=e.t))
        bus.subscribe(Migrate, lambda e: self.log(
            "event", event="migrate", t=e.t, app=e.app_id,
            src_shard=e.src_shard, dst_shard=e.dst_shard,
            forced=e.forced))
        bus.subscribe(Reallocated, lambda e: self.log(
            "event", event="reallocated", t=e.t,
            adjusted=list(e.result.adjusted_app_ids),
            started=list(e.result.started_app_ids)))
        bus.subscribe(ScaleDecision, lambda e: self.log(
            "event", event="scale_decision", t=e.t, app=e.app_id,
            reason=e.reason, qps=e.qps, utilization=e.utilization,
            n_min=e.n_min_new, n_max=e.n_max_new))

    def log_phase_breakdown(self, breakdown: Dict[str, float],
                            t: Optional[float] = None, **extra: Any) -> None:
        """Record a scheduler per-phase timing breakdown (DormMaster.
        phase_breakdown(): cumulative solve / drf_refill / colgen_pricing /
        enforce / metrics seconds) as a kind="phase" row."""
        row: Dict[str, Any] = dict(breakdown)
        if t is not None:
            row["t"] = t
        row.update(extra)
        self.log("phase", **row)

    def of_kind(self, kind: str) -> List[Dict[str, Any]]:
        return [r for r in self.rows if r["kind"] == kind]

    def close(self) -> None:
        if self._fh:
            self._fh.close()
            self._fh = None

    # ------------------------------------------------------------ exports

    def utilization_timeline(self):
        """[(t, utilization)] from simulator samples."""
        return [(r["t"], r["utilization"]) for r in self.of_kind("sample")]

    def summary(self) -> Dict[str, Any]:
        samples = self.of_kind("sample")
        if not samples:
            return {}
        out = {
            "events": len(samples),
            "max_fairness_loss": max(r["fairness_loss"] for r in samples),
            "total_adjustments": sum(r["adjustment_overhead"]
                                     for r in samples),
        }
        phases = self.of_kind("phase")
        if phases:
            out["phase_breakdown"] = {
                k: v for k, v in phases[-1].items()
                if k not in ("kind", "t") and isinstance(v, (int, float))}
        return out


# ------------------------------------------------------------------ spans


class Spans:
    """A registry of named spans, read by `DormMaster.phase_s` and the
    optimizers' `refill_s` / `pricing_s`.

    `span(name, **meta)` is a context manager that adds its wall time to
    `total_s[name]`. While a profiler session records, it also enters
    `jax.profiler.TraceAnnotation(name, **meta)`, so the trace holds the
    span on the device's clock (an annotation made with no session records
    nothing, so none is made)."""

    def __init__(self) -> None:
        from jax.profiler import TraceAnnotation
        self._annotation = TraceAnnotation
        self.total_s: Dict[str, float] = {}

    def span(self, name: str, **meta: Any) -> "_Span":
        ann = self._annotation
        return _Span(self.total_s, name,
                     ann(name, **meta) if ann.is_enabled() else None)


class _Span:
    __slots__ = ("total", "name", "ann", "t0")

    def __init__(self, total: Dict[str, float], name: str, ann: Any) -> None:
        self.total, self.name, self.ann = total, name, ann

    def __enter__(self) -> "_Span":
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc: Any) -> None:
        dt = perf_counter() - self.t0
        total, name = self.total, self.name
        total[name] = total.get(name, 0.0) + dt
        if self.ann is not None:
            self.ann.__exit__(*exc)


# --------------------------------------------------------------- compiles

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HITS = "/jax/compilation_cache/cache_hits"
_JIT_NAME = re.compile(r"^jit\((.*)\)$")


class CompileCounter:
    """jax's compiles per program name, from its own monitoring events.

    `count[name]` and `seconds[name]` count each XLA compile of program
    `name`. jax times its persistent-cache lookup inside the same event,
    so a load from that cache counts as a compile; `cache_hits` counts the
    loads (jax does not name the program on that event). A program is
    named by its dispatcher where one has set `owner.name` around the call
    (`JaxBackend` names its programs `dorm.<program>`, which no other jit
    in the process can collide with), else by the jitted function's
    name."""

    def __init__(self) -> None:
        self.count: Dict[str, int] = {}
        self.seconds: Dict[str, float] = {}
        self.cache_hits = 0
        self.owner = threading.local()

    def on_duration(self, event: str, duration: float, fun_name: str = "",
                    **_: Any) -> None:
        if event == _BACKEND_COMPILE:
            name = getattr(self.owner, "name", None)
            if name is None:
                m = _JIT_NAME.match(fun_name)
                name = m.group(1) if m else fun_name
            self.count[name] = self.count.get(name, 0) + 1
            self.seconds[name] = self.seconds.get(name, 0.0) + duration

    def on_event(self, event: str, **_: Any) -> None:
        if event == _CACHE_HITS:
            self.cache_hits += 1


_COMPILES: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    """The process's compile counter; jax's compile caches are process-wide,
    so is this count. Registered with `jax.monitoring` on first use."""
    global _COMPILES
    if _COMPILES is None:
        from jax import monitoring
        _COMPILES = CompileCounter()
        monitoring.register_event_duration_secs_listener(
            _COMPILES.on_duration)
        monitoring.register_event_listener(_COMPILES.on_event)
    return _COMPILES
