"""Cluster simulation facades over the shared `core.runtime` event loop.

Reproduces the paper's evaluation (§V): the Table-II workload is submitted
online; on every arrival/completion the scheduler reallocates; application
progress follows linear data-parallel scaling (work is measured in
container-seconds); each Dorm adjustment (save → kill → resume) pauses the
affected application for the protocol's adjustment cost -- that pause IS the
sharing overhead of Fig 9(b).

Outputs a metric timeline (utilization Eq 1, fairness loss Eq 2, adjustment
overhead Eq 4) plus per-application completion records for speedup (Fig 9a).

Two implementations of the same semantics:

* `ClusterSimulator` -- the production path: a thin facade that builds a
  `runtime.ClusterRuntime` around the scheduler (any `SchedulerPolicy` or a
  legacy submit/complete scheduler) and runs the shared vectorized event
  loop. At `batch_window_s = 0` (default) the event sequence, samples and
  completions are bit-identical to the reference implementation (pinned by
  tests/test_scale.py).
* `ReferenceClusterSimulator` -- the seed's scalar event loop, kept verbatim
  as the golden reference for the runtime's vectorized path.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .runtime import (AbsorberConfig, AppRuntime, ClusterRuntime, EventBus,
                      MetricSample, ReallocationResult, SimResult, as_policy)
from .workload import WorkloadApp

_EPS = 1e-9

__all__ = [
    "AppRuntime", "MetricSample", "SimResult", "ClusterSimulator",
    "ReferenceClusterSimulator", "speedup_ratios",
]


class _SimulatorBase:
    """Shared construction + sampling for both simulator implementations."""

    _supports_batching = False

    def __init__(self, scheduler, workload: Sequence[WorkloadApp],
                 adjustment_cost_s: float = 60.0,
                 rate_multiplier: float = 1.0,
                 horizon_s: float = 48 * 3600.0,
                 logger=None,
                 batch_window_s: float = 0.0):
        """`rate_multiplier` < 1 models task-level scheduling overhead
        (baselines.TaskLevelOverheadModel); Dorm runs at 1.0 because its
        TaskSchedulers place tasks locally (§III-D). `logger`: optional
        core.telemetry.MetricsLogger receiving every sample/event row.
        `batch_window_s` > 0 coalesces arrivals landing within that window
        (and before the next completion) into ONE scheduler pass."""
        self.scheduler = scheduler
        self.workload = list(workload)
        self.adjustment_cost_s = adjustment_cost_s
        self.rate_multiplier = rate_multiplier
        self.horizon_s = horizon_s
        self.logger = logger
        self.batch_window_s = batch_window_s
        if batch_window_s > 0:
            # Fail loudly: silently falling back to per-arrival scheduling
            # would let a "batched" benchmark measure an unbatched run.
            if not self._supports_batching:
                raise ValueError(
                    f"{type(self).__name__} does not support batch_window_s")
            if not (hasattr(scheduler, "on_arrival")
                    or hasattr(scheduler, "submit_batch")):
                raise ValueError(
                    f"batch_window_s > 0 requires a scheduler with "
                    f"on_arrival or submit_batch; "
                    f"{type(scheduler).__name__} has neither")
        self.runtimes: Dict[str, AppRuntime] = {}
        self.samples: List[MetricSample] = []
        self.total_adjustments = 0

    def _sample(self, res: ReallocationResult, t: float) -> None:
        self.samples.append(MetricSample(
            t=t,
            utilization=res.utilization,
            fairness_loss=res.fairness_loss,
            adjustment_overhead=res.adjustment_overhead,
            running=len(res.allocation.app_ids),
            pending=len(res.pending_app_ids),
            goodput=res.goodput))
        if self.logger is not None:
            self.logger.log("sample", t=t, utilization=res.utilization,
                            fairness_loss=res.fairness_loss,
                            adjustment_overhead=res.adjustment_overhead,
                            running=len(res.allocation.app_ids),
                            pending=len(res.pending_app_ids),
                            adjusted=list(res.adjusted_app_ids),
                            started=list(res.started_app_ids))


class ClusterSimulator(_SimulatorBase):
    """Facade: one `ClusterRuntime` drive of the scheduler (production path).

    Kept for API stability (every benchmark/example constructs simulators);
    new code that needs Resize/Tick injection or bus subscribers should use
    `runtime.ClusterRuntime` directly -- `self.runtime` is that instance."""

    _supports_batching = True

    def __init__(self, scheduler, workload: Sequence[WorkloadApp],
                 adjustment_cost_s: float = 60.0,
                 rate_multiplier: float = 1.0,
                 horizon_s: float = 48 * 3600.0,
                 logger=None,
                 batch_window_s: float = 0.0,
                 tick_interval_s: float = 0.0,
                 bus: Optional[EventBus] = None,
                 absorber: Optional[AbsorberConfig] = None,
                 chaos=None):
        """`absorber` (runtime.AbsorberConfig) turns on the mixed-flood
        event-storm absorber: arrivals + completions + resizes at the same
        timestamp (or inside the configured window) coalesce into ONE
        policy pass. Mutually exclusive with `batch_window_s`.

        `chaos` (chaos.ChaosConfig) injects a seeded slave failure /
        drain / straggler schedule into the run (fault-injection)."""
        super().__init__(scheduler, workload,
                         adjustment_cost_s=adjustment_cost_s,
                         rate_multiplier=rate_multiplier,
                         horizon_s=horizon_s, logger=logger,
                         batch_window_s=batch_window_s)
        self.runtime = ClusterRuntime(
            as_policy(scheduler),
            adjustment_cost_s=adjustment_cost_s,
            rate_multiplier=rate_multiplier,
            horizon_s=horizon_s, logger=logger,
            batch_window_s=batch_window_s,
            tick_interval_s=tick_interval_s, bus=bus,
            absorber=absorber, chaos=chaos)

    # ------------------------------------------------------------------ run

    def run(self) -> SimResult:
        result = self.runtime.run(self.workload)
        # Mirror runtime state so pre-runtime consumers of the simulator
        # object itself keep working.
        self.runtimes = self.runtime.runtimes
        self.samples = self.runtime.samples
        self.total_adjustments = self.runtime.total_adjustments
        return result


class ReferenceClusterSimulator(_SimulatorBase):
    """The seed's scalar event loop -- golden reference for the runtime's
    vectorized path (no event batching; one scheduler pass per arrival)."""

    # ------------------------------------------------------------------ run

    def run(self) -> SimResult:
        arrivals = sorted(self.workload, key=lambda w: w.spec.submit_time)
        ai = 0
        t = 0.0
        active: Dict[str, AppRuntime] = {}

        while True:
            t_arr = (arrivals[ai].spec.submit_time
                     if ai < len(arrivals) else np.inf)
            t_fin, fin_app = self._next_completion(active, t)
            t_next = min(t_arr, t_fin)
            if not np.isfinite(t_next) or t_next > self.horizon_s:
                self._advance(active, t, min(self.horizon_s, t_next))
                break
            self._advance(active, t, t_next)
            t = t_next

            if t_fin <= t_arr and fin_app is not None:
                rt = active.pop(fin_app)
                rt.finished_at = t
                rt.containers = 0
                res = self.scheduler.complete(fin_app)
                self._apply(res, active, t)
                self._sample(res, t)
            else:
                w = arrivals[ai]
                ai += 1
                rt = AppRuntime(app=w, remaining_work=w.spec.serial_work,
                                submitted_at=t)
                self.runtimes[w.spec.app_id] = rt
                active[w.spec.app_id] = rt
                res = self.scheduler.submit(w.spec)
                self._apply(res, active, t)
                self._sample(res, t)

        return SimResult(samples=self.samples, completions=self.runtimes,
                         total_adjustments=self.total_adjustments,
                         horizon_s=min(self.horizon_s, t))

    # ------------------------------------------------------------ internals

    def _advance(self, active: Dict[str, AppRuntime], t0: float, t1: float,
                 ) -> None:
        """Integrate progress over [t0, t1] (rates are piecewise-constant,
        changing only at pause expiries inside the interval)."""
        if t1 <= t0:
            return
        for rt in active.values():
            lo = t0
            if rt.paused_until > lo:
                lo = min(rt.paused_until, t1)
            dt = t1 - lo
            if dt > 0:
                # speedup() is the container count itself under the
                # default linear model (seed arithmetic unchanged) and
                # goodput(N) for curved apps.
                spd = rt.app.spec.speedup(rt.containers)
                rt.remaining_work = max(
                    0.0, rt.remaining_work
                    - dt * spd * self.rate_multiplier)

    def _next_completion(self, active: Dict[str, AppRuntime], t: float,
                         ) -> Tuple[float, Optional[str]]:
        best_t, best_a = np.inf, None
        for a, rt in active.items():
            rate = rt.app.spec.speedup(rt.containers) * self.rate_multiplier
            if rate <= 0:
                continue
            start = max(t, rt.paused_until)
            tf = start + rt.remaining_work / rate
            if tf < best_t:
                best_t, best_a = tf, a
        return best_t, best_a

    def _apply(self, res: ReallocationResult, active: Dict[str, AppRuntime],
               t: float) -> None:
        # container counts
        counts = {a: 0 for a in active}
        for i, app_id in enumerate(res.allocation.app_ids):
            counts[app_id] = int(res.allocation.row_at(i).sum())
        for a, rt in active.items():
            rt.containers = counts.get(a, 0)
            if rt.containers > 0 and rt.started_at is None:
                rt.started_at = t
        # adjustment downtime (save -> kill -> resume)
        for a in res.adjusted_app_ids:
            if a in active:
                active[a].paused_until = t + self.adjustment_cost_s
                active[a].n_adjustments += 1
        self.total_adjustments += len(res.adjusted_app_ids)


def speedup_ratios(dorm: SimResult, baseline: SimResult,
                   skipped: Optional[Dict[str, str]] = None,
                   ) -> Dict[str, float]:
    """Fig 9(a): per-app duration(baseline) / duration(dorm).

    Only apps that completed in BOTH runs are comparable; previously the
    others (and any zero-duration dorm app) were dropped SILENTLY, so a
    run where Dorm finished half the jobs could report a great "speedup"
    over the half it happened to share with the baseline. Now:

    * pass `skipped` (a dict) to receive every non-comparable app with
      the reason -- "dorm-only" (finished under Dorm but not the
      baseline) or "baseline-only";
    * a non-positive duration for a dorm-completed app raises instead of
      being filtered: completions always carry finished_at > submitted_at
      in a healthy run, so a zero/negative duration means broken clock
      bookkeeping, not a fast job, and dividing by it would fabricate an
      infinite speedup.
    """
    d1, d0 = dorm.durations(), baseline.durations()
    out: Dict[str, float] = {}
    for a, dur in d1.items():
        if a not in d0:
            if skipped is not None:
                skipped[a] = "dorm-only"
            continue
        if dur <= 0:
            raise ValueError(
                f"non-positive dorm duration for {a!r}: {dur} "
                f"(finished_at <= submitted_at -- corrupt completion record)")
        out[a] = d0[a] / dur
    if skipped is not None:
        for a in d0:
            if a not in d1:
                skipped[a] = "baseline-only"
    return out
