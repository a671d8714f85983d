"""Event-driven cluster runtime: ONE event loop for master, simulator and
baselines.

Before this module existed the repo had three divergent event loops --
`DormMaster.reallocate` (live enforcement), `ClusterSimulator` (vectorized
simulation) and the baseline schedulers in `baselines.py` (each owning a
private submit/complete loop). They are now collapsed into:

  * a typed event vocabulary -- `Arrival`, `Completion`, `Resize`, `Tick`
    (inputs) and `Reallocated` (output notification),
  * an `EventBus` observers subscribe to by event type (telemetry export,
    live-training bridges, dashboards),
  * a `SchedulerPolicy` interface that every cluster manager implements:
    Dorm (`DormMaster` with MILP/greedy/auto optimizers), static
    partitioning (`baselines.StaticScheduler`) and the Mesos/YARN-style DRF
    allocator (`baselines.DRFScheduler`),
  * `ClusterRuntime` -- the single event loop. It owns time: it orders
    arrivals, predicts completions from vectorized progress integration,
    merges externally injected `Resize` requests and periodic `Tick`s, calls
    the policy exactly once per event, applies the resulting allocation to
    the per-app progress state, and samples the paper's Eq-1/2/4 metrics.

The progress arithmetic is lifted unchanged from the PR-1 vectorized
simulator, so a `ClusterRuntime` drive of any policy reproduces the seed
`ReferenceClusterSimulator` timeline bit-for-bit (pinned by
tests/test_scale.py via `ClusterSimulator`, which is now a thin facade over
this runtime).
"""
from __future__ import annotations

import dataclasses
import heapq
import time as _time
from typing import (Any, Callable, Dict, List, Optional, Protocol, Sequence,
                    Tuple, Union, runtime_checkable)

import numpy as np

from .telemetry import Spans
from .types import Allocation, ApplicationSpec
from .workload import WorkloadApp

_EPS = 1e-9


# ---------------------------------------------------------------------------
# Typed events
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Arrival:
    """One or more applications submitted at time `t` (a burst admitted in
    one scheduler pass when event batching is on)."""
    t: float
    specs: Tuple[ApplicationSpec, ...]


@dataclasses.dataclass(frozen=True)
class Completion:
    """Application `app_id` finished at time `t`."""
    t: float
    app_id: str


@dataclasses.dataclass(frozen=True)
class Resize:
    """External request to re-bound `app_id`'s elasticity at time `t` (e.g.
    a user widening n_max, or a serving job pinned down during an incident).
    The policy decides the actual container count; `None` keeps a bound."""
    t: float
    app_id: str
    n_min: Optional[int] = None
    n_max: Optional[int] = None


@dataclasses.dataclass(frozen=True)
class Tick:
    """Periodic heartbeat: lets a policy rebalance without an arrival or
    completion trigger (rolling-horizon re-planning hooks in here)."""
    t: float


# ---------------------------------------------------------------------------
# Chaos events (fault injection -- see `repro.core.chaos`)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SlaveFailed:
    """Slave `slave_id` crashed at time `t`: its capacity vanishes
    instantly and every container it hosted is orphaned. Policies with an
    `on_slave_failed` hook run a recovery pass (evict + re-place); policies
    without one simply never see the event (the bus still publishes it)."""
    t: float
    slave_id: str


@dataclasses.dataclass(frozen=True)
class SlaveDrained:
    """Slave `slave_id` drained at time `t` (graceful decommission): its
    capacity is fenced and hosted apps are migrated off. Mechanically the
    capacity goes to zero like a crash; the distinction is semantic (the
    ChaosMonitor attributes drains separately from crashes)."""
    t: float
    slave_id: str


@dataclasses.dataclass(frozen=True)
class SlaveDegraded:
    """Straggler: slave `slave_id` runs at `factor` of its nominal capacity
    from time `t` until a matching `SlaveRestored`."""
    t: float
    slave_id: str
    factor: float = 0.5


@dataclasses.dataclass(frozen=True)
class SlaveRestored:
    """Slave `slave_id` returned to full nominal capacity at time `t`
    (crash replacement arrived, drain finished, straggler recovered)."""
    t: float
    slave_id: str


ChaosEvent = Union[SlaveFailed, SlaveDrained, SlaveDegraded, SlaveRestored]
_CHAOS_TYPES = (SlaveFailed, SlaveDrained, SlaveDegraded, SlaveRestored)


@dataclasses.dataclass(frozen=True)
class AbsorberConfig:
    """Queue-based event-storm absorber: how `ClusterRuntime` coalesces
    event floods into ONE policy pass (queue-based load leveling).

    With an absorber attached (and a policy implementing `on_batch`),
    arrivals, completions and injected `Resize` events landing at the SAME
    timestamp always coalesce; `window_s` > 0 additionally absorbs events
    within that window of the first one. This generalizes the arrival-only
    `batch_window_s`: completions and resizes join the batch instead of
    splitting it. `Tick`s and non-Resize injections are barriers that end
    collection.

    `adaptive=True` sizes the window from an EWMA of recent policy-pass
    wall time (`latency_factor * ewma`, clipped to [`min_window_s`,
    `max_window_s`], never below `window_s`): when the solver is the
    bottleneck the window widens so floods amortize it; when it is fast it
    shrinks toward pure same-timestamp coalescing.

    Windowed / adaptive absorption intentionally CHANGES the timeline --
    decisions are deferred to the end of the window (and adaptive windows
    depend on wall-clock latency, so they are not run-to-run
    deterministic). Same-timestamp coalescing (window_s=0) does not defer
    anything: simulation time never advances past the triggering instant.
    """
    window_s: float = 0.0
    adaptive: bool = False
    latency_factor: float = 10.0
    min_window_s: float = 0.0
    max_window_s: float = 60.0


@dataclasses.dataclass(frozen=True)
class Storm:
    """One absorbed mixed-event flood (see `AbsorberConfig`): completions,
    resizes and arrivals coalesced into a single policy pass. Every
    constituent event is still published individually on the bus; the Storm
    is the event attached to the flood's single `Reallocated`."""
    t: float
    completions: Tuple[str, ...]
    resizes: Tuple["Resize", ...]
    arrivals: Tuple[ApplicationSpec, ...]
    # Same-instant chaos events (correlated rack loss) folded into the same
    # recovery solve. Empty for ordinary load floods.
    chaos: Tuple["ChaosEvent", ...] = ()


@dataclasses.dataclass(frozen=True)
class Migrate:
    """App migration between control-plane shards (see `repro.core.shard`):
    teardown on the source shard + re-admission on the destination, one
    first-class runtime event. Published by the coordinator for every
    rebalance move it executes, and injectable like `Resize` to force a
    move by hand (dispatched to the policy's `on_migrate` hook; policies
    without the hook get publish-only semantics). `forced` marks moves of
    RUNNING apps (teardown churn charged like PR-8's evictions); a pending
    app's move is free and reported with forced=False."""
    t: float
    app_id: str
    src_shard: int
    dst_shard: int
    forced: bool = True


@dataclasses.dataclass(frozen=True)
class Reallocated:
    """Published on the bus after every applied policy decision."""
    t: float
    event: "Event"
    result: "ReallocationResult"


@dataclasses.dataclass(frozen=True)
class ScaleDecision:
    """Published by an autoscaler (`autoscale.AutoscalePolicy`) when its
    target-tracking control re-bounds a serving app: the observed load, the
    provisioned-capacity utilization it implies, and the [n_min, n_max]
    move. The matching `Resize` is injected separately, so the optimizer --
    not the autoscaler -- still arbitrates the actual container counts."""
    t: float
    app_id: str
    qps: float
    utilization: float               # qps / (containers * qps_per_container)
    containers: int
    n_min_old: int
    n_max_old: int
    n_min_new: int
    n_max_new: int
    reason: str                      # "scale-up" | "scale-down"


Event = Union[Arrival, Completion, Resize, Tick, Storm, Migrate,
              SlaveFailed, SlaveDrained, SlaveDegraded, SlaveRestored]


class EventBus:
    """Minimal typed pub/sub: subscribers register per event class and
    receive every published instance of exactly that class."""

    def __init__(self) -> None:
        self._subs: Dict[type, List[Callable[[Any], None]]] = {}

    def subscribe(self, event_type: type, fn: Callable[[Any], None]) -> None:
        self._subs.setdefault(event_type, []).append(fn)

    def publish(self, event: Any) -> None:
        for fn in self._subs.get(type(event), ()):
            fn(event)


# ---------------------------------------------------------------------------
# Policy interface + results
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ReallocationResult:
    """Outcome of one policy invocation (optimizer pass + enforcement)."""
    allocation: Allocation
    adjusted_app_ids: Tuple[str, ...]       # killed+resumed (Eq 3's r_i = 1)
    started_app_ids: Tuple[str, ...]
    pending_app_ids: Tuple[str, ...]        # admitted but waiting (infeasible)
    utilization: float
    fairness_loss: float
    adjustment_overhead: int
    # Incremental-sync contract with the runtime: {app_id: new container
    # count} for EXACTLY the apps whose count changed since this policy's
    # previous result (empty dict = nothing changed). None = no guarantee;
    # the runtime must rebuild every app's count from `allocation` (the
    # unbounded-churn baselines leave it None on reallocation events).
    changed_counts: Optional[Dict[str, int]] = None
    # Certified optimality gap of the solve that produced this allocation
    # (exact solver paths that can prove a bound: column generation's LP
    # bound, the monolithic MILP's dual bound). None = the path taken
    # certifies nothing (greedy heuristic, rolling horizon, keep-previous
    # fallbacks). 0.0 = proven optimal for P2's utilization objective.
    optimality_gap: Optional[float] = None
    # Chaos recovery attribution (empty on healthy-cluster passes).
    # `forced_adjusted_app_ids` splits Eq-4's churn: the subset of
    # `adjusted_app_ids` whose adjustment was forced by capacity loss, not
    # chosen by the optimizer. `displaced_app_ids` lists every app that lost
    # containers to the dead/fenced slave (including ones that completed or
    # were parked in the same pass); `parked_app_ids` the displaced apps the
    # recovery could not re-place at >= n_min and returned to pending.
    forced_adjusted_app_ids: Tuple[str, ...] = ()
    displaced_app_ids: Tuple[str, ...] = ()
    parked_app_ids: Tuple[str, ...] = ()
    # Apps moved between control-plane shards in this pass (sharded plane
    # only, see `repro.core.shard`). A migrated RUNNING app also appears in
    # `adjusted_app_ids` + `forced_adjusted_app_ids` (teardown +
    # re-admission = one forced Eq-4 adjustment); a migrated PENDING app
    # only appears here (moving a queued app costs nothing).
    migrated_app_ids: Tuple[str, ...] = ()
    # Instantaneous cluster goodput sum_i goodput_i(N_i) of this
    # allocation, in container-equivalents (equals the total granted
    # container count when every app scales linearly). Policies that do
    # not track speedup curves leave the 0.0 default.
    goodput: float = 0.0


@runtime_checkable
class SchedulerPolicy(Protocol):
    """What every cluster manager implements to be driven by the runtime.

    `on_resize` / `on_tick` may return None ("nothing changed, no sample").
    """

    def on_arrival(self, specs: Sequence[ApplicationSpec],
                   ) -> ReallocationResult: ...

    def on_completion(self, app_id: str) -> ReallocationResult: ...

    def on_resize(self, app_id: str, n_min: Optional[int] = None,
                  n_max: Optional[int] = None,
                  ) -> Optional[ReallocationResult]: ...

    def on_tick(self, t: float) -> Optional[ReallocationResult]: ...

    def containers_of(self, app_id: str) -> int: ...


class _LegacyPolicyAdapter:
    """Adapts a pre-runtime scheduler (submit/submit_batch/complete) to the
    SchedulerPolicy interface, for third-party schedulers."""

    def __init__(self, scheduler: Any):
        self.scheduler = scheduler

    def on_arrival(self, specs: Sequence[ApplicationSpec]):
        if len(specs) > 1:
            if not hasattr(self.scheduler, "submit_batch"):
                # Looping submit() would apply/sample only the LAST result,
                # silently dropping the burst's earlier adjustments.
                raise ValueError(
                    f"batched arrival of {len(specs)} specs requires "
                    f"{type(self.scheduler).__name__}.submit_batch")
            return self.scheduler.submit_batch(specs)
        return self.scheduler.submit(specs[0])

    def on_completion(self, app_id: str):
        return self.scheduler.complete(app_id)

    def on_resize(self, app_id: str, n_min=None, n_max=None):
        return None                          # legacy schedulers cannot resize

    def on_tick(self, t: float):
        return None

    def containers_of(self, app_id: str) -> int:
        return self.scheduler.containers_of(app_id)


def as_policy(scheduler: Any) -> Any:
    """Return `scheduler` if it already speaks SchedulerPolicy, else wrap it."""
    if hasattr(scheduler, "on_arrival") and hasattr(scheduler, "on_completion"):
        return scheduler
    if hasattr(scheduler, "submit") and hasattr(scheduler, "complete"):
        return _LegacyPolicyAdapter(scheduler)
    raise TypeError(
        f"{type(scheduler).__name__} implements neither SchedulerPolicy "
        f"(on_arrival/on_completion) nor the legacy submit/complete API")


class PolicyTimer:
    """Transparent SchedulerPolicy wrapper that measures per-event scheduling
    wall time -- the quantity the paper calls per-event sharing overhead and
    benchmarks/bench_scale.py reports as `per_event_policy_ms`.

    Every event is charged the full wall time of the pass that decided it
    (an absorbed flood of K events books K entries of the whole pass), jit
    compiles included. `compile_s` adds up the policy's
    `backend_compile_s` growth over the timed calls."""

    def __init__(self, policy: Any):
        self.policy = as_policy(policy)
        self.calls: List[Tuple[str, float]] = []     # (kind, seconds)
        self.compile_s = 0.0

    def _timed(self, kind: str, fn, *args, k: int = 1, **kw):
        c0 = getattr(self.policy, "backend_compile_s", 0.0)
        t0 = _time.perf_counter()
        try:
            return fn(*args, **kw)
        finally:
            dt = _time.perf_counter() - t0
            self.compile_s += getattr(self.policy, "backend_compile_s",
                                      0.0) - c0
            self.calls.extend([(kind, dt)] * k)

    def on_arrival(self, specs):
        return self._timed("arrival", self.policy.on_arrival, specs)

    def on_completion(self, app_id):
        return self._timed("completion", self.policy.on_completion, app_id)

    def on_resize(self, app_id, n_min=None, n_max=None):
        return self._timed("resize", self.policy.on_resize,
                           app_id, n_min, n_max)

    def on_tick(self, t):
        return self._timed("tick", self.policy.on_tick, t)

    def _on_batch_timed(self, completions, resizes, arrivals, chaos=()):
        """One absorbed flood of K events: K entries under the `absorb`
        kind, each the whole pass's wall time."""
        k = max(len(completions) + len(resizes) + len(arrivals)
                + len(chaos), 1)
        if chaos:
            return self._timed("absorb", self.policy.on_batch, completions,
                               resizes, arrivals, k=k, chaos=chaos)
        return self._timed("absorb", self.policy.on_batch, completions,
                           resizes, arrivals, k=k)

    def containers_of(self, app_id):
        return self.policy.containers_of(app_id)

    def __getattr__(self, name):
        if name == "on_batch":
            # Capability probe: the runtime's absorber checks
            # hasattr(policy, "on_batch") -- expose the timed wrapper only
            # when the wrapped policy implements the hook, so baselines
            # without it still read as batch-incapable through the timer.
            getattr(self.policy, "on_batch")
            return self._on_batch_timed
        return getattr(self.policy, name)

    # ------------------------------------------------------------- readouts

    @property
    def n_calls(self) -> int:
        return len(self.calls)

    def total_s(self) -> float:
        return float(sum(s for _, s in self.calls))

    def mean_ms(self) -> float:
        return 1e3 * self.total_s() / max(self.n_calls, 1)

    def median_ms(self) -> float:
        """Median per-event policy time: robust to OS-jitter spikes and to
        the rare expensive events (full refills), so cross-config ratios
        computed from it are stable even on a loaded machine."""
        if not self.calls:
            return 0.0
        times = sorted(s for _, s in self.calls)
        mid = len(times) // 2
        if len(times) % 2:
            return 1e3 * times[mid]
        return 1e3 * 0.5 * (times[mid - 1] + times[mid])

    def by_kind(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for kind, s in self.calls:
            out[kind] = out.get(kind, 0.0) + s
        return out


# ---------------------------------------------------------------------------
# Per-app progress state + metric records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AppRuntime:
    app: WorkloadApp
    remaining_work: float            # container-seconds
    containers: int = 0
    paused_until: float = 0.0        # adjustment downtime
    submitted_at: float = 0.0
    started_at: Optional[float] = None
    finished_at: Optional[float] = None
    n_adjustments: int = 0

    def rate(self, t: float) -> float:
        if t < self.paused_until - _EPS:
            return 0.0
        # speedup() is float(containers) for the default linear model and
        # goodput(containers) when the spec carries a curve.
        return self.app.spec.speedup(self.containers)


@dataclasses.dataclass
class MetricSample:
    t: float
    utilization: float               # Eq 1 (sum over m resources, in [0, m])
    fairness_loss: float             # Eq 2
    adjustment_overhead: int         # Eq 4 for this reallocation event
    running: int
    pending: int
    # Forced share of this event's Eq-4 churn (chaos recovery; 0 on
    # healthy-cluster passes).
    forced_adjustments: int = 0
    # Instantaneous cluster goodput sum_i goodput_i(N_i) in container-
    # equivalents (== total granted containers under the linear model).
    # 0.0 for policies that do not report it.
    goodput: float = 0.0


@dataclasses.dataclass
class SimResult:
    samples: List[MetricSample]
    completions: Dict[str, AppRuntime]
    total_adjustments: int
    horizon_s: float
    # Chaos reproducibility plumbing: the seed and config hash of the
    # injected `ChaosConfig` schedule (None = healthy run). Any failure
    # replay serialized from this result can be re-run bit-exact by
    # reconstructing the same ChaosConfig and checking the hash matches.
    chaos_seed: Optional[int] = None
    chaos_config_hash: Optional[str] = None
    total_forced_adjustments: int = 0

    def _time_averaged(self, values: np.ndarray,
                       t_max: Optional[float]) -> float:
        """Time-weighted mean of a per-sample step function over [0, t_end]:
        interval k carries sample k-1's value (0 before the first sample),
        clipped to [0, t_end]."""
        if not self.samples:
            return 0.0
        t_end = t_max if t_max is not None else self.horizon_s
        ns = len(self.samples)
        st = np.fromiter((s.t for s in self.samples), np.float64, ns)
        edges = np.concatenate(([0.0], np.minimum(st, t_end), [t_end]))
        u = np.concatenate(([0.0], values))
        total = float((u * np.maximum(0.0, np.diff(edges))).sum())
        return total / max(t_end, _EPS)

    def time_averaged_utilization(self, t_max: Optional[float] = None) -> float:
        """Time-weighted mean of Eq-1 utilization over [0, t_max]."""
        ns = len(self.samples)
        return self._time_averaged(
            np.fromiter((s.utilization for s in self.samples),
                        np.float64, ns), t_max)

    def time_averaged_fairness_loss(self,
                                    t_max: Optional[float] = None) -> float:
        """Time-weighted mean of Eq-2 fairness loss over [0, t_max].

        The event-weighted `mean_fairness_loss` over-counts runs that
        SAMPLE more often inside contention windows (e.g. autoscalers
        injecting Resize events exactly when load spikes); this weights
        each sample by how long its allocation was actually in force, so
        two runs of the same scenario are comparable."""
        ns = len(self.samples)
        return self._time_averaged(
            np.fromiter((s.fairness_loss for s in self.samples),
                        np.float64, ns), t_max)

    def time_averaged_goodput(self, t_max: Optional[float] = None) -> float:
        """Time-weighted mean of instantaneous cluster goodput
        sum_i goodput_i(N_i) over [0, t_max] -- the tentpole metric
        benchmarks/bench_goodput.py compares between count-linear and
        goodput-aware allocation. 0.0 when the driving policy does not
        report goodput (see `ReallocationResult.goodput`)."""
        ns = len(self.samples)
        return self._time_averaged(
            np.fromiter((s.goodput for s in self.samples),
                        np.float64, ns), t_max)

    def max_fairness_loss(self) -> float:
        return max((s.fairness_loss for s in self.samples), default=0.0)

    def mean_fairness_loss(self) -> float:
        if not self.samples:
            return 0.0
        return float(np.fromiter((s.fairness_loss for s in self.samples),
                                 np.float64, len(self.samples)).mean())

    def durations(self) -> Dict[str, float]:
        return {a: (rt.finished_at - rt.submitted_at)
                for a, rt in self.completions.items()
                if rt.finished_at is not None}


# ---------------------------------------------------------------------------
# The runtime
# ---------------------------------------------------------------------------

class ClusterRuntime:
    """The shared event loop.

    Drives a `SchedulerPolicy` over a workload: arrivals come from the
    workload stream, completions from vectorized progress integration
    (linear data-parallel scaling, work in container-seconds; adjustment
    downtime charged per §III-C.2), and `Resize`/`Tick` events from
    `inject()` / `tick_interval_s`. Every processed event and every applied
    `ReallocationResult` is published on `bus`.

    With no injected events and `tick_interval_s=0` the event sequence,
    samples and completions are bit-identical to the seed scalar loop
    (`simulator.ReferenceClusterSimulator`).
    """

    def __init__(self, policy: Any,
                 adjustment_cost_s: float = 60.0,
                 rate_multiplier: float = 1.0,
                 horizon_s: float = 48 * 3600.0,
                 logger=None,
                 batch_window_s: float = 0.0,
                 tick_interval_s: float = 0.0,
                 bus: Optional[EventBus] = None,
                 absorber: Optional[AbsorberConfig] = None,
                 chaos: Optional[Any] = None):
        """`rate_multiplier` < 1 models task-level scheduling overhead
        (baselines.TaskLevelOverheadModel); Dorm runs at 1.0 because its
        TaskSchedulers place tasks locally (§III-D). `batch_window_s` > 0
        coalesces arrivals landing within that window (and before the next
        completion or injected event) into ONE policy pass. `absorber`
        generalizes that to MIXED floods (arrivals + completions + resizes
        in one pass, see `AbsorberConfig`); the two are mutually
        exclusive. `chaos` is a `repro.core.chaos.ChaosConfig`: a seeded
        slave failure/drain/straggler schedule generated from the policy's
        cluster and injected at `run()` start."""
        self.policy = as_policy(policy)
        self.chaos = chaos
        self._chaos_injected = False
        # Chaos events absorb into recovery floods only when the policy can
        # actually recover (probed through PolicyTimer like on_batch);
        # otherwise they are publish-only barriers.
        self._chaos_capable = hasattr(self.policy, "on_slave_failed")
        self.total_forced_adjustments = 0
        self.absorber = absorber
        if absorber is not None:
            if batch_window_s > 0:
                raise ValueError(
                    "absorber and batch_window_s are mutually exclusive: "
                    "AbsorberConfig.window_s generalizes arrival batching "
                    "to mixed event floods")
            if not hasattr(self.policy, "on_batch"):
                raise ValueError(
                    f"absorber requires a policy implementing on_batch("
                    f"completions, resizes, arrivals); "
                    f"{type(self.policy).__name__} does not")
        if (batch_window_s > 0
                and isinstance(self.policy, _LegacyPolicyAdapter)
                and not hasattr(self.policy.scheduler, "submit_batch")):
            # A legacy scheduler without submit_batch would process a burst
            # as N separate submits and only the last result would be
            # applied/sampled -- reject instead of silently dropping events.
            raise ValueError(
                f"batch_window_s > 0 requires a SchedulerPolicy or a "
                f"scheduler with submit_batch; "
                f"{type(self.policy.scheduler).__name__} has neither")
        self.adjustment_cost_s = adjustment_cost_s
        self.rate_multiplier = rate_multiplier
        self.horizon_s = horizon_s
        self.logger = logger
        self.batch_window_s = batch_window_s
        self.tick_interval_s = tick_interval_s
        self.bus = bus if bus is not None else EventBus()
        # (t, seq, event) min-heap: popping by (t, seq) reproduces the old
        # stable sort-by-t order for pre-run injections, and accepts LIVE
        # injections while `run` is in flight (an autoscaler reacting to a
        # Tick injects Resize events for the same instant).
        self._inj_heap: List[Tuple[float, int, Event]] = []
        self._inj_seq = 0
        self.runtimes: Dict[str, AppRuntime] = {}
        self.samples: List[MetricSample] = []
        self.total_adjustments = 0
        # Absorber telemetry: `events` counts events routed through the
        # absorber path, `batches` the coalesced (>= 2 event) passes,
        # `absorbed_events` the events inside those passes, `batch_hist`
        # maps batch size -> occurrences (size-1 "batches" included so the
        # histogram shows the full distribution).
        self.absorber_stats: Dict[str, Any] = {
            "events": 0, "passes": 0, "batches": 0,
            "absorbed_events": 0, "batch_hist": {}}
        self._lat_ewma: Optional[float] = None
        # The event loop's spans: `runtime.scan` (a step's O(trace length)
        # passes over the slot arrays: the next completion and the
        # progress up to it), `runtime.collect` (the absorber's flood
        # collection, whose own scans it holds), `runtime.pass` (one policy
        # call; metadata `pass_id`, `k` events; the policy's own spans nest
        # inside it) and `runtime.finish` (applying, sampling and
        # publishing a decision).
        self.spans = Spans()
        self._pass_id = 0

    def _window_s(self) -> float:
        """Current absorber window: fixed, or latency-adaptive (EWMA of
        recent policy-pass wall time x latency_factor, clipped)."""
        ab = self.absorber
        if ab.adaptive and self._lat_ewma is not None:
            w = ab.latency_factor * self._lat_ewma
            w = min(max(w, ab.min_window_s), ab.max_window_s)
            return max(w, ab.window_s)
        return ab.window_s

    def inject(self, *events: Event) -> None:
        """Queue external events (typically `Resize`). Callable before
        `run` and from WITHIN a running simulation (policy hooks / bus
        subscribers): a mid-run event timestamped at or before the current
        simulation time fires before time advances further."""
        for e in events:
            heapq.heappush(self._inj_heap, (e.t, self._inj_seq, e))
            self._inj_seq += 1

    # ------------------------------------------------------------------ run

    def run(self, workload: Sequence[WorkloadApp]) -> SimResult:
        if self.chaos is not None and not self._chaos_injected:
            # Lazy import: chaos.py imports this module's event types.
            from .chaos import chaos_schedule
            cl = getattr(self.policy, "cluster", None)
            if cl is None:
                raise ValueError("chaos injection requires a policy "
                                 "exposing .cluster")
            self.inject(*chaos_schedule(self.chaos, cl, self.horizon_s))
            self._chaos_injected = True
        arrivals = sorted(workload, key=lambda w: w.spec.submit_time)
        inj_heap = self._inj_heap
        n_total = len(arrivals)
        ai = 0
        t = 0.0
        tick_dt = self.tick_interval_s
        next_tick = tick_dt if tick_dt > 0 else np.inf

        # Slot arrays (slot assigned at submission, in arrival order).
        rem = np.zeros(n_total)
        cont = np.zeros(n_total, dtype=np.int64)
        paused = np.zeros(n_total)
        active = np.zeros(n_total, dtype=bool)
        svc = np.zeros(n_total, dtype=bool)      # service-lifetime apps
        slot_ids: List[Optional[str]] = [None] * n_total
        slot_of: Dict[str, int] = {}
        # Batch slots whose spec carries a goodput curve: rate is
        # goodput(N) * rate_mult instead of N * rate_mult. Empty for every
        # seed workload, so the all-linear rates() array is untouched
        # (bit-exact timelines).
        curved: Dict[int, Any] = {}
        next_slot = 0
        rate_mult = self.rate_multiplier
        use_batch = self.batch_window_s > 0
        absorb = self.absorber is not None
        spans = self.spans

        def rates() -> np.ndarray:
            """Per-slot progress rate. Batch jobs burn container-seconds
            (linear data-parallel scaling, or goodput(N) for curved apps);
            SERVICE apps burn wall-clock seconds of being up -- rate 1
            while any container is placed, regardless of count (extra
            containers are serving capacity, not speedup)."""
            r = np.where(svc, (cont > 0).astype(np.float64),
                         cont * rate_mult)
            for s, curve in curved.items():
                r[s] = curve.at(int(cont[s])) * rate_mult
            return r

        def advance(t0: float, t1: float) -> None:
            """Integrate progress over [t0, t1] (rates are piecewise-
            constant, changing only at pause expiries in the interval)."""
            if t1 <= t0:
                return
            lo = np.maximum(t0, np.minimum(paused, t1))
            dt = t1 - lo
            np.copyto(rem, np.maximum(0.0, rem - dt * rates()),
                      where=active)

        def next_completion() -> Tuple[float, Optional[int]]:
            if n_total == 0:
                return np.inf, None
            rate = rates()
            with np.errstate(divide="ignore", invalid="ignore"):
                tf = np.where(active & (rate > 0),
                              np.maximum(t, paused) + rem / rate, np.inf)
            s = int(np.argmin(tf))
            if not np.isfinite(tf[s]):
                return np.inf, None
            return float(tf[s]), s

        def decide(k: int, fn, *args, **kw) -> Optional[ReallocationResult]:
            """One policy pass deciding `k` events."""
            self._pass_id += 1
            with spans.span("runtime.pass", pass_id=self._pass_id, k=k):
                return fn(*args, **kw)

        def apply(res: ReallocationResult) -> None:
            if res.changed_counts is not None:
                # Incremental sync: touch ONLY the apps the policy reports
                # as changed (adjusted + started), instead of rebuilding
                # every running app's slot state each event.
                for app_id, c in res.changed_counts.items():
                    s = slot_of.get(app_id)
                    if s is None or not active[s]:
                        continue
                    cont[s] = c
                    rt = self.runtimes[app_id]
                    if c > 0 and rt.started_at is None:
                        rt.started_at = t
            else:
                cont[active] = 0
                counts = res.allocation.x.sum(axis=1)
                for i, app_id in enumerate(res.allocation.app_ids):
                    s = slot_of.get(app_id)
                    if s is None or not active[s]:
                        continue
                    c = int(counts[i])
                    cont[s] = c
                    rt = self.runtimes[app_id]
                    if c > 0 and rt.started_at is None:
                        rt.started_at = t
            for app_id in res.adjusted_app_ids:
                s = slot_of.get(app_id)
                if s is not None and active[s]:
                    paused[s] = t + self.adjustment_cost_s
                    self.runtimes[app_id].n_adjustments += 1
            self.total_adjustments += len(res.adjusted_app_ids)
            self.total_forced_adjustments += len(res.forced_adjusted_app_ids)

        def admit(w: WorkloadApp, at: float) -> int:
            nonlocal next_slot
            s = next_slot
            next_slot += 1
            is_svc = w.spec.service_s > 0
            budget = w.spec.service_s if is_svc else w.spec.serial_work
            rt = AppRuntime(app=w, remaining_work=budget, submitted_at=at)
            self.runtimes[w.spec.app_id] = rt
            slot_ids[s] = w.spec.app_id
            slot_of[w.spec.app_id] = s
            svc[s] = is_svc
            if not is_svc and w.spec.goodput is not None:
                curved[s] = w.spec.goodput
            rem[s] = budget
            cont[s] = 0
            paused[s] = 0.0
            active[s] = True
            return s

        def finish(event: Event, res: Optional[ReallocationResult],
                   before: Sequence[Event] = ()) -> None:
            """Publish `before` and `event`, then apply, sample and publish
            the decision `res` (None: nothing was decided)."""
            with spans.span("runtime.finish"):
                for ev in before:
                    self.bus.publish(ev)
                self.bus.publish(event)
                if res is not None:
                    apply(res)
                    self._sample(res, t)
                    self.bus.publish(Reallocated(t, event, res))

        while True:
            t_arr = (arrivals[ai].spec.submit_time
                     if ai < n_total else np.inf)
            # A live injection stamped in the past fires "now": simulation
            # time never moves backwards.
            t_inj = max(inj_heap[0][0], t) if inj_heap else np.inf
            t_ext = min(t_inj, next_tick)
            with spans.span("runtime.scan"):
                t_fin, fin_slot = next_completion()
                t_next = min(t_arr, t_fin, t_ext)
                done = not np.isfinite(t_next) or t_next > self.horizon_s
                advance(t, min(self.horizon_s, t_next) if done else t_next)
            if done:
                break
            t = t_next

            if absorb:
                # Is the event at t_next absorbable (completion, injected
                # Resize/chaos, or arrival)? Ticks and other injections are
                # barriers and fall through to the per-event branches.
                # Chaos events absorb only for recovery-capable policies
                # (a rack-loss flood coalesces into ONE recovery solve).
                inj_abs = ((Resize,) + _CHAOS_TYPES if self._chaos_capable
                           else (Resize,))
                is_fin = (t_fin <= t_arr and t_fin <= t_ext
                          and fin_slot is not None)
                is_ext = (not is_fin) and t_ext <= t_arr
                is_inj = is_ext and t_inj <= next_tick
                absorbable = (is_fin
                              or (is_inj
                                  and isinstance(inj_heap[0][2], inj_abs))
                              or (not is_fin and not is_ext))
                if absorbable:
                    # Collect the flood: every absorbable event at the same
                    # timestamp (window_s=0) or inside the window, in the
                    # SAME tie-break order as the per-event branches below
                    # (completion, then injection, then arrival). State
                    # mutations (slot teardown, admission) happen during
                    # collection; the policy sees the merged flood once.
                    t_end = min(t_next + self._window_s(), self.horizon_s)
                    batch_c: List[str] = []
                    batch_r: List[Resize] = []
                    batch_a: List[WorkloadApp] = []
                    batch_x: List[ChaosEvent] = []
                    pubs: List[Event] = []
                    with spans.span("runtime.collect"):
                        while True:
                            t_arr = (arrivals[ai].spec.submit_time
                                     if ai < n_total else np.inf)
                            t_inj = max(inj_heap[0][0], t) if inj_heap else np.inf
                            t_ext = min(t_inj, next_tick)
                            t_fin, fin_slot = next_completion()
                            if min(t_arr, t_fin, t_ext) > t_end:
                                break
                            if (t_fin <= t_arr and t_fin <= t_ext
                                    and fin_slot is not None):
                                advance(t, t_fin)
                                t = t_fin
                                app_id = slot_ids[fin_slot]
                                rt = self.runtimes[app_id]
                                rt.finished_at = t
                                rt.remaining_work = float(rem[fin_slot])
                                rt.containers = 0
                                rt.paused_until = float(paused[fin_slot])
                                active[fin_slot] = False
                                cont[fin_slot] = 0
                                del slot_of[app_id]
                                curved.pop(fin_slot, None)
                                batch_c.append(app_id)
                                pubs.append(Completion(t, app_id))
                            elif t_ext <= t_arr:
                                if not (t_inj <= next_tick and isinstance(
                                        inj_heap[0][2], inj_abs)):
                                    break         # tick / foreign injection
                                ev = heapq.heappop(inj_heap)[2]
                                advance(t, t_inj)
                                t = t_inj
                                if isinstance(ev, _CHAOS_TYPES):
                                    batch_x.append(ev)
                                    pubs.append(ev)
                                    continue
                                s = slot_of.get(ev.app_id)
                                if s is not None and active[s]:
                                    batch_r.append(ev)
                                    pubs.append(ev)
                                else:
                                    # Dead-target resize: published with no
                                    # result, exactly like the per-event path.
                                    finish(ev, None)
                            else:
                                w = arrivals[ai]
                                ai += 1
                                advance(t, t_arr)
                                t = t_arr
                                admit(w, t_arr)
                                batch_a.append(w)
                    k = (len(batch_c) + len(batch_r) + len(batch_a)
                         + len(batch_x))
                    st = self.absorber_stats
                    st["events"] += k
                    st["passes"] += 1
                    st["batch_hist"][k] = st["batch_hist"].get(k, 0) + 1
                    if k >= 2:
                        st["batches"] += 1
                        st["absorbed_events"] += k
                    t0_wall = _time.perf_counter()
                    if k == 1:
                        # Single event in the window: dispatch through the
                        # per-event hooks so unabsorbed timelines stay
                        # bit-identical to an absorber-free run.
                        if batch_c:
                            finish(pubs[0], decide(
                                1, self.policy.on_completion, batch_c[0]))
                        elif batch_r:
                            ev = batch_r[0]
                            finish(ev, decide(1, self.policy.on_resize,
                                              ev.app_id, ev.n_min, ev.n_max))
                        elif batch_x:
                            finish(pubs[0], decide(1, self._dispatch_chaos,
                                                   batch_x[0]))
                        else:
                            w = batch_a[0]
                            finish(Arrival(t, (w.spec,)), decide(
                                1, self.policy.on_arrival, (w.spec,)))
                    elif k >= 2:
                        specs = tuple(w.spec for w in batch_a)
                        resizes = tuple((r.app_id, r.n_min, r.n_max)
                                        for r in batch_r)
                        if batch_x:
                            res = decide(k, self.policy.on_batch,
                                         tuple(batch_c), resizes, specs,
                                         chaos=tuple(batch_x))
                        else:
                            res = decide(k, self.policy.on_batch,
                                         tuple(batch_c), resizes, specs)
                        if specs:
                            pubs.append(Arrival(t, specs))
                        finish(Storm(t, tuple(batch_c), tuple(batch_r),
                                     specs, tuple(batch_x)), res,
                               before=pubs)
                    # k == 0: flood was only dead-target resizes, already
                    # published during collection; nothing to solve.
                    if self.absorber.adaptive and k:
                        dt_wall = _time.perf_counter() - t0_wall
                        e = self._lat_ewma
                        self._lat_ewma = (dt_wall if e is None
                                          else 0.8 * e + 0.2 * dt_wall)
                    continue

            if t_fin <= t_arr and t_fin <= t_ext and fin_slot is not None:
                app_id = slot_ids[fin_slot]
                rt = self.runtimes[app_id]
                rt.finished_at = t
                rt.remaining_work = float(rem[fin_slot])
                rt.containers = 0
                rt.paused_until = float(paused[fin_slot])
                active[fin_slot] = False
                cont[fin_slot] = 0
                del slot_of[app_id]
                curved.pop(fin_slot, None)
                finish(Completion(t, app_id),
                       decide(1, self.policy.on_completion, app_id))
            elif t_ext <= t_arr:
                if t_inj <= next_tick:
                    ev = heapq.heappop(inj_heap)[2]
                    res = None
                    if isinstance(ev, Resize):
                        s = slot_of.get(ev.app_id)
                        if s is not None and active[s]:
                            res = decide(1, self.policy.on_resize,
                                         ev.app_id, ev.n_min, ev.n_max)
                    elif isinstance(ev, Tick):
                        res = decide(1, self.policy.on_tick, t)
                    elif isinstance(ev, Migrate):
                        # First-class migration: route to the sharded
                        # plane's hook. Single-master policies have no
                        # shards to move between -- publish-only.
                        fn = getattr(self.policy, "on_migrate", None)
                        if fn is not None:
                            res = decide(1, fn, ev.app_id, ev.dst_shard)
                    elif isinstance(ev, _CHAOS_TYPES):
                        res = decide(1, self._dispatch_chaos, ev)
                    finish(ev, res)
                else:
                    next_tick += tick_dt
                    finish(Tick(t), decide(1, self.policy.on_tick, t))
            elif use_batch:
                # Event batching: pull in every arrival landing within the
                # window (and strictly before the next completion or external
                # event); admit the whole burst with ONE policy pass at the
                # last arrival.
                batch = [arrivals[ai]]
                ai += 1
                t_end = min(t + self.batch_window_s, self.horizon_s)
                t_stop = min(t_fin, t_ext)
                while (ai < n_total
                       and arrivals[ai].spec.submit_time <= t_end
                       and arrivals[ai].spec.submit_time < t_stop):
                    batch.append(arrivals[ai])
                    ai += 1
                t_last = batch[-1].spec.submit_time
                with spans.span("runtime.scan"):
                    advance(t, t_last)
                t = t_last
                for w in batch:
                    admit(w, w.spec.submit_time)
                specs = tuple(w.spec for w in batch)
                finish(Arrival(t, specs),
                       decide(len(specs), self.policy.on_arrival, specs))
            else:
                w = arrivals[ai]
                ai += 1
                admit(w, t)
                finish(Arrival(t, (w.spec,)),
                       decide(1, self.policy.on_arrival, (w.spec,)))

        # Sync runtime objects from the slot arrays for result consumers.
        for app_id, s in slot_of.items():
            rt = self.runtimes[app_id]
            rt.remaining_work = float(rem[s])
            rt.containers = int(cont[s])
            rt.paused_until = float(paused[s])

        chaos_seed = None
        chaos_hash = None
        if self.chaos is not None:
            from .chaos import chaos_config_hash
            chaos_seed = int(self.chaos.seed)
            chaos_hash = chaos_config_hash(self.chaos)
        return SimResult(samples=self.samples, completions=self.runtimes,
                         total_adjustments=self.total_adjustments,
                         horizon_s=min(self.horizon_s, t),
                         chaos_seed=chaos_seed,
                         chaos_config_hash=chaos_hash,
                         total_forced_adjustments=(
                             self.total_forced_adjustments))

    # --------------------------------------------------------------- chaos

    def _dispatch_chaos(self, ev: "ChaosEvent"
                        ) -> Optional[ReallocationResult]:
        """Route one chaos event to the policy's recovery hook. Policies
        without the hook get publish-only semantics (res=None): the bus
        still carries the event for monitors, nothing is solved."""
        p = self.policy
        if isinstance(ev, SlaveFailed):
            fn = getattr(p, "on_slave_failed", None)
        elif isinstance(ev, SlaveDrained):
            fn = getattr(p, "on_slave_drained", None)
        elif isinstance(ev, SlaveDegraded):
            fn = getattr(p, "on_slave_degraded", None)
            return fn(ev.slave_id, ev.factor) if fn is not None else None
        else:
            fn = getattr(p, "on_slave_restored", None)
        return fn(ev.slave_id) if fn is not None else None

    # ------------------------------------------------------------- sampling

    def _sample(self, res: ReallocationResult, t: float) -> None:
        self.samples.append(MetricSample(
            t=t,
            utilization=res.utilization,
            fairness_loss=res.fairness_loss,
            adjustment_overhead=res.adjustment_overhead,
            running=len(res.allocation.app_ids),
            pending=len(res.pending_app_ids),
            forced_adjustments=len(res.forced_adjusted_app_ids),
            goodput=res.goodput))
        if self.logger is not None:
            self.logger.log("sample", t=t, utilization=res.utilization,
                            fairness_loss=res.fairness_loss,
                            adjustment_overhead=res.adjustment_overhead,
                            running=len(res.allocation.app_ids),
                            pending=len(res.pending_app_ids),
                            adjusted=list(res.adjusted_app_ids),
                            started=list(res.started_app_ids))
