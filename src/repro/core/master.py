"""DormMaster: central resource manager (§III-A.1).

Responsibilities:
  * accept 6-tuple application submissions,
  * detect arrivals/completions and invoke the utilization-fairness optimizer,
  * enforce new allocations by creating/destroying containers on DormSlaves,
    running the checkpoint-based adjustment protocol for resized apps,
  * keep previous allocations when the optimizer reports infeasibility
    (paper: "Dorm would keep existing resource allocations until more running
    applications finish and release their resources").

Two bookkeeping engines behind the same API (`OptimizerConfig.soa`):

  * SoA (default): all placement state lives in a `core.state.ClusterState`
    -- one in-place matrix, incrementally-maintained free capacity, and
    LAZY materialization of `Partition`/`TaskExecutor`/`TaskScheduler`/
    container objects. Enforcement touches only the apps whose rows
    changed; metrics are computed from O(n*m) arrays.
  * legacy (`soa=False`): the PR-2 dict-of-objects engine -- one Container +
    TaskExecutor + TaskScheduler Python object per granted container,
    created and destroyed on every adjustment. Kept (like
    `ReferenceClusterSimulator`) as the golden baseline that
    benchmarks/bench_scale.py measures the SoA speedup ratio against, in
    ONE process. Both engines produce bit-identical allocation timelines
    (tests/test_state.py).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .adjustment import AdjustmentProtocol, CheckpointHandle, RecordingProtocol
from .goodput import GoodputCurve
from .metrics import (cluster_fairness_loss, resource_adjustment_overhead,
                      resource_utilization)
from .optimizer import OptimizerConfig, _shares_vec, make_optimizer
from .partition import Partition, TaskExecutor, TaskScheduler
from .runtime import (ChaosEvent, ReallocationResult, SlaveDegraded,
                      SlaveRestored)
from .slave import DormSlave
from .state import ClusterState, LazyAppViews, LazySlaveViews
from .telemetry import Spans
from .types import Allocation, ApplicationSpec, ClusterSpec, validate_allocation

_EPS = 1e-9

__all__ = ["DormMaster", "ReallocationResult"]


class DormMaster:
    def __init__(self, cluster: ClusterSpec,
                 optimizer_kind: str = "milp",
                 optimizer_cfg: OptimizerConfig = OptimizerConfig(),
                 protocol: Optional[AdjustmentProtocol] = None):
        self.cluster = cluster
        cfg = optimizer_cfg
        self._soa = cfg.soa
        self.slave_ids: Tuple[str, ...] = tuple(s.slave_id for s in cluster.slaves)
        # Chaos capacity tracking: `cluster` above is the CURRENT effective
        # spec (swapped for a rescaled one on slave failure/degrade/restore
        # -- see `_apply_slave_scale`); `_base_cluster` keeps the nominal
        # capacities that restores return to.
        self._base_cluster = cluster
        self._slave_scale = np.ones(cluster.b)
        self._slave_pos: Dict[str, int] = {
            s: j for j, s in enumerate(self.slave_ids)}
        # Spans of the master, its optimizer and its backend (see
        # `phase_s` and `phase_breakdown`).
        self.spans = Spans()
        # "milp" (exact), "greedy" (heuristic), or "auto" (MILP below
        # cfg.auto_switch_vars variables, greedy above -- the scale path).
        self.optimizer = make_optimizer(optimizer_kind, cfg, self.spans)
        self.protocol: AdjustmentProtocol = protocol or RecordingProtocol()
        self.specs: Dict[str, ApplicationSpec] = {}      # running + pending
        self.pending: List[str] = []                     # admitted, not placed
        # Admitted apps carrying a goodput curve (see core.goodput). The
        # cluster-goodput metric in `_result` turns on at the FIRST curved
        # admission and stays on (a sample timeline mixing real sums with
        # gated 0.0s would corrupt time averages); uncurved (seed)
        # workloads never flip it and pay nothing per event.
        self._curved: Dict[str, GoodputCurve] = {}
        self._goodput_on = False
        self.prev_alloc: Optional[Allocation] = None
        self.checkpoints: Dict[str, CheckpointHandle] = {}
        if self._soa:
            self.state: Optional[ClusterState] = ClusterState(cluster)
            self.slaves = LazySlaveViews(self.state)
            self.partitions = LazyAppViews(self.state, self.state.partition)
            self.executors = LazyAppViews(self.state, self.state.executors)
            self.schedulers = LazyAppViews(self.state, self.state.schedulers)
        else:
            self.state = None
            self.slaves: Dict[str, DormSlave] = {
                s.slave_id: DormSlave(s) for s in cluster.slaves}
            self.partitions: Dict[str, Partition] = {}   # running apps
            self.executors: Dict[str, List[TaskExecutor]] = {}
            self.schedulers: Dict[str, List[TaskScheduler]] = {}
            # Placement rows (x_{i,.}) cached per running app: recomputing
            # them from container lists is O(b) dict-building per app per
            # event, which dominates at 1000 slaves.
            self._placements: Dict[str, np.ndarray] = {}

    # ------------------------------------------- SchedulerPolicy interface
    # (runtime.ClusterRuntime drives the master through these four hooks;
    #  submit/submit_batch/complete remain as the user-facing API.)

    def on_arrival(self, specs: Sequence[ApplicationSpec],
                   ) -> ReallocationResult:
        return self.submit_batch(specs)

    def on_completion(self, app_id: str) -> ReallocationResult:
        return self.complete(app_id)

    def on_resize(self, app_id: str, n_min: Optional[int] = None,
                  n_max: Optional[int] = None,
                  ) -> Optional[ReallocationResult]:
        """External elasticity-bound change (runtime `Resize` event): update
        the app's [n_min, n_max] and let the optimizer re-size its partition
        through the usual checkpoint-based adjustment protocol.

        No-op resizes (bounds unchanged after `with_bounds` clamping) return
        None WITHOUT solving: an autoscaler re-asserting the current bounds
        every tick must not cost a reallocation pass per app per tick.

        A TIGHTENING resize that makes P2 infeasible is REJECTED: the
        bounds revert and None is returned. The paper's keep-allocations
        fallback is the right response to an arrival the cluster cannot
        place yet -- but a load-driven scaling request that sticks as an
        unsatisfiable floor (a raised n_min), or an n_max cut below the
        current count that the Eq-16 budget can never enforce, would wedge
        every future solve until the app finishes. Admission control for
        those (OASiS-style): the requester may retry later. A resize that
        only RELAXES the bounds cannot have caused the infeasibility, so
        it keeps the normal fallback -- critically, a step-paced guarantee
        release must still walk n_min down while the cluster is infeasible
        for unrelated reasons, or the release would livelock."""
        spec = self.specs.get(app_id)
        if spec is None:
            return None
        new = spec.with_bounds(n_min=n_min, n_max=n_max)
        if new.n_min == spec.n_min and new.n_max == spec.n_max:
            return None
        tightening = (new.n_min > spec.n_min
                      or new.n_max < self.containers_of(app_id))
        self.specs[app_id] = new
        if self.state is not None:
            self.state.rebound(new)       # fast path: no re-admission
        res = self.reallocate(reject_infeasible=tightening)
        if res is None:
            self.specs[app_id] = spec
            if self.state is not None:
                self.state.rebound(spec)
        return res

    def on_tick(self, t: float) -> Optional[ReallocationResult]:
        """Periodic rebalance (runtime `Tick` event)."""
        return self.reallocate()

    # ------------------------------------------------- chaos recovery hooks
    # (runtime SlaveFailed/SlaveDrained/SlaveDegraded/SlaveRestored events;
    #  see `repro.core.chaos` for injection and accounting.)

    def on_slave_failed(self, slave_id: str) -> Optional[ReallocationResult]:
        """Slave crashed: its capacity vanishes instantly and every
        container it hosted is orphaned. Recovery pass: evict the dead
        slave's allocation rows, fence the capacity, then re-place the
        displaced apps under the existing Eq-16 adjustment budget. If the
        shrunk cluster cannot hold every displaced app at n_min, the ones
        below n_min are PARKED (torn down, returned to pending -- graceful
        degradation instead of all-or-nothing rejection) and the solve
        retries with the keep-allocations fallback. Eq-4 churn caused here
        is attributed as FORCED (`forced_adjusted_app_ids`)."""
        return self._chaos_capacity(slave_id, 0.0)

    def on_slave_drained(self, slave_id: str) -> Optional[ReallocationResult]:
        """Graceful decommission: mechanically identical to a crash (the
        capacity is fenced and apps migrate off), but monitors attribute it
        separately. A real deployment would checkpoint before the kill;
        the simulated adjustment cost is the same either way."""
        return self._chaos_capacity(slave_id, 0.0)

    def on_slave_degraded(self, slave_id: str, factor: float = 0.5,
                          ) -> Optional[ReallocationResult]:
        """Straggler: the slave keeps only `factor` of its nominal
        capacity. Containers that no longer fit are evicted (most recently
        placed first) until the remaining usage fits."""
        return self._chaos_capacity(slave_id,
                                    min(max(float(factor), 0.0), 1.0))

    def on_slave_restored(self, slave_id: str) -> Optional[ReallocationResult]:
        """Capacity returned (replacement arrived / straggler recovered):
        un-fence the slave and rebalance -- parked apps restart here."""
        return self._chaos_capacity(slave_id, 1.0)

    def _chaos_capacity(self, slave_id: str, factor: float,
                        ) -> Optional[ReallocationResult]:
        j = self._slave_pos.get(slave_id)
        if j is None or self._slave_scale[j] == factor:
            return None                      # unknown slave / no-op repeat
        displaced, parked = self._apply_slave_scale(j, factor)
        res = self.reallocate(reject_infeasible=True)
        if res is None:
            # Shrink-toward-n_min failed for the whole set: park the
            # displaced apps the eviction left below their floor, then the
            # keep-allocations fallback always produces a result.
            parked = parked + self._park_below_min(displaced)
            res = self.reallocate()
        return self._chaos_result(res, displaced, parked)

    def _apply_slave_scale(self, j: int, factor: float,
                           ) -> Tuple[Dict[str, int], List[str]]:
        """Set slave j's capacity multiplier: evict placements that no
        longer fit (most recently admitted first -- specs insertion order
        is the canonical engine-invariant order; the engines' internal
        placement orders drift after a park/re-place cycle), swap in the
        rescaled
        ClusterSpec, and re-anchor prev_alloc at the post-eviction rows so
        the recovery solve's Eq-16 budget charges forced moves.

        Returns `(displaced, parked)`: displaced maps app_id -> container
        count AFTER eviction (0 = lost everything) in eviction order;
        parked lists the apps returned to pending (fully evicted)."""
        with self.spans.span("master.enforce"):
            self._slave_scale[j] = factor
            from .chaos import scale_cluster
            new_cluster = scale_cluster(self._base_cluster, self._slave_scale)
            new_cap_row = new_cluster.capacity_matrix()[j].astype(np.float64)
            displaced: Dict[str, int] = {}
            parked: List[str] = []
            if self.state is not None:
                st = self.state
                used_row = st.cap[j] - st.free[j]
                if (used_row > new_cap_row + _EPS).any():
                    for app_id in reversed([a for a in self.specs
                                            if st.is_placed(a)]):
                        i = st.row_of[app_id]
                        cij = int(st.x[i, j])
                        if cij == 0:
                            continue
                        used_row = used_row - cij * st.demand[i]
                        remaining = int(st.counts[i]) - cij
                        displaced[app_id] = remaining
                        if remaining > 0:
                            row = st.x[i].copy()
                            row[j] = 0
                            st.place(app_id, row)
                        else:
                            self._park(app_id)
                            parked.append(app_id)
                        if not (used_row > new_cap_row + _EPS).any():
                            break
                st.set_cluster(new_cluster)
            else:
                sid = self.slave_ids[j]
                slave = self.slaves[sid]
                used_row = slave.used()
                if (used_row > new_cap_row + _EPS).any():
                    for app_id in reversed([a for a in self.specs
                                            if a in self.partitions]):
                        part = self.partitions[app_id]
                        victims = [c for c in part.containers
                                   if c.slave_id == sid]
                        if not victims:
                            continue
                        d = self.specs[app_id].demand.as_array()
                        used_row = used_row - len(victims) * d
                        remaining = part.n_containers - len(victims)
                        displaced[app_id] = remaining
                        if remaining > 0:
                            for c in victims:
                                slave.destroy_container(c.container_id)
                                part.containers.remove(c)
                            self._placements[app_id][j] = 0
                        else:
                            self._park(app_id)
                            parked.append(app_id)
                        if not (used_row > new_cap_row + _EPS).any():
                            break
                # Swap the slave's spec so used()/available() report against
                # the post-failure capacity.
                slave.spec = new_cluster.slaves[j]
            self.cluster = new_cluster
            # Re-anchor stickiness: the recovery solve diffs against the
            # POST-eviction placements, so re-placing a displaced app counts
            # against the Eq-16 budget while untouched apps stay free to keep.
            if self.prev_alloc is not None:
                self.prev_alloc = self._current_allocation()
        return displaced, parked

    def _park(self, app_id: str) -> None:
        """Forced surrender: tear the app down, drop its prev_alloc row and
        return it to the admission queue. A later solve (completion freeing
        capacity, or the slave's SlaveRestored) restarts it. Crash-path
        kills bypass the checkpoint protocol: the containers are already
        gone."""
        self._teardown(app_id)
        if self.prev_alloc is not None \
                and app_id in self.prev_alloc.app_ids:
            self.prev_alloc = self.prev_alloc.take(
                [i for i, a in enumerate(self.prev_alloc.app_ids)
                 if a != app_id])
        if app_id not in self.pending:
            self.pending.append(app_id)

    def _park_below_min(self, displaced: Dict[str, int]) -> List[str]:
        """Park every still-placed displaced app whose post-eviction count
        fell below its n_min floor (the infeasible-recovery path)."""
        parked: List[str] = []
        for app_id in displaced:
            spec = self.specs.get(app_id)
            if spec is None:
                continue
            placed = (self.state.is_placed(app_id)
                      if self.state is not None
                      else app_id in self.partitions)
            if placed and self.containers_of(app_id) < spec.n_min:
                self._park(app_id)
                parked.append(app_id)
        return parked

    def _chaos_result(self, res: ReallocationResult,
                      displaced: Dict[str, int], parked: List[str],
                      ) -> ReallocationResult:
        """Fold forced-churn attribution into a solve result: displaced
        apps are adjusted (forced), parked apps report count 0, and the
        eviction counts reach the runtime even when the solve fell back to
        keep-allocations (whose changed_counts would otherwise be empty)."""
        if not displaced and not parked:
            return res
        forced = tuple(a for a in displaced if a in self.specs)
        adj = list(res.adjusted_app_ids)
        seen = set(adj)
        adj += [a for a in forced if a not in seen]
        changed: Dict[str, int] = dict(displaced)
        for a in parked:
            changed[a] = 0
        if res.changed_counts:
            changed.update(res.changed_counts)
        # An eviction-parked app the recovery solve re-placed (or that
        # completed in the same flood) is not parked: only still-admitted
        # apps holding nothing after the solve are.
        counts = res.allocation.x.sum(axis=1)
        replaced = {a for a, c in zip(res.allocation.app_ids, counts)
                    if c > 0}
        still_parked = tuple(a for a in parked
                             if a in self.specs and a not in replaced)
        return dataclasses.replace(
            res,
            adjusted_app_ids=tuple(adj),
            adjustment_overhead=len(adj),
            changed_counts=changed,
            forced_adjusted_app_ids=forced,
            displaced_app_ids=tuple(displaced),
            parked_app_ids=still_parked)

    def on_batch(self, completions: Sequence[str],
                 resizes: Sequence[Tuple[str, Optional[int], Optional[int]]],
                 arrivals: Sequence[ApplicationSpec],
                 chaos: Sequence[ChaosEvent] = (),
                 ) -> ReallocationResult:
        """One policy pass absorbing a mixed event flood (runtime `Storm`):
        the queue-based load-leveling endpoint of `AbsorberConfig`.

        Merge semantics:
          * an arrival whose app_id also appears in `completions` CANCELS
            against it (both dropped) -- cannot arise from the runtime's
            absorber (an unadmitted app cannot complete) but direct API
            callers get the documented queue-merge behavior;
          * completions fold into a single free-capacity update (every
            finished partition torn down, its prev_alloc row dropped)
            before the solve;
          * resizes dedupe LAST-WINS per app; resizes targeting apps that
            completed in the same flood (or were never admitted) drop;
          * arrivals admit with `submit_batch`'s rollback-safe contract;
          * ONE reallocation solves the merged state. If any surviving
            resize TIGHTENED its bounds and the merged solve is
            infeasible, the tightening resizes are rejected as a GROUP
            (bounds revert, relaxing resizes stick -- they cannot have
            caused the infeasibility) and the flood re-solves with the
            keep-allocations fallback. Per-event processing rejects
            tightening resizes individually; the absorber trades that
            granularity for one solve per flood.

        A failure flood (`chaos` -- correlated rack loss) is processed
        FIRST: dead/fenced slaves evict their rows before the completions'
        folded free-capacity update, so the merged solve never sees
        capacity that no longer exists. All displaced apps then share ONE
        recovery solve; forced churn is attributed per `_chaos_result`.

        Merge bookkeeping is timed into the `absorb` phase bucket."""
        displaced: Dict[str, int] = {}
        parked: List[str] = []
        for ev in chaos:
            j = self._slave_pos.get(ev.slave_id)
            if j is None:
                continue
            if isinstance(ev, SlaveDegraded):
                factor = min(max(float(ev.factor), 0.0), 1.0)
            elif isinstance(ev, SlaveRestored):
                factor = 1.0
            else:
                factor = 0.0              # SlaveFailed / SlaveDrained
            if self._slave_scale[j] == factor:
                continue
            dd, pp = self._apply_slave_scale(j, factor)
            displaced.update(dd)          # latest count wins, order kept
            parked.extend(pp)
        with self.spans.span("master.absorb"):
            comp_set = set(completions)
            cancelled = {s.app_id for s in arrivals} & comp_set
            arrivals = [s for s in arrivals if s.app_id not in cancelled]
            # -- completions: one folded free-capacity update.
            for app_id in completions:
                if app_id in cancelled:
                    continue
                if app_id in self.partitions and app_id in self.specs:
                    self.protocol.kill(self.specs[app_id])
                self._teardown(app_id)
                self.specs.pop(app_id, None)
                self._curved.pop(app_id, None)
                if self.state is not None and app_id in self.state:
                    self.state.forget(app_id)
                if app_id in self.pending:
                    self.pending.remove(app_id)
            drop = comp_set - cancelled
            if drop and self.prev_alloc is not None \
                    and drop & set(self.prev_alloc.app_ids):
                self.prev_alloc = self.prev_alloc.take(
                    [i for i, a in enumerate(self.prev_alloc.app_ids)
                     if a not in drop])
            # -- resizes: last-wins per app, dead targets dropped.
            merged: Dict[str, Tuple[Optional[int], Optional[int]]] = {}
            for app_id, n_min, n_max in resizes:
                if app_id in self.specs:
                    merged[app_id] = (n_min, n_max)
            reverts: List[ApplicationSpec] = []      # tightened old specs
            tightening = False
            for app_id, (n_min, n_max) in merged.items():
                spec = self.specs[app_id]
                new = spec.with_bounds(n_min=n_min, n_max=n_max)
                if new.n_min == spec.n_min and new.n_max == spec.n_max:
                    continue
                if (new.n_min > spec.n_min
                        or new.n_max < self.containers_of(app_id)):
                    tightening = True
                    reverts.append(spec)
                self.specs[app_id] = new
                if self.state is not None:
                    self.state.rebound(new)
            # -- arrivals: submit_batch's rollback-safe admission.
            seen = set()
            for spec in arrivals:
                if spec.app_id in self.specs or spec.app_id in seen:
                    raise ValueError(f"duplicate app_id {spec.app_id}")
                seen.add(spec.app_id)
            if self.state is not None and arrivals:
                admitted: List[str] = []
                try:
                    for spec in arrivals:
                        self.state.admit(spec)
                        admitted.append(spec.app_id)
                except Exception:
                    for app_id in admitted:
                        self.state.forget(app_id)
                    raise
            for spec in arrivals:
                self.specs[spec.app_id] = spec
                self.pending.append(spec.app_id)
                if spec.goodput is not None:
                    self._curved[spec.app_id] = spec.goodput
        # -- ONE solve for the whole flood.
        res = self.reallocate(
            reject_infeasible=tightening or bool(displaced))
        if res is None:
            # Group-reject the tightening resizes, park displaced apps the
            # eviction left below n_min, and solve once more with the
            # keep-allocations fallback (always returns a result).
            with self.spans.span("master.absorb"):
                for spec in reverts:
                    self.specs[spec.app_id] = spec
                    if self.state is not None:
                        self.state.rebound(spec)
                parked.extend(self._park_below_min(displaced))
            res = self.reallocate()
        return self._chaos_result(res, displaced, parked)

    # ------------------------------------------------------------------ API

    def submit(self, spec: ApplicationSpec) -> ReallocationResult:
        """§III-B: submit a 6-tuple; triggers reallocation."""
        return self.submit_batch([spec])

    def submit_batch(self, specs: Sequence[ApplicationSpec],
                     ) -> ReallocationResult:
        """Admit several applications, then reallocate ONCE (event batching:
        under bursty arrivals one optimizer pass absorbs the whole burst)."""
        seen = set()
        for spec in specs:
            if spec.app_id in self.specs or spec.app_id in seen:
                raise ValueError(f"duplicate app_id {spec.app_id}")
            seen.add(spec.app_id)
        # Admit into the state FIRST (it validates demand shape): mutating
        # specs/pending before a failed admission would wedge every later
        # reallocate on an app the state never interned.
        if self.state is not None:
            admitted: List[str] = []
            try:
                for spec in specs:
                    self.state.admit(spec)
                    admitted.append(spec.app_id)
            except Exception:
                for app_id in admitted:
                    self.state.forget(app_id)
                raise
        for spec in specs:
            self.specs[spec.app_id] = spec
            self.pending.append(spec.app_id)
            if spec.goodput is not None:
                self._curved[spec.app_id] = spec.goodput
        return self.reallocate()

    def complete(self, app_id: str) -> ReallocationResult:
        """Application finished; release its partition and reallocate."""
        if app_id in self.partitions and app_id in self.specs:
            # notify the protocol so live integrations (ElasticJaxProtocol)
            # release the finished app's device group
            self.protocol.kill(self.specs[app_id])
        self._teardown(app_id)
        self.specs.pop(app_id, None)
        self._curved.pop(app_id, None)
        if self.state is not None and app_id in self.state:
            self.state.forget(app_id)
        if app_id in self.pending:
            self.pending.remove(app_id)
        # Drop the finished app from prev_alloc so Eq-4 excludes it.
        if self.prev_alloc is not None and app_id in self.prev_alloc.app_ids:
            self.prev_alloc = self.prev_alloc.take(
                [i for i, a in enumerate(self.prev_alloc.app_ids)
                 if a != app_id])
        return self.reallocate()

    def running_apps(self) -> List[ApplicationSpec]:
        return [self.specs[a] for a in self.partitions]

    def containers_of(self, app_id: str) -> int:
        if self.state is not None:
            return self.state.containers_of(app_id)
        p = self.partitions.get(app_id)
        return p.n_containers if p else 0

    @property
    def backend_compile_s(self) -> float:
        """Seconds jax spent compiling the optimizer backend's programs in
        this process (`telemetry.compile_counter()`; 0.0 for the numpy
        backend)."""
        be = getattr(self.optimizer, "backend", None)
        return float(be.compile_s) if be is not None else 0.0

    @property
    def phase_s(self) -> Dict[str, float]:
        """Cumulative wall seconds of the master's four phases: the
        optimizer's solve, enforcement, Eq-1/2/4 metrics and the absorber's
        flood merging (the `master.*` spans)."""
        total = self.spans.total_s
        return {p: total.get("master." + p, 0.0)
                for p in ("solve", "enforce", "metrics", "absorb")}

    def phase_breakdown(self) -> Dict[str, float]:
        """Cumulative per-phase scheduling seconds: optimizer solve (split
        into the DRF-refill share, the column-generation pricing share and
        the rest, device time and any compile inside it included),
        enforcement (container create/destroy + protocol calls), Eq-1/2/4
        metric evaluation, the absorber's flood-merge bookkeeping
        (`absorb`), and the backend's jit compiles (`backend_compile`,
        also counted inside whichever phase triggered them)."""
        refill = float(getattr(self.optimizer, "refill_s", 0.0))
        pricing = float(getattr(self.optimizer, "pricing_s", 0.0))
        phase = self.phase_s
        return {
            "drf_refill": refill,
            "colgen_pricing": pricing,
            "backend_compile": self.backend_compile_s,
            "solve": phase["solve"] - refill - pricing,
            "enforce": phase["enforce"],
            "metrics": phase["metrics"],
            "absorb": phase["absorb"],
        }

    # --------------------------------------------------------- reallocation

    def reallocate(self, reject_infeasible: bool = False,
                   ) -> Optional[ReallocationResult]:
        """Invoke the optimizer over all admitted apps and enforce the result.

        `reject_infeasible`: return None instead of the keep-allocations
        result when the solve is infeasible (the resize path reverts the
        triggering bound change in that case)."""
        apps = list(self.specs.values())
        with self.spans.span("master.solve"):
            alloc = self.optimizer.solve(apps, self.cluster, self.prev_alloc,
                                         state=self.state)
        if alloc is None:
            if reject_infeasible:
                return None
            # Infeasible: keep existing allocations; newly admitted apps wait.
            return self._result(self._current_allocation(), (), (),
                                tuple(self.pending), counts_changed={})
        return self._enforce(alloc, apps)

    def _current_allocation(self) -> Allocation:
        # Canonical app order = specs insertion order. The engines' internal
        # structures drift apart after a chaos eviction re-places a parked
        # app (legacy dict re-inserts adjusted apps behind it, SoA keeps
        # interned slots), so neither is a stable exposure order.
        if self.state is not None:
            alloc = self.state.allocation()
            ids = tuple(a for a in self.specs if a in set(alloc.app_ids))
            if ids == alloc.app_ids:
                return alloc
            pos = {a: i for i, a in enumerate(alloc.app_ids)}
            return Allocation.trusted(ids, alloc.x[[pos[a] for a in ids]])
        app_ids = tuple(a for a in self.specs if a in self._placements)
        x = np.stack([self._placements[a] for a in app_ids]) if app_ids else \
            np.zeros((0, len(self.slave_ids)), np.int64)
        return Allocation(app_ids, x)

    def _enforce(self, alloc: Allocation, apps: Sequence[ApplicationSpec],
                 ) -> ReallocationResult:
        """§III-C.2 + Fig 5: apply a new allocation.

        For every running app whose placement changed: save -> kill ->
        create/destroy containers -> resume. For pending apps that received
        containers: create containers -> configure executors/schedulers ->
        start.
        """
        with self.spans.span("master.enforce"):
            adjusted: List[str] = []
            started: List[str] = []
            counts_changed: Dict[str, int] = {}
            spec_of = {a.app_id: a for a in apps}

            if self.state is not None:
                to_place = self._changed_soa(alloc)
            else:
                to_place = self._changed_legacy(alloc)

            # Phase 1 (Fig 5, step 3): save + kill + destroy containers of
            # every running app whose placement changed -- frees capacity
            # first, so phase-2 creations never race the teardowns.
            for app_id, _, was_running in to_place:
                if was_running:
                    spec = spec_of[app_id]
                    self.checkpoints[app_id] = self.protocol.save_state(spec)
                    self.protocol.kill(spec)
                    self._teardown(app_id)

            # Phase 2 (Fig 5, step 4): create containers, configure
            # executors and schedulers, resume adjusted apps / start new
            # ones.
            for app_id, new_row, was_running in to_place:
                spec = spec_of[app_id]
                self._place(spec, new_row)
                n_new = int(new_row.sum())
                counts_changed[app_id] = n_new
                if was_running:
                    self.protocol.resume(spec, n_new,
                                         self.checkpoints.get(app_id))
                    adjusted.append(app_id)
                else:
                    self.protocol.start(spec, n_new)
                    started.append(app_id)
                    if app_id in self.pending:
                        self.pending.remove(app_id)
        result = self._result(alloc, tuple(adjusted), tuple(started),
                              tuple(self.pending),
                              counts_changed=counts_changed,
                              trusted_shares=True)
        self.prev_alloc = alloc
        return result

    def _changed_legacy(self, alloc: Allocation,
                        ) -> List[Tuple[str, np.ndarray, bool]]:
        """PR-2 changed-row detection: one bulk compare of every running
        app's cached placement row against the new allocation."""
        validate_allocation(alloc, [self.specs[a] for a in alloc.app_ids],
                            self.cluster)
        row_sums = alloc.x.sum(axis=1)
        running_i = [i for i, a in enumerate(alloc.app_ids)
                     if a in self.partitions]
        changed_i: set = set()
        if running_i:
            old = np.stack([self._placements[alloc.app_ids[i]]
                            for i in running_i])
            diff = (alloc.x[running_i] != old).any(axis=1)
            changed_i = {running_i[k] for k in np.flatnonzero(diff)}
        to_place: List[Tuple[str, np.ndarray, bool]] = []
        for i, app_id in enumerate(alloc.app_ids):
            if app_id in self.partitions:
                if i in changed_i:
                    to_place.append((app_id, alloc.x[i], True))
            elif row_sums[i] > 0:
                to_place.append((app_id, alloc.x[i], False))
        return to_place

    def _changed_soa(self, alloc: Allocation,
                     ) -> List[Tuple[str, np.ndarray, bool]]:
        """SoA changed-row detection: the solver already proved which rows
        changed (`optimizer.last_changed`, exact by construction on the
        delta path); otherwise one bulk compare against the state rows.
        Starts are found by scanning only the pending list, never every
        running app. The allocation is NOT re-validated here -- every solver
        path validated it on construction."""
        state = self.state
        pos = None
        changed_ids = getattr(self.optimizer, "last_changed", None)
        to_place: List[Tuple[str, np.ndarray, bool]] = []
        if changed_ids is None:
            # e.g. a MILP solve: diff the running apps' rows in bulk.
            running_i = [i for i, a in enumerate(alloc.app_ids)
                         if state.is_placed(a)]
            if running_i:
                old = state.x[state.rows_for(
                    [alloc.app_ids[i] for i in running_i])]
                diff = (alloc.x[running_i] != old).any(axis=1)
                for k in np.flatnonzero(diff):
                    i = running_i[int(k)]
                    to_place.append((alloc.app_ids[i], alloc.x[i], True))
        elif changed_ids:
            pos = dict(zip(alloc.app_ids, range(len(alloc.app_ids))))
            # Allocation order, matching the legacy engine's adjusted order.
            for app_id in sorted(changed_ids, key=pos.get):
                if state.is_placed(app_id):
                    to_place.append((app_id, alloc.row_at(pos[app_id]), True))
        # Starts: pending apps that received containers.
        if self.pending:
            if pos is None:
                pos = dict(zip(alloc.app_ids, range(len(alloc.app_ids))))
            hits = []
            for app_id in self.pending:
                i = pos.get(app_id)
                if i is not None and alloc.row_at(i).any():
                    hits.append(i)
            # Allocation order, matching the legacy engine's started order
            # (chaos parking appends to pending out of specs order).
            for i in sorted(hits):
                to_place.append((alloc.app_ids[i], alloc.row_at(i), False))
        return to_place

    # ------------------------------------------------------------- internal

    def _place(self, spec: ApplicationSpec, row: np.ndarray) -> None:
        if self.state is not None:
            self.state.place(spec.app_id, row)
            return
        part = Partition(spec)
        execs: List[TaskExecutor] = []
        scheds: List[TaskScheduler] = []
        for j, slave_id in enumerate(self.slave_ids):
            for _ in range(int(row[j])):
                c = self.slaves[slave_id].create_container(
                    spec.app_id, spec.demand)
                part.containers.append(c)
                # §III-A.3: a TaskExecutor + TaskScheduler per container.
                execs.append(TaskExecutor(c.container_id, spec.app_id))
                scheds.append(TaskScheduler(c.container_id, spec.app_id))
        self.partitions[spec.app_id] = part
        self.executors[spec.app_id] = execs
        self.schedulers[spec.app_id] = scheds
        self._placements[spec.app_id] = np.asarray(row, dtype=np.int64).copy()

    def _teardown(self, app_id: str) -> None:
        if self.state is not None:
            if self.state.is_placed(app_id):
                self.state.clear(app_id)
            return
        part = self.partitions.pop(app_id, None)
        if part is None:
            return
        for c in part.containers:
            self.slaves[c.slave_id].destroy_container(c.container_id)
        self.executors.pop(app_id, None)
        self.schedulers.pop(app_id, None)
        self._placements.pop(app_id, None)

    def _result(self, alloc: Allocation, adjusted: Tuple[str, ...],
                started: Tuple[str, ...], pending: Tuple[str, ...],
                counts_changed: Optional[Dict[str, int]] = None,
                trusted_shares: bool = False) -> ReallocationResult:
        with self.spans.span("master.metrics"):
            if alloc.app_ids == tuple(self.specs):
                keep = None
                apps = list(self.specs.values())
                sub = alloc
            else:
                keep = [i for i, a in enumerate(alloc.app_ids)
                        if a in self.specs]
                apps = [self.specs[alloc.app_ids[i]] for i in keep]
                sub = alloc.take(keep)
            d = totals = None
            if self.state is not None and apps:
                idx = self.state.rows_for([a.app_id for a in apps])
                d = self.state.demand[idx]
                # After enforcement the state rows ARE this allocation, so the
                # maintained per-app counts equal sub.x.sum(axis=1).
                totals = self.state.counts[idx]
            if self.state is not None:
                # Eq 4 evaluated by construction: every adjusted app changed
                # its row (and only those), summed over A^t ∩ A^{t-1}.
                overhead = len(adjusted)
            else:
                overhead = resource_adjustment_overhead(self.prev_alloc, sub)
            shares_vec = getattr(self.optimizer, "last_shares_vec", None)
            if trusted_shares and totals is not None \
                    and shares_vec is not None \
                    and len(shares_vec) == len(apps):
                # Eq 2 fully in arrays: actual dominant shares from the
                # maintained counts vs the solver's s_hat vector (same app
                # order as this result, by the trusted-shares contract).
                actual_vec = _shares_vec(totals, d,
                                         self.cluster.total_capacity())
                loss = float(np.abs(actual_vec - shares_vec).sum())
            else:
                # Reuse the optimizer's DRF targets for Eq 2 when they cover
                # exactly this app set (true for every feasible solve): the
                # fairness metric then costs O(n*m) instead of a second
                # progressive-filling pass.
                shares = getattr(self.optimizer, "last_shares", None)
                if not trusted_shares and shares is not None \
                        and set(shares) != {a.app_id for a in apps}:
                    shares = None
                loss = cluster_fairness_loss(sub, apps, self.cluster,
                                             theoretical=shares,
                                             d=d, totals=totals)
            # Instantaneous cluster goodput Σ gp_i(N_i) in
            # container-equivalents
            # (gp_i(N) = N for uncurved apps). Only computed when some admitted
            # app carries a curve; every other workload keeps the 0.0 default.
            goodput = 0.0
            if self._curved:
                self._goodput_on = True
            if self._goodput_on:
                cnts = totals if totals is not None else sub.x.sum(axis=1)
                goodput = float(cnts.sum())
                for i, a in enumerate(apps):
                    curve = self._curved.get(a.app_id)
                    if curve is not None:
                        n_i = int(cnts[i])
                        goodput += curve.at(n_i) - float(n_i)
            result = ReallocationResult(
                allocation=sub,
                adjusted_app_ids=adjusted,
                started_app_ids=started,
                pending_app_ids=pending,
                utilization=resource_utilization(sub, apps, self.cluster,
                                                 d=d, totals=totals),
                fairness_loss=loss,
                # Eq 4 evaluated literally: r_i = 1 iff any x_{i,j} changed vs
                # the previous allocation, summed over A^t ∩ A^{t-1}.
                adjustment_overhead=overhead,
                changed_counts=counts_changed,
                # Certified gap of the solve (colgen LP bound / monolithic MILP
                # dual bound); None when the path proves nothing.
                optimality_gap=getattr(self.optimizer, "last_gap", None),
                goodput=goodput,
            )
        return result
