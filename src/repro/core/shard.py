"""Sharded multi-master control plane: Dorm past 100k slaves.

One `DormMaster` solving one global allocation per event is the last
scalability wall: even with the jax-jit kernels (PR 6), the delta solves
(PR 3) and the storm absorber (PR 7), a single `ClusterState` over 100k
slaves pays O(b) per placement pass and one monolithic DRF ladder over
every admitted app. The paper's dynamically-partitioned mechanism (§III)
already treats partitions as the unit of isolation, and colgen's
eligibility-class pricing rows decompose cleanly per shard -- so the
scale move is horizontal: partition the CLUSTER, not the algorithm.

    ShardedControlPlane          N shards, each a full DormMaster over its
                                 own ClusterState; routes every runtime
                                 event to the owning shard and merges the
                                 per-shard results into one global
                                 ReallocationResult.
    Migrate (runtime.py)         app migration as a first-class runtime
                                 event: teardown on the source shard +
                                 re-admission on the destination, charged
                                 to the destination's Eq-16 budget and
                                 attributed as FORCED Eq-4 churn (exactly
                                 like PR 8's chaos evictions) when the app
                                 was running.
    Coordinator                  thin rebalancer on a slow tick: watches
                                 per-shard dominant-share/pending/goodput
                                 summaries and publishes `Migrate` events
                                 (pending relief first -- free moves --
                                 then load-spread moves under hysteresis).
    cross_shard_certificate      certified bound on the cross-shard
                                 optimality loss: per-shard colgen dual
                                 bounds (rescaled to global units) and the
                                 sharded achieved objective vs the
                                 single-master colgen bound, at scales
                                 where the single master still runs.

Scaling model. Every per-event cost inside a shard is a function of the
SHARD size (b/K slaves, ~n/K apps), so K shards cut per-event policy time
near-linearly until the O(n) merge bookkeeping shows up -- and the merge
here is O(placed apps) tuple concatenation plus O(m) vector sums, never a
dense matrix: the merged allocation materializes its (n, b) matrix only
if a consumer actually asks for `.x` (the runtime does not when
`changed_counts` is provided, which every DormMaster result does).
Shards are small, so the numpy/jax crossover that was moot for one giant
master matters again: each shard's `backend="auto"` dispatch picks per
shard (see `shard_summaries` / `backend.auto_dispatch_report`).

Semantics vs the single master, precisely:

  * K=1 is BIT-EXACT pass-through: every hook returns the single
    DormMaster's result object unchanged (no merge arithmetic touches
    it), pinned by tests/test_shard_properties.py.
  * K>1 is federated DRF: fairness (Eq 2) is evaluated per shard against
    the shard's own progressive-filling targets and the losses are
    summed; utilization (Eq 1) merges exactly (used and capacity vectors
    are additive across shards); the Eq-15/16 budgets apply per shard
    (each shard solves its own P2). The cross-shard optimality loss this
    introduces is what `cross_shard_certificate` certifies.
  * Routing: slaves round-robin (global slave j -> shard j % K, so a
    homogeneous cluster splits proportionally and rack-correlated chaos
    spreads across shards); each arriving app goes to the least-loaded
    ELIGIBLE shard (normalized dominant-share pressure), where eligible
    means some slave fits one container and the shard can hold n_min.
"""
from __future__ import annotations

import dataclasses
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

import numpy as np

from .backend import auto_dispatch_report
from .master import DormMaster
from .optimizer import (MilpOptimizer, OptimizerConfig, utilization_objective)
from .runtime import Migrate, ReallocationResult, Tick
from .types import (Allocation, ApplicationSpec, ClusterSpec, SlaveSpec,
                    demand_matrix)

__all__ = [
    "ShardConfig", "partition_cluster", "ShardedControlPlane",
    "Coordinator", "cross_shard_certificate",
]


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardConfig:
    """Knobs of the sharded plane (the masters' own knobs stay in
    `OptimizerConfig`, passed through untouched)."""
    n_shards: int = 4
    # Coordinator rebalance cadence and limits (see `Coordinator`).
    rebalance_interval_s: float = 600.0
    # Move RUNNING apps only when the normalized-load spread
    # (max - min) / mean exceeds this; pending relief is always on.
    imbalance_threshold: float = 0.25
    # Hysteresis margin: a move must close at least this fraction of the
    # spread or it is skipped (stops ping-pong at the threshold edge).
    hysteresis: float = 0.05
    max_migrations_per_tick: int = 4

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError("n_shards must be >= 1")


def partition_cluster(cluster: ClusterSpec, n_shards: int,
                      ) -> List[ClusterSpec]:
    """Round-robin the slaves: shard s owns global slaves s, s+K, s+2K...

    Round-robin (not contiguous blocks) so that (a) a homogeneous cluster
    splits into exactly-proportional shards whenever b % K == 0 -- the
    proportionality the certificate's dual rescaling relies on -- and
    (b) rack-correlated chaos bursts (contiguous slave ranges) spread
    across shards instead of concentrating on one. Slave ids and specs
    are preserved verbatim, so chaos events route by id unchanged."""
    if n_shards < 1:
        raise ValueError("n_shards must be >= 1")
    if n_shards > cluster.b:
        raise ValueError(
            f"n_shards={n_shards} exceeds the cluster's {cluster.b} slaves")
    return [
        ClusterSpec(resource_types=cluster.resource_types,
                    slaves=tuple(cluster.slaves[s::n_shards]))
        for s in range(n_shards)
    ]


# ---------------------------------------------------------------------------
# merged allocation (lazy dense matrix)
# ---------------------------------------------------------------------------

class _MergedAllocation:
    """Duck-typed `Allocation` over per-shard placed allocations.

    `app_ids` is eager (tuple concatenation: O(placed) pointer copies);
    the dense (n, b) `x` in GLOBAL slave columns is materialized only on
    first access -- at 100k slaves x 50k apps that matrix is ~40 GB and
    must never exist unless a consumer explicitly demands it (the runtime
    does not: every merged result carries `changed_counts`)."""

    __slots__ = ("app_ids", "_parts", "_b", "_x")

    def __init__(self, app_ids: Tuple[str, ...],
                 parts: Sequence[Tuple[np.ndarray, Allocation]], b: int):
        self.app_ids = app_ids
        self._parts = list(parts)           # [(global col indices, alloc)]
        self._b = b
        self._x: Optional[np.ndarray] = None

    @property
    def x(self) -> np.ndarray:
        if self._x is None:
            x = np.zeros((len(self.app_ids), self._b), np.int64)
            row = 0
            for cols, alloc in self._parts:
                n = len(alloc.app_ids)
                if n:
                    x[row:row + n, cols] = alloc.x
                row += n
            self._x = x
        return self._x

    def containers_of(self, app_id: str) -> int:
        i = self.app_ids.index(app_id)
        for cols, alloc in self._parts:
            if i < len(alloc.app_ids):
                return int(alloc.row_at(i).sum())
            i -= len(alloc.app_ids)
        return 0

    def row(self, app_id: str) -> np.ndarray:
        return self.x[self.app_ids.index(app_id)]

    def as_dict(self) -> Dict[str, np.ndarray]:
        return {a: self.x[i].copy() for i, a in enumerate(self.app_ids)}


# ---------------------------------------------------------------------------
# per-shard cache
# ---------------------------------------------------------------------------

class _Shard:
    """One shard: a DormMaster plus the merge-time caches that keep the
    global result O(K + changed) per event instead of O(n_total)."""

    __slots__ = ("index", "master", "cols", "max_slave_cap", "nominal_cap",
                 "placed_ids", "alloc", "used", "cap", "fairness",
                 "goodput", "pending", "load")

    def __init__(self, index: int, master: DormMaster, cols: np.ndarray):
        self.index = index
        self.master = master
        self.cols = cols                     # global slave columns it owns
        cm = master.cluster.capacity_matrix()
        self.max_slave_cap = cm.max(axis=0)  # (m,) biggest single slave
        self.nominal_cap = master.cluster.total_capacity().copy()
        self.placed_ids: Tuple[str, ...] = ()
        self.alloc: Allocation = Allocation.trusted(
            (), np.zeros((0, master.cluster.b), np.int64))
        self.used = np.zeros(master.cluster.m)
        self.cap = self.nominal_cap.copy()
        self.fairness = 0.0
        self.goodput = 0.0
        self.pending: Tuple[str, ...] = ()
        self.load = 0.0                      # routing pressure (see _route)

    def refresh(self, res: ReallocationResult) -> None:
        """Sync the merge caches from this shard's latest result. O(b_s*m)
        for the used vector (state-maintained free matrix), O(1) refs for
        the rest -- never O(n_shard * b_s)."""
        m = self.master
        self.placed_ids = res.allocation.app_ids
        self.alloc = res.allocation
        if m.state is not None:
            self.used = m.state.used_totals()
        else:                                # legacy engine (tests only)
            ids = res.allocation.app_ids
            if ids:
                d = demand_matrix([m.specs[a] for a in ids])
                self.used = res.allocation.x.sum(axis=1).astype(float) @ d
            else:
                self.used = np.zeros(m.cluster.m)
        # Effective capacity: the master swaps its cluster spec on chaos
        # failures/restores, so re-read it every refresh.
        self.cap = m.cluster.total_capacity()
        self.fairness = res.fairness_loss
        self.goodput = res.goodput
        self.pending = res.pending_app_ids

    @property
    def alpha(self) -> float:
        """This shard's share of nominal global capacity (scalar proxy:
        mean over resources of the per-resource share is exact for the
        proportional shards round-robin produces)."""
        return float(self.nominal_cap.sum())

    def normalized_load(self) -> float:
        return self.load / max(self.alpha, 1e-12)


# ---------------------------------------------------------------------------
# the sharded plane
# ---------------------------------------------------------------------------

class ShardedControlPlane:
    """N DormMasters behind one `SchedulerPolicy` face.

    Implements the full policy surface the runtime probes for -- per-event
    hooks, `on_batch` (per-shard storm coalescing), the four chaos
    recovery hooks, `containers_of`, `.cluster`, `backend_compile_s`,
    `phase_breakdown` -- plus `on_migrate` (the `Migrate` runtime event)
    and `migrate()` as the direct API. Wrap in `PolicyTimer`/`ClusterRuntime`
    exactly like a bare DormMaster.
    """

    def __init__(self, cluster: ClusterSpec,
                 config: ShardConfig = ShardConfig(),
                 optimizer_kind: str = "milp",
                 optimizer_cfg: OptimizerConfig = OptimizerConfig(),
                 master_factory: Optional[
                     Callable[[ClusterSpec], Any]] = None):
        """`master_factory(shard_spec) -> policy` overrides the default
        per-shard `DormMaster(shard_spec, optimizer_kind, optimizer_cfg)`
        -- any existing policy with the DormMaster surface works."""
        self.cluster = cluster
        self.config = config
        self.k = config.n_shards
        specs = partition_cluster(cluster, self.k)
        if master_factory is None:
            def master_factory(cs: ClusterSpec) -> DormMaster:
                return DormMaster(cs, optimizer_kind=optimizer_kind,
                                  optimizer_cfg=optimizer_cfg)
        self.shards: List[_Shard] = [
            _Shard(s, master_factory(specs[s]),
                   np.arange(s, cluster.b, self.k))
            for s in range(self.k)
        ]
        # app_id -> owning shard index; exactly one owner per admitted app
        # (the no-dual-ownership invariant of test_shard_properties.py).
        self.owner: Dict[str, int] = {}
        # app_id -> dominant-share routing contribution g_i * anchor_i
        # (global-normalized dominant share per container x the elasticity
        # midpoint), removed exactly on completion/migration.
        self._contrib: Dict[str, float] = {}
        self._global_cap = cluster.total_capacity()
        self.migration_count = 0
        self.migrated_ids: List[str] = []

    # ------------------------------------------------------------- routing

    def _app_pressure(self, spec: ApplicationSpec) -> float:
        d = spec.demand.as_array()
        with np.errstate(divide="ignore", invalid="ignore"):
            g = float(np.where(self._global_cap > 0,
                               d / self._global_cap, 0.0).max())
        return g * 0.5 * (spec.n_min + spec.n_max)

    def _eligible(self, spec: ApplicationSpec, shard: _Shard) -> bool:
        d = spec.demand.as_array()
        return bool((d <= shard.max_slave_cap + 1e-9).all()
                    and (spec.n_min * d <= shard.nominal_cap + 1e-9).all())

    def _route(self, spec: ApplicationSpec) -> int:
        """Least normalized-load eligible shard; ties break on the lowest
        shard index (deterministic). An app NO shard can hold still gets
        the least-loaded shard -- it will sit pending there, matching the
        single master's admit-and-wait semantics."""
        best, best_load = -1, np.inf
        for sh in self.shards:
            if self._eligible(spec, sh):
                nl = sh.normalized_load()
                if nl < best_load - 1e-15:
                    best, best_load = sh.index, nl
        if best < 0:
            best = min(self.shards,
                       key=lambda s: (s.normalized_load(), s.index)).index
        return best

    def _assign(self, spec: ApplicationSpec, shard_idx: int) -> None:
        c = self._app_pressure(spec)
        self.owner[spec.app_id] = shard_idx
        self._contrib[spec.app_id] = c
        self.shards[shard_idx].load += c

    def _release(self, app_id: str) -> None:
        s = self.owner.pop(app_id, None)
        c = self._contrib.pop(app_id, 0.0)
        if s is not None:
            self.shards[s].load = max(0.0, self.shards[s].load - c)

    # ------------------------------------------------------------- merging

    def _merge(self, event_results: Sequence[Tuple[_Shard, ReallocationResult]],
               migrated: Tuple[str, ...] = (),
               ) -> ReallocationResult:
        """Fold the event shards' fresh results with every other shard's
        cached snapshot into one global ReallocationResult."""
        for sh, res in event_results:
            sh.refresh(res)
        app_ids: Tuple[str, ...] = ()
        parts: List[Tuple[np.ndarray, Allocation]] = []
        used = np.zeros_like(self._global_cap)
        cap = np.zeros_like(self._global_cap)
        fairness = 0.0
        goodput = 0.0
        pending: Tuple[str, ...] = ()
        for sh in self.shards:
            app_ids += sh.placed_ids
            parts.append((sh.cols, sh.alloc))
            used = used + sh.used
            cap = cap + sh.cap
            fairness += sh.fairness
            goodput += sh.goodput
            pending += sh.pending
        with np.errstate(divide="ignore", invalid="ignore"):
            util = float(np.where(cap > 0, used / cap, 0.0).sum())
        adjusted: Tuple[str, ...] = ()
        started: Tuple[str, ...] = ()
        forced: Tuple[str, ...] = ()
        displaced: Tuple[str, ...] = ()
        parked: Tuple[str, ...] = ()
        changed: Optional[Dict[str, int]] = {}
        gaps: List[Optional[float]] = []
        for _, res in event_results:
            adjusted += res.adjusted_app_ids
            started += res.started_app_ids
            forced += res.forced_adjusted_app_ids
            displaced += res.displaced_app_ids
            parked += res.parked_app_ids
            if changed is not None:
                if res.changed_counts is None:
                    changed = None
                else:
                    changed.update(res.changed_counts)
            gaps.append(res.optimality_gap)
        gap = (max(g for g in gaps) if gaps and all(g is not None
                                                   for g in gaps) else None)
        return ReallocationResult(
            allocation=_MergedAllocation(app_ids, parts, self.cluster.b),
            adjusted_app_ids=adjusted,
            started_app_ids=started,
            pending_app_ids=pending,
            utilization=util,
            fairness_loss=fairness,
            adjustment_overhead=len(adjusted),
            changed_counts=changed,
            optimality_gap=gap,
            forced_adjusted_app_ids=forced,
            displaced_app_ids=displaced,
            parked_app_ids=parked,
            migrated_app_ids=migrated,
            goodput=goodput,
        )

    # ------------------------------------------- SchedulerPolicy interface

    def on_arrival(self, specs: Sequence[ApplicationSpec],
                   ) -> ReallocationResult:
        if self.k == 1:
            for spec in specs:
                self._assign(spec, 0)
            res = self.shards[0].master.on_arrival(specs)
            self.shards[0].refresh(res)
            return res
        groups: Dict[int, List[ApplicationSpec]] = {}
        for spec in specs:
            # Route sequentially (each assignment bumps the target's load)
            # so one burst spreads instead of dogpiling the lightest shard.
            s = self._route(spec)
            self._assign(spec, s)
            groups.setdefault(s, []).append(spec)
        results = [(self.shards[s], self.shards[s].master.on_arrival(
            tuple(group))) for s, group in sorted(groups.items())]
        return self._merge(results)

    def on_completion(self, app_id: str) -> ReallocationResult:
        s = self.owner.get(app_id, 0)
        self._release(app_id)
        res = self.shards[s].master.on_completion(app_id)
        if self.k == 1:
            self.shards[0].refresh(res)
            return res
        return self._merge([(self.shards[s], res)])

    def on_resize(self, app_id: str, n_min: Optional[int] = None,
                  n_max: Optional[int] = None,
                  ) -> Optional[ReallocationResult]:
        s = self.owner.get(app_id)
        if s is None:
            return None
        res = self.shards[s].master.on_resize(app_id, n_min, n_max)
        if res is None:
            return None
        # Accepted resize: refresh the app's routing pressure from the
        # master's (clamped) view of the new bounds.
        spec = self.shards[s].master.specs.get(app_id)
        if spec is not None:
            old = self._contrib.get(app_id, 0.0)
            new = self._app_pressure(spec)
            self._contrib[app_id] = new
            self.shards[s].load = max(0.0, self.shards[s].load - old + new)
        if self.k == 1:
            self.shards[0].refresh(res)
            return res
        return self._merge([(self.shards[s], res)])

    def on_tick(self, t: float) -> Optional[ReallocationResult]:
        if self.k == 1:
            res = self.shards[0].master.on_tick(t)
            if res is not None:
                self.shards[0].refresh(res)
            return res
        results = [(sh, res) for sh in self.shards
                   for res in (sh.master.on_tick(t),) if res is not None]
        if not results:
            return None
        return self._merge(results)

    def containers_of(self, app_id: str) -> int:
        s = self.owner.get(app_id)
        if s is None:
            return 0
        return self.shards[s].master.containers_of(app_id)

    # ------------------------------------------------------- storm absorber

    def on_batch(self, completions: Sequence[str],
                 resizes: Sequence[Tuple[str, Optional[int], Optional[int]]],
                 arrivals: Sequence[ApplicationSpec],
                 chaos: Sequence[Any] = (),
                 ) -> ReallocationResult:
        """One absorbed flood, split per shard: each involved shard gets
        ONE `DormMaster.on_batch` pass over its slice of the flood.

        Arrivals are routed (owners assigned) BEFORE completions are
        grouped, so an arrival+completion of the same app inside one flood
        lands on the same shard and cancels there, exactly like the single
        master's queue-merge semantics. Chaos events route by the failed
        slave's owning shard."""
        if self.k == 1:
            for spec in arrivals:
                if spec.app_id not in self.owner:
                    self._assign(spec, 0)
            res = self.shards[0].master.on_batch(completions, resizes,
                                                 arrivals, chaos=chaos)
            for app_id in completions:
                self._release(app_id)
            self.shards[0].refresh(res)
            return res
        arr: Dict[int, List[ApplicationSpec]] = {}
        for spec in arrivals:
            s = self.owner.get(spec.app_id)
            if s is None:
                s = self._route(spec)
                self._assign(spec, s)
            arr.setdefault(s, []).append(spec)
        comp: Dict[int, List[str]] = {}
        for app_id in completions:
            comp.setdefault(self.owner.get(app_id, 0), []).append(app_id)
        rz: Dict[int, List[Tuple[str, Optional[int], Optional[int]]]] = {}
        for app_id, lo, hi in resizes:
            s = self.owner.get(app_id)
            if s is not None:
                rz.setdefault(s, []).append((app_id, lo, hi))
        xx: Dict[int, List[Any]] = {}
        for ev in chaos:
            xx.setdefault(self._shard_of_slave(ev.slave_id), []).append(ev)
        involved = sorted(set(arr) | set(comp) | set(rz) | set(xx))
        results = []
        for s in involved:
            sh = self.shards[s]
            res = sh.master.on_batch(
                tuple(comp.get(s, ())),
                tuple(rz.get(s, ())),
                tuple(arr.get(s, ())),
                chaos=tuple(xx.get(s, ())))
            results.append((sh, res))
        for app_id in completions:
            self._release(app_id)
        return self._merge(results)

    # --------------------------------------------------------- chaos hooks

    def _shard_of_slave(self, slave_id: str) -> int:
        # Round-robin partition: global slave position j lives on shard
        # j % K. Falls back to a per-shard lookup for foreign ids.
        for sh in self.shards:
            if slave_id in sh.master._slave_pos:
                return sh.index
        return 0

    def _chaos(self, slave_id: str, hook: str, *args,
               ) -> Optional[ReallocationResult]:
        sh = self.shards[self._shard_of_slave(slave_id)]
        res = getattr(sh.master, hook)(slave_id, *args)
        if res is None:
            return None
        if self.k == 1:
            sh.refresh(res)
            return res
        return self._merge([(sh, res)])

    def on_slave_failed(self, slave_id: str) -> Optional[ReallocationResult]:
        return self._chaos(slave_id, "on_slave_failed")

    def on_slave_drained(self, slave_id: str) -> Optional[ReallocationResult]:
        return self._chaos(slave_id, "on_slave_drained")

    def on_slave_degraded(self, slave_id: str, factor: float = 0.5,
                          ) -> Optional[ReallocationResult]:
        return self._chaos(slave_id, "on_slave_degraded", factor)

    def on_slave_restored(self, slave_id: str,
                          ) -> Optional[ReallocationResult]:
        return self._chaos(slave_id, "on_slave_restored")

    # ----------------------------------------------------------- migration

    def migrate(self, app_id: str, dst_shard: int,
                ) -> Optional[ReallocationResult]:
        """Move an app between shards: teardown + source re-solve, then
        re-admission + destination solve (under the DESTINATION's Eq-16
        adjustment budget -- the destination's optimizer decides when the
        migrant actually gets containers).

        A RUNNING migrant is forced churn: it lands in `adjusted_app_ids`
        and `forced_adjusted_app_ids` (the runtime charges one §III-C.2
        adjustment pause, identical to a chaos eviction), with
        `changed_counts` carrying its post-migration count (0 while it
        waits in the destination's pending queue). A PENDING migrant moves
        for free: only `migrated_app_ids` records it. Returns None when
        the app is unknown or already on `dst_shard`."""
        src = self.owner.get(app_id)
        if src is None or not (0 <= dst_shard < self.k) or dst_shard == src:
            return None
        src_sh, dst_sh = self.shards[src], self.shards[dst_shard]
        spec = src_sh.master.specs.get(app_id)
        if spec is None:
            return None
        was_running = src_sh.master.containers_of(app_id) > 0
        res_src = src_sh.master.complete(app_id)
        res_dst = dst_sh.master.submit(spec)
        # Ownership/load bookkeeping: contribution moves with the app.
        self._release(app_id)
        self._assign(spec, dst_shard)
        self.migration_count += 1
        self.migrated_ids.append(app_id)
        merged = self._merge([(src_sh, res_src), (dst_sh, res_dst)],
                             migrated=(app_id,))
        changed = dict(merged.changed_counts or {})
        # The migrant's count defaults to 0 (torn down on the source);
        # the destination's result overrides when it placed the app.
        changed.setdefault(app_id, 0)
        adjusted = merged.adjusted_app_ids
        started = merged.started_app_ids
        forced = merged.forced_adjusted_app_ids
        if was_running:
            # Forced adjustment, not a fresh start: the app saves state,
            # tears down, and resumes wherever the destination places it.
            started = tuple(a for a in started if a != app_id)
            if app_id not in adjusted:
                adjusted += (app_id,)
            if app_id not in forced:
                forced += (app_id,)
        return dataclasses.replace(
            merged, adjusted_app_ids=adjusted, started_app_ids=started,
            forced_adjusted_app_ids=forced,
            adjustment_overhead=len(adjusted), changed_counts=changed)

    def on_migrate(self, app_id: str, dst_shard: int,
                   ) -> Optional[ReallocationResult]:
        """Runtime `Migrate` event hook (the coordinator publishes these;
        `inject(Migrate(...))` forces one by hand)."""
        return self.migrate(app_id, dst_shard)

    # ----------------------------------------------------------- telemetry

    @property
    def backend_compile_s(self) -> float:
        # The compile count is process-wide: every jax shard reads the same
        # number, so it is taken once, not summed.
        return max((sh.master.backend_compile_s for sh in self.shards),
                   default=0.0)

    def phase_breakdown(self) -> Dict[str, float]:
        """Cumulative per-phase seconds summed over shards (same buckets
        as `DormMaster.phase_breakdown`)."""
        out: Dict[str, float] = {}
        for sh in self.shards:
            for phase, secs in sh.master.phase_breakdown().items():
                out[phase] = out.get(phase, 0.0) + secs
        if out:
            out["backend_compile"] = self.backend_compile_s
        return out

    def shard_summaries(self) -> List[Dict[str, Any]]:
        """Per-shard health the coordinator (and bench_shard.py) reads:
        size, ownership, pressure, Eq-1/2 snapshots, and which engine the
        per-shard `backend="auto"` dispatch selects at this shard's size."""
        out = []
        for sh in self.shards:
            m = sh.master
            be = getattr(m.optimizer, "backend", None)
            n_owned = sum(1 for s in self.owner.values() if s == sh.index)
            with np.errstate(divide="ignore", invalid="ignore"):
                util = float(np.where(sh.cap > 0, sh.used / sh.cap,
                                      0.0).sum())
            entry: Dict[str, Any] = {
                "shard": sh.index,
                "slaves": m.cluster.b,
                "apps_owned": n_owned,
                "placed": len(sh.placed_ids),
                "pending": len(sh.pending),
                "load": sh.load,
                "normalized_load": sh.normalized_load(),
                "utilization": util,
                "fairness_loss": sh.fairness,
                "goodput": sh.goodput,
            }
            if type(be).__name__ == "AutoBackend":
                entry["auto_dispatch"] = auto_dispatch_report(
                    m.cluster.b, max(n_owned, 1), backend=be)
            out.append(entry)
        return out


# ---------------------------------------------------------------------------
# the coordinator
# ---------------------------------------------------------------------------

class Coordinator:
    """Thin cross-shard rebalancer on a slow tick.

    Never solves anything itself: it reads the plane's per-shard
    summaries and publishes `Migrate` events, which the runtime routes
    back into `ShardedControlPlane.on_migrate` (each migration is then a
    normal sampled/published reallocation). Two phases per rebalance:

      1. PENDING RELIEF -- a pending app is waiting on a shard while
         another eligible shard has lower pressure: move it (free -- a
         pending migrant costs zero churn).
      2. LOAD SPREAD -- when (max - min) / mean normalized load exceeds
         `ShardConfig.imbalance_threshold`, move the smallest-pressure
         running apps from the heaviest to the lightest shard, stopping
         once the projected spread closes by less than the hysteresis
         margin (ping-pong guard).

    Attach to a runtime (`coordinator.attach(runtime)`; set
    `tick_interval_s` so ticks fire) for event-loop driving, or call
    `rebalance(t)` directly for step-driven use. Bounded by
    `ShardConfig.max_migrations_per_tick` per rebalance."""

    def __init__(self, plane: ShardedControlPlane,
                 config: Optional[ShardConfig] = None):
        self.plane = plane
        self.config = config if config is not None else plane.config
        self.runtime = None
        self._last_rebalance = -np.inf
        self.migrations: List[Migrate] = []

    def attach(self, runtime) -> "Coordinator":
        """Bind to the `ClusterRuntime` driving the plane: rebalances on
        the runtime's `Tick` stream, injecting `Migrate` events."""
        self.runtime = runtime
        runtime.bus.subscribe(Tick, self._on_tick)
        return self

    def _on_tick(self, ev: Tick) -> None:
        self.rebalance(ev.t)

    # ---------------------------------------------------------------- plan

    def plan(self, t: float) -> List[Migrate]:
        """Compute this rebalance's moves WITHOUT executing them."""
        plane, cfg = self.plane, self.config
        if plane.k < 2:
            return []
        moves: List[Migrate] = []
        budget = cfg.max_migrations_per_tick
        loads = {sh.index: sh.normalized_load() for sh in plane.shards}
        # Phase 1: pending relief (free moves).
        for sh in plane.shards:
            if budget <= len(moves):
                break
            for app_id in sh.pending:
                if budget <= len(moves):
                    break
                spec = sh.master.specs.get(app_id)
                if spec is None:
                    continue
                c = plane._contrib.get(app_id, 0.0)
                best, best_load = -1, loads[sh.index]
                for other in plane.shards:
                    if other.index == sh.index:
                        continue
                    if (plane._eligible(spec, other)
                            and loads[other.index] + 1e-12 < best_load):
                        best, best_load = other.index, loads[other.index]
                if best >= 0:
                    moves.append(Migrate(t=t, app_id=app_id,
                                         src_shard=sh.index, dst_shard=best,
                                         forced=False))
                    loads[sh.index] -= c / max(sh.alpha, 1e-12)
                    loads[best] += c / max(plane.shards[best].alpha, 1e-12)
        # Phase 2: load-spread moves (forced churn, so gated + hysteretic).
        mean = sum(loads.values()) / len(loads)
        if mean <= 0:
            return moves
        while len(moves) < budget:
            hi = max(loads, key=lambda s: (loads[s], -s))
            lo = min(loads, key=lambda s: (loads[s], s))
            spread = (loads[hi] - loads[lo]) / mean
            if spread <= cfg.imbalance_threshold:
                break
            src_sh, dst_sh = plane.shards[hi], plane.shards[lo]
            planned = {mv.app_id for mv in moves}
            # Smallest-pressure running app that fits the target and whose
            # move closes a meaningful fraction of the spread.
            candidates = sorted(
                ((plane._contrib.get(a, 0.0), a)
                 for a in src_sh.placed_ids
                 if a not in planned
                 and a in src_sh.master.specs
                 and plane._eligible(src_sh.master.specs[a], dst_sh)),
                key=lambda p: (p[0], p[1]))
            moved = False
            for c, app_id in candidates:
                dl = c / max(src_sh.alpha, 1e-12)
                if dl < cfg.hysteresis * spread * mean:
                    continue             # too small to matter: skip, next
                new_hi = loads[hi] - dl
                new_lo = loads[lo] + c / max(dst_sh.alpha, 1e-12)
                if new_lo >= new_hi:     # would overshoot into ping-pong
                    continue
                moves.append(Migrate(t=t, app_id=app_id, src_shard=hi,
                                     dst_shard=lo, forced=True))
                loads[hi], loads[lo] = new_hi, new_lo
                moved = True
                break
            if not moved:
                break
        return moves

    def rebalance(self, t: float) -> List[Migrate]:
        """Run one rebalance if the interval elapsed: plan, then execute
        (inject into the attached runtime, or apply directly)."""
        if t - self._last_rebalance < self.config.rebalance_interval_s:
            return []
        self._last_rebalance = t
        moves = self.plan(t)
        for mv in moves:
            self.migrations.append(mv)
            if self.runtime is not None:
                # Injected at the current instant: the runtime dispatches
                # it to `on_migrate` before time advances, publishing the
                # event + its Reallocated sample like any other event.
                self.runtime.inject(mv)
            else:
                self.plane.migrate(mv.app_id, mv.dst_shard)
        return moves


# ---------------------------------------------------------------------------
# cross-shard optimality certificate
# ---------------------------------------------------------------------------

def _proportional_alphas(plane: ShardedControlPlane,
                         ) -> Optional[List[float]]:
    """alpha_s with C^s = alpha_s * C^g exactly (within fp tolerance), or
    None when the shards are not proportional slices of the global
    capacity. Proportionality is what makes a shard-normalized colgen
    dual bound rescale EXACTLY to global units: w^shard_i = w^global_i /
    alpha_s, so (shard bound) * alpha_s bounds the shard's contribution
    to the global objective."""
    total = plane.cluster.total_capacity()
    alphas: List[float] = []
    for sh in plane.shards:
        with np.errstate(divide="ignore", invalid="ignore"):
            ratio = np.where(total > 0, sh.nominal_cap / total, np.nan)
        vals = ratio[~np.isnan(ratio)]
        if vals.size == 0 or not np.allclose(vals, vals[0], rtol=1e-9):
            return None
        alphas.append(float(vals[0]))
    return alphas


def cross_shard_certificate(plane: ShardedControlPlane,
                            optimizer_cfg: Optional[OptimizerConfig] = None,
                            ) -> Dict[str, Optional[float]]:
    """Certify the cross-shard optimality loss of the CURRENT app set.

    Runs fresh column-generation solves (no Eq-16 coupling: prev=None)
    over (a) each shard's owned apps on its shard spec and (b) the whole
    app set on the global spec, all against NOMINAL capacities. Colgen
    proves an LP dual bound on every solve, so both sides come certified:

      global_bound      >= the true single-master optimum (global units),
      sharded_objective  = what the shard-partitioned solves achieved,
                           re-scored in global units exactly
                           (`utilization_objective` vs the global spec),
      sharded_bound      = sum_s alpha_s * (shard dual bound): the best
                           ANY allocation honoring this app partition can
                           achieve (None when shards are not proportional
                           slices -- the rescaling is only exact then).

      cross_shard_gap  = max(0, global_bound - sharded_objective)
                         / global_bound

    is therefore a CERTIFIED upper bound on the fraction of utilization
    lost to sharding (it also absorbs any per-shard solve suboptimality,
    making it conservative). `partition_gap` isolates the partition's own
    ceiling: max(0, global_bound - sharded_bound) / global_bound."""
    cfg = optimizer_cfg if optimizer_cfg is not None else OptimizerConfig()
    cfg = dataclasses.replace(cfg, column_generation=True, soa=True)
    all_specs: List[ApplicationSpec] = []
    shard_specs: List[List[ApplicationSpec]] = []
    for sh in plane.shards:
        owned = list(sh.master.specs.values())
        shard_specs.append(owned)
        all_specs.extend(owned)
    # -- single-master colgen over the global problem.
    opt = MilpOptimizer(cfg)
    g_alloc = opt.solve(all_specs, plane.cluster, None)
    if g_alloc is None or opt.last_bound is None:
        return {"global_bound": None, "global_objective": None,
                "sharded_objective": None, "sharded_bound": None,
                "cross_shard_gap": None, "partition_gap": None,
                "n_apps": float(len(all_specs))}
    global_bound = float(opt.last_bound)
    global_objective = float(opt.last_objective)
    # -- per-shard colgen, achieved value re-scored in GLOBAL units.
    sharded_objective = 0.0
    shard_bounds: List[Optional[float]] = []
    for sh, owned in zip(plane.shards, shard_specs):
        if not owned:
            shard_bounds.append(0.0)
            continue
        sopt = MilpOptimizer(cfg)
        # Nominal shard spec (chaos-scaled capacity would certify a
        # different problem than the single-master reference).
        nominal = ClusterSpec(
            resource_types=plane.cluster.resource_types,
            slaves=tuple(plane.cluster.slaves[sh.index::plane.k]))
        s_alloc = sopt.solve(owned, nominal, None)
        if s_alloc is None:
            shard_bounds.append(None)
            continue
        sharded_objective += utilization_objective(s_alloc, owned,
                                                   plane.cluster)
        shard_bounds.append(float(sopt.last_bound)
                            if sopt.last_bound is not None else None)
    alphas = _proportional_alphas(plane)
    sharded_bound: Optional[float] = None
    if alphas is not None and all(b is not None for b in shard_bounds):
        sharded_bound = float(sum(a * b for a, b
                                  in zip(alphas, shard_bounds)))
    denom = max(abs(global_bound), 1e-12)
    cross_gap = max(0.0, global_bound - sharded_objective) / denom
    partition_gap = (max(0.0, global_bound - sharded_bound) / denom
                     if sharded_bound is not None else None)
    return {
        "global_bound": global_bound,
        "global_objective": global_objective,
        "sharded_objective": float(sharded_objective),
        "sharded_bound": sharded_bound,
        "cross_shard_gap": float(cross_gap),
        "partition_gap": partition_gap,
        "n_apps": float(len(all_specs)),
    }
