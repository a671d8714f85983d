"""The utilization-fairness optimizer (paper §IV, problem P2).

P2 (Eqs 10-18):  choose x_{i,j} (containers of app i on slave j) to

    max   sum_k sum_i sum_j  x_{i,j} d_{i,k} / C_k          (utilization, Eq 10)
    s.t.  sum_i x_{i,j} d_{i,k} <= c_{j,k}                  (capacity,   Eq 6)
          n_min_i <= sum_j x_{i,j} <= n_max_i               (bounds, Eqs 7-8)
          l_i >= | s_i - s_hat_i |                          (Eqs 11-12, linearized)
          M r_i >= | x_{i,j} - x^{t-1}_{i,j} |              (Eqs 13-14, big-M)
          sum_i l_i <= theta1 * 2m     [optionally ceil'd]  (Eq 15)
          sum_i r_i <= ceil(theta2 * |A^t ∩ A^{t-1}|)       (Eq 16)

Key linearization fact: the dominant resource of app i is argmax_k d_{i,k}/C_k,
which does NOT depend on the container count, so the actual dominant share is
s_i = g_i * N_i with the constant g_i = max_k d_{i,k}/C_k and N_i = sum_j x_{i,j}.
Hence Eqs 11-12 are linear in x.

Three solvers behind one interface:
  * `MilpOptimizer`  -- exact, scipy.optimize.milp (HiGHS; stands in for CPLEX).
    Two exact-at-scale routes live behind it: the rolling-horizon block
    decomposition (`OptimizerConfig.rolling_horizon_vars`; block-exact but
    greedy across blocks, so no global bound) and column generation
    (`OptimizerConfig.column_generation` / `make_optimizer("colgen")`),
    which prices per-app container-count columns against the LP duals of an
    aggregate restricted master and certifies a GLOBAL optimality gap
    (`last_gap`/`last_bound`) on every solve.
    Constraints are assembled as `scipy.sparse` matrices by default (the dense
    matrix has (b*m + 2*n*b) rows x n*b columns and collapses beyond a few
    hundred slaves); set `OptimizerConfig.sparse=False` for the loop-built
    dense reference assembly. With `warm_start=True` a feasible incumbent is
    derived from the previous allocation via the greedy heuristic: its
    objective value is added as a cutoff plane, and if HiGHS fails or times
    out the incumbent is returned instead of None.
  * `GreedyOptimizer`-- fast DRF-guided heuristic with placement stickiness
    (used for very large instances and as a cross-check). Hot paths are
    incremental/vectorized so a 500-app x 1000-slave solve stays in the
    tens of milliseconds.
  * `AutoOptimizer`  -- size-aware dispatcher: exact MILP while
    n_apps * b <= `OptimizerConfig.auto_switch_vars`, greedy beyond.

Paper fallback: if P2 is infeasible, "Dorm would keep existing resource
allocations until more running applications finish" -- `solve()` returns None
and the DormMaster keeps the previous allocation (new apps stay pending).
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .backend import NumpyBackend, _place_counts_np, get_backend

# Host reference backend for spec-only SoA solves (no ClusterState): the
# fused placement schedule then runs the sequential numpy loop regardless
# of the configured device backend (it is the master's state-backed hot
# path that the device fusion targets).
_HOST_BACKEND = NumpyBackend()
from .telemetry import Spans
from .drf import (IncrementalDRF, drf_container_counts,
                  drf_container_counts_reference, drf_shares)
from .types import (Allocation, ApplicationSpec, ClusterSpec, demand_matrix,
                    validate_allocation)

try:  # scipy is available in this environment; keep the import soft anyway.
    from scipy import sparse as _sp
    from scipy.optimize import LinearConstraint, linprog, milp
    from scipy.optimize import Bounds as _Bounds
    _HAVE_SCIPY = True
except Exception:  # pragma: no cover
    _HAVE_SCIPY = False


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    theta1: float = 0.1          # fairness-loss threshold   (paper theta_1)
    theta2: float = 0.1          # adjustment-overhead threshold (paper theta_2)
    # Eq 15 writes ceil(theta1 * 2m); the observed Fig-7 bounds match the
    # un-ceiled budget, so that is the default. Set True for the literal text.
    ceil_fairness_budget: bool = False
    ceil_adjust_budget: bool = True     # Eq 16's ceil (integer count anyway)
    time_limit_s: float = 30.0
    mip_rel_gap: float = 1e-4
    # -- scale knobs ------------------------------------------------------
    sparse: bool = True          # sparse MILP constraint assembly
    warm_start: bool = False     # greedy incumbent: cutoff + timeout fallback
    auto_switch_vars: int = 2_000    # AutoOptimizer: MILP while n*b <= this
    # Per-event incremental path (GreedyOptimizer only): warm-start the
    # solve from prev_alloc and skip the DRF refill + stickiness repacking
    # whenever the saturating-DRF fast path proves the result unchanged.
    # Bit-exact with incremental=False by construction (tests/
    # test_incremental.py), so it is safe to leave on by default.
    incremental: bool = True
    # Structure-of-arrays engine (PR 3). True: the greedy solver uses the
    # vectorized ladder DRF filling, batched best-fit placement and bulk
    # changed-row detection, and DormMaster keeps its bookkeeping in a
    # `core.state.ClusterState` with lazily materialized container objects.
    # False: the PR-2 dict-of-objects reference engine (kept, like
    # ReferenceClusterSimulator, as the golden baseline the benchmark
    # measures the SoA speedup ratio against -- in ONE process).
    # Both engines are bit-exact with each other (tests/test_state.py).
    soa: bool = True
    # Rolling-horizon exact solve (MilpOptimizer): monolithic MILP while
    # n_apps * b <= this, block decomposition beyond -- blocks ordered by
    # utilization weight (DRF-target tie-broken), each solved exactly
    # against residual capacity, consuming the remaining global Eq-15/16
    # budgets. 0 disables the decomposition (always monolithic).
    rolling_horizon_vars: int = 4_000
    # Column-generation exact solve (MilpOptimizer; also via
    # make_optimizer("colgen")). True routes EVERY solve through a
    # Dantzig-Wolfe restricted master LP over per-app container-count
    # columns: pricing against the duals on the m aggregate capacity rows
    # (+ the Eq-15 fairness and Eq-16 adjustment rows) generates improving
    # columns in closed form, the greedy solution seeds the pool, and a
    # final integer solve over the pool yields the allocation. Unlike the
    # rolling horizon (block-exact, greedy across blocks, unbounded global
    # gap) the LP bound certifies a GLOBAL optimality gap, reported as
    # `MilpOptimizer.last_gap` / `ReallocationResult.optimality_gap`.
    column_generation: bool = False
    # Pricing-iteration cap: each iteration re-solves the restricted master
    # LP and adds at most one improving column per app. The Lagrangian
    # bound stays certified when the cap bites (the gap merely widens).
    colgen_max_iters: int = 60
    # Column-pool ceiling (seed + generated): pricing stops growing the
    # pool past this and the final integer solve runs on what exists.
    colgen_pool_max: int = 100_000
    # Packing repair: the aggregate master ignores per-slave fragmentation,
    # so the selected counts may not pack heuristically. Identical demand
    # rows are interchangeable, so the packer works on DISTINCT demand
    # types (T << n on real clusters): while T * b <= this, an exact
    # row-sum-fixed packing MILP (a cheap feasibility problem, NOT the
    # full P2 grid) realizes the counts; within 10x this, a packing LP +
    # round-down + best-fit repair approximates them; a selection that
    # provably cannot pack is excluded with a no-good cut and re-selected,
    # up to `colgen_pack_rounds` times. 0 disables the repair (heuristic
    # placement only; the certified gap simply widens).
    colgen_pack_vars: int = 20_000
    colgen_pack_rounds: int = 3
    # Array backend for the greedy solver's hot kernels (PR 6): "numpy"
    # (the bit-exactness reference) or "jax" (jit/lax programs, a stable
    # sort fill for placement -- see core.backend). The env default
    # lets CI run the whole tier-1 suite on the jax backend without code
    # changes (REPRO_BACKEND=jax).
    backend: str = dataclasses.field(
        default_factory=lambda: os.environ.get("REPRO_BACKEND", "numpy"))
    # Goodput-aware allocation (speedup curves, see core.goodput). True:
    # the greedy solver targets each curved app at its goodput KNEE
    # instead of n_max (containers past the knee buy < goodput_knee of a
    # container's progress -- better spent on apps still on the steep
    # part), and the column-generation exact route weights every column
    # by its goodput w_i * gp_i(N) instead of the count w_i * N. Apps
    # without a curve -- every seed workload -- are untouched on both
    # paths, so existing solves stay bit-identical; the monolithic MILP
    # and rolling-horizon paths keep the count-linear Eq-10 objective
    # either way (P2's linearization needs s_i = g_i * N_i).
    goodput_aware: bool = True
    # Knee definition: the marginal-goodput fraction below which an extra
    # container is no longer targeted (GoodputCurve.knee's `frac`).
    goodput_knee: float = 0.5


def fairness_budget(cfg: OptimizerConfig, m: int) -> float:
    raw = cfg.theta1 * 2 * m
    return float(math.ceil(raw)) if cfg.ceil_fairness_budget else float(raw)


def adjust_budget(cfg: OptimizerConfig, n_common: int) -> int:
    return int(math.ceil(cfg.theta2 * n_common)) if cfg.ceil_adjust_budget \
        else int(cfg.theta2 * n_common)


def _dominant_coeff(apps: Sequence[ApplicationSpec], cluster: ClusterSpec,
                    d: Optional[np.ndarray] = None) -> np.ndarray:
    """g_i = max_k d_{i,k} / C_k  (share per container)."""
    if d is None:
        d = demand_matrix(apps)                 # (n, m)
    cap = cluster.total_capacity()              # (m,)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratios = np.where(cap > 0, d / cap, 0.0)
    return ratios.max(axis=1)


def _util_coeff(apps: Sequence[ApplicationSpec], cluster: ClusterSpec,
                d: Optional[np.ndarray] = None) -> np.ndarray:
    """w_i = sum_k d_{i,k} / C_k -- utilization gained per container of app i."""
    if d is None:
        d = demand_matrix(apps)
    cap = cluster.total_capacity()
    with np.errstate(divide="ignore", invalid="ignore"):
        ratios = np.where(cap > 0, d / cap, 0.0)
    return ratios.sum(axis=1)


def utilization_objective(alloc: Allocation,
                          apps: Sequence[ApplicationSpec],
                          cluster: ClusterSpec,
                          d: Optional[np.ndarray] = None) -> float:
    """P2's Eq-10 utilization value of an allocation, normalized by
    `cluster.total_capacity()`: sum_i w_i * N_i with w_i = sum_k d_ik/C_k.

    The normalizing cluster is a parameter on purpose: a per-shard solve
    certifies its bound against the SHARD's capacity, so re-scoring the
    shard's allocation here against the GLOBAL spec expresses it in global
    units -- the cross-shard certificate (`repro.core.shard.
    cross_shard_certificate`) sums these against the single-master colgen
    bound. `apps` may be any superset of the allocation's apps."""
    if not alloc.app_ids:
        return 0.0
    by_id = {a.app_id: a for a in apps}
    specs = [by_id[i] for i in alloc.app_ids]
    w = _util_coeff(specs, cluster,
                    d if d is not None else demand_matrix(specs))
    counts = alloc.x.sum(axis=1).astype(np.float64)
    return float(w @ counts)


def _knee_caps(apps: Sequence[ApplicationSpec], nmin_v: np.ndarray,
               nmax_v: np.ndarray, frac: float) -> Optional[np.ndarray]:
    """Effective n_max under goodput-aware allocation: each app carrying a
    non-linear speedup curve is capped at max(n_min, its goodput knee).
    Returns the capped copy, or None when no cap bites (no curved apps --
    the bit-exactness guarantee: the caller then keeps its own nmax_v
    object and every downstream array is unchanged)."""
    capped = None
    for i, a in enumerate(apps):
        curve = a.goodput
        if curve is None or curve.is_linear:
            continue
        eff = max(int(nmin_v[i]),
                  min(int(nmax_v[i]), curve.knee(int(nmax_v[i]), frac)))
        if eff < int(nmax_v[i]):
            if capped is None:
                capped = nmax_v.copy()
            capped[i] = eff
    return capped


def _shares_vec(counts: np.ndarray, d: np.ndarray, total: np.ndarray,
                ) -> np.ndarray:
    """Dominant shares for given counts (same arithmetic as `drf_shares`)."""
    n_vec = counts.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratios = np.where(total[None, :] > 0,
                          n_vec[:, None] * d / total[None, :], 0.0)
    return ratios.max(axis=1) if ratios.size else np.zeros(len(counts))


def _drf_targets(apps: Sequence[ApplicationSpec], cluster: ClusterSpec,
                 reference: bool = False,
                 d: Optional[np.ndarray] = None,
                 ) -> Tuple[Dict[str, int], np.ndarray]:
    """One progressive-filling pass -> (counts, s_hat vector in app order).
    `reference=True` runs the seed's one-grant-at-a-time filling (the legacy
    engine's cost model); both produce identical counts."""
    fill = drf_container_counts_reference if reference \
        else drf_container_counts
    counts = fill(apps, cluster)
    shares = drf_shares(apps, cluster, counts=counts, d=d)
    s_hat = np.array([shares[a.app_id] for a in apps])
    return counts, s_hat


class MilpOptimizer:
    """Exact P2 via scipy.optimize.milp (HiGHS)."""

    def __init__(self, cfg: OptimizerConfig = OptimizerConfig(),
                 spans: Optional[Spans] = None):
        if not _HAVE_SCIPY:  # pragma: no cover
            raise RuntimeError("scipy not available; use GreedyOptimizer")
        self.cfg = cfg
        self.spans = spans if spans is not None else Spans()
        self.last_shares: Optional[Dict[str, float]] = None
        self.last_shares_vec: Optional[np.ndarray] = None  # solve app order
        self.last_changed: Optional[Tuple[str, ...]] = None  # never proven
        self.monolithic_solves = 0
        self.rolling_solves = 0
        self.colgen_solves = 0
        self.colgen_iters = 0      # cumulative pricing iterations
        self.colgen_columns = 0    # pool size of the last colgen solve
        # Certified optimality-gap report of the last solve (None when the
        # path taken cannot certify one -- rolling horizon, or a failed
        # solve). `last_bound` is a PROVEN upper bound on the P2 utilization
        # objective; `last_objective` the achieved objective; `last_gap`
        # their relative gap in [0, inf).
        self.last_gap: Optional[float] = None
        self.last_bound: Optional[float] = None
        self.last_objective: Optional[float] = None

    @property
    def refill_s(self) -> float:
        """Cumulative DRF-refill seconds (the `optimizer.refill` span)."""
        return self.spans.total_s.get("optimizer.refill", 0.0)

    @property
    def pricing_s(self) -> float:
        """Cumulative column-generation pricing seconds (the
        `optimizer.pricing` span)."""
        return self.spans.total_s.get("optimizer.pricing", 0.0)

    # ------------------------------------------------------ dense assembly

    def _assemble_dense(self, apps, d, cap, g, s_hat_vec, prev_map, common,
                        budget_l: float, budget_r: float,
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Loop-built dense (A, lb, ub) -- the reference assembly. Row order
        must match `_assemble_sparse` exactly. `budget_l`/`budget_r` are the
        Eq-15/Eq-16 right-hand sides (a rolling-horizon block receives its
        proportional slice of the global budgets)."""
        n, b = d.shape[0], cap.shape[0]
        m = cap.shape[1]
        app_ids = tuple(a.app_id for a in apps)
        n_r = len(common)
        nx, nl = n * b, n
        nvar = nx + nl + n_r

        def xi(i: int, j: int) -> int:
            return i * b + j

        A_rows: List[np.ndarray] = []
        lb_rows: List[float] = []
        ub_rows: List[float] = []

        def add(row: np.ndarray, lo: float, hi: float) -> None:
            A_rows.append(row)
            lb_rows.append(lo)
            ub_rows.append(hi)

        # Eq 6: capacity per (slave, resource).
        for j in range(b):
            for k in range(m):
                if not np.any(d[:, k] > 0):
                    continue
                row = np.zeros(nvar)
                for i in range(n):
                    row[xi(i, j)] = d[i, k]
                add(row, -np.inf, cap[j, k])

        # Eqs 7-8: container-count bounds.
        for i in range(n):
            row = np.zeros(nvar)
            row[i * b:(i + 1) * b] = 1.0
            add(row, apps[i].n_min, apps[i].n_max)

        # Eqs 11-12: l_i >= |g_i * N_i - s_hat_i|.
        for i in range(n):
            row = np.zeros(nvar)
            row[i * b:(i + 1) * b] = g[i]
            row[nx + i] = -1.0
            add(row, -np.inf, s_hat_vec[i])         # g N - l <= s_hat
            row2 = np.zeros(nvar)
            row2[i * b:(i + 1) * b] = g[i]
            row2[nx + i] = 1.0
            add(row2, s_hat_vec[i], np.inf)         # g N + l >= s_hat

        # Eqs 13-14: M r_i >= |x_ij - x^{t-1}_ij|,  M = max over n_max.
        bigM = float(max(a.n_max for a in apps) + 1)
        for ridx, i in enumerate(common):
            xprev = prev_map[app_ids[i]]
            for j in range(b):
                row = np.zeros(nvar)
                row[xi(i, j)] = 1.0
                row[nx + nl + ridx] = -bigM
                add(row, -np.inf, float(xprev[j]))  # x - M r <= x_prev
                row2 = np.zeros(nvar)
                row2[xi(i, j)] = 1.0
                row2[nx + nl + ridx] = bigM
                add(row2, float(xprev[j]), np.inf)  # x + M r >= x_prev

        # Eq 15: total fairness loss budget.
        row = np.zeros(nvar)
        row[nx:nx + nl] = 1.0
        add(row, -np.inf, budget_l)

        # Eq 16: adjustment budget.
        if n_r:
            row = np.zeros(nvar)
            row[nx + nl:] = 1.0
            add(row, -np.inf, float(budget_r))

        return np.stack(A_rows), np.array(lb_rows), np.array(ub_rows)

    # ----------------------------------------------------- sparse assembly

    def _assemble_sparse(self, apps, d, cap, g, s_hat_vec, prev_map, common,
                         budget_l: float, budget_r: float):
        """Vectorized COO assembly of the same constraint system (same row
        order as `_assemble_dense`), returned as a csr_array."""
        n, b = d.shape[0], cap.shape[0]
        m = cap.shape[1]
        app_ids = tuple(a.app_id for a in apps)
        n_r = len(common)
        nx, nl = n * b, n
        nvar = nx + nl + n_r

        rows: List[np.ndarray] = []
        cols: List[np.ndarray] = []
        vals: List[np.ndarray] = []
        lbs: List[np.ndarray] = []
        ubs: List[np.ndarray] = []

        # Eq 6: capacity per (slave, used resource); row id = j * nk + q.
        ks = np.flatnonzero((d > 0).any(axis=0))
        nk = ks.size
        if nk:
            jj, qq, ii = np.meshgrid(np.arange(b), np.arange(nk),
                                     np.arange(n), indexing="ij")
            v = d[ii.ravel(), ks[qq.ravel()]]
            nz = v != 0
            rows.append((jj.ravel() * nk + qq.ravel())[nz])
            cols.append((ii.ravel() * b + jj.ravel())[nz])
            vals.append(v[nz])
            lbs.append(np.full(b * nk, -np.inf))
            ubs.append(cap[:, ks].ravel())
        o1 = b * nk

        # Eqs 7-8: container-count bounds; row id = o1 + i.
        rows.append(o1 + np.repeat(np.arange(n), b))
        cols.append(np.arange(nx))
        vals.append(np.ones(nx))
        lbs.append(np.array([a.n_min for a in apps], dtype=np.float64))
        ubs.append(np.array([a.n_max for a in apps], dtype=np.float64))
        o2 = o1 + n

        # Eqs 11-12: rows o2 + 2i (g N - l <= s_hat), o2 + 2i + 1 (>= s_hat).
        r_hi = o2 + 2 * np.repeat(np.arange(n), b)
        rows.extend([r_hi, r_hi + 1,
                     o2 + 2 * np.arange(n), o2 + 2 * np.arange(n) + 1])
        cols.extend([np.arange(nx), np.arange(nx),
                     nx + np.arange(n), nx + np.arange(n)])
        gg = np.repeat(g, b)
        vals.extend([gg, gg, -np.ones(n), np.ones(n)])
        lb_f = np.empty(2 * n)
        ub_f = np.empty(2 * n)
        lb_f[0::2], lb_f[1::2] = -np.inf, s_hat_vec
        ub_f[0::2], ub_f[1::2] = s_hat_vec, np.inf
        lbs.append(lb_f)
        ubs.append(ub_f)
        o3 = o2 + 2 * n

        # Eqs 13-14: per (ridx, j) a <=/>= pair; row id = o3 + 2*(ridx*b + j).
        if n_r:
            bigM = float(max(a.n_max for a in apps) + 1)
            ci = np.array(common)
            xprev = np.stack([prev_map[app_ids[i]] for i in common]
                             ).astype(np.float64)                   # (n_r, b)
            rr, jj = np.meshgrid(np.arange(n_r), np.arange(b), indexing="ij")
            base = o3 + 2 * (rr.ravel() * b + jj.ravel())
            xcols = (ci[rr.ravel()] * b + jj.ravel())
            rows.extend([base, base + 1, base, base + 1])
            cols.extend([xcols, xcols,
                         nx + nl + rr.ravel(), nx + nl + rr.ravel()])
            vals.extend([np.ones(n_r * b), np.ones(n_r * b),
                         np.full(n_r * b, -bigM), np.full(n_r * b, bigM)])
            lb_a = np.empty(2 * n_r * b)
            ub_a = np.empty(2 * n_r * b)
            lb_a[0::2], lb_a[1::2] = -np.inf, xprev.ravel()
            ub_a[0::2], ub_a[1::2] = xprev.ravel(), np.inf
            lbs.append(lb_a)
            ubs.append(ub_a)
        o4 = o3 + 2 * n_r * b

        # Eq 15: total fairness loss budget.
        rows.append(np.full(nl, o4))
        cols.append(nx + np.arange(nl))
        vals.append(np.ones(nl))
        lbs.append(np.array([-np.inf]))
        ubs.append(np.array([budget_l]))
        n_rows = o4 + 1

        # Eq 16: adjustment budget.
        if n_r:
            rows.append(np.full(n_r, n_rows))
            cols.append(nx + nl + np.arange(n_r))
            vals.append(np.ones(n_r))
            lbs.append(np.array([-np.inf]))
            ubs.append(np.array([float(budget_r)]))
            n_rows += 1

        A = _sp.coo_array(
            (np.concatenate(vals),
             (np.concatenate(rows), np.concatenate(cols))),
            shape=(n_rows, nvar)).tocsc()
        # HiGHS's cython wrapper requires 32-bit sparse indices.
        A.indices = A.indices.astype(np.int32)
        A.indptr = A.indptr.astype(np.int32)
        return A, np.concatenate(lbs), np.concatenate(ubs)

    # --------------------------------------------------------------- solve

    def solve(self, apps: Sequence[ApplicationSpec], cluster: ClusterSpec,
              prev: Optional[Allocation] = None, state=None,
              ) -> Optional[Allocation]:
        """Exact P2. Monolithic while n * b <= cfg.rolling_horizon_vars;
        rolling-horizon block decomposition beyond (the scale path for the
        exact solver -- instances with >= 5k x-variables stay solvable).
        `state` is accepted for SchedulerPolicy-interface parity and passed
        to the greedy incumbent."""
        self.last_changed = None
        self.last_gap = None
        self.last_bound = None
        self.last_objective = None
        if not apps:
            self.last_shares = {}
            self.last_shares_vec = np.zeros(0)
            self.last_gap = 0.0
            self.last_bound = 0.0
            self.last_objective = 0.0
            return Allocation.empty((), cluster.b)
        app_ids = tuple(a.app_id for a in apps)
        with self.spans.span("optimizer.refill"):
            drf_counts, s_hat_vec = _drf_targets(apps, cluster)
        self.last_shares = dict(zip(app_ids, map(float, s_hat_vec)))
        self.last_shares_vec = s_hat_vec
        if self.cfg.column_generation:
            self.colgen_solves += 1
            return self._solve_colgen(apps, cluster, prev, drf_counts,
                                      s_hat_vec, state)
        rh = self.cfg.rolling_horizon_vars
        if rh and len(apps) > 1 and len(apps) * cluster.b > rh:
            self.rolling_solves += 1
            return self._solve_rolling(apps, cluster, prev, drf_counts,
                                       s_hat_vec, state)
        self.monolithic_solves += 1
        return self._solve_block(apps, cluster, prev,
                                 (drf_counts, s_hat_vec), state=state)

    def _solve_block(self, apps: Sequence[ApplicationSpec],
                     cluster: ClusterSpec, prev: Optional[Allocation],
                     targets, cap: Optional[np.ndarray] = None,
                     budget_l: Optional[float] = None,
                     budget_r: Optional[int] = None,
                     incumbent="warm", state=None) -> Optional[Allocation]:
        """One exact MILP over `apps`.

        Overrides for rolling-horizon blocks: `cap` (residual per-slave
        capacity), `budget_l`/`budget_r` (the block's slice of the Eq-15/16
        budgets), `incumbent` (an Allocation used as cutoff + fallback;
        "warm" derives one from the greedy heuristic when cfg.warm_start).
        Any incumbent is used only if it honors the Eq-15 AND Eq-16 budgets
        itself: cutting off against (or falling back to) a budget-violating
        point would silently replace the exact solver's correct
        "infeasible" answer."""
        n, b, m = len(apps), cluster.b, cluster.m
        app_ids = tuple(a.app_id for a in apps)
        d = demand_matrix(apps)                     # (n, m)
        residual = cap is not None                  # rolling-horizon block?
        if cap is None:
            cap = cluster.capacity_matrix()         # (b, m)
        g = _dominant_coeff(apps, cluster, d)       # (n,)
        drf_counts, s_hat_vec = targets

        prev_map = prev.as_dict() if prev is not None else {}
        common = [i for i, a in enumerate(app_ids) if a in prev_map]
        n_r = len(common)
        if budget_l is None:
            budget_l = fairness_budget(self.cfg, m)
        if budget_r is None:
            budget_r = adjust_budget(self.cfg, n_r)

        # Variable layout: [ x (n*b ints) | l (n cont) | r (n_r binary) ]
        nx, nl = n * b, n
        nvar = nx + nl + n_r

        c_obj = np.zeros(nvar)
        util_w = _util_coeff(apps, cluster, d)      # (n,)
        c_obj[:nx] = -np.repeat(util_w, b)          # milp minimizes

        if self.cfg.sparse:
            A, lb_rows, ub_rows = self._assemble_sparse(
                apps, d, cap, g, s_hat_vec, prev_map, common,
                budget_l, float(budget_r))
        else:
            A, lb_rows, ub_rows = self._assemble_dense(
                apps, d, cap, g, s_hat_vec, prev_map, common,
                budget_l, float(budget_r))

        if incumbent == "warm":
            incumbent = None
            if self.cfg.warm_start:
                incumbent = GreedyOptimizer(self.cfg).solve(
                    apps, cluster, prev, _targets=(drf_counts, s_hat_vec),
                    state=state)
        if incumbent is not None:
            inc_loss = float(np.abs(
                g * incumbent.x.sum(axis=1) - s_hat_vec).sum())
            if inc_loss > budget_l + 1e-9:
                incumbent = None
        if incumbent is not None and common:
            inc_changed = sum(
                1 for i in common
                if not np.array_equal(incumbent.x[i], prev_map[app_ids[i]]))
            if inc_changed > budget_r:
                incumbent = None
        if incumbent is not None:
            inc_obj = float(-util_w @ incumbent.x.sum(axis=1))
            cut = np.zeros((1, nvar))
            cut[0, :nx] = c_obj[:nx]
            if self.cfg.sparse:
                A = _sp.vstack([A, _sp.csc_array(cut)]).tocsc()
                A.indices = A.indices.astype(np.int32)
                A.indptr = A.indptr.astype(np.int32)
            else:
                A = np.vstack([A, cut])
            lb_rows = np.concatenate([lb_rows, [-np.inf]])
            ub_rows = np.concatenate([ub_rows, [inc_obj + 1e-9]])

        constraints = LinearConstraint(A, lb_rows, ub_rows)

        lb = np.zeros(nvar)
        ub = np.full(nvar, np.inf)
        ub[:nx] = np.repeat(np.array([a.n_max for a in apps], np.float64), b)
        ub[nx + nl:] = 1.0
        integrality = np.concatenate([
            np.ones(nx), np.zeros(nl), np.ones(n_r)])

        res = milp(c=c_obj, constraints=constraints,
                   bounds=_Bounds(lb, ub), integrality=integrality,
                   options={"time_limit": self.cfg.time_limit_s,
                            "mip_rel_gap": self.cfg.mip_rel_gap})
        if not res.success or res.x is None:
            return incumbent            # None unless an incumbent survived
        x = np.rint(res.x[:nx]).astype(np.int64).reshape(n, b)
        alloc = Allocation(app_ids, x)
        if not residual:
            # Monolithic solves validate here; rolling blocks are checked
            # once, on the combined allocation.
            validate_allocation(alloc, apps, cluster, d=d)
            # HiGHS's dual bound certifies the monolithic solve too: milp
            # minimizes -utilization, so -mip_dual_bound is a proven upper
            # bound on the P2 utilization objective (the warm-start cutoff
            # plane never excludes the optimum, so the bound stays valid).
            dual = getattr(res, "mip_dual_bound", None)
            self._record_gap(
                float(-dual) if dual is not None and np.isfinite(dual)
                else None,
                float(util_w @ x.sum(axis=1)))
        return alloc

    def _record_gap(self, bound: Optional[float], objective: float) -> None:
        """Set the certified-gap report (`last_bound`/`last_objective`/
        `last_gap`) from a proven utilization upper bound and the achieved
        objective -- the ONE formula both the monolithic dual-bound path
        and the colgen path report through (check.sh/CI gate on it)."""
        self.last_objective = objective
        if bound is None:
            return
        self.last_bound = max(bound, objective)
        self.last_gap = max(0.0, self.last_bound - objective) / \
            max(abs(self.last_bound), 1e-12)

    def _solve_rolling(self, apps: Sequence[ApplicationSpec],
                       cluster: ClusterSpec, prev: Optional[Allocation],
                       drf_counts: Dict[str, int], s_hat_vec: np.ndarray,
                       state=None) -> Optional[Allocation]:
        """Rolling-horizon decomposition of P2 (the exact path past ~2k
        variables).

        Apps are partitioned into blocks of at most
        floor(rolling_horizon_vars / b) apps, ordered by utilization weight
        with the DRF target as tie-break (the same priority order the
        monolithic objective pushes apps past their targets in). Each block
        is solved as an exact sub-MILP against the residual capacity left
        by earlier blocks, with a GLOBAL greedy guide supplying (a) the
        later blocks' reserved placements -- an early block can never
        starve a later block below the guide point, (b) each block's
        incumbent (cutoff + fallback), and (c) the budget split: a block
        may spend the remaining global Eq-15/Eq-16 budgets minus the later
        blocks' guide spend, so the incumbent always fits and the totals
        stay within the monolithic bounds. The union of the block solutions
        is feasible for P2 by construction; on instances small enough to
        also solve monolithically the objective lands within ~1%
        (tests/test_rolling_horizon.py)."""
        n, b, m = len(apps), cluster.b, cluster.m
        app_ids = tuple(a.app_id for a in apps)
        d = demand_matrix(apps)
        cap = cluster.capacity_matrix().astype(np.float64)
        inv_cap = 1.0 / np.maximum(cap, 1e-9)
        prev_map = prev.as_dict() if prev is not None else {}

        # GLOBAL greedy guide: a P2-feasible point (capacity, n_min/n_max,
        # Eq-15/16 budgets all honored globally). Its placements become the
        # per-block reservations + incumbents, and its per-block budget
        # spend anchors the budget split -- so every block's sub-MILP
        # starts from a feasible incumbent and can only improve on the
        # guide. If even the greedy cannot find a feasible point, the
        # monolithic MILP would almost surely time out too: keep previous
        # allocations (paper semantics).
        guide = GreedyOptimizer(self.cfg).solve(
            apps, cluster, prev, _targets=(drf_counts, s_hat_vec),
            state=state)
        if guide is None:
            return None
        g = _dominant_coeff(apps, cluster, d)
        guide_loss = np.abs(g * guide.x.sum(axis=1) - s_hat_vec)    # (n,)
        guide_changed = np.zeros(n, bool)
        for i, a in enumerate(app_ids):
            pr = prev_map.get(a)
            if pr is not None and not np.array_equal(guide.x[i], pr):
                guide_changed[i] = True

        per_block = max(1, self.cfg.rolling_horizon_vars // b)
        # Block order = the greedy utilization push's priority order
        # (utilization gained per container, tie-broken by DRF target then
        # index): the budget slack is then spent on the same apps the
        # monolithic objective would push past their DRF targets first.
        util_w = _util_coeff(apps, cluster, d)
        order = np.lexsort((np.arange(n), s_hat_vec, -util_w))
        blocks = [[int(i) for i in order[k:k + per_block]]
                  for k in range(0, n, per_block)]

        # Budget split: block t may spend (global budget) - (actual spend
        # of earlier blocks) - (guide spend reserved for later blocks).
        # Inductively that is always >= the block's own guide spend, so the
        # guide incumbent is never rejected, and the final totals are
        # within the global Eq-15/Eq-16 budgets.
        budget_l_slack = max(
            fairness_budget(self.cfg, m) - float(guide_loss.sum()), 0.0)
        c_total = sum(1 for a in app_ids if a in prev_map)
        budget_r_slack = max(
            (adjust_budget(self.cfg, c_total) if c_total else 0)
            - int(guide_changed.sum()), 0)

        free = cap - guide.x.T.astype(np.float64) @ d
        x = np.zeros((n, b), np.int64)
        for blk in blocks:
            bapps = [apps[i] for i in blk]
            bids = tuple(app_ids[i] for i in blk)
            d_blk = d[blk]
            # Release this block's guide rows into its own residual (the
            # sub-MILP re-decides those placements freely).
            free += guide.x[blk].T.astype(np.float64) @ d_blk
            incumbent = Allocation(bids, guide.x[blk].copy())
            bprev = None
            if prev_map:
                pids = tuple(a for a in bids if a in prev_map)
                if pids:
                    bprev = Allocation(pids, np.stack(
                        [prev_map[a] for a in pids]))
            # Block budget = current slack + this block's guide spend;
            # invariant: slack' = block budget - actual spend >= 0 (the
            # sub-MILP enforces actual <= budget), so the final totals sum
            # to at most the global budgets.
            bl = budget_l_slack + float(guide_loss[blk].sum())
            br = budget_r_slack + int(guide_changed[blk].sum())
            sub = self._solve_block(
                bapps, cluster, bprev, (drf_counts, s_hat_vec[blk]),
                cap=free, budget_l=bl, budget_r=br,
                incumbent=incumbent, state=state)
            if sub is None:
                return None              # unreachable while the guide fits
            x[blk] = sub.x
            free -= sub.x.T.astype(np.float64) @ d_blk
            loss_t = float(np.abs(g[blk] * sub.x.sum(axis=1)
                                  - s_hat_vec[blk]).sum())
            budget_l_slack = max(bl - loss_t, 0.0)
            if bprev is not None:
                changed_t = sum(
                    1 for r, a in enumerate(bids)
                    if a in prev_map
                    and not np.array_equal(sub.x[r], prev_map[a]))
            else:
                changed_t = 0
            budget_r_slack = max(br - changed_t, 0)

        alloc = Allocation(app_ids, x)
        validate_allocation(alloc, apps, cluster, d=d)
        return alloc

    # ------------------------------------------------- column generation

    def _solve_colgen(self, apps: Sequence[ApplicationSpec],
                      cluster: ClusterSpec, prev: Optional[Allocation],
                      drf_counts: Dict[str, int], s_hat_vec: np.ndarray,
                      state=None) -> Optional[Allocation]:
        """Dantzig-Wolfe column generation over per-app count columns (the
        second exact-at-scale route; the one with a certified GLOBAL gap).

        A column = app i running N containers, N in [n_min_i, n_max_i],
        carrying its exact objective contribution (Eq-13 utilization
        w_i * N), its exact Eq-11/15 fairness loss |g_i N - s_hat_i| (no
        linearization needed: N is fixed per column), and an Eq-16 change
        flag [N != N^{t-1}_i]. The restricted master LP picks a convex
        combination per app subject to eligibility-CLASS capacity rows
        (the per-slave Eq-6 system aggregated per distinct eligible-slave
        set -- see the class-row construction below), the Eq-15 budget row
        and the Eq-16 budget row -- every row is valid for P2, so the LP
        value bounds the P2 optimum from above. Pricing: the reduced cost
        of column (i, N) is convex piecewise linear + a point discount at
        N^{t-1}_i, so its exact integer minimizer lies in {n_min, n_max,
        floor/ceil of s_hat/g, N^{t-1}} -- one vectorized evaluation
        prices every app per iteration. The Lagrangian bound
        z_RMP + sum_i min_rc_i certifies the LP bound even when
        `colgen_max_iters` stops pricing early.

        The greedy solution seeds the pool (RMP feasibility + the fallback
        incumbent, though greedy infeasibility does NOT end the solve), a
        pool MILP picks one column per app (unpackable selections get
        no-good cuts), and `_colgen_place` realizes the counts on slaves:
        count-unchanged apps keep their previous rows verbatim (making the
        Eq-16 count flag exact), changed/new apps go through stickiness,
        FFD best-fit and the type-grouped exact packer. The certified gap
        (upper bound - achieved objective) / upper bound is exposed as
        `last_gap`; placement shortfalls fall back toward the greedy
        incumbent and only widen the reported gap, never invalidate it."""
        cfg = self.cfg
        n, b, m = len(apps), cluster.b, cluster.m
        app_ids = tuple(a.app_id for a in apps)
        d = demand_matrix(apps)                       # (n, m)
        cap = cluster.capacity_matrix().astype(np.float64)
        g = _dominant_coeff(apps, cluster, d)
        util_w = _util_coeff(apps, cluster, d)
        nmin_v = np.fromiter((a.n_min for a in apps), np.int64, n)
        nmax_v = np.fromiter((a.n_max for a in apps), np.int64, n)

        # Goodput weighting (cfg.goodput_aware): a column is one app at one
        # count, so attaching its measured goodput is free -- the objective
        # weight becomes w_i * gp_i(N) instead of w_i * N. `gp_tab[i, N]`
        # is the speedup at N (the count itself for uncurved apps), padded
        # to the widest n_max. With no curved apps every code path below
        # takes the original count-linear branch unchanged.
        curves = [a.goodput for a in apps]
        use_gp = self.cfg.goodput_aware and any(
            c is not None and not c.is_linear for c in curves)
        if use_gp:
            nmx = int(nmax_v.max())
            gp_tab = np.tile(np.arange(nmx + 1, dtype=np.float64), (n, 1))
            for i, c in enumerate(curves):
                if c is not None and not c.is_linear:
                    gp_tab[i] = c.eval(np.arange(nmx + 1))

        def col_gp(ca: np.ndarray, cn: np.ndarray) -> np.ndarray:
            """Per-column speedup value: gp_i(N) (== N when not use_gp)."""
            if use_gp:
                return gp_tab[ca, cn]
            return cn.astype(np.float64)

        def ach_obj(alloc: Allocation) -> float:
            """Achieved objective of an allocation under the active
            weighting (count-linear, or goodput-weighted)."""
            cnts = alloc.x.sum(axis=1)
            return float(util_w @ col_gp(np.arange(n), cnts))

        prev_map = prev.as_dict() if prev is not None else {}
        prev_n = np.full(n, -1, np.int64)             # -1 = not in prev
        for i, a in enumerate(app_ids):
            pr = prev_map.get(a)
            if pr is not None:
                prev_n[i] = int(pr.sum())
        n_r = int((prev_n >= 0).sum())
        budget_l = fairness_budget(cfg, m)
        budget_r = adjust_budget(cfg, n_r) if n_r else 0

        # -- capacity rows: one row per (eligibility class, resource).
        # A container of app i can only live on slaves carrying every
        # resource it demands; on heterogeneous clusters the cluster-wide
        # aggregate wildly overestimates what e.g. GPU apps can draw (their
        # CPU/RAM must come from GPU slaves too). For each distinct
        # eligible-slave set E: every app whose own eligible set is a
        # SUBSET of E places all containers inside E, so
        # sum_members N_i d_{i,k} <= sum_{j in E} c_{j,k} is valid for P2
        # -- the bound stays certified and tightens. The full-cluster
        # class reproduces the plain aggregate rows; distinct classes are
        # few (one per slave-flavor support combination).
        pos_d = d > 0
        cap_pos = cap > 0
        elig = (pos_d.astype(np.int64)
                @ (~cap_pos).astype(np.int64).T) == 0      # (n, b)
        uniq_e, inv_e = np.unique(elig, axis=0, return_inverse=True)
        row_mask_l: List[np.ndarray] = []
        row_k_l: List[int] = []
        row_rhs_l: List[float] = []
        for u in range(uniq_e.shape[0]):
            E = uniq_e[u]
            subset_of_E = ~((uniq_e & ~E[None, :]).any(axis=1))
            members = subset_of_E[inv_e]                   # (n,)
            rhs_vec = cap[E].sum(axis=0) if E.any() else np.zeros(m)
            for k in range(m):
                if pos_d[members, k].any():
                    row_mask_l.append(members)
                    row_k_l.append(k)
                    row_rhs_l.append(float(rhs_vec[k]))
        if row_mask_l:
            cap_mask = np.stack(row_mask_l)                # (R, n) bool
            cap_k = np.array(row_k_l)
            cap_rhs = np.array(row_rhs_l)
        else:                                              # zero-demand apps
            cap_mask = np.zeros((0, n), bool)
            cap_k = np.zeros(0, np.int64)
            cap_rhs = np.zeros(0)
        n_cap = cap_mask.shape[0]

        # Greedy seed: a P2-feasible point (hence feasible for the
        # aggregate master) that seeds the pool and backs the placement
        # fallbacks. Unlike the rolling path, a greedy infeasibility does
        # NOT end the solve -- the exact machinery itself decides (the
        # greedy's two-pass packer can give up on saturated clusters where
        # a feasible point exists; an aggregate-infeasible RMP or an
        # unrealizable pool selection still returns None below).
        guide = GreedyOptimizer(cfg).solve(
            apps, cluster, prev, _targets=(drf_counts, s_hat_vec),
            state=state)
        guide_counts = guide.x.sum(axis=1) if guide is not None else None

        # -- column pool (parallel arrays; one entry = one (app, N) pair).
        # The previous-count columns are load-bearing: without an
        # "unchanged" column per running app the Eq-16 change row can make
        # even the INITIAL restricted master infeasible (every pool column
        # of a running app would count as changed).
        seed = {(i, int(nmin_v[i])) for i in range(n)}
        seed |= {(i, int(nmax_v[i])) for i in range(n)}
        seed |= {(i, int(drf_counts[a])) for i, a in enumerate(app_ids)}
        seed |= {(i, int(prev_n[i])) for i in np.flatnonzero(
            (prev_n >= nmin_v) & (prev_n <= nmax_v))}
        if guide_counts is not None:
            seed |= {(i, int(c)) for i, c in enumerate(guide_counts)}
        pool = sorted(seed)                # deterministic column order
        seen = set(pool)
        col_app = np.fromiter((i for i, _ in pool), np.int64, len(pool))
        col_n = np.fromiter((c for _, c in pool), np.int64, len(pool))

        def _col_rows(ca: np.ndarray, cn: np.ndarray) -> np.ndarray:
            """Dense (n_cap + 1 [+ 1], P) A_ub block: the class capacity
            rows, the Eq-15 loss row and (with a previous allocation) the
            Eq-16 change row."""
            rows = [cap_mask[:, ca] * (d[ca][:, cap_k].T * cn[None, :]),
                    np.abs(g[ca] * cn - s_hat_vec[ca])[None, :]]
            if n_r:
                rows.append(((prev_n[ca] >= 0) & (cn != prev_n[ca]))
                            .astype(np.float64)[None, :])
            return np.concatenate(rows, axis=0)

        ub_rhs = np.concatenate([cap_rhs, [budget_l]]
                                + ([[float(budget_r)]] if n_r else []))
        util_bound = None                  # tightest certified upper bound
        iters = 0
        for _ in range(max(1, cfg.colgen_max_iters)):
            iters += 1
            P = col_n.size
            c_lp = -(util_w[col_app] * col_gp(col_app, col_n))
            A_ub = _col_rows(col_app, col_n)
            A_eq = _sp.coo_array(
                (np.ones(P), (col_app, np.arange(P))), shape=(n, P)).tocsr()
            res = linprog(c_lp, A_ub=A_ub, b_ub=ub_rhs, A_eq=A_eq,
                          b_eq=np.ones(n), bounds=(0, None), method="highs")
            if not res.success or res.x is None:
                # Infeasible RMP. With a (P2-feasible) guide in the pool
                # that means a degenerate instance (e.g. the greedy blew
                # the Eq-15 budget because even the DRF point does) -- keep
                # the guide, certify nothing. Without one the aggregate
                # relaxation itself is infeasible, so P2 is too: keep
                # previous allocations (paper semantics).
                self.colgen_iters += iters
                self.colgen_columns = int(col_n.size)
                if guide is None:
                    return None
                return self._colgen_finish(apps, cluster, guide, None,
                                           util_w, d,
                                           objective=ach_obj(guide))
            z_rmp = float(res.fun)
            y_ub = np.asarray(res.ineqlin.marginals, np.float64)
            sigma = np.asarray(res.eqlin.marginals, np.float64)
            pi_cap, pi_f = y_ub[:n_cap], float(y_ub[n_cap])
            pi_r = float(y_ub[n_cap + 1]) if n_r else 0.0

            # -- pricing (the phase breakdown's colgen_pricing).
            with self.spans.span("optimizer.pricing"):
                if use_gp:
                    # Goodput objective: -w_i gp_i(N) is convex piecewise
                    # linear with a breakpoint at EVERY integer, so the
                    # 5-candidate closed form below is no longer the exact
                    # minimizer -- price over the full level range instead
                    # (same enumeration the pool enrichment uses; exactness is
                    # what keeps the Lagrangian bound rigorous).
                    cap_slope = -(cap_mask * d[:, cap_k].T
                                  * pi_cap[:, None]).sum(axis=0)
                    lv = nmax_v - nmin_v + 1
                    starts = np.cumsum(lv) - lv
                    l_app = np.repeat(np.arange(n), lv)
                    l_n = nmin_v[l_app] \
                        + (np.arange(int(lv.sum())) - starts[l_app])
                    rc_l = (-util_w[l_app] * gp_tab[l_app, l_n]
                            + cap_slope[l_app] * l_n
                            - pi_f * np.abs(g[l_app] * l_n - s_hat_vec[l_app])
                            - pi_r * ((prev_n[l_app] >= 0)
                                      & (l_n != prev_n[l_app]))
                            - sigma[l_app])
                    best_n = np.empty(n, np.int64)
                    min_rc = np.empty(n)
                    for i in range(n):
                        sl = rc_l[starts[i]: starts[i] + lv[i]]
                        k = int(np.argmin(sl))
                        min_rc[i] = sl[k]
                        best_n[i] = int(nmin_v[i]) + k
                else:
                    # a_lin: the slope in N.
                    a_lin = -util_w - (cap_mask * d[:, cap_k].T
                                       * pi_cap[:, None]).sum(axis=0)
                    with np.errstate(divide="ignore", invalid="ignore"):
                        bp = np.where(g > 0, s_hat_vec / np.maximum(g, 1e-300),
                                      nmin_v.astype(np.float64))
                    # pre-clip keeps floor/ceil inside int64 range for tiny g
                    bp = np.clip(bp, 0.0, nmax_v.astype(np.float64) + 1.0)
                    cand = np.stack([
                        nmin_v, nmax_v,
                        np.floor(bp).astype(np.int64),
                        np.ceil(bp).astype(np.int64),
                        np.where(prev_n >= 0, prev_n, nmin_v)], axis=1)
                    cand = np.clip(cand, nmin_v[:, None], nmax_v[:, None])
                    loss_c = np.abs(g[:, None] * cand - s_hat_vec[:, None])
                    chg_c = (prev_n[:, None] >= 0) & (cand != prev_n[:, None])
                    rc = (a_lin[:, None] * cand - pi_f * loss_c
                          - pi_r * chg_c - sigma[:, None])
                    best = np.argmin(rc, axis=1)
                    min_rc = rc[np.arange(n), best]
                    best_n = cand[np.arange(n), best]
                # Lagrangian bound: z_LP >= z_RMP + sum_i min(0, min_rc_i)
                # (each convexity block contributes exactly one unit of weight;
                # the candidate set provably contains the true minimizer).
                bound = -(z_rmp + float(np.minimum(min_rc, 0.0).sum()))
                util_bound = bound if util_bound is None \
                    else min(util_bound, bound)
                improving = np.flatnonzero(min_rc < -1e-7)
            if not improving.size:
                # Converged: `bound` (with its tiny within-tolerance
                # Lagrangian correction) is already the rigorous value.
                break
            new = [(int(i), int(best_n[i])) for i in improving
                   if (int(i), int(best_n[i])) not in seen]
            if not new or col_n.size + len(new) > cfg.colgen_pool_max:
                break
            seen.update(new)
            col_app = np.concatenate(
                [col_app, np.fromiter((i for i, _ in new), np.int64,
                                      len(new))])
            col_n = np.concatenate(
                [col_n, np.fromiter((c for _, c in new), np.int64,
                                    len(new))])
        self.colgen_iters += iters

        # -- enrich the pool for the integer solve. Pricing generates only
        # the columns the LP needs; the integer optimum may sit at
        # intermediate counts the LP never priced. When the FULL level
        # enumeration fits the pool cap (bounded n_max ranges -- the
        # common cluster case) the integer solve runs over every column
        # and is exact for the aggregate master; otherwise widen a +-2
        # neighborhood around every generated column. Either way the
        # certified bound comes from the pricing loop above and is
        # unaffected.
        levels = nmax_v - nmin_v + 1
        if int(levels.sum()) <= cfg.colgen_pool_max:
            col_app = np.repeat(np.arange(n), levels)
            offs = np.arange(int(levels.sum())) \
                - np.repeat(np.cumsum(levels) - levels, levels)
            col_n = nmin_v[col_app] + offs
        else:
            nb_app = np.repeat(col_app, 4)
            nb_n = (col_n[:, None]
                    + np.array([-2, -1, 1, 2])[None, :]).ravel()
            ok = (nb_n >= nmin_v[nb_app]) & (nb_n <= nmax_v[nb_app])
            extra = sorted({(int(i), int(c)) for i, c in
                            zip(nb_app[ok], nb_n[ok])} - seen)
            # Never truncate the generated pool itself -- the guide's
            # columns keep the integer solve feasible.
            pool = sorted(seen) \
                + extra[:max(0, cfg.colgen_pool_max - len(seen))]
            col_app = np.fromiter((i for i, _ in pool), np.int64, len(pool))
            col_n = np.fromiter((c for _, c in pool), np.int64, len(pool))
        self.colgen_columns = int(col_n.size)

        # -- final integer solve over the generated pool: pick exactly one
        # column per app (multiple-choice knapsack over the master rows).
        # A selection whose counts provably cannot pack per-slave is cut
        # off (no-good cut on its exact column set) and re-selected.
        P = col_n.size
        c_ip = -(util_w[col_app] * col_gp(col_app, col_n))
        A_ub = _col_rows(col_app, col_n)
        A_eq = _sp.coo_array(
            (np.ones(P), (col_app, np.arange(P))), shape=(n, P)).tocsc()
        A_eq.indices = A_eq.indices.astype(np.int32)
        A_eq.indptr = A_eq.indptr.astype(np.int32)
        cons = [LinearConstraint(A_ub, -np.inf, ub_rhs),
                LinearConstraint(A_eq, 1.0, 1.0)]
        best: Optional[Tuple[float, Allocation]] = None
        for _ in range(max(1, cfg.colgen_pack_rounds)):
            res = milp(c=c_ip, constraints=cons,
                       bounds=_Bounds(np.zeros(P), np.ones(P)),
                       integrality=np.ones(P),
                       options={"time_limit": cfg.time_limit_s,
                                "mip_rel_gap": cfg.mip_rel_gap})
            if res.x is not None:
                # One column per app = the app's highest-weight pool entry
                # (robust to HiGHS's integrality tolerance).
                order = np.argsort(res.x, kind="stable")
                choice = np.empty(n, np.int64)
                choice[col_app[order]] = order  # last write = max weight
                counts = col_n[choice]
            elif guide_counts is not None:
                counts, choice = guide_counts, None
            else:
                break                   # pool IP infeasible, no incumbent

            alloc, realized = self._colgen_place(
                apps, app_ids, d, cap, counts, prev_map, prev_n,
                nmin_v, nmax_v, g, s_hat_vec, budget_l, util_w, guide)
            if alloc is not None:
                obj = ach_obj(alloc)
                if best is None or obj > best[0] + 1e-12:
                    best = (obj, alloc)
            if realized or choice is None:
                break
            cut = np.zeros((1, P))
            cut[0, choice] = 1.0
            cons = cons + [LinearConstraint(cut, -np.inf, float(n - 1))]
        if best is None:
            # No realizable selection and no greedy incumbent: keep
            # previous allocations (paper semantics).
            return None
        return self._colgen_finish(apps, cluster, best[1], util_bound,
                                   util_w, d, objective=best[0])

    def _colgen_place(self, apps, app_ids, d, cap, counts, prev_map, prev_n,
                      nmin_v, nmax_v, g, s_hat_vec, budget_l, util_w,
                      guide: Optional[Allocation],
                      ) -> Tuple[Optional[Allocation], bool]:
        """Aggregate counts -> per-slave placement; returns (allocation,
        realized) with realized=True iff every app got exactly its selected
        count (allocation may be None when the counts are unusable and no
        greedy incumbent exists). Count-unchanged apps keep their previous
        rows VERBATIM
        (jointly feasible: they are a subset of the previous allocation;
        this is what makes the master's count-change flag equal P2's
        row-change r_i). Changed and new apps keep as much of their
        previous row as fits (stickiness), then two-pass best-fit in
        first-fit-decreasing order (everyone to n_min before anyone tops
        up; big per-container items first -- a CPU-saturated selection
        needs exact fills). If the heuristic falls short, the type-grouped
        packer (`_pack_changed`) realizes the counts exactly where its
        size limits allow. Falling below n_min or past the Eq-15 budget
        falls back to the greedy incumbent (the achieved objective drops;
        the certified bound stays valid)."""
        n, b = d.shape[0], cap.shape[0]
        x = np.zeros((n, b), np.int64)
        free = cap.copy()
        inv_cap = 1.0 / np.maximum(cap, 1e-9)
        unchanged_mask = (prev_n >= 0) & (counts == prev_n)
        for i in np.flatnonzero(unchanged_mask):
            row = np.asarray(prev_map[app_ids[int(i)]], np.int64)
            x[i] = row
            free -= row[:, None].astype(np.float64) * d[i][None, :]
        free_unchanged = free.copy()       # residual for the exact packer
        for i in np.flatnonzero(~unchanged_mask):
            pr = prev_map.get(app_ids[int(i)])
            if pr is None or counts[i] <= 0:
                continue
            di = d[i]
            pos = di > 0
            if pos.any():
                fit = np.floor((free[:, pos] + 1e-9) / di[pos]).min(axis=1)
                fit = np.maximum(fit, 0.0).astype(np.int64)
            else:
                fit = np.full(b, int(counts[i]), np.int64)
            keep = np.minimum(np.asarray(pr, np.int64), fit)
            csum = np.minimum(np.cumsum(keep), int(counts[i]))
            keep = np.diff(np.concatenate(([0], csum)))
            if keep.any():
                x[i] = keep
                free -= keep[:, None] * di[None, :]
        sums = x.sum(axis=1)
        with np.errstate(divide="ignore", invalid="ignore"):
            dom = np.where(cap.max(axis=0) > 0,
                           d / np.maximum(cap.max(axis=0), 1e-300),
                           0.0).max(axis=1)
        ffd = np.lexsort((np.arange(n), -dom))
        for i in ffd[sums[ffd] < nmin_v[ffd]]:
            i = int(i)
            _best_fit_place_batch(x, free, d, inv_cap, i, int(nmin_v[i]))
            sums[i] = int(x[i].sum())
        for i in ffd[sums[ffd] < counts[ffd]]:
            i = int(i)
            _best_fit_place_batch(x, free, d, inv_cap, i, int(counts[i]))
            sums[i] = int(x[i].sum())
        realized = bool((sums == counts).all())
        if not realized and self.cfg.colgen_pack_vars:
            c_idx = np.flatnonzero(~unchanged_mask)
            if c_idx.size:
                xr, packed = self._pack_changed(
                    d[c_idx], np.maximum(free_unchanged, 0.0),
                    counts[c_idx], nmin_v[c_idx])
                if xr is not None and (
                        packed
                        or float(util_w[c_idx] @ xr.sum(axis=1))
                        > float(util_w[c_idx] @ x[c_idx].sum(axis=1))):
                    x[c_idx] = xr
                    sums = x.sum(axis=1)
                    realized = packed
        if (sums < nmin_v).any():
            # Fragmentation below a floor: only the guide (None without
            # one -- the caller then reports infeasible) remains usable.
            return guide, False
        if float(np.abs(g * sums - s_hat_vec).sum()) > budget_l + 1e-6:
            # A shortfall blew Eq-15 (a realized selection cannot: the
            # pool IP enforced the loss row). The guide keeps its greedy
            # semantics even on degenerate instances where it too violates.
            return guide, False
        return Allocation(tuple(app_ids), x), realized

    def _pack_changed(self, d_c: np.ndarray, cap_res: np.ndarray,
                      counts_c: np.ndarray, nmin_c: np.ndarray,
                      ) -> Tuple[Optional[np.ndarray], bool]:
        """Type-grouped packing of the changed apps' counts into the
        residual capacity. Apps with IDENTICAL demand vectors are
        interchangeable at placement time, so the hard packing runs over
        the T distinct demand types (T << n on real clusters: a 2000-app
        instance typically has a few dozen types) and each type's
        per-slave placement is split back over its members. Exact
        feasibility MILP while T * b <= colgen_pack_vars; packing LP +
        round-down + best-fit repair within 10x that (may fall short);
        (None, False) beyond. Returns (x_changed, realized)."""
        nc, _ = d_c.shape
        b = cap_res.shape[0]
        uniq, inv = np.unique(d_c, axis=0, return_inverse=True)
        T = uniq.shape[0]
        tcounts = np.rint(np.bincount(
            inv, weights=counts_c.astype(np.float64))).astype(np.int64)
        xt = None
        if T * b <= self.cfg.colgen_pack_vars:
            xt = self._exact_pack(uniq, cap_res, tcounts)
        if xt is None and T * b <= 10 * self.cfg.colgen_pack_vars:
            xt = self._lp_pack(uniq, cap_res, tcounts)
        if xt is None:
            return None, False
        ach_t = xt.sum(axis=1)
        realized = bool((ach_t == tcounts).all())

        # Split each type's placements over its member apps; a type-level
        # shortfall lands on the members with the most slack above n_min.
        x_c = np.zeros((nc, b), np.int64)
        for t in range(T):
            members = np.flatnonzero(inv == t)
            targets = counts_c[members].astype(np.int64).copy()
            short = int(tcounts[t] - ach_t[t])
            if short > 0:
                slack = targets - nmin_c[members]
                order = np.argsort(-slack, kind="stable")
                for mi in order:
                    if short <= 0:
                        break
                    cut = int(min(short, max(int(slack[mi]), 0)))
                    targets[mi] -= cut
                    short -= cut
                for mi in order[::-1]:
                    if short <= 0:
                        break
                    cut = int(min(short, int(targets[mi])))
                    targets[mi] -= cut
                    short -= cut
            mi = 0
            for j in np.flatnonzero(xt[t]):
                q = int(xt[t, j])
                while q > 0 and mi < members.size:
                    take = min(q, int(targets[mi]))
                    if take > 0:
                        x_c[members[mi], j] += take
                        targets[mi] -= take
                        q -= take
                    if targets[mi] == 0:
                        mi += 1
        return x_c, realized

    @staticmethod
    def _pack_matrix(d_c: np.ndarray, b: int):
        """COO pieces of the packing system: per-(slave, used-resource)
        capacity rows over the nc * b placement grid, then nc row-sum
        rows. Shared by the exact and LP packers."""
        nc = d_c.shape[0]
        nx = nc * b
        ks = np.flatnonzero((d_c > 0).any(axis=0))
        nk = ks.size
        rows_l: List[np.ndarray] = []
        cols_l: List[np.ndarray] = []
        vals_l: List[np.ndarray] = []
        if nk:
            jj, qq, ii = np.meshgrid(np.arange(b), np.arange(nk),
                                     np.arange(nc), indexing="ij")
            v = d_c[ii.ravel(), ks[qq.ravel()]]
            nz = v != 0
            rows_l.append((jj.ravel() * nk + qq.ravel())[nz])
            cols_l.append((ii.ravel() * b + jj.ravel())[nz])
            vals_l.append(v[nz])
        rows_l.append(b * nk + np.repeat(np.arange(nc), b))
        cols_l.append(np.arange(nx))
        vals_l.append(np.ones(nx))
        A = _sp.coo_array(
            (np.concatenate(vals_l),
             (np.concatenate(rows_l), np.concatenate(cols_l))),
            shape=(b * nk + nc, nx)).tocsc()
        A.indices = A.indices.astype(np.int32)
        A.indptr = A.indptr.astype(np.int32)
        return A, ks, nk

    def _exact_pack(self, d_c: np.ndarray, cap_res: np.ndarray,
                    counts_c: np.ndarray) -> Optional[np.ndarray]:
        """Row-sum-fixed packing feasibility MILP: place exactly
        `counts_c[i]` containers of each demand type onto slaves with
        residual capacity `cap_res`. Far cheaper than the P2 grid (no
        fairness/adjustment machinery, zero objective); returns the
        (n_c, b) placement or None when the counts provably cannot pack
        (or the time limit bites)."""
        nc = d_c.shape[0]
        b = cap_res.shape[0]
        nx = nc * b
        A, ks, nk = self._pack_matrix(d_c, b)
        cc = counts_c.astype(np.float64)
        lb = np.concatenate([np.full(b * nk, -np.inf), cc])
        ub = np.concatenate([cap_res[:, ks].ravel(), cc])
        res = milp(c=np.zeros(nx),
                   constraints=LinearConstraint(A, lb, ub),
                   bounds=_Bounds(np.zeros(nx), np.repeat(cc, b)),
                   integrality=np.ones(nx),
                   options={"time_limit": self.cfg.time_limit_s})
        if not res.success or res.x is None:
            return None
        return np.rint(res.x).astype(np.int64).reshape(nc, b)

    def _lp_pack(self, d_c: np.ndarray, cap_res: np.ndarray,
                 counts_c: np.ndarray) -> Optional[np.ndarray]:
        """Packing LP + round-down + best-fit repair: the at-scale tier of
        the packer (continuous relaxation of `_exact_pack`, so it scales
        an order of magnitude further). The repaired placement may fall
        short of the counts; the caller treats that as unrealized."""
        nc = d_c.shape[0]
        b = cap_res.shape[0]
        A, ks, nk = self._pack_matrix(d_c, b)
        cc = counts_c.astype(np.float64)
        lb = np.concatenate([np.full(b * nk, -np.inf), cc])
        ub = np.concatenate([cap_res[:, ks].ravel(), cc])
        res = linprog(np.zeros(nc * b),
                      A_ub=A[:b * nk], b_ub=ub[:b * nk],
                      A_eq=A[b * nk:], b_eq=cc,
                      bounds=(0, None), method="highs")
        if not res.success or res.x is None:
            return None
        x = np.floor(res.x.reshape(nc, b) + 1e-9).astype(np.int64)
        free = cap_res - x.T.astype(np.float64) @ d_c
        inv_cap = 1.0 / np.maximum(cap_res, 1e-9)
        with np.errstate(divide="ignore", invalid="ignore"):
            dom = np.where(cap_res.max(axis=0) > 0,
                           d_c / np.maximum(cap_res.max(axis=0), 1e-300),
                           0.0).max(axis=1)
        for t in np.lexsort((np.arange(nc), -dom)):
            t = int(t)
            if int(x[t].sum()) < int(counts_c[t]):
                _best_fit_place_batch(x, free, d_c, inv_cap, t,
                                      int(counts_c[t]))
        return x

    def _colgen_finish(self, apps, cluster, alloc: Allocation,
                       util_bound: Optional[float], util_w: np.ndarray,
                       d: np.ndarray,
                       objective: Optional[float] = None) -> Allocation:
        """Validate + record the certified-gap report of a colgen solve.
        `objective`: the achieved objective under the solve's weighting
        (goodput-weighted colgen passes it; default = count-linear)."""
        validate_allocation(alloc, apps, cluster, d=d)
        if objective is None:
            objective = float(util_w @ alloc.x.sum(axis=1))
        self._record_gap(util_bound, objective)
        return alloc


def _best_fit_place(x: np.ndarray, free: np.ndarray, d: np.ndarray,
                    inv_cap: np.ndarray, i: int, limit: int) -> None:
    """Raise app i to `limit` containers, one at a time, onto the slave with
    the least residual normalized capacity after placing. Shared by the full
    and delta greedy paths -- identical arithmetic is what keeps the
    incremental solve bit-exact with the full one.

    Only the chosen slave's free vector changes between grants, so the
    fits mask and the score vector are maintained incrementally (O(m) per
    grant after the O(b*m) setup) -- recomputing them per grant is the
    same arithmetic on unchanged rows, so the placements are identical."""
    di = d[i]
    need = limit - int(x[i].sum())
    if need <= 0:
        return
    fits = (di <= free + 1e-9).all(axis=1)
    if not fits.any():
        return
    score = ((free - di) * inv_cap).sum(axis=1)
    masked = np.where(fits, score, np.inf)
    while need > 0:
        j = int(np.argmin(masked))
        if not np.isfinite(masked[j]):
            return
        x[i, j] += 1
        free[j] -= di
        score_j = float(((free[j] - di) * inv_cap[j]).sum())
        fit_j = bool((di <= free[j] + 1e-9).all())
        masked[j] = score_j if fit_j else np.inf
        need -= 1


def _best_fit_place_batch(x: np.ndarray, free: np.ndarray, d: np.ndarray,
                          inv_cap: np.ndarray, i: int, limit: int) -> bool:
    """Batched equivalent of `_best_fit_place`: ALL of app i's containers are
    placed with one masked argsort + scatter over the slave axis instead of a
    per-container argmin loop.

    Identical placements by construction: granting a container onto slave j
    only lowers j's best-fit score (free shrinks monotonically), so the
    sequential argmin keeps choosing j until it no longer fits -- i.e. it
    fills each slave to its max feasible count in ascending order of the
    INITIAL (score, index) key, which is exactly what the argsort/scatter
    computes. Bit-identical for integer-valued demands (the delta path's
    guard); for fractional demands the batched capacity arithmetic can
    differ from the one-at-a-time subtraction in the last ulp, which is why
    the engines are never mixed within one solve path.

    Returns True iff at least one container was granted (changed-row
    tracking for the master's incremental enforcement).
    """
    di = d[i]
    need = limit - int(x[i].sum())
    if need <= 0:
        return False
    # The compute half lives in `core.backend._place_counts_np` (the seam
    # the jax backend implements against); this wrapper applies the grants.
    out = _place_counts_np(free, di, inv_cap, need)
    if out is None:
        return False
    js, counts = out
    x[i, js] += counts
    free[js] -= counts[:, None].astype(np.float64) * di[None, :]
    return True


@functools.lru_cache(maxsize=8)
def _zero_row(b: int) -> np.ndarray:
    """The one read-only zeros row a delta solve gives every new app that
    placed nothing (row-form allocations share it)."""
    row = np.zeros(b, np.int64)
    row.flags.writeable = False
    return row


class GreedyOptimizer:
    """DRF-guided heuristic for P2 with placement stickiness.

    1. Target container counts from weighted-DRF progressive filling (the
       fairness-optimal point, loss ~= 0), then greedily add containers to the
       apps with the best utilization-per-fairness-cost while the Eq-15 budget
       holds (utilization maximization is P2's objective). The Eq-15 check is
       maintained incrementally (O(1) per candidate container).
    2. Place counts onto slaves, preferring each app's previous placement
       (stickiness, closed-form per app) and vectorized best-fit for the rest.
    3. Enforce the Eq-16 adjustment budget by reverting whole apps (restore
       their previous rows) in order of least utilization gain until within
       budget; reverted capacity is reused where possible. Feasibility of a
       revert is checked against an incrementally maintained usage matrix.

    Per-event incremental path (cfg.incremental, on by default): when the
    saturating-DRF fast path proves every app's target is its n_max
    (`drf.saturating_counts`) and a previous allocation covers a subset of
    the current apps, steps 1-2 collapse: the utilization push is a no-op
    (nothing can grow past n_max) and the stickiness loop provably keeps
    every previous row unchanged, so the solve warm-starts from
    `prev_alloc`'s rows directly and only places the delta (new apps, plus
    top-ups of apps below target). Output is bit-exact with the full solve
    -- both run the same `_best_fit_place` passes and step-3 budget
    enforcement -- but the per-event cost drops from
    O(total-grants + n_running * b) to O(delta * b).
    `delta_solves` / `full_solves` count which path answered.
    """

    def __init__(self, cfg: OptimizerConfig = OptimizerConfig(),
                 spans: Optional[Spans] = None):
        self.cfg = cfg
        self.drf = IncrementalDRF()
        # The owner's span registry (a DormMaster hands over its own),
        # shared with the backend.
        self.spans = spans if spans is not None else Spans()
        # Array backend for the hot kernels (core.backend); "numpy" is the
        # bit-exactness reference, "jax" the jit/lax port. `compile_s` on it
        # feeds the master's `backend_compile` phase bucket.
        self.backend = get_backend(cfg.backend, self.spans)
        self._last_shares: Optional[Dict[str, float]] = None
        self._last_share_ids: Optional[Tuple[str, ...]] = None
        self.last_shares_vec: Optional[np.ndarray] = None  # solve app order
        # App ids (within prev's) whose placement row changed vs `prev`,
        # when the solve can prove it cheaply (SoA engine: tracked during
        # placement / one bulk compare). None = the caller must diff rows
        # itself (legacy engine, MILP results).
        self.last_changed: Optional[Tuple[str, ...]] = None
        self.delta_solves = 0
        self.full_solves = 0
        # Row-form delta solves: allocation rows taken by reference from
        # the previous allocation (or the shared zeros row), and rows copied
        # out of the state for the placement schedule.
        self.rows_shared = 0
        self.rows_copied = 0
        # Futile top-up memo: app_id -> (state.epoch, target) of a delta
        # placement attempt that could not reach its target. Free capacity
        # only shrinks while the epoch is unchanged, so the retry is
        # provably a no-op and is skipped (results identical by proof).
        # Cleared whenever the epoch moves -- every entry is stale then,
        # and this bounds the dict at O(live apps) over unbounded streams.
        self._futile: Dict[str, Tuple[int, int]] = {}
        self._futile_epoch = -1

    @property
    def refill_s(self) -> float:
        """Cumulative DRF-refill seconds (the `optimizer.refill` span)."""
        return self.spans.total_s.get("optimizer.refill", 0.0)

    @property
    def last_shares(self) -> Optional[Dict[str, float]]:
        """{app_id: s_hat} of the last solve. Built lazily on the fast
        path: the SoA master consumes `last_shares_vec` directly, so the
        O(n) dict would otherwise be thrown away every event."""
        if self._last_shares is None and self._last_share_ids is not None:
            self._last_shares = dict(zip(self._last_share_ids,
                                         self.last_shares_vec.tolist()))
        return self._last_shares

    @last_shares.setter
    def last_shares(self, value: Optional[Dict[str, float]]) -> None:
        self._last_shares = value
        self._last_share_ids = None

    def solve(self, apps: Sequence[ApplicationSpec], cluster: ClusterSpec,
              prev: Optional[Allocation] = None,
              _targets=None, state=None) -> Optional[Allocation]:
        """`_targets`: optional precomputed `_drf_targets` result, so a
        caller that already ran the progressive filling (MilpOptimizer's
        warm start) does not pay for a second pass. `state`: optional
        `core.state.ClusterState` whose placement rows mirror `prev`
        (the DormMaster's SoA engine) -- per-app coefficient arrays and the
        incrementally-maintained free/aggregate vectors are then reused
        instead of being rebuilt from the spec objects every event."""
        with self.spans.span("optimizer.solve"):
            return self._solve(apps, cluster, prev, _targets, state)

    def _solve(self, apps, cluster, prev, _targets, state,
               ) -> Optional[Allocation]:
        self.last_changed = None
        if not apps:
            self.last_shares = {}
            self.last_shares_vec = np.zeros(0)
            self.last_changed = ()
            return Allocation.empty((), cluster.b)
        with self.spans.span("optimizer.gather"):
            soa = self.cfg.soa
            n, b, m = len(apps), cluster.b, cluster.m
            app_ids = tuple(a.app_id for a in apps)
            if state is not None:
                idx = state.rows_for(app_ids)
                d = state.demand[idx]
                g = state.g[idx]
                util_w = state.util_w[idx]
                nmin_v = state.n_min[idx]
                nmax_v = state.n_max[idx]
                integral = state.all_integral()
            else:
                d = demand_matrix(apps)
                g = _dominant_coeff(apps, cluster, d)
                util_w = _util_coeff(apps, cluster, d)
                nmin_v = np.fromiter((a.n_min for a in apps), np.int64, n)
                nmax_v = np.fromiter((a.n_max for a in apps), np.int64, n)
                integral = bool((d == np.floor(d)).all())
            cap = cluster.capacity_matrix().astype(np.float64)
            total_cap = cluster.total_capacity()
            budget_l = fairness_budget(self.cfg, m)

            # Goodput knee-capping (cfg.goodput_aware): apps with a non-linear
            # speedup curve are targeted at their knee instead of n_max --
            # containers past it buy < goodput_knee of a container's progress
            # and are better spent on apps still on the steep part. The cap is
            # an effective-BOUNDS shrink applied before the DRF refill, so the
            # shares, the utilization push and the placement all see the same
            # (capped) problem and Eq-15's budget stays self-consistent. With
            # no curved apps (_knee_caps -> None; every seed workload) nothing
            # changes and the solve is bit-identical. Skipped when the caller
            # supplies `_targets`: MILP warm starts own the problem definition
            # (the exact paths keep P2's count-linear objective).
            apps_fill: Sequence[ApplicationSpec] = apps
            if self.cfg.goodput_aware and _targets is None:
                kc = _knee_caps(apps, nmin_v, nmax_v, self.cfg.goodput_knee)
                if kc is not None:
                    nmax_v = kc
                    apps_fill = [
                        a if a.n_max <= int(kc[i])
                        else a.with_bounds(n_max=int(kc[i]))
                        for i, a in enumerate(apps)]

        with self.spans.span("optimizer.refill"):
            # -- DRF refill (the phase breakdown's drf_refill bucket).
            fast = False
            if _targets is not None:
                drf_counts, s_hat_vec = _targets
                self.last_shares = dict(zip(app_ids, map(float, s_hat_vec)))
                target = np.fromiter((drf_counts[a] for a in app_ids),
                                     np.int64, n)
            elif self.cfg.incremental:
                if state is not None:
                    if integral:
                        # O(m) probe against the incrementally-maintained
                        # aggregate n_max demand (exact for integral demands)
                        # instead of the O(n*m) re-aggregation in
                        # `drf.saturating_counts`.
                        fast = state.saturates_at_nmax()
                    else:
                        # Fractional demands: the running aggregate is not
                        # ulp-exact, so probe against a fresh aggregation
                        # (same arithmetic as `drf.saturating_counts`, on the
                        # state's SoA arrays via the backend seam).
                        fast = self.backend.saturating_probe(
                            d, nmax_v.astype(np.float64), total_cap)
                    if fast:
                        self.drf.fast_hits += 1
                        target = nmax_v.astype(np.int64, copy=True)
                        s_hat_vec = _shares_vec(target, d, total_cap)
                        self._last_shares = None          # built lazily
                        self._last_share_ids = app_ids
                    else:
                        # Full ladder refill straight on the SoA arrays (the
                        # backend seam: numpy = the reference fill, jax = the
                        # jitted ladder program); shares follow in one
                        # vectorized pass, dict built lazily.
                        self.drf.full_refills += 1
                        target = self.backend.ladder_counts(
                            d, nmin_v, nmax_v,
                            state.weight[idx].astype(np.float64), total_cap)
                        s_hat_vec = _shares_vec(target, d, total_cap)
                        self._last_shares = None          # built lazily
                        self._last_share_ids = app_ids
                else:
                    # Incremental DRF refill: O(n*m) saturating fast path when
                    # it provably matches the full filling, full otherwise.
                    drf_counts, shares, fast = self.drf.targets(
                        apps_fill, cluster, reference=not soa)
                    self.last_shares = shares
                    s_hat_vec = np.fromiter((shares[a] for a in app_ids),
                                            np.float64, n)
                    target = np.fromiter((drf_counts[a] for a in app_ids),
                                         np.int64, n)
            else:
                # Full re-solve semantics (the seed's per-event behaviour):
                # progressive filling from scratch on every event.
                drf_counts, s_hat_vec = _drf_targets(apps_fill, cluster,
                                                     reference=not soa, d=d)
                self.last_shares = dict(zip(app_ids, map(float, s_hat_vec)))
                target = np.fromiter((drf_counts[a] for a in app_ids),
                                     np.int64, n)
            self.last_shares_vec = s_hat_vec

        with self.spans.span("optimizer.targets"):
            # -- step 1: choose target counts.
            if np.any(target < nmin_v):
                # Aggregate capacity cannot host every app's minimum ->
                # infeasible; paper behaviour: keep existing allocations
                # (master handles it).
                return None

            def total_loss(counts: np.ndarray) -> float:
                return float(np.abs(g * counts - s_hat_vec).sum())

            drf_target0 = target       # pre-push DRF point (step-3 re-check)

            # The master appends new apps after surviving ones, so prev's app
            # list is almost always a prefix of the current one; membership is
            # then just an index compare and NO prev dict is built at all.
            # Otherwise: row views, not copies (as_dict copies every row; this
            # runs per event and the solver only reads previous rows).
            n_prev = len(prev.app_ids) if prev is not None else 0
            k_prefix = 0
            prev_map: Optional[Dict[str, np.ndarray]] = None
            if soa and n_prev and prev.app_ids == app_ids[:n_prev]:
                k_prefix = n_prev
            elif prev is not None:
                prev_map = dict(zip(prev.app_ids, prev.rows))
            else:
                prev_map = {}

            def in_prev(i: int) -> bool:
                return i < k_prefix if prev_map is None \
                    else app_ids[i] in prev_map

            def prev_row(i: int) -> np.ndarray:
                return prev.row_at(i) if prev_map is None \
                    else prev_map[app_ids[i]]

            delta = bool(self.cfg.incremental and fast and n_prev
                         and (prev_map is None
                              or set(prev_map).issubset(app_ids)))
            if delta:
                # Guard: a shrunk bound (Resize event) can push a target below
                # the previous count; the stickiness loop must then TRIM rows,
                # so the prev-rows warm start would not match -- full path.
                if state is not None:
                    if bool((state.counts[idx] > target).any()):
                        delta = False
                elif prev_map is None:
                    if bool((prev.x.sum(axis=1) > target[:k_prefix]).any()):
                        delta = False
                else:
                    tgt_of = dict(zip(app_ids, target.tolist()))
                    if any(int(row.sum()) > tgt_of[a]
                           for a, row in prev_map.items()):
                        delta = False
            if delta and not integral and not soa:
                # Legacy-engine guard: with fractional demands (e.g. Philly
                # n_cpus/n_gpus or Alibaba plan_cpu/100 replays) the delta
                # path's one-matmul free computation and the legacy full path's
                # sequential row subtraction can differ in the last ulp and
                # flip a near-tied best-fit argmin. The SoA engine closes that
                # hole by CANONICALIZING free on both paths (one
                # cap - x^T d matmul, order-independent -- see the warm-start
                # block below), so fractional replays take the delta path
                # there; the legacy engine stays the frozen reference.
                delta = False

            if not fast:
                # Greedy utilization push above the DRF point within the Eq-15
                # budget (skipped on the fast path: every target already sits
                # at n_max, so the push is provably a no-op). Pure-python
                # incremental loop: the loss delta of one extra container is
                # local to the app, so the Eq-15 re-check is O(1), not O(n).
                remaining = (total_cap - target @ d).tolist()
                d_list = d.tolist()
                g_list = g.tolist()
                s_hat_list = s_hat_vec.tolist()
                tgt = target.tolist()
                nmax_list = nmax_v.tolist()
                cur_loss = sum(abs(g_list[i] * tgt[i] - s_hat_list[i])
                               for i in range(n))
                order = np.argsort(-util_w).tolist()  # best utilization first
                rng_m = range(m)
                improved = True
                while improved:
                    improved = False
                    for i in order:
                        if tgt[i] >= nmax_list[i]:
                            continue
                        di = d_list[i]
                        if any(di[k] > remaining[k] + 1e-9 for k in rng_m):
                            continue
                        old_li = abs(g_list[i] * tgt[i] - s_hat_list[i])
                        new_li = abs(g_list[i] * (tgt[i] + 1) - s_hat_list[i])
                        if cur_loss - old_li + new_li <= budget_l + 1e-9:
                            tgt[i] += 1
                            cur_loss += new_li - old_li
                            for k in rng_m:
                                remaining[k] -= di[k]
                            improved = True
                target = np.array(tgt, dtype=np.int64)

        with self.spans.span("optimizer.place"):
            # -- step 2: placement with stickiness. The backend seam covers the
            # SoA state-backed solves (the master's hot path); spec-only solves
            # (MILP warm starts, standalone calls) keep the host scatter.
            if not soa:
                place_fn = _best_fit_place
                place_be = None
            else:
                # The whole two-pass placement schedule is executed by ONE
                # backend call (`Backend.place_run`): numpy runs the reference
                # sequential loop, jax fuses the schedule into a single device
                # program. Spec-only SoA solves stay on the host backend.
                place_fn = None
                place_be = self.backend if state is not None else _HOST_BACKEND
            inv_cap = 1.0 / np.maximum(cap, 1e-9)
            # Indices changed vs prev rows.
            changed_track: Optional[set] = None
            # Row form (delta solve on the state): the new allocation's rows
            # are prev's row objects, the shared zeros row for new apps, and
            # fresh rows for the apps the schedule grants to -- no (n, b)
            # matrix is built.
            rows: Optional[List[np.ndarray]] = None
            fresh: set = set()
            if delta:
                # Delta warm start: every surviving app keeps its previous row
                # verbatim (the stickiness loop below would reproduce exactly
                # that: targets are at n_max >= previous counts, and previous
                # rows are jointly capacity-feasible, so nothing is trimmed).
                self.delta_solves += 1
                # Only the SoA placement loops feed the tracker; the legacy
                # engine must fall back to the row compare.
                changed_track = set() if soa else None
                if state is not None:
                    # The state's rows, counts and free matrix ARE the previous
                    # allocation's: rows are read only for the scheduled apps.
                    x = None
                    if prev_map is None:
                        rows = list(prev.rows)
                        rows += [_zero_row(b)] * (n - k_prefix)
                    else:
                        rows = [prev_map.get(a, _zero_row(b)) for a in app_ids]
                    if integral:
                        free = state.free.copy()
                    else:
                        # Fractional demands: derive free canonically from the
                        # rows (one order-independent matmul). The full path
                        # below canonicalizes its free the same way after the
                        # stickiness loop, so both paths feed the best-fit
                        # scatter bit-identical scores -- for integral demands
                        # the incrementally-maintained matrix already IS that
                        # value exactly, and the copy is cheaper.
                        free = cap - state.x[idx].T.astype(np.float64) @ d
                    sums = state.counts[idx].copy()
                else:
                    x = np.zeros((n, b), dtype=np.int64)
                    if k_prefix:
                        x[:k_prefix] = prev.x       # one bulk copy
                    else:
                        for i, a in enumerate(app_ids):
                            pr = prev_map.get(a)
                            if pr is not None:
                                x[i] = pr
                    free = cap - x.T.astype(np.float64) @ d
                    sums = x.sum(axis=1)
            else:
                self.full_solves += 1
                x = np.zeros((n, b), dtype=np.int64)
                free = cap.copy()
                # Keep previous placements first (up to the new target): per
                # app the per-slave keepable count has the closed form
                # min(prev_j, max q: q*d <= free_j + eps), capped cumulatively.
                for i, a in enumerate(app_ids):
                    if prev_map is None:
                        pr = prev.row_at(i) if i < k_prefix else None
                    else:
                        pr = prev_map.get(a)
                    if pr is None or target[i] <= 0:
                        continue
                    di = d[i]
                    pos = di > 0
                    if pos.any():
                        fit = np.floor((free[:, pos] + 1e-9)
                                       / di[pos]).min(axis=1)
                        fit = np.maximum(fit, 0.0).astype(np.int64)
                    else:
                        fit = np.full(b, int(target[i]), dtype=np.int64)
                    keep = np.minimum(np.asarray(pr, dtype=np.int64), fit)
                    csum = np.minimum(np.cumsum(keep), int(target[i]))
                    keep = np.diff(np.concatenate(([0], csum)))
                    if keep.any():
                        x[i] = keep
                        free -= keep[:, None] * di[None, :]
                sums = x.sum(axis=1)
                if soa and not integral:
                    # Canonical free (fractional demands, SoA engine): replace
                    # the stickiness loop's sequentially-updated matrix with
                    # one order-independent  cap - x^T d  matmul. Exact no-op
                    # for integral demands (float64 integer products/sums are
                    # associativity-independent); for fractional demands it is
                    # what makes the delta warm start above bit-exact with this
                    # path -- both now derive free from x the same way before
                    # any best-fit score is computed.
                    free = cap - x.T.astype(np.float64) @ d
            # Best-fit the remainder. Two passes: every app is raised to its
            # n_min before anyone is topped up to the full target -- packing
            # early apps to their whole target first would starve the tail
            # below n_min on a saturated cluster and spuriously report P2
            # infeasible.
            if soa:
                # Only the apps below target are visited (ascending index
                # order, same as the legacy scan), and row sums are bookkept
                # instead of re-reduced per app.
                memo = epoch = None
                if changed_track is not None and state is not None:
                    memo = self._futile
                    epoch = state.epoch
                    if epoch != self._futile_epoch:
                        memo.clear()
                        self._futile_epoch = epoch
                # Build the full two-pass schedule up front, memo-skips
                # excluded (decidable before any placement: a memoized app held
                # >= n_min at the same epoch, so pass 1 never visits it and its
                # target is unchanged), and execute it with ONE backend call.
                pass1 = [int(i) for i in np.flatnonzero(sums < nmin_v)]
                pass2: List[int] = []
                for i in np.flatnonzero(sums < target):
                    i = int(i)
                    if memo is not None:
                        # Skip a top-up that already found no fitting slave at
                        # this capacity epoch (no capacity was freed since, so
                        # the attempt is provably a no-op; such apps already
                        # hold >= n_min from the previous allocation).
                        rec = memo.get(app_ids[i])
                        if rec is not None and rec[0] == epoch \
                                and rec[1] == int(target[i]):
                            continue
                    pass2.append(i)
                schedule = [(i, int(nmin_v[i])) for i in pass1] \
                    + [(i, int(target[i])) for i in pass2]
                if not schedule:
                    grants = []
                elif rows is None:
                    grants = place_be.place_run(x, free, d, inv_cap, schedule)
                else:
                    # Place on a (K_u, b) copy of the scheduled apps' state
                    # rows, items remapped to its local indices; each app
                    # granted something gets its own frozen new row.
                    uniq = sorted({i for i, _ in schedule})
                    local = {i: k for k, i in enumerate(uniq)}
                    xs = state.x[idx[uniq]]
                    grants = place_be.place_run(
                        xs, free, d[uniq], inv_cap,
                        [(local[i], lim) for i, lim in schedule])
                    self.rows_copied += len(uniq)
                    xs.flags.writeable = False
                    for (i, _), got in zip(schedule, grants):
                        if got:
                            rows[i] = xs[local[i]]
                            fresh.add(i)
                # Replay the sequential bookkeeping over the fused results:
                # per-app row sums, changed-row tracking, the below-n_min
                # infeasibility abort and the futile-top-up memo updates stop
                # exactly where the sequential loop would have stopped.
                for k, i in enumerate(pass1):
                    if grants[k]:
                        sums[i] += grants[k]
                        if changed_track is not None and in_prev(i):
                            changed_track.add(i)
                for k, i in enumerate(pass2):
                    tgt_i = int(target[i])
                    if sums[i] >= tgt_i:
                        # Raised to target by pass 1 already: the sequential
                        # pass-2 scan (computed on post-pass-1 sums) never
                        # visits this app; its fused grant is provably zero.
                        continue
                    g = grants[len(pass1) + k]
                    if g:
                        sums[i] += g
                        if changed_track is not None and in_prev(i):
                            changed_track.add(i)
                    if sums[i] < nmin_v[i]:
                        # Packing failed below n_min -> infeasible signal.
                        return None
                    if memo is not None:
                        if sums[i] < tgt_i:
                            memo[app_ids[i]] = (epoch, tgt_i)
                        else:
                            memo.pop(app_ids[i], None)
            else:
                for i in range(n):
                    if sums[i] < apps[i].n_min:
                        place_fn(x, free, d, inv_cap, i, apps[i].n_min)
                for i in range(n):
                    if x[i].sum() < target[i]:
                        place_fn(x, free, d, inv_cap, i, int(target[i]))
                    if x[i].sum() < apps[i].n_min:
                        # Packing failed below n_min: give up -> infeasible.
                        return None
                sums = x.sum(axis=1)

        with self.spans.span("optimizer.budget"):
            # -- step 3: adjustment budget.
            if k_prefix:
                common = list(range(k_prefix))
            elif prev_map:
                common = [i for i, a in enumerate(app_ids) if a in prev_map]
            else:
                common = []
            if common:
                budget_r = adjust_budget(self.cfg, len(common))
                if changed_track is not None:
                    # Delta path: rows start as prev's rows, so the placement
                    # grants above are EXACTLY the changed rows -- no compare.
                    changed = sorted(changed_track)
                elif soa and k_prefix:
                    diff = (x[:k_prefix] != prev.x).any(axis=1)
                    changed = np.flatnonzero(diff).tolist()
                else:
                    changed = [i for i in common
                               if not np.array_equal(x[i], prev_row(i))]
                # Revert least-valuable changes until within budget (reverting
                # must stay capacity-feasible; reverts free or consume
                # capacity).
                changed.sort(key=lambda i: util_w[i] * (sums[i]
                                                        - prev_row(i).sum()))
                if len(changed) > budget_r:
                    if rows is not None and integral:
                        # Exact: integer counts and demands.
                        used = cap - free                   # (b, m)
                    else:
                        xd = x if rows is None else np.stack(rows)
                        used = xd.T.astype(np.float64) @ d
                    while len(changed) > budget_r:
                        reverted = False
                        for pos_i in range(len(changed) - 1, -1, -1):
                            i = changed[pos_i]
                            pr = prev_row(i)
                            pr_n = int(pr.sum())
                            if pr_n > nmax_v[i] or pr_n < nmin_v[i]:
                                # Bounds moved since the previous allocation
                                # (Resize event): the old row is no longer a
                                # legal state to revert to.
                                continue
                            cur = x[i] if rows is None else rows[i]
                            delta_u = (pr - cur).astype(np.float64)[:, None] \
                                * d[i][None, :]
                            if np.all(used + delta_u <= cap + 1e-6):
                                used += delta_u
                                if rows is None:
                                    x[i] = pr
                                else:
                                    rows[i] = pr
                                    fresh.discard(i)
                                sums[i] = pr_n
                                changed.pop(pos_i)
                                reverted = True
                                break
                        if not reverted:
                            # Cannot satisfy Eq 16 -> infeasible.
                            return None
                # Re-check fairness budget after reverts; if blown, also
                # infeasible (paper keeps previous allocation in that case).
                if total_loss(sums) > budget_l + 1e-6:
                    drf_loss = total_loss(np.clip(drf_target0, nmin_v, nmax_v))
                    if drf_loss <= budget_l + 1e-6:
                        return None
                if soa:
                    self.last_changed = tuple(app_ids[i] for i in changed)
            elif soa:
                self.last_changed = ()

            if delta:
                if rows is None:
                    alloc = Allocation.trusted(app_ids, x)
                else:
                    self.rows_shared += n - len(fresh)
                    alloc = Allocation.from_rows(app_ids, tuple(rows), b)
                if integral:
                    # Provably feasible, skip the O(n*b) re-validation: rows
                    # start from the (validated) previous allocation, every
                    # grant stayed within the exactly-maintained free capacity
                    # (exact for integral demands), and counts end in
                    # [n_min, target <= n_max]. The legacy engine still
                    # validates, so the engine bit-exactness tests cross-check
                    # this proof.
                    return alloc
                # Fractional demands: the free matrix carries rounding, so the
                # feasibility proof is only epsilon-exact -- keep the cheap
                # trusted construction but run the full capacity/bounds check.
                validate_allocation(alloc, apps, cluster, d=d)
                return alloc
            alloc = Allocation(app_ids, x)
            validate_allocation(alloc, apps, cluster, d=d)
            return alloc


class AutoOptimizer:
    """Size-aware dispatcher: exact MILP while the instance is small enough
    (n_apps * b <= cfg.auto_switch_vars), greedy heuristic beyond -- the
    scale path for 1000-slave clusters where the MILP's n*b integer grid
    is intractable."""

    def __init__(self, cfg: OptimizerConfig = OptimizerConfig(),
                 spans: Optional[Spans] = None):
        self.cfg = cfg
        self.spans = spans if spans is not None else Spans()
        self._milp = MilpOptimizer(cfg, self.spans) if _HAVE_SCIPY else None
        self._greedy = GreedyOptimizer(cfg, self.spans)
        self._last_solver = self._greedy

    @property
    def last_shares(self) -> Optional[Dict[str, float]]:
        return self._last_solver.last_shares

    @property
    def last_shares_vec(self) -> Optional[np.ndarray]:
        return self._last_solver.last_shares_vec

    @property
    def last_changed(self) -> Optional[Tuple[str, ...]]:
        return self._last_solver.last_changed

    # Both solvers record into `self.spans`.
    refill_s = MilpOptimizer.refill_s
    pricing_s = MilpOptimizer.pricing_s

    @property
    def backend(self):
        """The greedy solver's array backend (compile_s feeds the master's
        `backend_compile` phase bucket)."""
        return self._greedy.backend

    @property
    def last_gap(self) -> Optional[float]:
        return getattr(self._last_solver, "last_gap", None)

    @property
    def last_bound(self) -> Optional[float]:
        return getattr(self._last_solver, "last_bound", None)

    def select(self, apps: Sequence[ApplicationSpec], cluster: ClusterSpec):
        """The solver that `solve` would dispatch to for this instance."""
        if self._milp is not None and \
                len(apps) * cluster.b <= self.cfg.auto_switch_vars:
            return self._milp
        return self._greedy

    def solve(self, apps: Sequence[ApplicationSpec], cluster: ClusterSpec,
              prev: Optional[Allocation] = None, state=None,
              ) -> Optional[Allocation]:
        solver = self.select(apps, cluster)
        alloc = solver.solve(apps, cluster, prev, state=state)
        self._last_solver = solver
        return alloc


def make_optimizer(kind: str, cfg: OptimizerConfig = OptimizerConfig(),
                   spans: Optional[Spans] = None):
    """`spans`: the owner's span registry (a DormMaster hands over its own);
    a fresh one when None."""
    if kind == "milp":
        return MilpOptimizer(cfg, spans)
    if kind == "colgen":
        # The column-generation exact route: a MilpOptimizer with the
        # colgen path forced on (certified global gap on every solve).
        return MilpOptimizer(dataclasses.replace(cfg,
                                                 column_generation=True),
                             spans)
    if kind == "greedy":
        return GreedyOptimizer(cfg, spans)
    if kind == "auto":
        return AutoOptimizer(cfg, spans)
    raise ValueError(f"unknown optimizer kind: {kind!r}")
