"""Plain reference for what the timed path decides.

A straightforward implementation of the same semantics as Dorm's greedy
scheduling pass, written against plain arrays and imported from nothing of
the program: the DRF ladder one grant at a time, best-fit placement as its
closed form (fill slaves in ascending (score, index) order), the pass as
`DormMaster` + `GreedyOptimizer` define it (stickiness, two best-fit
passes, Eq-15 and Eq-16 budgets, keep-allocations on infeasibility), the
Eq-1/2/4 outcomes, and the runtime's event loop with the storm absorber
(progress in container-seconds, adjustment downtime, floods inside the
absorber window).

Every float function takes `dtype`: float64 is the reference, float32 is
the control (the same code one precision down), which the comparison must
refuse.
"""
from __future__ import annotations

import heapq
import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

EPS = 1e-9


# ----------------------------------------------------------------- kernels

def shares(counts: np.ndarray, d: np.ndarray, total: np.ndarray,
           dtype=np.float64) -> np.ndarray:
    """Dominant share max_k(N_i d_ik / C_k) (zero where C_k is 0)."""
    n = counts.astype(dtype)
    tot = total.astype(dtype)
    with np.errstate(divide="ignore", invalid="ignore"):
        r = np.where(tot[None, :] > 0, (n[:, None] * d.astype(dtype))
                     / tot[None, :], dtype(0))
    return r.max(axis=1) if r.size else np.zeros(len(counts), dtype)


def ladder(d: np.ndarray, n_min: np.ndarray, n_max: np.ndarray,
           w: np.ndarray, total: np.ndarray, dtype=np.float64) -> np.ndarray:
    """Weighted DRF progressive filling, one container at a time.

    Phase 1 grants every app its n_min in ascending (weighted share at
    n_min, index) order while it fits. Phase 2 repeatedly grants one more
    container to the app of smallest weighted share (ties: lowest index)
    among those holding their minimum and below n_max; an app whose next
    container no longer fits the remaining capacity is done. Where every
    app's n_max fits at once, every grant fits, and the filling ends at
    n_max."""
    n, m = d.shape
    d = d.astype(dtype)
    tot = total.astype(dtype)
    if np.all(n_max.astype(dtype) @ d <= tot):
        return n_max.astype(np.int64)
    # Scalars of `dtype`, one grant at a time: the arithmetic stays in that
    # precision (Python's float is IEEE float64), and a grant costs a few
    # scalar operations.
    sc = float if dtype == np.float64 else dtype
    dl = [[sc(v) for v in row] for row in d]
    totl = [sc(v) for v in tot]
    wl = [sc(v) for v in w]
    pos = [k for k in range(m) if totl[k] > 0]
    zero = sc(0)

    def key(i: int, c: int):
        cc, di = sc(c), dl[i]
        return max([zero] + [cc * di[k] / totl[k] for k in pos]) / wl[i]

    def fits(i: int, rem) -> bool:
        di = dl[i]
        return all(di[k] <= rem[k] + EPS for k in range(m))

    cnt = np.zeros(n, np.int64)
    need = n_min[:, None].astype(dtype) * d
    if np.all(need.sum(axis=0) <= tot + EPS):
        cnt[:] = n_min
        remaining = [sc(v) for v in tot - need.sum(axis=0)]
    else:
        remaining = [sc(v) for v in tot]
        k0 = np.array([key(i, int(n_min[i])) for i in range(n)], dtype)
        for i in np.argsort(k0, kind="stable"):
            if all(need[i, k] <= remaining[k] + EPS for k in range(m)):
                cnt[i] = n_min[i]
                remaining = [remaining[k] - sc(need[i, k]) for k in range(m)]
    cl, top = cnt.tolist(), n_max.tolist()
    heap = [(key(i, cl[i]), i) for i in range(n) if 0 < cl[i] < top[i]]
    heapq.heapify(heap)
    while heap:
        _, i = heapq.heappop(heap)
        if not fits(i, remaining):
            continue
        cl[i] += 1
        di = dl[i]
        remaining = [remaining[k] - di[k] for k in range(m)]
        if cl[i] < top[i]:
            heapq.heappush(heap, (key(i, cl[i]), i))
    return np.asarray(cl, np.int64)


def best_fit(free: np.ndarray, di: np.ndarray, inv_cap: np.ndarray,
             need: int, dtype=np.float64) -> np.ndarray:
    """Containers of one app per slave: slaves that fit one container, in
    ascending (score, index) order with score = sum_k (free - d) / cap,
    each filled to the most it holds, until `need` are placed."""
    b = free.shape[0]
    out = np.zeros(b, np.int64)
    if need <= 0:
        return out
    f = free.astype(dtype)
    dd = di.astype(dtype)
    fit = np.flatnonzero((dd <= f + EPS).all(axis=1))
    if not fit.size:
        return out
    sub = f[fit]
    pos = dd > 0
    if pos.any():
        q = np.floor((sub[:, pos] + EPS) / dd[pos]).min(axis=1)
        q = np.maximum(q, 1).astype(np.int64)
    else:
        q = np.full(fit.size, need, np.int64)
    score = ((sub - dd) * inv_cap[fit].astype(dtype)).sum(axis=1)
    order = np.argsort(score, kind="stable")
    csum = np.minimum(np.cumsum(q[order]), need)
    out[fit[order]] = np.diff(np.concatenate(([0], csum)))
    return out


def place_run(free: np.ndarray, inv_cap: np.ndarray, d_items: np.ndarray,
              limits: Sequence[int], bases: Sequence[int],
              app_of: Sequence[int], dtype=np.float64) -> np.ndarray:
    """A placement schedule run in order: item k raises its app to
    limits[k] containers (its total before the schedule is bases[k], plus
    what earlier items of the same app granted). -> (K, b) grants."""
    free = free.astype(dtype).copy()
    K = len(limits)
    grants = np.zeros((K, free.shape[0]), np.int64)
    got: Dict[int, int] = {}
    for k in range(K):
        a = app_of[k]
        need = int(limits[k]) - int(bases[k]) - got.get(a, 0)
        if need > 0:
            g = best_fit(free, d_items[k], inv_cap, need, dtype)
            grants[k] = g
            free -= g[:, None].astype(dtype) * d_items[k].astype(dtype)[None, :]
            got[a] = got.get(a, 0) + int(g.sum())
    return grants


# ------------------------------------------------------------- the solve

def greedy_solve(d: np.ndarray, w: np.ndarray, n_min: np.ndarray,
                 n_max: np.ndarray, prev_rows: Dict[int, np.ndarray],
                 cap: np.ndarray, theta1: float, theta2: float,
                 dtype=np.float64):
    """Dorm's greedy P2 solve over apps 0..n-1 (admission order).

    `prev_rows` maps an app index to its row in the previous allocation.
    -> (x or None, s_hat): s_hat is the DRF point's dominant shares."""
    n, m = d.shape
    b = cap.shape[0]
    total = cap.sum(axis=0)
    with np.errstate(divide="ignore", invalid="ignore"):
        ratios = np.where(total > 0, d / total, 0.0)
    g = ratios.max(axis=1)
    util_w = ratios.sum(axis=1)
    budget_l = theta1 * 2 * m

    target = ladder(d, n_min, n_max, w, total, dtype)
    s_hat = shares(target, d, total, dtype)
    if np.any(target < n_min):
        return None, s_hat

    def total_loss(counts):
        return float(np.abs(g * counts - s_hat).sum())

    drf_target0 = target
    # Utilization push above the DRF point within the Eq-15 budget.
    remaining = (total - target @ d).tolist()
    d_l, g_l, s_l = d.tolist(), g.tolist(), s_hat.tolist()
    tgt = target.tolist()
    nmax_l = n_max.tolist()
    cur = sum(abs(g_l[i] * tgt[i] - s_l[i]) for i in range(n))
    improved = True
    while improved:
        improved = False
        for i in np.argsort(-util_w).tolist():
            if tgt[i] >= nmax_l[i]:
                continue
            di = d_l[i]
            if any(di[k] > remaining[k] + EPS for k in range(m)):
                continue
            old = abs(g_l[i] * tgt[i] - s_l[i])
            new = abs(g_l[i] * (tgt[i] + 1) - s_l[i])
            if cur - old + new <= budget_l + EPS:
                tgt[i] += 1
                cur += new - old
                for k in range(m):
                    remaining[k] -= di[k]
                improved = True
    target = np.array(tgt, np.int64)

    # Placement: keep previous rows up to the target, then best-fit.
    x = np.zeros((n, b), np.int64)
    free = cap.astype(np.float64).copy()
    inv_cap = 1.0 / np.maximum(cap, 1e-9)
    for i in range(n):
        pr = prev_rows.get(i)
        if pr is None or target[i] <= 0:
            continue
        # Only the slaves the app held can keep containers.
        js = np.flatnonzero(pr)
        di = d[i]
        pos = di > 0
        if pos.any():
            fit = np.floor((free[js][:, pos] + EPS) / di[pos]).min(axis=1)
            fit = np.maximum(fit, 0.0).astype(np.int64)
        else:
            fit = np.full(js.size, int(target[i]), np.int64)
        keep = np.minimum(pr[js], fit)
        csum = np.minimum(np.cumsum(keep), int(target[i]))
        keep = np.diff(np.concatenate(([0], csum)))
        if keep.any():
            x[i, js] = keep
            free[js] -= keep[:, None] * di[None, :]
    if not bool((d == np.floor(d)).all()):
        free = cap - x.T.astype(np.float64) @ d
    sums = x.sum(axis=1)
    for limit_of in (n_min, target):          # pass 1: n_min; pass 2: target
        for i in range(n):
            if sums[i] < limit_of[i]:
                gr = best_fit(free, d[i], inv_cap,
                              int(limit_of[i]) - int(sums[i]), dtype)
                x[i] += gr
                sums[i] += int(gr.sum())
                free = free - gr[:, None].astype(np.float64) * d[i][None, :]
            if limit_of is target and sums[i] < n_min[i]:
                return None, s_hat

    # Eq-16 adjustment budget: revert the least valuable changed apps.
    common = sorted(prev_rows)
    if common:
        budget_r = int(math.ceil(theta2 * len(common)))
        differs = (x[common] != np.stack([prev_rows[i] for i in common])
                   ).any(axis=1)
        changed = [i for i, c in zip(common, differs.tolist()) if c]
        changed.sort(key=lambda i: util_w[i] * (sums[i] - prev_rows[i].sum()))
        if len(changed) > budget_r:
            used = x.T.astype(np.float64) @ d
            while len(changed) > budget_r:
                reverted = False
                for p in range(len(changed) - 1, -1, -1):
                    i = changed[p]
                    pr = prev_rows[i]
                    pr_n = int(pr.sum())
                    if pr_n > n_max[i] or pr_n < n_min[i]:
                        continue
                    du = (pr - x[i]).astype(np.float64)[:, None] * d[i][None, :]
                    if np.all(used + du <= cap + 1e-6):
                        used += du
                        x[i] = pr
                        sums[i] = pr_n
                        changed.pop(p)
                        reverted = True
                        break
                if not reverted:
                    return None, s_hat
        if total_loss(sums) > budget_l + 1e-6:
            if total_loss(np.clip(drf_target0, n_min, n_max)) <= budget_l + 1e-6:
                return None, s_hat
    return x, s_hat


def utilization(counts: np.ndarray, d: np.ndarray, total: np.ndarray,
                dtype=np.float64) -> float:
    """Eq 1: sum_k (sum_i N_i d_ik) / C_k."""
    if not len(counts):
        return 0.0
    used = counts.astype(dtype) @ d.astype(dtype)
    tot = total.astype(dtype)
    with np.errstate(divide="ignore", invalid="ignore"):
        return float(np.where(tot > 0, used / tot, dtype(0)).sum())


def dorm_pass(jobs: Dict[str, dict], order: List[str], pending: List[str],
              prev_ids: Tuple[str, ...], prev_x: Optional[np.ndarray],
              completions: Sequence[str], arrivals: Sequence[str],
              cap: np.ndarray, theta1: float, theta2: float,
              dtype=np.float64) -> dict:
    """One `DormMaster` pass over a flood: completions leave, arrivals are
    admitted behind the survivors, one solve runs over every admitted app,
    and an infeasible solve keeps the current allocation.

    `order`/`pending` are the admitted and the waiting apps before the
    flood, `prev_ids`/`prev_x` the previous allocation. -> the pass's
    outcome: allocation ids and rows, adjusted/started/pending apps, Eq-1
    utilization and Eq-2 fairness loss."""
    done = set(completions)
    order = [a for a in order if a not in done] + list(arrivals)
    pending = [a for a in pending if a not in done] + list(arrivals)
    total = cap.sum(axis=0)
    prev_map: Dict[str, np.ndarray] = {}
    if prev_x is not None:
        prev_map = {a: prev_x[r] for r, a in enumerate(prev_ids)
                    if a not in done}
    placed = {a for a, row in prev_map.items() if row.any()}
    pos = {a: i for i, a in enumerate(order)}
    d = np.array([jobs[a]["demand"] for a in order], np.float64).reshape(
        len(order), cap.shape[1])
    w = np.array([jobs[a]["weight"] for a in order], np.int64)
    n_min = np.array([jobs[a]["n_min"] for a in order], np.int64)
    n_max = np.array([jobs[a]["n_max"] for a in order], np.int64)
    prev_rows = {pos[a]: row for a, row in prev_map.items() if a in pos}
    if order:
        x, s_hat = greedy_solve(d, w, n_min, n_max, prev_rows, cap,
                                theta1, theta2, dtype)
    else:
        x, s_hat = np.zeros((0, cap.shape[0]), np.int64), np.zeros(0)
    if x is None:
        ids = [a for a in order if a in placed]
        rows = (np.stack([prev_map[a] for a in ids]) if ids
                else np.zeros((0, cap.shape[0]), np.int64))
        sel = [pos[a] for a in ids]
        counts = rows.sum(axis=1)
        if set(ids) == set(order):
            theo = s_hat
        else:
            theo = shares(ladder(d[sel], n_min[sel], n_max[sel], w[sel],
                                 total, dtype), d[sel], total, dtype)
        loss = float(np.abs(shares(counts, d[sel], total, dtype)
                            - theo).sum()) if ids else 0.0
        return {"feasible": False, "ids": tuple(ids), "x": rows,
                "adjusted": (), "started": (), "pending": tuple(pending),
                "utilization": utilization(counts, d[sel], total, dtype),
                "fairness_loss": loss}
    counts = x.sum(axis=1)
    adjusted = tuple(a for i, a in enumerate(order)
                     if a in placed and not np.array_equal(x[i], prev_map[a]))
    started = tuple(a for i, a in enumerate(order)
                    if a in set(pending) and counts[i] > 0)
    new_pending = tuple(a for a in pending if a not in set(started))
    loss = float(np.abs(shares(counts, d, total, dtype) - s_hat).sum())
    return {"feasible": True, "ids": tuple(order), "x": x,
            "adjusted": adjusted, "started": started, "pending": new_pending,
            "utilization": utilization(counts, d, total, dtype),
            "fairness_loss": loss}


# ------------------------------------------------------------- the loop

def event_loop(jobs: List[dict], passes: List[dict], window_s: float,
               adjustment_cost_s: float, dtype=np.float64) -> List[dict]:
    """The runtime's event loop with the storm absorber, driven by the
    program's decisions (each pass's changed container counts and adjusted
    apps, as the runtime applies them).

    Jobs arrive at their submit time; a job with N containers burns N
    container-seconds of work per second except while paused for an
    adjustment; a flood collects every completion and arrival within
    `window_s` of its first event. -> per pass {"t", "completions",
    "arrivals"} as this loop predicts them, one per decision given."""
    n = len(jobs)
    slot = {j["id"]: s for s, j in enumerate(jobs)}
    rem = np.zeros(n, dtype)
    cont = np.zeros(n, np.int64)
    paused = np.zeros(n, dtype)
    active = np.zeros(n, bool)
    submit = np.array([j["submit"] for j in jobs], dtype)
    work = np.array([j["work"] for j in jobs], dtype)
    t = dtype(0)
    ai = 0
    out: List[dict] = []

    def advance(t0, t1):
        if t1 <= t0:
            return
        lo = np.maximum(t0, np.minimum(paused, t1))
        np.copyto(rem, np.maximum(dtype(0), rem - (t1 - lo) * cont.astype(dtype)),
                  where=active)

    def next_completion():
        rate = cont.astype(dtype)
        with np.errstate(divide="ignore", invalid="ignore"):
            tf = np.where(active & (rate > 0),
                          np.maximum(t, paused) + rem / rate, np.inf)
        s = int(np.argmin(tf)) if n else 0
        return (tf[s], s) if n and np.isfinite(tf[s]) else (np.inf, None)

    for p in passes:
        t_arr = submit[ai] if ai < n else np.inf
        t_fin, fin = next_completion()
        t_next = min(t_arr, t_fin)
        if not np.isfinite(t_next):
            break
        advance(t, t_next)
        t = t_next
        t_end = t_next + dtype(window_s)
        comp: List[str] = []
        arr: List[str] = []
        while True:
            t_arr = submit[ai] if ai < n else np.inf
            t_fin, fin = next_completion()
            if min(t_arr, t_fin) > t_end:
                break
            if t_fin <= t_arr and fin is not None:
                advance(t, t_fin)
                t = t_fin
                active[fin] = False
                cont[fin] = 0
                comp.append(jobs[fin]["id"])
            else:
                advance(t, t_arr)
                t = t_arr
                rem[ai] = work[ai]
                cont[ai] = 0
                paused[ai] = 0
                active[ai] = True
                arr.append(jobs[ai]["id"])
                ai += 1
        out.append({"t": float(t), "completions": tuple(comp),
                    "arrivals": tuple(arr)})
        for a, c in p["changed"].items():
            s = slot[a]
            if active[s]:
                cont[s] = c
        for a in p["adjusted"]:
            s = slot[a]
            if active[s]:
                paused[s] = t + dtype(adjustment_cost_s)
    return out
