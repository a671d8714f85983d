"""The one traffic generator: a cluster and its job stream from a
configuration file, a traffic-mix file and a seed.

Copied from `repro.core.workload` (`heterogeneous_cluster`,
`generate_trace`) and generalised so that every number comes from the two
files. One change in kind: the seed only reorders. Every seed gets the same
multiset of slaves, of arrival gaps, of job classes, of burst sizes and of
durations (stratified quantiles of the configured distributions), and the
seed permutes which job gets which. So two seeds offer the same work in a
different order, and a run-to-run spread is not a change of workload.

Output is plain data (numpy arrays and dicts); `driver.py` turns it into
the program's types, and `reference.py` reads it as it is.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np
from scipy.special import ndtri


def flavor_counts(config: dict) -> List[int]:
    """Slaves of each flavor. Flavors carry either an exact `count` or a
    `weight`: weights are floored into counts of `slaves` and the
    remainder goes to the first flavor (as `heterogeneous_cluster` does)."""
    cl = config["cluster"]
    flavors = cl["flavors"]
    if all("count" in f for f in flavors):
        return [int(f["count"]) for f in flavors]
    n = int(cl["slaves"])
    w = np.asarray([f["weight"] for f in flavors], np.float64)
    w = w / w.sum()
    counts = np.floor(w * n).astype(np.int64).tolist()
    counts[0] += n - int(sum(counts))
    return counts


def build_cluster(config: dict, seed: int) -> dict:
    """-> {"ids": [...], "cap": (b, m) float64, "resources": (...)}.
    The seed shuffles slave order only."""
    flavors = config["cluster"]["flavors"]
    counts = flavor_counts(config)
    order: List[int] = []
    for fi, c in enumerate(counts):
        order.extend([fi] * c)
    rng = np.random.default_rng([seed, 1])
    flavor = np.asarray(order, np.int64)
    rng.shuffle(flavor)
    cap = np.asarray([flavors[f]["capacity"] for f in flavor], np.float64)
    ids = [f"slave-{j:05d}" for j in range(len(flavor))]
    return {"ids": ids, "cap": cap,
            "resources": tuple(config["cluster"]["resources"])}


# ------------------------------------------------------------- durations

def _quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of a duration distribution at probabilities `u`.

    `lognormal_range` is `generate_trace`'s shape: median at the geometric
    midpoint of [lo, hi], sigma a quarter of the log range, clipped to it.
    `lognormal_median` is a median, a sigma and a clip range."""
    z = ndtri(np.asarray(u, np.float64))
    kind = dist["dist"]
    if kind == "lognormal_range":
        lo, hi = float(dist["lo"]), float(dist["hi"])
        mu = 0.5 * (math.log(lo) + math.log(hi))
        sigma = (math.log(hi) - math.log(lo)) / 4.0
    elif kind == "lognormal_median":
        lo, hi = float(dist["lo"]), float(dist["hi"])
        mu, sigma = math.log(float(dist["median"])), float(dist["sigma"])
    else:
        raise ValueError(f"unknown duration distribution {kind!r}")
    return np.clip(np.exp(mu + sigma * z), lo, hi)


def mean_duration(dist: dict, n: int = 200_000) -> float:
    """Mean of the clipped distribution (stratified, as the jobs draw it)."""
    return float(_quantile(dist, (np.arange(n) + 0.5) / n).mean())


def _residual_quantile(dist: dict, u: np.ndarray, grid: int = 20_000,
                       ) -> np.ndarray:
    """Inverse CDF of the equilibrium residual life R of a job in progress
    (renewal theory: F_R(r) = int_0^r S(x) dx / E[D]), on a log grid."""
    lo, hi = float(dist["lo"]), float(dist["hi"])
    pts = (np.arange(grid) + 0.5) / grid
    d = np.sort(_quantile(dist, pts))
    r = np.concatenate(([0.0], np.geomspace(lo * 1e-3, hi, 4096)))
    # S(r) = P(D > r), from the stratified sample of D.
    surv = 1.0 - np.searchsorted(d, r, side="right") / d.size
    integ = np.concatenate(([0.0], np.cumsum(
        0.5 * (surv[1:] + surv[:-1]) * np.diff(r))))
    cdf = integ / integ[-1]
    return np.interp(u, cdf, r)


def _stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """n stratified probabilities (i + 0.5) / n in a seeded order."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def _apportion(shares: List[float], n: int) -> List[int]:
    """Largest-remainder split of n items by `shares`."""
    w = np.asarray(shares, np.float64)
    w = w / w.sum()
    raw = w * n
    cnt = np.floor(raw).astype(np.int64)
    rest = n - int(cnt.sum())
    for i in np.argsort(-(raw - cnt), kind="stable")[:rest]:
        cnt[i] += 1
    return cnt.tolist()


# ------------------------------------------------------------------ load

def _anchor(rule: str, c: dict) -> int:
    """Containers at which a class's sampled duration is its run time:
    `class` takes the class's own `anchor` (a recorded size, such as the
    paper's static baseline counts), `midpoint` the middle of [n_min,
    n_max], `n_max` its top."""
    if rule == "class":
        return int(c["anchor"])
    if rule == "midpoint":
        return max(1, (int(c["n_min"]) + int(c["n_max"])) // 2)
    if rule == "n_max":
        return int(c["n_max"])
    raise ValueError(f"unknown anchor rule {rule!r}")


def _job_mix(config: dict, traffic: dict) -> List[tuple]:
    """-> [(probability, class, mean duration, anchor)] of one job."""
    kinds = config["jobs"]["kinds"]
    groups = traffic["groups"]
    per_kind: Dict[str, float] = {}
    for g in groups:
        per_kind[g["kind"]] = per_kind.get(g["kind"], 0.0) + \
            g["share"] * float(np.mean(g["sizes"]))
    total = sum(per_kind.values())
    out = []
    for kind, w in per_kind.items():
        kc = kinds[kind]
        shares = np.asarray([c.get("share", 1.0) for c in kc["classes"]])
        shares = shares / shares.sum()
        dur = mean_duration(kc["duration_s"])
        for c, s in zip(kc["classes"], shares):
            out.append((w / total * float(s), c, dur,
                        _anchor(config["jobs"]["anchor"], c)))
    return out


def offered_jobs_per_s(config: dict, traffic: dict) -> float:
    """Job arrival rate at the traffic's `offered_load`: the rate at which
    the mean work a job brings (its duration times its anchor, in
    containers, times each container's demand) fills that share of the
    cluster's total of its most loaded resource."""
    cap = np.asarray([f["capacity"] for f in config["cluster"]["flavors"]],
                     np.float64)
    total = (np.asarray(flavor_counts(config), np.float64)[:, None]
             * cap).sum(axis=0)
    work = sum(p * dur * a * np.asarray(c["demand"], np.float64)
               for p, c, dur, a in _job_mix(config, traffic))
    pos = total > 0
    return float(traffic["arrivals"]["offered_load"]
                 / (work[pos] / total[pos]).max())


def resident_jobs(config: dict, traffic: dict) -> int:
    """Jobs in progress at the cluster's steady occupancy when every job
    runs at n_max (true below saturation): arrival rate times the mean time
    a job takes at n_max."""
    t_at_max = sum(p * dur * a / int(c["n_max"])
                   for p, c, dur, a in _job_mix(config, traffic))
    return int(round(offered_jobs_per_s(config, traffic) * t_at_max))


# --------------------------------------------------------------- arrivals

def _warp(tau: np.ndarray, amplitude: float, period: float) -> np.ndarray:
    """Real time t with Lambda(t) = tau for the diurnal rate
    lambda(t) = lambda0 (1 + A sin(2 pi t / P)) (tau in units of 1/lambda0):
    t + A P / (2 pi) (1 - cos(2 pi t / P)) = tau, solved by bisection."""
    if amplitude == 0.0:
        return tau.copy()
    lo = tau / (1.0 + amplitude)
    hi = tau / (1.0 - amplitude)
    c = amplitude * period / (2.0 * math.pi)
    for _ in range(64):
        mid = 0.5 * (lo + hi)
        f = mid + c * (1.0 - np.cos(2.0 * math.pi * mid / period)) - tau
        lo = np.where(f < 0.0, mid, lo)
        hi = np.where(f < 0.0, hi, mid)
    return 0.5 * (lo + hi)


def build_jobs(config: dict, traffic: dict, seed: int) -> List[Dict]:
    """-> jobs in submit order, each {"id", "submit", "demand", "weight",
    "n_min", "n_max", "work", "kind", "cls", "duration"}.

    Arrival instants follow the traffic's (diurnal) Poisson process, at a
    written mean gap or at the rate of an `offered_load`; each
    instant is a group of one kind and a size (bursts put several jobs at
    one timestamp). `resident` jobs are already in progress at the start:
    they arrive spread over `ramp_s` with residual durations drawn from the
    equilibrium residual-life distribution, so the cluster starts near its
    steady occupancy instead of empty (`"count": "auto"`: the occupancy
    `resident_jobs` works out)."""
    jobs_cfg = config["jobs"]
    kinds = jobs_cfg["kinds"]
    anchor_rule = jobs_cfg["anchor"]
    rng = np.random.default_rng([seed, 2])
    n_apps = int(traffic["n_apps"])

    # -- groups: (kind, size) per arrival instant, fixed composition.
    groups = traffic["groups"]
    mean_size = sum(g["share"] * float(np.mean(g["sizes"])) for g in groups)
    n_inst = int(math.ceil(n_apps / mean_size))
    per_group = _apportion([g["share"] for g in groups], n_inst)
    inst: List[tuple] = []
    for g, cnt in zip(groups, per_group):
        sizes = g["sizes"]
        inst.extend((g["kind"], int(sizes[i % len(sizes)]))
                    for i in range(cnt))
    inst = [inst[i] for i in rng.permutation(len(inst))]

    # -- instant times: exponential gap quantiles, seeded order, warped.
    arr = traffic["arrivals"]
    if "offered_load" in arr:
        mean_gap = mean_size / offered_jobs_per_s(config, traffic)
    else:
        mean_gap = float(arr["mean_interarrival_s"])
    gaps = -np.log1p(-_stratified(len(inst), rng)) * mean_gap
    t_inst = _warp(np.cumsum(gaps), float(arr.get("diurnal_amplitude", 0.0)),
                   float(arr.get("diurnal_period_s", 86400.0)))

    resident = traffic.get("resident")
    n_res = 0
    if resident:
        n_res = (resident_jobs(config, traffic) if resident["count"] == "auto"
                 else int(resident["count"]))
    # -- expand instants into job slots (truncated at n_apps).
    slots: List[tuple] = []                   # (kind, submit, resident?)
    for i in range(n_res):
        slots.append(("__resident__", float(resident["ramp_s"])
                      * (i + 0.5) / n_res, True))
    for (kind, size), t in zip(inst, t_inst):
        for _ in range(size):
            if len(slots) - n_res >= n_apps:
                break
            slots.append((kind, float(t), False))

    res_kind = resident["kind"] if resident else None
    by_kind: Dict[str, List[int]] = {}
    for s, (kind, _, res) in enumerate(slots):
        by_kind.setdefault(res_kind if res else kind, []).append(s)

    jobs: List[Dict] = [None] * len(slots)     # type: ignore[list-item]
    for kind, members in by_kind.items():
        kc = kinds[kind]
        classes = kc["classes"]
        n_k = len(members)
        shares = [c.get("share", 1.0) for c in classes]
        cls_list: List[int] = []
        for ci, cnt in enumerate(_apportion(shares, n_k)):
            cls_list.extend([ci] * cnt)
        cls_list = [cls_list[i] for i in rng.permutation(n_k)]
        is_res = [slots[s][2] for s in members]
        n_fresh = n_k - sum(is_res)
        dur_fresh = _quantile(kc["duration_s"], _stratified(n_fresh, rng)) \
            if n_fresh else np.zeros(0)
        n_res_k = n_k - n_fresh
        dur_res = _residual_quantile(kc["duration_s"],
                                     _stratified(n_res_k, rng)) \
            if n_res_k else np.zeros(0)
        fi = ri = 0
        for s, ci, res in zip(members, cls_list, is_res):
            c = classes[ci]
            if res:
                dur = float(dur_res[ri])
                ri += 1
            else:
                dur = float(dur_fresh[fi])
                fi += 1
            n_min, n_max = int(c["n_min"]), int(c["n_max"])
            anchor = _anchor(anchor_rule, c)
            jobs[s] = {
                "kind": kind, "cls": c["name"], "submit": slots[s][1],
                "demand": tuple(float(v) for v in c["demand"]),
                "weight": int(c.get("weight", 1)),
                "n_min": n_min, "n_max": n_max,
                "duration": dur, "work": dur * anchor,
                "executor": c.get("executor", kind),
            }
    order = sorted(range(len(jobs)), key=lambda s: (jobs[s]["submit"], s))
    out = []
    for slot, s in enumerate(order):
        j = jobs[s]
        j["id"] = f"job-{slot:05d}-{j['cls']}"
        out.append(j)
    return out
