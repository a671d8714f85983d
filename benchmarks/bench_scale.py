"""Large-scale simulation benchmark: Dorm on heterogeneous clusters under
diurnal/bursty traces, driven through the shared `repro.core.runtime` loop.

FOUR measured runs of the SAME trace, all in ONE process (never compare
absolute milliseconds across runs/machines -- only in-process ratios):

  * soa incremental    -- PR-3 structure-of-arrays engine + delta solve
  * legacy incremental -- PR-2 dict-of-objects engine (the golden baseline
                          kept behind `OptimizerConfig(soa=False)`)
  * soa full re-solve  -- the seed's full per-event re-solve semantics
  * jax incremental    -- the SoA engine on `OptimizerConfig(backend=
                          "jax")` (jit/lax scheduler kernels)

All allocation timelines must be bit-exact (the SoA engine, the delta
path and the jax backend are pure optimizations); the per-event
policy-time ratios are:

  * `incremental_speedup` = full / soa-incremental
  * `soa_speedup`         = legacy-incremental / soa-incremental
  * `jax_median_ratio`    = jax-incremental / soa-incremental (<= 1 means
                            jax wins; `PolicyTimer` charges first-touch
                            jit compiles to the events that paid them and
                            reports their seconds as `backend_compile`)

Ratios are reported from per-event MEDIANS (robust to OS jitter; means
are recorded too). Results go to stdout as CSV rows and to
`BENCH_scale.json` (machine-readable perf trajectory across PRs),
including the per-phase breakdown (DRF refill vs solve vs enforce vs
metrics vs backend compile).

Run:  PYTHONPATH=src python -m benchmarks.bench_scale \
          [--slaves 1000 --apps 500 --seed 0 --horizon-h 24 \
           --batch-window-s 60 --mean-interarrival-s 60 \
           --theta1 0.2 --theta2 0.2 --json BENCH_scale.json --xl]
or as part of the harness:  PYTHONPATH=src python -m benchmarks.run scale

`--xl` additionally runs the 5000 slaves x 2000 apps configuration
(SoA incremental, on the numpy AND jax backends -- the point is that both
complete end-to-end on CPU) and records them under the "xl" / "xl_jax"
keys of the JSON report, with the post-compile median ratio under
"xl_jax_median_ratio".
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core import (AutoBackend, ClusterSimulator, DormMaster,
                        MilpOptimizer, OptimizerConfig, PolicyTimer,
                        Reallocated, RecordingProtocol, TraceConfig,
                        configure_compile_cache, container_churn,
                        generate_trace, heterogeneous_cluster,
                        resource_utilization)

from .common import emit


def _run_once(cluster, wl, incremental: bool, horizon_s: float,
              batch_window_s: float, theta1: float, theta2: float,
              auto_switch_vars: int, soa: bool = True,
              backend: str = "numpy"):
    cfg = OptimizerConfig(theta1, theta2, warm_start=True,
                          auto_switch_vars=auto_switch_vars,
                          incremental=incremental, soa=soa,
                          backend=backend)
    master = DormMaster(cluster, "auto", cfg, protocol=RecordingProtocol())
    timer = PolicyTimer(master)
    sim = ClusterSimulator(timer, wl, adjustment_cost_s=60.0,
                           horizon_s=horizon_s,
                           batch_window_s=batch_window_s)
    churn = {"total": 0, "last": None}

    def on_realloc(ev):
        churn["total"] += container_churn(churn["last"],
                                          ev.result.allocation)
        churn["last"] = ev.result.allocation

    sim.runtime.bus.subscribe(Reallocated, on_realloc)
    t0 = time.perf_counter()
    res = sim.run()
    wall = time.perf_counter() - t0
    greedy = master.optimizer._greedy
    return {
        "engine": "soa" if soa else "legacy",
        "backend": backend,
        "incremental": incremental,
        "backend_compile_s": timer.compile_s,
        "wall_s": wall,
        "events": len(res.samples),
        "events_per_s": len(res.samples) / max(wall, 1e-9),
        "policy_time_s": timer.total_s(),
        "per_event_policy_ms": timer.mean_ms(),
        "per_event_policy_ms_median": timer.median_ms(),
        "phases_s": master.phase_breakdown(),
        "completed": sum(1 for rt in res.completions.values()
                         if rt.finished_at is not None),
        "util_mean": res.time_averaged_utilization(),
        "fairness_mean": res.mean_fairness_loss(),
        "fairness_max": res.max_fairness_loss(),
        "adjustments": res.total_adjustments,
        "container_churn": churn["total"],
        "delta_solves": greedy.delta_solves,
        "full_solves": greedy.full_solves,
        "drf_fast_hits": greedy.drf.fast_hits,
        "drf_full_refills": greedy.drf.full_refills,
    }, res


def exact_head_to_head(n_slaves: int, n_apps: int, seed: int,
                       theta1: float, theta2: float,
                       time_limit_s: float = 60.0) -> dict:
    """ONE static instance solved by the three exact routes: monolithic
    MILP (certified via HiGHS's dual bound), rolling horizon (block-exact,
    no global certificate) and column generation (certified via the master
    LP bound). Sized so the monolithic grid stays tractable; the solvers
    run in THIS process back to back, so the solve-second columns are
    comparable to each other (never across machines)."""
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    apps = [w.spec for w in
            generate_trace(TraceConfig(n_apps=n_apps, seed=seed))]
    n, b = len(apps), cluster.b
    variants = {
        "monolithic": OptimizerConfig(theta1, theta2, rolling_horizon_vars=0,
                                      time_limit_s=time_limit_s),
        "rolling": OptimizerConfig(theta1, theta2,
                                   rolling_horizon_vars=max(b + 1,
                                                            n * b // 4),
                                   time_limit_s=time_limit_s),
        "colgen": OptimizerConfig(theta1, theta2, column_generation=True,
                                  time_limit_s=time_limit_s),
    }
    out: dict = {"slaves": n_slaves, "apps": n_apps, "vars": n * b}
    for name, cfg in variants.items():
        opt = MilpOptimizer(cfg)
        t0 = time.perf_counter()
        alloc = opt.solve(apps, cluster, None)
        out[name] = {
            "solve_s": time.perf_counter() - t0,
            "utilization": resource_utilization(alloc, apps, cluster)
            if alloc is not None else None,
            "certified_gap": opt.last_gap,
            "bound": opt.last_bound,
        }
    mono_u = out["monolithic"]["utilization"]
    for name in ("rolling", "colgen"):
        u = out[name]["utilization"]
        out[name]["util_vs_monolithic"] = \
            (u / mono_u) if (u and mono_u) else None
    return out


def same_timeline(a, b, exact_metrics: bool = True) -> bool:
    """Same event times/counts/durations; metric floats compared exactly or
    to 1e-9 (the SoA engine sums Eq-2 with pairwise float reduction, which
    can differ from the legacy sequential sum in the last ulp)."""
    if len(a.samples) != len(b.samples) or a.durations() != b.durations():
        return False
    for sa, sb in zip(a.samples, b.samples):
        if exact_metrics:
            if sa != sb:
                return False
        elif (sa.t != sb.t or sa.running != sb.running
              or sa.pending != sb.pending
              or sa.adjustment_overhead != sb.adjustment_overhead
              or abs(sa.utilization - sb.utilization) > 1e-9
              or abs(sa.fairness_loss - sb.fairness_loss) > 1e-9):
            return False
    return True


def run(n_slaves: int = 1000, n_apps: int = 500, seed: int = 0,
        horizon_s: float = 24 * 3600.0, batch_window_s: float = 60.0,
        mean_interarrival_s: float = 60.0,
        theta1: float = 0.2, theta2: float = 0.2,
        auto_switch_vars: int = 2_000,
        json_path: str = "BENCH_scale.json",
        xl: bool = False):
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    wl = generate_trace(TraceConfig(n_apps=n_apps, seed=seed,
                                    mean_interarrival_s=mean_interarrival_s))
    args = (horizon_s, batch_window_s, theta1, theta2, auto_switch_vars)
    inc, res_inc = _run_once(cluster, wl, True, *args, soa=True)
    leg, res_leg = _run_once(cluster, wl, True, *args, soa=False)
    full, res_full = _run_once(cluster, wl, False, *args, soa=True)
    jx, res_jx = _run_once(cluster, wl, True, *args, soa=True,
                           backend="jax")
    bit_exact = same_timeline(res_inc, res_full)
    bit_exact_engines = same_timeline(res_inc, res_leg,
                                      exact_metrics=False)
    bit_exact_jax = same_timeline(res_inc, res_jx)
    speedup = full["per_event_policy_ms_median"] / max(
        inc["per_event_policy_ms_median"], 1e-9)
    soa_speedup = leg["per_event_policy_ms_median"] / max(
        inc["per_event_policy_ms_median"], 1e-9)
    jax_ratio = (jx["per_event_policy_ms_median"]
                 / max(inc["per_event_policy_ms_median"], 1e-9))

    # NOTE: notes must stay comma-free -- common.emit writes unquoted CSV.
    phases = inc["phases_s"]
    rows = [
        ("scale.slaves", n_slaves, "count", ""),
        ("scale.apps", n_apps, "count", ""),
        ("scale.wall", inc["wall_s"], "s", "end-to-end; soa incremental"),
        ("scale.events", inc["events"], "count", "reallocation events"),
        ("scale.events_per_s", inc["events_per_s"], "1/s", ""),
        ("scale.policy_ms", inc["per_event_policy_ms"], "ms",
         "per-event scheduling time; soa incremental"),
        ("scale.policy_ms_median", inc["per_event_policy_ms_median"], "ms",
         "median per-event; soa incremental"),
        ("scale.policy_ms_legacy", leg["per_event_policy_ms"], "ms",
         "per-event scheduling time; PR-2 object engine"),
        ("scale.policy_ms_full", full["per_event_policy_ms"], "ms",
         "per-event scheduling time; full re-solve"),
        ("scale.incremental_speedup", speedup, "x",
         f"median ratio; bit_exact={bit_exact}"),
        ("scale.soa_speedup", soa_speedup, "x",
         f"median ratio vs legacy engine; bit_exact={bit_exact_engines}"),
        ("scale.phase_drf_refill", phases["drf_refill"], "s",
         "cumulative; soa incremental"),
        ("scale.phase_solve", phases["solve"], "s", "cumulative"),
        ("scale.phase_enforce", phases["enforce"], "s", "cumulative"),
        ("scale.phase_metrics", phases["metrics"], "s", "cumulative"),
        ("scale.phase_backend_compile", phases["backend_compile"], "s",
         "cumulative; 0 on the numpy backend"),
        ("scale.delta_solves", inc["delta_solves"], "count",
         f"of {inc['delta_solves'] + inc['full_solves']} greedy solves"),
        ("scale.drf_fast_hits", inc["drf_fast_hits"], "count",
         f"vs {inc['drf_full_refills']} full refills"),
        ("scale.completed", inc["completed"], "count", f"of {n_apps}"),
        ("scale.util_mean", inc["util_mean"], "sum-util", ""),
        ("scale.fairness_mean", inc["fairness_mean"], "loss", ""),
        ("scale.fairness_max", inc["fairness_max"], "loss", ""),
        ("scale.adjustments", inc["adjustments"], "count", "Eq-4 total"),
        ("scale.container_churn", inc["container_churn"], "count",
         "containers created+destroyed"),
    ]
    rows += [
        ("scale.policy_ms_jax_median",
         jx["per_event_policy_ms_median"], "ms",
         "median per-event; jax backend; compiles included"),
        ("scale.jax_median_ratio", jax_ratio, "x",
         f"jax/numpy per-event medians; bit_exact={bit_exact_jax}"),
        ("scale.jax_compile_s", jx["backend_compile_s"], "s",
         "cumulative first-touch jit compile time"),
    ]

    # backend="auto" crossover record: the dispatcher's live thresholds and
    # which delegate it picks at this scale and at xl (5000x2000) -- the
    # measured basis for AUTO_CROSSOVER_* lives in the jax/numpy median
    # ratios above (and xl_jax_median_ratio below under --xl).
    auto_be = AutoBackend()
    backend_auto = {
        "crossover_slaves": auto_be.crossover_slaves,
        "crossover_apps": auto_be.crossover_apps,
        "picks_at_bench_scale": auto_be._pick(
            n_slaves, auto_be.crossover_slaves).name,
        "picks_at_xl_scale": auto_be._pick(
            5000, auto_be.crossover_slaves).name,
    }
    rows += [
        ("scale.auto_crossover_slaves", auto_be.crossover_slaves, "count",
         f"auto picks {backend_auto['picks_at_bench_scale']} at "
         f"{n_slaves} slaves / {backend_auto['picks_at_xl_scale']} at xl"),
    ]

    # Exact-solver head-to-head (monolithic vs rolling vs colgen) on ONE
    # static instance small enough for the monolithic grid: the certified
    # gaps and solve-time columns land in the JSON report and the colgen
    # gap is gated by `scripts/check.sh --bench` / the CI bench smoke.
    exact = exact_head_to_head(min(n_slaves, 60), min(n_apps, 40),
                               seed, theta1, theta2)
    rows += [
        ("scale.exact_vars", exact["vars"], "count",
         f"{exact['slaves']}x{exact['apps']} head-to-head instance"),
        ("scale.exact_mono_solve_s", exact["monolithic"]["solve_s"], "s",
         f"certified gap {exact['monolithic']['certified_gap']}"),
        ("scale.exact_rolling_solve_s", exact["rolling"]["solve_s"], "s",
         f"util vs mono {exact['rolling']['util_vs_monolithic']}; no "
         f"global certificate"),
        ("scale.exact_colgen_solve_s", exact["colgen"]["solve_s"], "s",
         f"util vs mono {exact['colgen']['util_vs_monolithic']}"),
        ("scale.exact_colgen_gap", exact["colgen"]["certified_gap"], "frac",
         "certified global optimality gap"),
    ]

    payload = {
        "config": {
            "slaves": n_slaves, "apps": n_apps, "seed": seed,
            "horizon_s": horizon_s, "batch_window_s": batch_window_s,
            "mean_interarrival_s": mean_interarrival_s,
            "theta1": theta1, "theta2": theta2,
            "auto_switch_vars": auto_switch_vars,
        },
        "incremental": inc,
        "legacy_incremental": leg,
        "full_resolve": full,
        "jax_incremental": jx,
        "incremental_speedup": speedup,
        "soa_speedup": soa_speedup,
        "jax_median_ratio": jax_ratio,
        "timeline_bit_exact": bit_exact,
        "timeline_bit_exact_vs_legacy_engine": bit_exact_engines,
        "timeline_bit_exact_vs_jax": bit_exact_jax,
        "backend_auto": backend_auto,
        "exact_solvers": exact,
    }

    if xl:
        xl_slaves, xl_apps = 5000, 2000
        xl_cluster = heterogeneous_cluster(xl_slaves, seed=seed)
        xl_wl = generate_trace(TraceConfig(
            n_apps=xl_apps, seed=seed, mean_interarrival_s=30.0))
        xl_res, _ = _run_once(xl_cluster, xl_wl, True, horizon_s,
                              batch_window_s, theta1, theta2,
                              auto_switch_vars, soa=True)
        payload["xl"] = {
            "config": {"slaves": xl_slaves, "apps": xl_apps, "seed": seed,
                       "horizon_s": horizon_s,
                       "batch_window_s": batch_window_s,
                       "mean_interarrival_s": 30.0},
            **xl_res,
        }
        rows += [
            ("scale.xl_wall", xl_res["wall_s"], "s",
             f"{xl_slaves}x{xl_apps} end-to-end; soa incremental"),
            ("scale.xl_policy_ms", xl_res["per_event_policy_ms"], "ms",
             f"{xl_slaves}x{xl_apps} per-event"),
            ("scale.xl_events", xl_res["events"], "count", ""),
            ("scale.xl_completed", xl_res["completed"], "count",
             f"of {xl_apps}"),
        ]
        xl_jax, _ = _run_once(xl_cluster, xl_wl, True, horizon_s,
                              batch_window_s, theta1, theta2,
                              auto_switch_vars, soa=True,
                              backend="jax")
        xl_ratio = (xl_jax["per_event_policy_ms_median"]
                    / max(xl_res["per_event_policy_ms_median"], 1e-9))
        payload["xl_jax"] = xl_jax
        payload["xl_jax_median_ratio"] = xl_ratio
        rows += [
            ("scale.xl_jax_policy_ms_median",
             xl_jax["per_event_policy_ms_median"], "ms",
             f"{xl_slaves}x{xl_apps} per-event median; jax backend"),
            ("scale.xl_jax_median_ratio", xl_ratio, "x",
             "jax/numpy per-event medians at xl; compiles included"),
            ("scale.xl_jax_compile_s", xl_jax["backend_compile_s"],
             "s", "cumulative first-touch jit compile time"),
            ("scale.xl_jax_completed", xl_jax["completed"], "count",
             f"of {xl_apps}"),
        ]

    emit(rows)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slaves", type=int, default=1000)
    ap.add_argument("--apps", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon-h", type=float, default=24.0)
    ap.add_argument("--batch-window-s", type=float, default=60.0)
    ap.add_argument("--mean-interarrival-s", type=float, default=60.0)
    ap.add_argument("--theta1", type=float, default=0.2)
    ap.add_argument("--theta2", type=float, default=0.2)
    ap.add_argument("--auto-switch-vars", type=int, default=2_000)
    ap.add_argument("--xl", action="store_true",
                    help="also run the 5000x2000 configuration")
    ap.add_argument("--json", default="BENCH_scale.json",
                    help="output path for the JSON report ('' disables)")
    args = ap.parse_args()
    configure_compile_cache()
    print("name,value,unit,notes")
    run(n_slaves=args.slaves, n_apps=args.apps, seed=args.seed,
        horizon_s=args.horizon_h * 3600.0,
        batch_window_s=args.batch_window_s,
        mean_interarrival_s=args.mean_interarrival_s,
        theta1=args.theta1, theta2=args.theta2,
        auto_switch_vars=args.auto_switch_vars,
        json_path=args.json, xl=args.xl)


if __name__ == "__main__":
    main()
