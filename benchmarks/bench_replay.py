"""Replay-driven XL benchmark: real-trace-schema workloads at 5000 slaves
x 2000 jobs (closes the measured-bench half of the ROADMAP's "replay-driven
XL benchmarks" item).

Three measurements over ONE replayed Philly-schema trace (synthetic by
default -- fractional per-container demands, served by the delta fast
path since the free-capacity vector is canonicalized on every solve
path, exactly like tests/test_replay_xl.py -- or a real log via
--trace):

  * runtime replay -- the full event-driven simulation through
    `ClusterRuntime` with the event-storm absorber engaged
    (`AbsorberConfig(window_s=--batch-window-s)`: mixed arrival +
    completion + resize floods coalesce into one policy pass each) and
    bench_scale-style timing (PolicyTimer charges every event of an
    absorbed pass the whole pass; absorbed-event fraction and the
    batch-size histogram are reported),
  * matched-scale synthetic trace -- the same cluster and scheduler over
    a `generate_trace` workload of the same size, closing the ROADMAP
    gate "replay per-event median within ~2x of the synthetic-trace
    median at matched scale",
  * exact static solve -- the column-generation optimizer driven from the
    replayed instance (every replayed job as one app), reporting its
    CERTIFIED optimality gap and solve seconds next to the greedy
    heuristic on the same instance in the same process.

Run:  PYTHONPATH=src python -m benchmarks.bench_replay \
          [--slaves 5000 --apps 2000 --seed 0 --horizon-h 96 \
           --batch-window-s 60 --theta1 0.2 --theta2 0.2 \
           --trace philly.csv --fmt philly --colgen-apps 2000 \
           --json BENCH_replay.json]
or as part of the harness:  PYTHONPATH=src python -m benchmarks.run replay

CI runs a scaled-down smoke (see .github/workflows/ci.yml); like every
BENCH_*.json the report is a local artifact, never committed.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import (AbsorberConfig, ClusterSimulator, DormMaster,
                        GreedyOptimizer, OptimizerConfig, PolicyTimer,
                        Reallocated, RecordingProtocol, TraceConfig,
                        configure_compile_cache, container_churn,
                        generate_trace, heterogeneous_cluster, make_optimizer,
                        replay_trace, resource_utilization)

from .common import emit


def synthetic_philly_csv(n_jobs: int, seed: int = 0) -> str:
    """Philly-schema rows with deliberately fractional per-container
    demands (num_cpus/mem_gb not divisible by num_gpus) -- the same recipe
    as tests/test_replay_xl.py, at benchmark scale."""
    rng = np.random.default_rng(seed)
    lines = ["jobid,submitted_time,run_time,num_gpus,num_cpus,mem_gb"]
    t = 0.0
    for j in range(n_jobs):
        t += float(rng.exponential(90.0))
        n_gpus = int(rng.integers(1, 9))
        run_time = float(rng.uniform(600.0, 7200.0))
        n_cpus = n_gpus * 3 + 1          # 3 + 1/n_gpus cpus per container
        mem = n_gpus * 20 + 5            # 20 + 5/n_gpus GB per container
        lines.append(f"job-{j:05d},{t:.1f},{run_time:.1f},"
                     f"{n_gpus},{n_cpus},{mem}")
    return "\n".join(lines) + "\n"


def _drive(cluster, wl, horizon_s: float, window_s: float,
           theta1: float, theta2: float):
    """One absorber-engaged runtime drive; returns per-run stats."""
    cfg = OptimizerConfig(theta1, theta2, warm_start=True, incremental=True)
    master = DormMaster(cluster, "auto", cfg, protocol=RecordingProtocol())
    timer = PolicyTimer(master)
    sim = ClusterSimulator(timer, wl, adjustment_cost_s=60.0,
                           horizon_s=horizon_s,
                           absorber=AbsorberConfig(window_s=window_s))
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
    ab = sim.runtime.absorber_stats
    stats = {
        "wall_s": wall,
        "events": ab["events"],
        "events_per_s": ab["events"] / max(wall, 1e-9),
        "policy_time_s": timer.total_s(),
        "per_event_policy_ms": timer.mean_ms(),
        "per_event_policy_ms_median": timer.median_ms(),
        "completed": sum(1 for rt in res.completions.values()
                         if rt.finished_at is not None),
        "util_mean": res.time_averaged_utilization(),
        "fairness_mean": res.mean_fairness_loss(),
        "adjustments": res.total_adjustments,
        "container_churn": churn["total"],
        "delta_solves": greedy.delta_solves,
        "full_solves": greedy.full_solves,
        "absorber": {
            "passes": ab["passes"],
            "batches": ab["batches"],
            "absorbed_events": ab["absorbed_events"],
            "absorbed_fraction": (ab["absorbed_events"]
                                  / max(ab["events"], 1)),
            "batch_hist": {str(k): v for k, v
                           in sorted(ab["batch_hist"].items())},
        },
    }
    return stats


def run(n_slaves: int = 5000, n_apps: int = 2000, seed: int = 0,
        trace: str = "", fmt: str = "philly",
        horizon_s: float = 96 * 3600.0, batch_window_s: float = 60.0,
        theta1: float = 0.2, theta2: float = 0.2,
        colgen_apps: int = 0,
        json_path: str = "BENCH_replay.json"):
    wl = replay_trace(trace or synthetic_philly_csv(n_apps, seed), fmt=fmt)
    cluster = heterogeneous_cluster(n_slaves, seed=seed)

    # -- runtime replay (the measured 5000x2000 half of the ROADMAP item),
    # with the storm absorber coalescing mixed event floods.
    replay_stats = _drive(cluster, wl, horizon_s, batch_window_s,
                          theta1, theta2)

    # -- matched-scale synthetic trace: same cluster, same scheduler, same
    # absorber window, `generate_trace` workload of the same size -- the
    # denominator of the ROADMAP's replay-within-2x gate.
    syn_wl = generate_trace(TraceConfig(n_apps=len(wl), seed=seed,
                                        mean_interarrival_s=90.0))
    synthetic_stats = _drive(cluster, syn_wl, horizon_s, batch_window_s,
                             theta1, theta2)
    median_ratio = (replay_stats["per_event_policy_ms_median"]
                    / max(synthetic_stats["per_event_policy_ms_median"],
                          1e-9))
    replay_stats["vs_synthetic_median"] = median_ratio

    # -- exact static solve of the replayed instance: colgen's certified
    # gap vs the greedy heuristic, back to back in THIS process.
    specs = [w.spec for w in wl][:colgen_apps or len(wl)]
    col = make_optimizer("colgen", OptimizerConfig(
        theta1, theta2, time_limit_s=120.0))
    t0 = time.perf_counter()
    alloc_c = col.solve(specs, cluster, None)
    colgen_stats = {
        "apps": len(specs),
        "solve_s": time.perf_counter() - t0,
        "utilization": resource_utilization(alloc_c, specs, cluster)
        if alloc_c is not None else None,
        "certified_gap": col.last_gap,
        "bound": col.last_bound,
        "pricing_iters": col.colgen_iters,
        "columns": col.colgen_columns,
    }
    gr = GreedyOptimizer(OptimizerConfig(theta1, theta2))
    t0 = time.perf_counter()
    alloc_g = gr.solve(specs, cluster, None)
    greedy_stats = {
        "solve_s": time.perf_counter() - t0,
        "utilization": resource_utilization(alloc_g, specs, cluster)
        if alloc_g is not None else None,
    }
    colgen_stats["util_vs_greedy"] = (
        colgen_stats["utilization"] / greedy_stats["utilization"]
        if colgen_stats["utilization"] and greedy_stats["utilization"]
        else None)

    rows = [
        ("replay.slaves", n_slaves, "count", ""),
        ("replay.apps", len(wl), "count",
         "synthetic philly" if not trace else f"fmt={fmt}"),
        ("replay.wall", replay_stats["wall_s"], "s", "end-to-end"),
        ("replay.events", replay_stats["events"], "count", ""),
        ("replay.policy_ms", replay_stats["per_event_policy_ms"], "ms",
         "per-event scheduling time"),
        ("replay.policy_ms_median",
         replay_stats["per_event_policy_ms_median"], "ms", ""),
        ("replay.completed", replay_stats["completed"], "count",
         f"of {len(wl)}"),
        ("replay.full_solves", replay_stats["full_solves"], "count",
         "first event + churny events re-solve in full"),
        ("replay.delta_solves", replay_stats["delta_solves"], "count",
         "fractional demands ride the canonicalized delta path"),
        ("replay.container_churn", replay_stats["container_churn"],
         "count", ""),
        ("replay.absorbed_fraction",
         replay_stats["absorber"]["absorbed_fraction"], "frac",
         f"{replay_stats['absorber']['batches']} batches absorbed "
         f"{replay_stats['absorber']['absorbed_events']} events"),
        ("replay.synthetic_policy_ms_median",
         synthetic_stats["per_event_policy_ms_median"], "ms",
         f"matched-scale generate_trace ({len(syn_wl)} apps)"),
        ("replay.vs_synthetic_median", median_ratio, "x",
         "ROADMAP gate: <= 2x synthetic median at matched scale"),
        ("replay.colgen_solve_s", colgen_stats["solve_s"], "s",
         f"{colgen_stats['apps']} replayed apps; static instance"),
        ("replay.colgen_gap", colgen_stats["certified_gap"], "frac",
         "certified global optimality gap"),
        ("replay.colgen_util_vs_greedy",
         colgen_stats["util_vs_greedy"], "x",
         f"greedy solve {greedy_stats['solve_s']:.3f}s same instance"),
    ]
    emit(rows)

    payload = {
        "config": {"slaves": n_slaves, "apps": len(wl), "seed": seed,
                   "trace": trace or "synthetic", "fmt": fmt,
                   "horizon_s": horizon_s,
                   "batch_window_s": batch_window_s,
                   "theta1": theta1, "theta2": theta2},
        "replay": replay_stats,
        "synthetic": synthetic_stats,
        "colgen": colgen_stats,
        "greedy": greedy_stats,
    }
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slaves", type=int, default=5000)
    ap.add_argument("--apps", type=int, default=2000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default="",
                    help="path to a real trace CSV ('' = synthetic)")
    ap.add_argument("--fmt", default="philly",
                    choices=("philly", "alibaba", "generic"))
    ap.add_argument("--horizon-h", type=float, default=96.0)
    ap.add_argument("--batch-window-s", type=float, default=60.0)
    ap.add_argument("--theta1", type=float, default=0.2)
    ap.add_argument("--theta2", type=float, default=0.2)
    ap.add_argument("--colgen-apps", type=int, default=0,
                    help="cap the static colgen instance (0 = all apps)")
    ap.add_argument("--json", default="BENCH_replay.json",
                    help="output path for the JSON report ('' disables)")
    args = ap.parse_args()
    configure_compile_cache()
    print("name,value,unit,notes")
    run(n_slaves=args.slaves, n_apps=args.apps, seed=args.seed,
        trace=args.trace, fmt=args.fmt, horizon_s=args.horizon_h * 3600.0,
        batch_window_s=args.batch_window_s,
        theta1=args.theta1, theta2=args.theta2,
        colgen_apps=args.colgen_apps, json_path=args.json)


if __name__ == "__main__":
    main()
