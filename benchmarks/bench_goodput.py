"""Goodput-aware allocation benchmark: count-linear vs knee-aware targets
on a curved configs-registry workload.

TWO measured runs of the SAME trace (train jobs carry roofline-derived
`GoodputCurve`s over the configs registry; MoE models saturate early,
dense models late), both in ONE process -- compare only the cross-run
RATIOS, never absolute numbers across machines:

  * count-linear  -- `OptimizerConfig(goodput_aware=False)`: the seed's
    behaviour; the optimizer values every container at 1.0 and fills each
    app to n_max. Progress still follows the TRUE curves, so containers
    granted past a knee are (correctly) near-worthless.
  * goodput-aware -- `goodput_aware=True`: the greedy/DRF path caps each
    curved app's fill target at its curve's knee, and the freed containers
    go to apps whose marginal goodput is still high.

Reported: time-averaged cluster goodput sum_i goodput_i(N_i) (the tentpole
metric), Eq-1 utilization, Eq-2 fairness loss, completions and mean
completion time. Acceptance: goodput strictly better at equal-or-better
Eq-2 fairness (equal = within 1% of the Eq-15 budget the optimizer itself
enforces). All simulation metrics are deterministic.

Run:  PYTHONPATH=src python -m benchmarks.bench_goodput \
          [--slaves 200 --apps 160 --seed 0 --horizon-h 24 \
           --json BENCH_goodput.json]
or:   PYTHONPATH=src python -m benchmarks.run goodput
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core import (ClusterSimulator, DormMaster, OptimizerConfig,
                        RecordingProtocol, TraceConfig,
                        configure_compile_cache, fairness_budget,
                        generate_trace, heterogeneous_cluster)

from .common import emit


def _trace_config(n_apps: int, seed: int,
                  mean_interarrival_s: float = 90.0) -> TraceConfig:
    """The contention scenario the knee matters in: all-train arrivals
    (every job curved over the registry round-robin), paced so apps
    overlap and the cluster stays contended -- with slack capacity the
    linear policy's past-the-knee grants cost nobody anything."""
    return TraceConfig(
        n_apps=n_apps, seed=seed,
        mean_interarrival_s=mean_interarrival_s,
        diurnal_amplitude=0.5,
        serving_fraction=0.0,           # train-class only: every job curved
        goodput_curves=True,
    )


def _run_once(cluster, wl, horizon_s: float, theta1: float, theta2: float,
              goodput_aware: bool):
    cfg = OptimizerConfig(theta1, theta2, warm_start=True,
                          incremental=True, soa=True,
                          goodput_aware=goodput_aware)
    master = DormMaster(cluster, "greedy", cfg, protocol=RecordingProtocol())
    sim = ClusterSimulator(master, wl, adjustment_cost_s=60.0,
                           horizon_s=horizon_s)
    t0 = time.perf_counter()
    res = sim.run()
    wall = time.perf_counter() - t0
    done = [r for r in res.completions.values() if r.finished_at is not None]
    durs = [r.finished_at - r.submitted_at for r in done]
    return {
        "goodput_aware": goodput_aware,
        "wall_s": wall,
        "events": len(res.samples),
        "completed": len(done),
        "goodput_mean": res.time_averaged_goodput(),
        "util_mean": res.time_averaged_utilization(),
        "fairness_mean": res.time_averaged_fairness_loss(),
        "fairness_max": res.max_fairness_loss(),
        "adjustments": res.total_adjustments,
        "completion_time_mean_s": float(np.mean(durs)) if durs else 0.0,
    }


def run(n_slaves: int = 200, n_apps: int = 160, seed: int = 0,
        horizon_s: float = 24 * 3600.0,
        theta1: float = 0.2, theta2: float = 0.2,
        mean_interarrival_s: float = 90.0,
        json_path: str = "BENCH_goodput.json"):
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    wl = generate_trace(_trace_config(n_apps, seed, mean_interarrival_s))
    args = (horizon_s, theta1, theta2)
    lin = _run_once(cluster, wl, *args, False)
    gp = _run_once(cluster, wl, *args, True)

    goodput_ratio = gp["goodput_mean"] / max(lin["goodput_mean"], 1e-9)
    fairness_delta = gp["fairness_mean"] - lin["fairness_mean"]
    ct_ratio = (gp["completion_time_mean_s"]
                / max(lin["completion_time_mean_s"], 1e-9))
    budget_l = fairness_budget(OptimizerConfig(theta1, theta2), cluster.m)
    accept = (goodput_ratio > 1.0
              and fairness_delta <= 0.01 * budget_l)

    rows = [
        ("goodput.slaves", n_slaves, "count", ""),
        ("goodput.apps", n_apps, "count", "all train-class, all curved"),
        ("goodput.events_linear", lin["events"], "count", ""),
        ("goodput.events_aware", gp["events"], "count", ""),
        ("goodput.goodput_linear", lin["goodput_mean"], "container-eq",
         "time-averaged sum_i goodput_i(N_i)"),
        ("goodput.goodput_aware", gp["goodput_mean"], "container-eq", ""),
        ("goodput.goodput_ratio", goodput_ratio, "x",
         "aware / linear; the acceptance ratio"),
        ("goodput.util_linear", lin["util_mean"], "sum-util", ""),
        ("goodput.util_aware", gp["util_mean"], "sum-util",
         "Eq-1 counts containers; knee-capped fills can only lower it"),
        ("goodput.fairness_linear", lin["fairness_mean"], "loss", ""),
        ("goodput.fairness_aware", gp["fairness_mean"], "loss",
         f"delta={fairness_delta:+.4f}"),
        ("goodput.completion_time_linear",
         lin["completion_time_mean_s"], "s", ""),
        ("goodput.completion_time_aware",
         gp["completion_time_mean_s"], "s",
         f"ratio={ct_ratio:.3f} (lower is better)"),
        ("goodput.completed_linear", lin["completed"], "count",
         f"of {n_apps}"),
        ("goodput.completed_aware", gp["completed"], "count",
         f"of {n_apps}"),
        ("goodput.adjustments_linear", lin["adjustments"], "count",
         "Eq-4 total"),
        ("goodput.adjustments_aware", gp["adjustments"], "count", ""),
        ("goodput.wall_aware", gp["wall_s"], "s", "end-to-end"),
        ("goodput.accept", int(accept), "bool",
         f"goodput_ratio>1 and fairness delta <= 1% of Eq-15 budget "
         f"({budget_l:.2f})"),
    ]

    payload = {
        "config": {
            "slaves": n_slaves, "apps": n_apps, "seed": seed,
            "horizon_s": horizon_s, "theta1": theta1, "theta2": theta2,
            "mean_interarrival_s": mean_interarrival_s,
        },
        "linear": lin,
        "aware": gp,
        "goodput_ratio": goodput_ratio,
        "fairness_delta": fairness_delta,
        "completion_time_ratio": ct_ratio,
        "accept": accept,
    }
    emit(rows)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slaves", type=int, default=200)
    ap.add_argument("--apps", type=int, default=160)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon-h", type=float, default=24.0)
    ap.add_argument("--theta1", type=float, default=0.2)
    ap.add_argument("--theta2", type=float, default=0.2)
    ap.add_argument("--mean-interarrival-s", type=float, default=90.0)
    ap.add_argument("--json", default="BENCH_goodput.json",
                    help="output path for the JSON report ('' disables)")
    args = ap.parse_args()
    configure_compile_cache()
    print("name,value,unit,notes")
    run(n_slaves=args.slaves, n_apps=args.apps, seed=args.seed,
        horizon_s=args.horizon_h * 3600.0,
        theta1=args.theta1, theta2=args.theta2,
        mean_interarrival_s=args.mean_interarrival_s, json_path=args.json)


if __name__ == "__main__":
    main()
