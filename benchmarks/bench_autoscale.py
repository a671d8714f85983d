"""Serving-workload autoscaling benchmark: diurnal + bursty QPS load on a
heterogeneous cluster, autoscaled bounds vs the static-bounds baseline.

TWO measured runs of the SAME trace (35% serving apps carrying
`ServingLoadProfile` QPS signals), both in ONE process -- compare only the
cross-run RATIOS, never absolute numbers across machines:

  * static bounds -- every serving app keeps its submission-time
    [n_min, n_max] for life (today's behaviour: resizes only happen when
    the optimizer reacts to arrivals/completions).
  * autoscaled    -- `autoscale.AutoscalePolicy` wraps the SAME DormMaster
    config; target-tracking control on runtime Ticks converts each app's
    QPS signal into `Resize` events (the optimizer still arbitrates).

Reported: Eq-1 utilization and Eq-2 fairness loss for both runs (the
acceptance ratio is utilization_autoscaled / utilization_static at equal or
better fairness), the SLO proxies (overload-seconds, scaling lag) and the
Eq-4 churn split by triggering event type. All simulation metrics are
deterministic -- only the wall-clock rows are machine-dependent.

Run:  PYTHONPATH=src python -m benchmarks.bench_autoscale \
          [--slaves 1000 --apps 500 --seed 0 --horizon-h 24 \
           --tick-s 300 --json BENCH_autoscale.json]
or:   PYTHONPATH=src python -m benchmarks.run autoscale
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

from repro.core import (AutoscaleConfig, AutoscalePolicy, ClusterRuntime,
                        DormMaster, OptimizerConfig, PolicyTimer,
                        RecordingProtocol, SLOMonitor, TraceConfig,
                        configure_compile_cache, fairness_budget,
                        generate_trace, heterogeneous_cluster,
                        signals_from_workload)

from .common import emit


def _trace_config(n_apps: int, seed: int,
                  mean_interarrival_s: float = 120.0) -> TraceConfig:
    """The serving-burst scenario: 35% serve-class arrivals, strong diurnal
    swing, hot mean load (bursts repeatedly exceed the spec bounds, so only
    runtime resizing can absorb them). Arrivals are paced so the cluster is
    loaded but not admission-wedged: the point is the scaling dynamics, not
    a standing queue."""
    return TraceConfig(
        n_apps=n_apps, seed=seed,
        mean_interarrival_s=mean_interarrival_s,
        diurnal_amplitude=0.7,
        serving_fraction=0.35,
        burst_prob=0.2,
        serve_lifetime=True,     # services live their duration; no speedup
        qps_mean_util=1.1,       # mean load ~ anchor capacity: bursts spill
        qps_burst_prob=0.5,
        qps_burst_mult=(2.0, 4.0),
    )


def _run_once(cluster, wl, signals, horizon_s: float, tick_s: float,
              theta1: float, theta2: float, autoscaled: bool,
              acfg: AutoscaleConfig):
    cfg = OptimizerConfig(theta1, theta2, warm_start=True,
                          auto_switch_vars=2_000, incremental=True, soa=True)
    master = DormMaster(cluster, "auto", cfg, protocol=RecordingProtocol())
    timer = PolicyTimer(master)
    policy = AutoscalePolicy(timer, signals, acfg) if autoscaled else timer
    rt = ClusterRuntime(policy, adjustment_cost_s=60.0, horizon_s=horizon_s,
                        batch_window_s=60.0, tick_interval_s=tick_s)
    if autoscaled:
        policy.attach(rt)
    monitor = SLOMonitor(signals, acfg).attach(rt)
    t0 = time.perf_counter()
    res = rt.run(wl)
    wall = time.perf_counter() - t0
    decisions = policy.decisions if autoscaled else []
    slo = monitor.summary(res.horizon_s, decisions)
    out = {
        "autoscaled": autoscaled,
        "wall_s": wall,
        "events": len(res.samples),
        "per_event_policy_ms_median": timer.median_ms(),
        "completed": sum(1 for r in res.completions.values()
                         if r.finished_at is not None),
        "util_mean": res.time_averaged_utilization(),
        "fairness_mean": res.time_averaged_fairness_loss(),
        "fairness_mean_event_weighted": res.mean_fairness_loss(),
        "fairness_max": res.max_fairness_loss(),
        "adjustments": res.total_adjustments,
        "decisions": len(decisions),
        "decisions_by_reason": (policy.decisions_by_reason()
                                if autoscaled else {}),
        **slo,
    }
    return out, res


def run(n_slaves: int = 1000, n_apps: int = 500, seed: int = 0,
        horizon_s: float = 24 * 3600.0, tick_s: float = 300.0,
        theta1: float = 0.2, theta2: float = 0.2,
        mean_interarrival_s: float = 120.0,
        json_path: str = "BENCH_autoscale.json"):
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    wl = generate_trace(_trace_config(n_apps, seed, mean_interarrival_s))
    signals = signals_from_workload(wl)
    # forward_ticks (the default): BOTH runs get the identical periodic
    # rebalance (the static run's ticks hit DormMaster.on_tick directly),
    # so the measured ratio isolates the autoscaling, not a lost cadence.
    acfg = AutoscaleConfig(forward_ticks=True)
    args = (horizon_s, tick_s, theta1, theta2)
    base, _ = _run_once(cluster, wl, signals, *args, False, acfg)
    auto, _ = _run_once(cluster, wl, signals, *args, True, acfg)

    util_ratio = auto["util_mean"] / max(base["util_mean"], 1e-9)
    overload_ratio = auto["overload_seconds_total"] / max(
        base["overload_seconds_total"], 1e-9)
    fairness_delta = auto["fairness_mean"] - base["fairness_mean"]
    # Acceptance: utilization strictly better at equal-or-better fairness
    # (equal = within 1% of the Eq-15 budget the optimizer itself enforces).
    budget_l = fairness_budget(
        OptimizerConfig(theta1, theta2), cluster.m)
    accept = (util_ratio > 1.0
              and fairness_delta <= 0.01 * budget_l)

    churn_auto = auto["churn_by_trigger"]
    rows = [
        ("autoscale.slaves", n_slaves, "count", ""),
        ("autoscale.apps", n_apps, "count",
         f"{len(signals)} serving apps with QPS signals"),
        ("autoscale.events_static", base["events"], "count", ""),
        ("autoscale.events_auto", auto["events"], "count",
         "includes tick-driven resizes"),
        ("autoscale.util_static", base["util_mean"], "sum-util", ""),
        ("autoscale.util_auto", auto["util_mean"], "sum-util", ""),
        ("autoscale.util_ratio", util_ratio, "x",
         "auto / static; the acceptance ratio"),
        ("autoscale.fairness_static", base["fairness_mean"], "loss", ""),
        ("autoscale.fairness_auto", auto["fairness_mean"], "loss",
         f"delta={fairness_delta:+.4f}"),
        ("autoscale.overload_static", base["overload_seconds_total"], "s",
         "serving time provisioned below load"),
        ("autoscale.overload_auto", auto["overload_seconds_total"], "s", ""),
        ("autoscale.overload_ratio", overload_ratio, "x",
         "auto / static; lower is better"),
        ("autoscale.scaling_lag", auto["scaling_lag_mean_s"], "s",
         f"{auto['scaleups_unresolved']} scale-ups unresolved"),
        ("autoscale.decisions", auto["decisions"], "count",
         str(auto["decisions_by_reason"]).replace(",", ";")),
        ("autoscale.adjustments_static", base["adjustments"], "count",
         "Eq-4 total"),
        ("autoscale.adjustments_auto", auto["adjustments"], "count",
         f"resize-attributed={churn_auto.get('Resize', 0)}"),
        ("autoscale.completed_static", base["completed"], "count",
         f"of {n_apps}"),
        ("autoscale.completed_auto", auto["completed"], "count",
         f"of {n_apps}"),
        ("autoscale.wall_auto", auto["wall_s"], "s", "end-to-end"),
        ("autoscale.accept", int(accept), "bool",
         f"util_ratio>1 and fairness delta <= 1% of Eq-15 budget "
         f"({budget_l:.2f})"),
    ]

    payload = {
        "config": {
            "slaves": n_slaves, "apps": n_apps, "seed": seed,
            "horizon_s": horizon_s, "tick_s": tick_s,
            "theta1": theta1, "theta2": theta2,
            "autoscale": dataclasses.asdict(acfg),
        },
        "static": base,
        "autoscaled": auto,
        "util_ratio": util_ratio,
        "overload_ratio": overload_ratio,
        "fairness_delta": fairness_delta,
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
    ap.add_argument("--slaves", type=int, default=1000)
    ap.add_argument("--apps", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon-h", type=float, default=24.0)
    ap.add_argument("--tick-s", type=float, default=300.0)
    ap.add_argument("--theta1", type=float, default=0.2)
    ap.add_argument("--theta2", type=float, default=0.2)
    ap.add_argument("--mean-interarrival-s", type=float, default=120.0)
    ap.add_argument("--json", default="BENCH_autoscale.json",
                    help="output path for the JSON report ('' disables)")
    args = ap.parse_args()
    configure_compile_cache()
    print("name,value,unit,notes")
    run(n_slaves=args.slaves, n_apps=args.apps, seed=args.seed,
        horizon_s=args.horizon_h * 3600.0, tick_s=args.tick_s,
        theta1=args.theta1, theta2=args.theta2,
        mean_interarrival_s=args.mean_interarrival_s, json_path=args.json)


if __name__ == "__main__":
    main()
