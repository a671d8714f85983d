"""Fault-injection benchmark: DormMaster vs Static vs Tetris vs DRF under
the SAME seeded failure replay (PR-8 robustness panel).

One `chaos.ChaosConfig` schedule -- correlated rack crashes, drains and
stragglers drawn from a seeded Poisson process -- is replayed against all
four cluster managers (Tetris is the alignment-score packer of Grandl et
al. with non-strict FCFS admission, static partitions like Static). A
`chaos.ChaosMonitor` on each run's bus computes the recovery panel:

  * `recovery_median_s` -- failure to every-displaced-app-running-again
    (parked apps keep the clock open: parking is surrender, not recovery),
  * `lost_capacity_seconds` -- integral of the fenced Eq-1 capacity
    fraction over each run's span (the loss-rate schedule is
    policy-independent; only the endpoint -- when the run drains -- moves
    it between schedulers),
  * `replaced_fraction` -- displaced apps that eventually ran again (or
    finished) over all displaced; gated > 0.95 by `scripts/check.sh
    --bench`,
  * forced vs voluntary Eq-4 churn -- what the failures made the
    scheduler do vs what it chose to do.

Dorm runs the greedy optimizer: chaos rescales slaves to zero capacity,
and the auto policy's late-run MILP solves on such degenerate clusters
are minutes-slow without changing the recovery semantics under test.

Determinism: the replay is pinned by (seed, ChaosConfig) alone --
`SimResult.chaos_seed` / `.chaos_config_hash` land in the JSON artifact,
and rebuilding the config from those fields reproduces the run bit-exact
(see examples/chaos_replay.py).

Run:  PYTHONPATH=src python -m benchmarks.bench_chaos \
          [--slaves 1000 --apps 500 --seed 0 --horizon-h 24 \
           --json BENCH_chaos.json]
or as part of the harness:  PYTHONPATH=src python -m benchmarks.run chaos
"""
from __future__ import annotations

import argparse
import json
import time

from repro.core import (ChaosConfig, ChaosMonitor, ClusterSimulator,
                        DormMaster, DRFScheduler, OptimizerConfig, Reallocated,
                        RecordingProtocol, StaticScheduler, TetrisScheduler,
                        TraceConfig, chaos_config_hash, chaos_schedule,
                        configure_compile_cache, container_churn,
                        generate_trace, heterogeneous_cluster)

from .common import emit


def default_chaos(seed: int) -> ChaosConfig:
    """The benchmark's failure model: ~one rack crash per hour-ish
    (correlated: a whole rack_size group dies at one instant), occasional
    drains, and a straggler tail degraded to half speed."""
    return ChaosConfig(seed=seed, crashes_per_day=24.0, rack_size=8,
                       crash_restore_s=2 * 3600.0, drains_per_day=6.0,
                       drain_restore_s=3600.0, straggler_frac=0.05,
                       degrade_factor=0.5, degrade_duration_s=3600.0)


def _run_once(name: str, scheduler, cluster, wl, chaos, horizon_s: float):
    mon = ChaosMonitor(cluster)
    sim = ClusterSimulator(scheduler, wl, adjustment_cost_s=60.0,
                           horizon_s=horizon_s, chaos=chaos)
    mon.attach(sim.runtime)
    churn = {"total": 0, "last": None}

    def on_realloc(ev):
        churn["total"] += container_churn(churn["last"],
                                          ev.result.allocation)
        churn["last"] = ev.result.allocation

    sim.runtime.bus.subscribe(Reallocated, on_realloc)
    t0 = time.perf_counter()
    res = sim.run()
    wall = time.perf_counter() - t0
    mon.finalize(res.horizon_s)
    return {
        "scheduler": name,
        "wall_s": wall,
        "events": len(res.samples),
        "completed": sum(1 for rt in res.completions.values()
                         if rt.finished_at is not None),
        "util_mean": res.time_averaged_utilization(),
        "fairness_mean": res.mean_fairness_loss(),
        "adjustments": res.total_adjustments,
        "forced_adjustments": res.total_forced_adjustments,
        "container_churn": churn["total"],
        "chaos_seed": res.chaos_seed,
        "chaos_config_hash": res.chaos_config_hash,
        "recovery": mon.summary(),
    }, res


def run(n_slaves: int = 1000, n_apps: int = 500, seed: int = 0,
        horizon_s: float = 24 * 3600.0,
        mean_interarrival_s: float = 60.0,
        theta1: float = 0.2, theta2: float = 0.2,
        json_path: str = "BENCH_chaos.json"):
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    wl = generate_trace(TraceConfig(n_apps=n_apps, seed=seed,
                                    mean_interarrival_s=mean_interarrival_s))
    chaos = default_chaos(seed)
    schedule = chaos_schedule(chaos, cluster, horizon_s)

    def dorm():
        cfg = OptimizerConfig(theta1, theta2, warm_start=True,
                              incremental=True, soa=True)
        return DormMaster(cluster, "greedy", cfg,
                          protocol=RecordingProtocol())

    # Static partitions at each app's n_max (the scale trace's class
    # indices outrun the Table-II BASELINE_STATIC_CONTAINERS list).
    static = {w.spec.app_id: w.spec.n_max for w in wl}
    runs = {}
    for name, sched in (("dorm", dorm()),
                        ("static", StaticScheduler(cluster, static)),
                        ("tetris", TetrisScheduler(cluster, static)),
                        ("drf", DRFScheduler(cluster))):
        runs[name], _ = _run_once(name, sched, cluster, wl, chaos,
                                  horizon_s)

    # NOTE: notes must stay comma-free -- common.emit writes unquoted CSV.
    rows = [
        ("chaos.slaves", n_slaves, "count", ""),
        ("chaos.apps", n_apps, "count", ""),
        ("chaos.schedule_events", len(schedule), "count",
         f"hash {chaos_config_hash(chaos)}"),
    ]
    for name, r in runs.items():
        rec = r["recovery"]
        med = rec["recovery_median_s"]
        rows += [
            (f"chaos.{name}_wall", r["wall_s"], "s", "end-to-end"),
            (f"chaos.{name}_completed", r["completed"], "count",
             f"of {n_apps}"),
            (f"chaos.{name}_util_mean", r["util_mean"], "sum-util", ""),
            (f"chaos.{name}_fairness_mean", r["fairness_mean"], "loss", ""),
            (f"chaos.{name}_forced_adjustments", r["forced_adjustments"],
             "count", f"of {r['adjustments']} Eq-4 total"),
            (f"chaos.{name}_displaced", rec["displaced"], "count",
             f"parked {rec['parked']}"),
            (f"chaos.{name}_replaced_fraction", rec["replaced_fraction"],
             "frac", "displaced apps that ran again or finished"),
            (f"chaos.{name}_recovery_median", med if med is not None
             else "", "s", f"{rec['recovery_events']} closed windows"),
            (f"chaos.{name}_lost_capacity", rec["lost_capacity_seconds"],
             "eq1-s", "schedule-determined; endpoint is the run's end"),
        ]

    payload = {
        "config": {
            "slaves": n_slaves, "apps": n_apps, "seed": seed,
            "horizon_s": horizon_s,
            "mean_interarrival_s": mean_interarrival_s,
            "theta1": theta1, "theta2": theta2,
            "chaos": {k: getattr(chaos, k)
                      for k in ChaosConfig.__dataclass_fields__},
            "chaos_config_hash": chaos_config_hash(chaos),
            "schedule_events": len(schedule),
        },
        **runs,
    }
    emit(rows)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")
    return payload


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slaves", type=int, default=1000)
    ap.add_argument("--apps", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon-h", type=float, default=24.0)
    ap.add_argument("--mean-interarrival-s", type=float, default=60.0)
    ap.add_argument("--theta1", type=float, default=0.2)
    ap.add_argument("--theta2", type=float, default=0.2)
    ap.add_argument("--json", default="BENCH_chaos.json",
                    help="output path for the JSON report ('' disables)")
    args = ap.parse_args()
    configure_compile_cache()
    print("name,value,unit,notes")
    run(n_slaves=args.slaves, n_apps=args.apps, seed=args.seed,
        horizon_s=args.horizon_h * 3600.0,
        mean_interarrival_s=args.mean_interarrival_s,
        theta1=args.theta1, theta2=args.theta2, json_path=args.json)


if __name__ == "__main__":
    main()
