"""Sharded control-plane benchmark: N DormMaster shards + coordinator vs
the single global master, on the SAME trace in ONE process.

Two measured runs (never compare absolute milliseconds across machines,
only in-process ratios):

  * 1 shard  -- `ShardedControlPlane(n_shards=1)`: bit-exact pass-through
                to a single DormMaster (the PR-10 property suite pins
                this), so it IS the unsharded baseline;
  * K shards -- the same trace routed across K per-shard masters, each
                solving only its own slice, with the coordinator
                rebalancing on the runtime Tick stream (cross-shard
                migrations charged as forced Eq-4 churn).

The headline ratio is scheduler EVENT THROUGHPUT (events per policy
second -- wall time divided out of trace generation and progress
integration): `throughput_ratio` = (K-shard events/policy-s) / (1-shard
events/policy-s). Event counts differ between the runs (different
allocations => different completion times and coalescing), which is why
throughput, not total time, is the gated number.

Also recorded:

  * coordinator migrations + the forced-churn attribution split
    (`migrated` rides next to forced/voluntary/displaced/parked);
  * per-shard summaries incl. the backend="auto" dispatch each shard
    size resolves to (a 20k cluster and its 5k shards can land on
    different sides of the jax crossover);
  * a cross-shard optimality certificate at a colgen-feasible scale
    (`cross_shard_certificate`: certified global dual bound vs the
    shard-partitioned achieved objective, homogeneous instance so the
    per-shard dual bounds rescale exactly);
  * under `--xxl`, the 100k slaves x 50k apps acceptance run (K-shard
    only -- the single master does not finish this in sane time; the
    point is that the sharded plane completes end-to-end on one CPU
    box). The "xxl" JSON section is PRESERVED across reruns without
    `--xxl`, like bench_scale's xl keys.

Run:  PYTHONPATH=src python -m benchmarks.bench_shard \
          [--slaves 20000 --apps 8000 --shards 4 --seed 0 \
           --horizon-h 16 --mean-interarrival-s 4 --tick-interval-s 600 \
           --json BENCH_shard.json --xxl]
or as part of the harness:  PYTHONPATH=src python -m benchmarks.run shard
"""
from __future__ import annotations

import argparse
import json
import os
import time
from types import SimpleNamespace

from repro.core import (AbsorberConfig, ChaosConfig, ClusterRuntime,
                        ClusterSpec, Coordinator, OptimizerConfig, PolicyTimer,
                        Reallocated, ResourceVector, ShardConfig,
                        ShardedControlPlane, TraceConfig,
                        configure_compile_cache, cross_shard_certificate,
                        forced_churn_attribution, generate_trace,
                        heterogeneous_cluster)

from .common import emit


def _run_once(cluster, wl, n_shards: int, horizon_s: float,
              tick_interval_s: float, theta1: float, theta2: float,
              seed: int, chaos: bool = True, backend: str = "auto"):
    cfg = OptimizerConfig(theta1, theta2, incremental=True, soa=True,
                          backend=backend)
    plane = ShardedControlPlane(
        cluster,
        ShardConfig(n_shards=n_shards, rebalance_interval_s=tick_interval_s),
        optimizer_kind="greedy", optimizer_cfg=cfg)
    coord = Coordinator(plane)
    timer = PolicyTimer(plane)
    chaos_cfg = ChaosConfig(seed=seed, crashes_per_day=8.0, rack_size=4,
                            crash_restore_s=1800.0) if chaos else None
    # Windowed adaptive absorption: at 20k-slave scale the per-event path
    # would pay one solve per arrival in a 4s-interarrival flood; both the
    # 1-shard and K-shard runs share the config, so the ratio stays fair.
    rt = ClusterRuntime(timer, horizon_s=horizon_s,
                        tick_interval_s=tick_interval_s,
                        absorber=AbsorberConfig(window_s=30.0,
                                                adaptive=True),
                        chaos=chaos_cfg)
    coord.attach(rt)
    # Project each Reallocated down to the id tuples the churn attribution
    # reads: retaining the events whole would pin every solve's per-shard
    # allocation matrices for the run's lifetime (>100 GB at 100k x 50k).
    events = []

    def _keep_churn_fields(ev):
        r = ev.result
        events.append(SimpleNamespace(result=SimpleNamespace(
            forced_adjusted_app_ids=tuple(r.forced_adjusted_app_ids),
            adjusted_app_ids=tuple(r.adjusted_app_ids),
            displaced_app_ids=tuple(r.displaced_app_ids),
            parked_app_ids=tuple(r.parked_app_ids),
            migrated_app_ids=tuple(getattr(r, "migrated_app_ids", ())))))

    rt.bus.subscribe(Reallocated, _keep_churn_fields)
    t0 = time.perf_counter()
    res = rt.run(wl)
    wall = time.perf_counter() - t0
    policy_s = timer.total_s()
    return {
        "shards": n_shards,
        "backend": backend,
        "wall_s": wall,
        "events": len(res.samples),
        "policy_time_s": policy_s,
        "events_per_policy_s": len(res.samples) / max(policy_s, 1e-9),
        "per_event_policy_ms": timer.mean_ms(),
        "per_event_policy_ms_median": timer.median_ms(),
        "backend_compile_s": timer.compile_s,
        "completed": sum(1 for a in res.completions.values()
                         if a.finished_at is not None),
        "migrations": plane.migration_count,
        "coordinator_moves": len(coord.migrations),
        "forced_churn": forced_churn_attribution(events),
        "util_mean": res.time_averaged_utilization(),
        "fairness_mean": res.mean_fairness_loss(),
        "adjustments": res.total_adjustments,
        "phases_s": plane.phase_breakdown(),
        "shard_summaries": plane.shard_summaries(),
    }, res


def certificate_instance(n_slaves: int, n_apps: int, n_shards: int,
                         seed: int, theta1: float, theta2: float) -> dict:
    """Cross-shard optimality certificate on a colgen-feasible instance.

    Homogeneous cluster with b % K == 0 so the round-robin shards are
    proportional capacity slices -- the per-shard colgen dual bounds then
    rescale exactly and `sharded_bound`/`partition_gap` come back
    non-None alongside the always-available `cross_shard_gap`."""
    n_slaves -= n_slaves % n_shards
    cluster = ClusterSpec.homogeneous(n_slaves, ResourceVector.of(16, 4, 64))
    plane = ShardedControlPlane(
        cluster, ShardConfig(n_shards=n_shards), optimizer_kind="greedy",
        optimizer_cfg=OptimizerConfig(theta1, theta2))
    specs = tuple(w.spec for w in
                  generate_trace(TraceConfig(n_apps=n_apps, seed=seed)))
    plane.on_arrival(specs)
    t0 = time.perf_counter()
    cert = cross_shard_certificate(
        plane, OptimizerConfig(theta1, theta2, time_limit_s=60.0))
    cert["solve_s"] = time.perf_counter() - t0
    cert["slaves"] = n_slaves
    cert["shards"] = n_shards
    return cert


def run(n_slaves: int = 20_000, n_apps: int = 8_000, seed: int = 0,
        n_shards: int = 4, horizon_s: float = 16 * 3600.0,
        mean_interarrival_s: float = 4.0, tick_interval_s: float = 600.0,
        theta1: float = 0.2, theta2: float = 0.2,
        cert_slaves: int = 128, cert_apps: int = 24,
        json_path: str = "BENCH_shard.json", xxl: bool = False):
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    wl = generate_trace(TraceConfig(n_apps=n_apps, seed=seed,
                                    mean_interarrival_s=mean_interarrival_s))
    args = (horizon_s, tick_interval_s, theta1, theta2, seed)
    one, _ = _run_once(cluster, wl, 1, *args)
    many, _ = _run_once(cluster, wl, n_shards, *args)
    ratio = many["events_per_policy_s"] / max(one["events_per_policy_s"],
                                              1e-9)
    cert = certificate_instance(cert_slaves, cert_apps, n_shards, seed,
                                theta1, theta2)

    # NOTE: notes must stay comma-free -- common.emit writes unquoted CSV.
    dispatches = "/".join(s.get("auto_dispatch", {}).get("placement", "?")
                          for s in many["shard_summaries"])
    rows = [
        ("shard.slaves", n_slaves, "count", ""),
        ("shard.apps", n_apps, "count", ""),
        ("shard.shards", n_shards, "count", "K-shard run"),
        ("shard.wall_1shard", one["wall_s"], "s", "end-to-end"),
        ("shard.wall_kshard", many["wall_s"], "s", "end-to-end"),
        ("shard.events_1shard", one["events"], "count", ""),
        ("shard.events_kshard", many["events"], "count", ""),
        ("shard.policy_ms_1shard", one["per_event_policy_ms"], "ms",
         "per-event mean; single master"),
        ("shard.policy_ms_kshard", many["per_event_policy_ms"], "ms",
         f"per-event mean; {n_shards} shards"),
        ("shard.throughput_1shard", one["events_per_policy_s"], "1/s",
         "events per policy second"),
        ("shard.throughput_kshard", many["events_per_policy_s"], "1/s",
         "events per policy second"),
        ("shard.throughput_ratio", ratio, "x",
         f"{n_shards}-shard over 1-shard event throughput"),
        ("shard.migrations", many["migrations"], "count",
         "coordinator cross-shard moves applied"),
        ("shard.migrated_churn", many["forced_churn"]["migrated"], "count",
         "Eq-4 attribution of the moves"),
        ("shard.completed_1shard", one["completed"], "count",
         f"of {n_apps}"),
        ("shard.completed_kshard", many["completed"], "count",
         f"of {n_apps}"),
        ("shard.util_mean_1shard", one["util_mean"], "sum-util", ""),
        ("shard.util_mean_kshard", many["util_mean"], "sum-util", ""),
        ("shard.auto_dispatch", 0, "", f"per-shard placement: {dispatches}"),
        ("shard.cert_gap", cert["cross_shard_gap"], "frac",
         f"certified cross-shard loss at {cert['slaves']}x"
         f"{int(cert['n_apps'])}"),
        ("shard.cert_partition_gap", cert["partition_gap"], "frac",
         "partition ceiling vs global dual bound"),
    ]

    payload = {
        "config": {
            "slaves": n_slaves, "apps": n_apps, "seed": seed,
            "shards": n_shards, "horizon_s": horizon_s,
            "mean_interarrival_s": mean_interarrival_s,
            "tick_interval_s": tick_interval_s,
            "theta1": theta1, "theta2": theta2,
        },
        "one_shard": one,
        "k_shard": many,
        "throughput_ratio": ratio,
        "certificate": cert,
    }

    # Preserve a previously recorded acceptance run: --xxl is a one-off
    # (an hour-scale run), reruns without it must not erase the record.
    if json_path and os.path.exists(json_path):
        try:
            with open(json_path) as fh:
                prev = json.load(fh)
            if "xxl" in prev and not xxl:
                payload["xxl"] = prev["xxl"]
        except (OSError, ValueError):
            pass

    if xxl:
        xxl_slaves, xxl_apps, xxl_shards = 100_000, 50_000, 8
        xxl_cluster = heterogeneous_cluster(xxl_slaves, seed=seed)
        xxl_wl = generate_trace(TraceConfig(n_apps=xxl_apps, seed=seed,
                                            mean_interarrival_s=1.0))
        xxl_res, _ = _run_once(xxl_cluster, xxl_wl, xxl_shards,
                               24 * 3600.0, tick_interval_s,
                               theta1, theta2, seed)
        payload["xxl"] = {
            "config": {"slaves": xxl_slaves, "apps": xxl_apps,
                       "shards": xxl_shards, "seed": seed,
                       "horizon_s": 24 * 3600.0,
                       "mean_interarrival_s": 1.0},
            **xxl_res,
        }
    if "xxl" in payload:
        x = payload["xxl"]
        rows += [
            ("shard.xxl_wall", x["wall_s"], "s",
             f"{x['config']['slaves']}x{x['config']['apps']} end-to-end; "
             f"{x['config']['shards']} shards"),
            ("shard.xxl_events", x["events"], "count", ""),
            ("shard.xxl_completed", x["completed"], "count",
             f"of {x['config']['apps']}"),
            ("shard.xxl_migrations", x["migrations"], "count", ""),
        ]

    emit(rows)
    if json_path:
        with open(json_path, "w") as fh:
            json.dump(payload, fh, indent=2)
            fh.write("\n")
    return rows


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--slaves", type=int, default=20_000)
    ap.add_argument("--apps", type=int, default=8_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--horizon-h", type=float, default=16.0)
    ap.add_argument("--mean-interarrival-s", type=float, default=4.0)
    ap.add_argument("--tick-interval-s", type=float, default=600.0)
    ap.add_argument("--theta1", type=float, default=0.2)
    ap.add_argument("--theta2", type=float, default=0.2)
    ap.add_argument("--cert-slaves", type=int, default=128)
    ap.add_argument("--cert-apps", type=int, default=24)
    ap.add_argument("--xxl", action="store_true",
                    help="also run the 100k x 50k acceptance configuration")
    ap.add_argument("--json", default="BENCH_shard.json",
                    help="output path for the JSON report ('' disables)")
    args = ap.parse_args()
    configure_compile_cache()
    print("name,value,unit,notes")
    run(n_slaves=args.slaves, n_apps=args.apps, seed=args.seed,
        n_shards=args.shards, horizon_s=args.horizon_h * 3600.0,
        mean_interarrival_s=args.mean_interarrival_s,
        tick_interval_s=args.tick_interval_s,
        theta1=args.theta1, theta2=args.theta2,
        cert_slaves=args.cert_slaves, cert_apps=args.cert_apps,
        json_path=args.json, xxl=args.xxl)


if __name__ == "__main__":
    main()
