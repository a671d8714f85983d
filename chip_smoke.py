"""Chip smoke: Dorm's scheduling engine end to end on one TPU.

Default run (one chip): the 5000-slave x 2000-app deployment of
`benchmarks/bench_scale.py --xl` (`heterogeneous_cluster` +
`generate_trace`, seed 0, 30 s mean inter-arrival, 24 h horizon) goes
through `ClusterRuntime` with the storm absorber on (60 s window, as the
replay benchmark runs it) and a `DormMaster` whose optimizer runs on
`OptimizerConfig(backend="jax")`; on a TPU that engine places containers
with the compiled Pallas best-fit kernel. The same trace then goes through
the numpy engine, the reference. The run fails unless:

  * every app completes on both engines,
  * every applied allocation respects capacity and n_min <= count <= n_max,
  * the jax engine's timeline equals the numpy engine's, event for event,
  * the compiled `place_run` program holds the Pallas kernel
    (`tpu_custom_call`).

`--four-chips` runs only the live-partition path across four chips: a
`DormMaster` drives two `ElasticTrainer`s through `ElasticJaxProtocol`;
an arrival forces a save -> kill -> resume of the first trainer onto a
different chip group, and its loss must match the same trainer run without
interruption on one chip, while the two trainers never share a chip.

The script exits non-zero, and prints no result line, when jax finds no
TPU. Its last line on success is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.

Run:  python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks.bench_scale import same_timeline  # noqa: E402
from repro.core import (AbsorberConfig, ApplicationSpec,  # noqa: E402
                        ClusterRuntime, ClusterSpec, DormMaster,
                        OptimizerConfig, Reallocated, RecordingProtocol,
                        ResourceVector, TraceConfig, configure_compile_cache,
                        generate_trace, heterogeneous_cluster,
                        validate_allocation)
from repro.core.telemetry import compile_counter  # noqa: E402

# Loss agreement between a trainer resharded across chip groups and the
# same trainer on one chip: f32 training whose only difference is the
# order of the data-parallel gradient reduction.
LOSS_RTOL = 1e-3


def _run_engine(backend: str, cluster, wl, horizon_s: float) -> dict:
    """One absorber-engaged runtime drive; checks every applied allocation
    against capacity and the apps' bounds."""
    cfg = OptimizerConfig(0.2, 0.2, warm_start=True, incremental=True,
                          backend=backend)
    master = DormMaster(cluster, "auto", cfg, protocol=RecordingProtocol())
    runtime = ClusterRuntime(master, adjustment_cost_s=60.0,
                             horizon_s=horizon_s,
                             absorber=AbsorberConfig(window_s=60.0))
    specs = {w.spec.app_id: w.spec for w in wl}
    violations: list = []
    fingerprints: list = []

    def check(ev) -> None:
        alloc = ev.result.allocation
        try:
            validate_allocation(alloc, [specs[a] for a in alloc.app_ids],
                                cluster)
        except ValueError as exc:
            violations.append(f"t={ev.t}: {exc}")
        digest = hashlib.blake2b(alloc.x.tobytes(), digest_size=16)
        digest.update("\0".join(alloc.app_ids).encode())
        fingerprints.append((ev.t, digest.hexdigest()))

    runtime.bus.subscribe(Reallocated, check)
    t0 = time.perf_counter()
    res = runtime.run(wl)
    wall = time.perf_counter() - t0
    completed = sum(1 for rt in res.completions.values()
                    if rt.finished_at is not None)
    return {"res": res, "backend": master.optimizer.backend, "wall_s": wall,
            "completed": completed, "violations": violations,
            "fingerprints": fingerprints}


def scheduler_phase(n_slaves: int = 5000, n_apps: int = 2000,
                    seed: int = 0, horizon_s: float = 24 * 3600.0) -> list:
    """The one-chip smoke. -> list of failures (empty = passed)."""
    cluster = heterogeneous_cluster(n_slaves, seed=seed)
    wl = generate_trace(TraceConfig(n_apps=n_apps, seed=seed,
                                    mean_interarrival_s=30.0))
    print(f"deployment: {n_slaves} slaves x {n_apps} apps, seed {seed}, "
          f"absorber window 60 s", flush=True)
    runs = {}
    for backend in ("jax", "numpy"):
        r = _run_engine(backend, cluster, wl, horizon_s)
        runs[backend] = r
        print(f"engine {backend}: {len(r['res'].samples)} events, "
              f"{r['completed']}/{n_apps} apps completed, "
              f"{len(r['violations'])} invariant violations, "
              f"wall {r['wall_s']:.3f} s (context only)", flush=True)
    be = runs["jax"]["backend"]
    compiles = compile_counter()
    print(f"jax engine: Pallas placement kernel "
          f"{'on' if be.use_pallas else 'off'}; compile "
          f"{be.compile_s:.3f} s ({compiles.cache_hits} program(s) loaded "
          f"from the persistent cache)", flush=True)
    for name in sorted(compiles.count):
        print(f"  compile {name}: {compiles.count[name]} program(s), "
              f"{compiles.seconds[name]:.3f} s", flush=True)

    failures = []
    for backend, r in runs.items():
        if r["completed"] != n_apps:
            failures.append(f"{backend}: {r['completed']}/{n_apps} apps "
                            f"completed")
        failures += [f"{backend}: {v}" for v in r["violations"][:5]]
    exact = same_timeline(runs["numpy"]["res"], runs["jax"]["res"])
    # Finer than the samples: the per-slave allocation of every event.
    fj, fn = runs["jax"]["fingerprints"], runs["numpy"]["fingerprints"]
    first = next((i for i, (a, b) in enumerate(zip(fj, fn)) if a != b),
                 None if len(fj) == len(fn) else min(len(fj), len(fn)))
    print(f"timelines bit-exact: {exact}; per-slave allocations equal on "
          f"every event: {first is None}"
          + ("" if first is None else f" (first differs at event {first} "
             f"of {len(fn)})"), flush=True)
    if not exact:
        failures.append("timelines differ between the jax and numpy engines")
    if first is not None:
        failures.append(f"allocations differ from event {first} on")
    if "dorm.place_run" not in compiles.count:
        failures.append("place_run never ran on the jax engine")
    else:
        has_kernel = "tpu_custom_call" in be.compiled_text("place_run")
        print(f"compiled place_run holds tpu_custom_call: {has_kernel}",
              flush=True)
        if not has_kernel:
            failures.append("compiled place_run has no tpu_custom_call")
    return failures


def _tiny_trainer(app_id: str, ckpt_dir: str):
    # The TINY model of examples/dorm_live_cluster.py.
    from repro.data import DataConfig
    from repro.models.config import ModelConfig
    from repro.training.elastic import ElasticConfig, ElasticTrainer
    from repro.training.optimizer import OptimizerSpec
    tiny = ModelConfig("tiny", "dense", 2, 128, 4, 2, 256, 512, head_dim=32,
                       dtype="float32", attn_impl="ref")
    return ElasticTrainer(ElasticConfig(
        model=tiny,
        optimizer=OptimizerSpec(peak_lr=1e-3, warmup_steps=5,
                                total_steps=200),
        data=DataConfig(vocab_size=512, seq_len=64, global_batch=8),
        ckpt_dir=ckpt_dir), app_id)


def four_chip_phase(devices, steps: int = 6) -> list:
    """Live partitions across four chips. -> list of failures."""
    from repro.training.elastic import ElasticJaxProtocol
    failures = []
    with tempfile.TemporaryDirectory(prefix="dorm-ckpt-") as ckpt_dir:
        ref = _tiny_trainer("reference", ckpt_dir)
        ref.start(devices[:1])
        ref.train_steps(2 * steps)
        ref_loss = [h["loss"] for h in ref.history]
        ref.kill()

        # One container = one chip; 4 slaves of one container each.
        cluster = ClusterSpec.homogeneous(4, ResourceVector.of(1, 0, 4))
        proto = ElasticJaxProtocol(devices, devices_per_container=1)
        master = DormMaster(cluster, "milp", OptimizerConfig(0.2, 0.5),
                            protocol=proto)
        demand = ResourceVector.of(1, 0, 4)
        a = ApplicationSpec("train-a", "repro", demand, n_min=1, n_max=4)
        b = ApplicationSpec("train-b", "repro", demand, n_min=2, n_max=2)
        for spec in (a, b):
            proto.register(spec.app_id, _tiny_trainer(spec.app_id, ckpt_dir))
        tr_a = proto.trainers["train-a"]

        def groups() -> dict:
            return {k: tuple(d.id for d in v)
                    for k, v in proto.assignments.items()}

        def disjoint(note: str) -> None:
            seen: dict = {}
            for app, ids in groups().items():
                for i in ids:
                    if i in seen:
                        failures.append(f"{note}: chip {i} shared by "
                                        f"{seen[i]} and {app}")
                    seen[i] = app

        master.submit(a)
        before = groups()["train-a"]
        tr_a.train_steps(steps)
        res = master.submit(b)
        after = groups()
        print(f"train-a chips {before} -> {after.get('train-a')}; "
              f"train-b chips {after.get('train-b')}; adjusted "
              f"{list(res.adjusted_app_ids)}", flush=True)
        if "train-a" not in res.adjusted_app_ids:
            failures.append("the arrival did not resize train-a")
        if after.get("train-a") == before:
            failures.append("train-a stayed on its chip group")
        if "train-b" not in after:
            failures.append("train-b did not start")
        disjoint("after resize")
        tr_a.train_steps(steps)
        if "train-b" in after:
            proto.trainers["train-b"].train_steps(steps)
        disjoint("after training")

        got = [h["loss"] for h in tr_a.history]
        rel = np.abs(np.asarray(got) - np.asarray(ref_loss)) / np.maximum(
            np.abs(np.asarray(ref_loss)), 1e-12)
        print(f"train-a loss vs one-chip reference over {len(got)} steps: "
              f"max relative difference {rel.max():.3e} "
              f"(limit {LOSS_RTOL:g}); step {len(got)} loss "
              f"{got[-1]:.6f} vs {ref_loss[-1]:.6f}", flush=True)
        if not rel.max() <= LOSS_RTOL:
            failures.append(f"resized trainer's loss differs from the "
                            f"one-chip run by {rel.max():.3e}")
    return failures


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the live-partition path on four chips")
    args = ap.parse_args()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r}",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x {len(devices)}; "
          f"compile cache {configure_compile_cache()}", flush=True)
    if args.four_chips:
        failures = four_chip_phase(devices[:4])
    else:
        failures = scheduler_phase()
    if failures:
        for f in failures:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
