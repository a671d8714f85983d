"""Backend-pluggable allocation engine: the same Dorm scheduler on numpy
or on JAX-jit kernels, bit-exact.

`repro.core.backend` puts the three hot scheduler kernels behind one
seam:

  * the ladder-DRF container fill (`drf.drf_container_counts`),
  * the saturating probe (does everyone fit at n_max?),
  * the batched best-fit placement scatter.

`NumpyBackend` is the bit-exactness REFERENCE -- its kernels are the
original sequential code, extracted verbatim.  `JaxBackend` re-expresses
them on `jax.jit`/`lax` and must agree to the last bit (enforced by
tests/test_backend_parity.py and the `timeline_bit_exact_vs_jax` gate in
`scripts/check.sh --bench`).

Selection is one config field (or the REPRO_BACKEND env var, which is how
CI runs the whole tier-1 suite on the jax backend):

    cfg = OptimizerConfig(0.2, 0.2, incremental=True, soa=True,
                          backend="jax")          # or backend="numpy"

The static-shape contract that makes jit caching work:
  * the apps axis is padded to the next power of two with zero-demand
    rows behind a validity mask,
  * the slaves axis is padded with unplaceable sentinel rows
    (free = -1, 1/capacity = 0),
  * the ladder level axis is padded to pow2(max n_max),
so a growing cluster/app set re-compiles O(log n) times, not O(n), and
steady-state events hit the jit cache.  jax's compile events are counted
per program name and reported as
`DormMaster.phase_breakdown()["backend_compile"]`; `PolicyTimer` charges
every event the whole pass that decided it, first-touch compiles
included, and reports the compile seconds beside it.

The placement inner loop is `repro.kernels.placement.best_fit_counts`
on every platform: one stable `lax.sort` of the score's exact f32 parts,
an exclusive cumsum and a second sort back to slave order, O(b log^2 b).
jax is required: this example does not fall back to numpy.

Run:  PYTHONPATH=src python examples/jax_backend.py [--slaves 120 --apps 60]
"""
import argparse
import time

import numpy as np

from repro.core import (ClusterSimulator, DormMaster, OptimizerConfig,
                        PolicyTimer, RecordingProtocol, TraceConfig,
                        generate_trace, heterogeneous_cluster)


def run_backend(backend: str, cluster, wl, horizon_s: float):
    cfg = OptimizerConfig(0.2, 0.2, warm_start=True, incremental=True,
                          soa=True, backend=backend)
    master = DormMaster(cluster, "greedy", cfg,
                        protocol=RecordingProtocol())
    timer = PolicyTimer(master)
    sim = ClusterSimulator(timer, wl, adjustment_cost_s=60.0,
                           horizon_s=horizon_s, batch_window_s=60.0)
    t0 = time.perf_counter()
    res = sim.run()
    wall = time.perf_counter() - t0
    print(f"{backend:>6}: {len(res.samples)} events in {wall:.2f}s wall, "
          f"median policy {timer.median_ms():.3f} ms/event "
          f"(jit compiles inside the run: {timer.compile_s:.2f}s), "
          f"{master.optimizer.delta_solves} delta / "
          f"{master.optimizer.full_solves} full solves")
    return res, master


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--slaves", type=int, default=120)
    ap.add_argument("--apps", type=int, default=60)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon-h", type=float, default=24.0)
    args = ap.parse_args()

    cluster = heterogeneous_cluster(args.slaves, seed=args.seed)
    wl = generate_trace(TraceConfig(n_apps=args.apps, seed=args.seed))
    horizon_s = args.horizon_h * 3600.0

    res_np, _ = run_backend("numpy", cluster, wl, horizon_s)
    res_jx, m_jx = run_backend("jax", cluster, wl, horizon_s)

    # The two timelines must be indistinguishable, sample for sample.
    assert len(res_np.samples) == len(res_jx.samples)
    for a, b in zip(res_np.samples, res_jx.samples):
        assert a == b, (a, b)
    assert res_np.durations() == res_jx.durations()
    print(f"timelines bit-exact across backends "
          f"({len(res_np.samples)} samples); per-phase seconds:")
    for phase, s in m_jx.phase_breakdown().items():
        print(f"    {phase:>16}: {s:.3f}")


if __name__ == "__main__":
    main()
