"""Benchmark harness: one module per paper figure/table + kernel benches.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run fig6 fig9a # selected

Output: `name,value,unit,notes` CSV rows per benchmark. Roofline terms for
the (arch x shape x mesh) matrix come from the dry-run (results/dryrun.jsonl,
see launch/dryrun.py), not from this harness.
"""
from __future__ import annotations

import sys
import time

from repro.core import configure_compile_cache

from . import (bench_autoscale, bench_chaos, bench_goodput, bench_kernels,
               bench_replay, bench_scale, bench_shard, fig1_durations,
               fig6_utilization, fig7_fairness, fig8_adjustment,
               fig9a_speedup, fig9b_overhead)

MODULES = {
    "fig1": fig1_durations,
    "fig6": fig6_utilization,
    "fig7": fig7_fairness,
    "fig8": fig8_adjustment,
    "fig9a": fig9a_speedup,
    "fig9b": fig9b_overhead,
    "kernels": bench_kernels,
    "scale": bench_scale,
    "autoscale": bench_autoscale,
    "goodput": bench_goodput,
    "replay": bench_replay,
    "chaos": bench_chaos,
    "shard": bench_shard,
}


def main() -> None:
    names = sys.argv[1:] or list(MODULES)
    configure_compile_cache()
    print("name,value,unit,notes")
    for n in names:
        t0 = time.time()
        MODULES[n].run()
        print(f"# {n} done in {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
