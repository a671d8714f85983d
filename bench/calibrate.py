"""Readings that the limits in `bench/limits.json` are set from.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --control <n> --seeds 1 2 3 ...

For each seed, one run of the cell (set-up, warm-up, a window of
`--seconds`) on the chip, then every compared number: for the program
(the lower readings) and, on the first `--control` seeds, for the
control, the plain reference computed in float32 put in the program's
place (the upper readings). All seeds run in one process, so set-up
compiles once. One JSON line per seed, then the largest program reading
and the smallest control reading of each number. The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench.harness import cells, check, driver
    cell = cells.find_cell(args.workload)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate: needs a TPU, jax found {dev.platform!r}",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    g = cell["config"]["guarantees"]
    lower: dict = {}
    upper: dict = {}
    for n, seed in enumerate(args.seeds):
        r = driver.run_window(cell, seed, args.seconds, None,
                              time.perf_counter(), lambda s: None)
        t0 = time.perf_counter()
        prog = check.numbers(r, g)
        t1 = time.perf_counter()
        ctrl = check.numbers(r, g, control=True) if n < args.control else {}
        print(json.dumps({"seed": seed, "device": dev.device_kind,
                          "passes": len(r["rec"].pass_wall),
                          "check_s": t1 - t0, "program": prog,
                          "control": ctrl}), flush=True)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, v), v)
        for k, v in ctrl.items():
            upper[k] = min(upper.get(k, v), v)
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
