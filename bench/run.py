"""Dorm scheduling benchmark: one run of one cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's cluster and job trace from the seed, warms up every
program shape the window uses, replays the trace through the program's
runtime, master, optimizer and jax backend as fast as decisions come back
(a closed loop) for `--seconds`, then checks what the window decided
against the plain reference and prints one JSON line. `--trace 1` records
a profiler trace of the window and reports the per-layer metrics instead
of the end-to-end ones.

Exits non-zero, with no result line, when jax finds no TPU or fewer chips
than the cell asks for. The compilation cache lives in `.jax_cache/bench`
inside the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)
CACHE_DIR = os.path.join(ROOT, ".jax_cache", "bench")
os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(msg, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import cells
    cell = cells.find_cell(args.workload)
    import repro.core  # noqa: F401  (the system under test, from src/)
    import jax
    devices = jax.devices()
    want = int(cell["workload"]["chips"])
    if devices[0].platform != "tpu" or len(devices) < want:
        print(f"bench: the cell needs {want} TPU chip(s); jax found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from bench.harness import report
    with tempfile.TemporaryDirectory(prefix="dormbench-") as tmp:
        line, checks = report.run(cell, args.seed, args.seconds,
                                  tmp if args.trace else None, T_START,
                                  devices, log)
    for name, (value, limit) in checks.items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
