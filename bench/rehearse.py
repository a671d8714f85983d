"""Compile a cell's device programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py place_run 8192 1 2 4 ... 256
    JAX_PLATFORMS=cpu python3 bench/rehearse.py ladder <apps> <levels>
    JAX_PLATFORMS=cpu python3 bench/rehearse.py probe <apps>

Prints one line per bucket with its cold compile time. This is what the
first run of a cell in a fresh checkout pays for that bucket (plus the
cache write), so it decides which cells can be proved: a bucket that takes
longer than a cell's whole proof is charged to every later check's first
run. The compile cache stays off: a program built for a described chip
cannot be read back without it.
"""
from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.core.backend import _build_jax_fns

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    fns = _build_jax_fns(True)
    m = 3
    f64, i64 = jnp.float64, jnp.int64

    def compile_(fn, shapes):
        with jax.enable_x64():
            args = [jax.ShapeDtypeStruct(s, d, sharding=chip)
                    for s, d in shapes]
            t0 = time.perf_counter()
            text = fn.lower(*args).compile().as_text()
            return time.perf_counter() - t0, text

    what = argv[0]
    if what == "place_run":
        b = int(argv[1])
        for k in map(int, argv[2:]):
            dt, text = compile_(fns["place_run"], [
                ((b, m), f64), ((b, m), f64), ((k, m), f64), ((k,), i64),
                ((k,), i64), ((k,), i64)])
            print(f"place_run slaves={b} schedule={k}: {dt:.1f} s cold "
                  f"(tpu_custom_call: {'tpu_custom_call' in text})",
                  flush=True)
    elif what == "ladder":
        n, levels = int(argv[1]), int(argv[2])
        dt, _ = compile_(fns["ladder"], [
            ((n, m), f64), ((n,), i64), ((n,), i64), ((n,), f64),
            ((n,), jnp.bool_), ((m,), f64), ((levels,), i64)])
        print(f"ladder apps={n} levels={levels}: {dt:.1f} s cold", flush=True)
    elif what == "probe":
        n = int(argv[1])
        dt, _ = compile_(fns["probe"], [((n, m), f64), ((n,), f64),
                                        ((m,), f64)])
        print(f"probe apps={n}: {dt:.1f} s cold", flush=True)
    else:
        raise SystemExit(__doc__)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
