"""Kernel micro-benchmarks: wall-time of the jnp oracle paths on CPU (the
deployable Pallas kernels target TPU; interpret mode is correctness-only, so
we time the XLA-compiled reference paths and report the kernels' VMEM tile
geometry as the derived column)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import configure_compile_cache
from repro.kernels import ops

from .common import emit


def _time(fn, *args, iters: int = 5, **kw) -> float:
    out = fn(*args, **kw)           # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args, **kw)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6     # us


def run():
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    rows = []

    q = jax.random.normal(ks[0], (1, 1024, 8, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 1024, 2, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 1024, 2, 64), jnp.float32)
    us = _time(ops.flash_attention, q, k, v, impl="ref")
    rows.append(("kernels.flash_attention.ref_1k", us, "us_per_call",
                 "pallas tile (G x 128 x Dh) q / (128 x Dh) kv"))

    xh = jax.random.normal(ks[3], (1, 2048, 8, 64))
    dt = jax.nn.softplus(jax.random.normal(ks[4], (1, 2048, 8)))
    A = -jnp.exp(jax.random.normal(ks[5], (8,)) * 0.3)
    Bm = jax.random.normal(ks[6], (1, 2048, 64))
    Cm = jax.random.normal(ks[7], (1, 2048, 64))
    us = _time(ops.ssd_scan, xh, dt, A, Bm, Cm, impl="ref")
    rows.append(("kernels.ssd_scan.ref_2k", us, "us_per_call",
                 "pallas tile (L=256 x P) + carried (P x N) state"))

    x = jax.random.normal(ks[0], (16, 256, 512), jnp.float32)
    w = jax.random.normal(ks[1], (16, 512, 512), jnp.float32)
    us = _time(ops.grouped_gemm, x, w, impl="ref")
    rows.append(("kernels.moe_gemm.ref_16e", us, "us_per_call",
                 "pallas (128x128x128) MXU tiles, E-major grid"))

    x = jax.random.normal(ks[2], (8192, 1024), jnp.float32)
    wn = jax.random.normal(ks[3], (1024,)) * 0.1
    us = _time(ops.rmsnorm, x, wn, impl="ref")
    rows.append(("kernels.rmsnorm.ref_8k", us, "us_per_call",
                 "pallas (256 x D) row tiles, fused (1+w) scale"))

    emit(rows)
    return rows


if __name__ == "__main__":
    configure_compile_cache()
    run()
