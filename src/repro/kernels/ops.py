"""Jit'd public wrappers around the Pallas kernels.

Each op takes the MODEL layout, adapts to the kernel layout, and dispatches:
  impl="pallas"     -> compiled Pallas kernel (raises off a TPU)
  impl="ref"        -> pure-jnp oracle
  impl="auto"       -> pallas on TPU backends, ref otherwise

Interpret mode is never chosen here; the kernel tests ask for it by
argument on the kernels themselves.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import ref as _ref
from .flash_attention import flash_attention_gqa
from .moe_gemm import moe_gemm as _moe_gemm
from .rmsnorm import rmsnorm as _rmsnorm_kernel
from .ssd_scan import ssd_scan as _ssd_scan


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(impl: str) -> bool:
    if impl == "ref":
        return False
    if impl == "pallas":
        if not _on_tpu():
            raise RuntimeError(
                "impl='pallas' needs a TPU; jax's default backend is "
                f"{jax.default_backend()!r}")
        return True
    if impl == "auto":
        return _on_tpu()
    raise ValueError(impl)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "logit_softcap", "impl", "block_q", "block_k"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    logit_softcap: float = 0.0, impl: str = "auto",
                    block_q: int = 128, block_k: int = 128) -> jnp.ndarray:
    """Model layout: q (B,S,Hq,Dh), k/v (B,S,Hkv,Dh) -> (B,S,Hq,Dh)."""
    B, S, Hq, Dh = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    use_pallas = _use_pallas(impl)
    qh = jnp.moveaxis(q, 1, 2).reshape(B, Hkv, G, S, Dh)
    kh = jnp.moveaxis(k, 1, 2)
    vh = jnp.moveaxis(v, 1, 2)
    if use_pallas:
        o = flash_attention_gqa(qh, kh, vh, causal=causal, window=window,
                                logit_softcap=logit_softcap, block_q=block_q,
                                block_k=block_k)
    else:
        o = _ref.attention_ref(qh.reshape(B, Hq, S, Dh), kh, vh,
                               causal=causal, window=window,
                               logit_softcap=logit_softcap
                               ).reshape(B, Hkv, G, S, Dh)
    return jnp.moveaxis(o.reshape(B, Hq, S, Dh), 1, 2)


@functools.partial(jax.jit, static_argnames=("chunk", "impl"))
def ssd_scan(xh: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
             Bm: jnp.ndarray, Cm: jnp.ndarray, *, chunk: int = 128,
             impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Model layout: xh (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N).

    Returns (y (B,S,H,P), h_final (B,H,P,N))."""
    B, S, H, P = xh.shape
    N = Bm.shape[-1]
    if not _use_pallas(impl):
        return _ref.ssd_ref(xh, dt, A, Bm, Cm)
    assert S % chunk == 0, (S, chunk)
    C = S // chunk
    xk = jnp.moveaxis(xh, 2, 1).reshape(B, H, C, chunk, P)
    dtk = jnp.moveaxis(dt, 2, 1).reshape(B, H, C, chunk)
    Bk = Bm.reshape(B, C, chunk, N)
    Ck = Cm.reshape(B, C, chunk, N)
    y, h = _ssd_scan(xk, dtk, A, Bk, Ck)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)
    return y, h


@functools.partial(jax.jit, static_argnames=("impl",))
def grouped_gemm(x: jnp.ndarray, w: jnp.ndarray, *, impl: str = "auto",
                 ) -> jnp.ndarray:
    """x (E, C, D), w (E, D, F) -> (E, C, F)."""
    if _use_pallas(impl):
        E, C, D = x.shape
        F = w.shape[-1]
        bm = 128 if C % 128 == 0 else C
        bn = 128 if F % 128 == 0 else F
        bk = 128 if D % 128 == 0 else D
        return _moe_gemm(x, w, block_m=bm, block_n=bn, block_k=bk)
    return _ref.moe_gemm_ref(x, w)


@functools.partial(jax.jit, static_argnames=("eps", "impl"))
def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, *, eps: float = 1e-6,
            impl: str = "auto") -> jnp.ndarray:
    """x (..., D), w (D,)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if _use_pallas(impl):
        R = x2.shape[0]
        br = 256 if R % 256 == 0 else (R if R <= 256 else 1)
        y = _rmsnorm_kernel(x2, w, eps=eps, block_rows=br)
    else:
        y = _ref.rmsnorm_ref(x2, w, eps)
    return y.reshape(shape)
