"""Best-fit placement inner loop as a Pallas TPU kernel.

The scheduler's batched best-fit scatter grants `need` containers of one
app across slaves in ascending (score, slave index) order, each slave
capped at its max feasible count q_j:

    order = argsort(score)            # stable
    counts[order] = diff(min(cumsum(q[order]), need))

A sort is an awkward TPU primitive, but the same result has a sort-free
closed form: slave j's position in the fill order is determined by the
total q of slaves that strictly precede it,

    before_j = sum_k q_k * [(score_k, k) < (score_j, j)]      (lexicographic)
    counts_j = clip(need - before_j, 0, q_j)

(b_j = min(cumsum) prefix available when j is reached; each slave takes
min(q_j, what's left)). That is an O(b^2) masked reduction -- a natural
(J, K) Pallas grid of rank-compare tiles with an accumulate-then-epilogue
pattern (same shape as the moe_gemm kernel's K loop), and for the
scheduler's b it is far below the flops the MXU wastes on a sort.

Contract (enforced by the caller, `repro.core.backend.JaxBackend`):
  * q int32, pre-clipped to [0, need]; infeasible slaves carry q = 0 (their
    score may be +inf). int32 accumulation then never overflows for
    b * need < 2^31.
  * score f32 or f64. The kernel itself compares f32 only: an f64 score is
    split outside the kernel into three f32 parts (hi, mid, lo) whose
    unevaluated sum is the score exactly, and the kernel compares them
    lexicographically. That order equals the f64 order for every finite
    score (IEEE f64 needs all three parts; the TPU's f64, a pair of f32,
    needs two), so two distinct f64 scores never merge into one tie.

`best_fit_counts_ref` is the pure-jnp oracle (the argsort/cumfill
composition itself).

Invocation context: `JaxBackend` calls this kernel both per item
(`place_batch`) and from inside the fused multi-app placement program
(`place_run`, one jit'd `lax.scan` over the whole batch's schedule).
Inside the scan the kernel is traced ONCE per padded (b,) bucket and
replayed for every scan step, so it must stay free of per-item host
logic -- everything item-specific (need, scores, q) arrives as traced
operands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_PARTS = 3
KERNEL_NAME = "dorm_best_fit"
# Block indices stay int32 under jax_enable_x64 (Mosaic rejects i64 ones).
_I0 = np.int32(0)


def _placement_kernel(key_j_ref, key_k_ref, q_k_ref, q_j_ref, need_ref,
                      out_ref, *, block: int):
    k = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    kj = key_j_ref[...]                                    # (PARTS, B)
    kk = key_k_ref[...]                                    # (PARTS, B)
    qk = q_k_ref[...].reshape(block, 1)                    # (B, 1)
    jidx = (pl.program_id(0) * block
            + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1))
    kidx = (k * block
            + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0))
    # (B_k, B_j) strict-predecessor mask: lexicographic over the key parts
    # (most significant first), ties broken by slave index.
    precedes = kidx < jidx
    for p in reversed(range(_PARTS)):
        sj = kj[p:p + 1, :]                                # (1, B)
        sk = kk[p:p + 1, :].reshape(block, 1)              # (B, 1)
        precedes = (sk < sj) | ((sk == sj) & precedes)
    out_ref[...] += jnp.sum(
        jnp.where(precedes, qk, jnp.int32(0)), axis=0, dtype=jnp.int32,
    ).reshape(1, block)

    @pl.when(k == nk - 1)
    def _epilogue():
        need = need_ref[0, 0]
        before = out_ref[...]
        out_ref[...] = jnp.clip(need - before, jnp.int32(0), q_j_ref[...])


def split_key(score: jnp.ndarray) -> jnp.ndarray:
    """(b,) f32/f64 score -> (PARTS, b) f32 parts, most significant first,
    summing exactly to the score (non-finite scores keep only part 0)."""
    if score.dtype == jnp.float32:
        zero = jnp.zeros_like(score)
        return jnp.stack([score] + [zero] * (_PARTS - 1))
    finite = jnp.isfinite(score)
    parts, rest = [], score
    for _ in range(_PARTS):
        part = rest.astype(jnp.float32)
        parts.append(part)
        rest = jnp.where(finite, rest - part.astype(score.dtype), 0.0)
    return jnp.stack(parts)


def best_fit_counts(score: jnp.ndarray, q: jnp.ndarray, need: jnp.ndarray,
                    *, block: int = 256,
                    interpret: bool = False) -> jnp.ndarray:
    """score (b,), q (b,) int32 in [0, need], need () int32 -> counts (b,).

    Compiled for the TPU unless the caller passes `interpret=True` (the
    tests do, to pin the kernel against the oracle on CPU)."""
    b = score.shape[0]
    bb = min(block, b)
    if b % bb:
        raise ValueError(f"slaves {b} must divide block {bb}")
    grid = (b // bb, b // bb)
    key = split_key(score)
    q2 = q.astype(jnp.int32).reshape(1, b)
    need2 = need.astype(jnp.int32).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_placement_kernel, block=bb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_PARTS, bb), lambda j, k: (_I0, j)),   # key, j tile
            pl.BlockSpec((_PARTS, bb), lambda j, k: (_I0, k)),   # key, k tile
            pl.BlockSpec((1, bb), lambda j, k: (_I0, k)),        # q, k tile
            pl.BlockSpec((1, bb), lambda j, k: (_I0, j)),        # q, j tile
            pl.BlockSpec((1, 1), lambda j, k: (_I0, _I0)),       # need
        ],
        out_specs=pl.BlockSpec((1, bb), lambda j, k: (_I0, j)),
        out_shape=jax.ShapeDtypeStruct((1, b), jnp.int32),
        interpret=interpret,
        # A stable name for the kernel in compiled text and device traces.
        name=KERNEL_NAME,
    )(key, key, q2, q2, need2)
    return out.reshape(b)


def best_fit_counts_ref(score: jnp.ndarray, q: jnp.ndarray,
                        need: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle: the argsort/cumfill composition itself."""
    order = jnp.argsort(score, stable=True)
    csum = jnp.minimum(jnp.cumsum(q[order]), need.astype(q.dtype))
    counts = csum - jnp.concatenate([jnp.zeros(1, csum.dtype), csum[:-1]])
    return jnp.zeros_like(q).at[order].set(counts.astype(q.dtype))
