"""Best-fit placement inner loop as one stable sort and a prefix sum.

The scheduler's batched best-fit scatter grants `need` containers of one
app across slaves in ascending (score, slave index) order, each slave
capped at its max feasible count q_j:

    order = argsort(score)            # stable
    counts[order] = diff(min(cumsum(q[order]), need))

Equivalently, slave j takes what is left of `need` once every slave
before it in that order has taken its q:

    before_j = exclusive cumsum of q in (score, index) order
    counts_j = clip(need - before_j, 0, q_j)

`best_fit_counts` computes exactly this: one stable `lax.sort` of the
score's key parts carrying q and the slave index along, an int32
exclusive cumsum of the sorted q, the clip, and a second sort keyed on
the slave index back to slave order. The work is two sort networks,
O(b log^2 b), on every platform.

Contract (enforced by the caller, `repro.core.backend.JaxBackend`):
  * q int32, pre-clipped to [0, need]; infeasible slaves carry q = 0 (their
    score may be +inf). The int32 cumsum then never overflows for
    b * need < 2^31.
  * score f32 or f64. The sort compares 32-bit keys: an f64 score is split
    into three f32 parts (hi, mid, lo) whose unevaluated sum is the score
    exactly, and the sort orders them lexicographically. That order
    equals the f64 order for every finite score (IEEE f64 needs all three
    parts; the TPU's f64, a pair of f32, needs two), so two distinct f64
    scores never merge into one tie. Each part is sorted as an int32 of
    the same order (`_order_key`), -0.0 and +0.0 mapped to one value, and
    the sort is stable: equal scores keep slave order, as in numpy's
    stable argsort.

`best_fit_counts_ref` is the pure-jnp oracle (the argsort/cumfill
composition itself).

Invocation context: `JaxBackend` calls this fill both per item
(`place_batch`) and from inside the fused multi-app placement program
(`place_run`, one jit'd `lax.scan` over the whole batch's schedule).
Inside the scan it is traced ONCE per padded (b,) bucket and replayed for
every scan step, so it must stay free of per-item host logic --
everything item-specific (need, scores, q) arrives as traced operands.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

_PARTS = 3


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


def _order_key(part: jnp.ndarray) -> jnp.ndarray:
    """f32 (no NaN) -> int32 in the same order, with -0.0 equal to +0.0.

    The sort then compares plain integers: float keys cost a NaN and zero
    canonicalization per compare, which doubles the v5e compile time of
    an 8192-slave sort."""
    i = jax.lax.bitcast_convert_type(part, jnp.int32)
    flipped = i ^ ((i >> 31) & jnp.int32(0x7FFFFFFF))   # negatives reversed
    return jnp.where(i == jnp.int32(-2 ** 31), jnp.int32(0), flipped)


def best_fit_counts(score: jnp.ndarray, q: jnp.ndarray,
                    need: jnp.ndarray) -> jnp.ndarray:
    """score (b,), q (b,) int32 in [0, need], need () int32 -> counts (b,)
    int32, slave j's grant in slave order."""
    b = score.shape[0]
    keys = [_order_key(part) for part in split_key(score)]
    slave = jax.lax.iota(jnp.int32, b)
    *_, q_s, order = jax.lax.sort((*keys, q.astype(jnp.int32), slave),
                                  num_keys=_PARTS, is_stable=True)
    before = jnp.cumsum(q_s) - q_s
    counts_s = jnp.clip(need.astype(jnp.int32) - before, 0, q_s)
    # Back to slave order by a second sort keyed on the permutation: on a
    # v5e it costs less than a scatter (PERF.md).
    return jax.lax.sort((order, counts_s), num_keys=1)[1]


def best_fit_counts_ref(score: jnp.ndarray, q: jnp.ndarray,
                        need: jnp.ndarray) -> jnp.ndarray:
    """Pure-jnp oracle: the argsort/cumfill composition itself."""
    order = jnp.argsort(score, stable=True)
    csum = jnp.minimum(jnp.cumsum(q[order]), need.astype(q.dtype))
    counts = csum - jnp.concatenate([jnp.zeros(1, csum.dtype), csum[:-1]])
    return jnp.zeros_like(q).at[order].set(counts.astype(q.dtype))
