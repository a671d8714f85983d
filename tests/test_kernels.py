"""Pallas-kernel tests: shape/dtype sweeps against the pure-jnp oracles,
executed in interpret mode on CPU (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention_gqa
from repro.kernels.moe_gemm import moe_gemm
from repro.kernels.rmsnorm import rmsnorm as rmsnorm_kernel
from repro.kernels.ssd_scan import ssd_scan as ssd_kernel


def _tol(dtype):
    return 6e-2 if dtype == jnp.bfloat16 else 3e-5


# ------------------------------------------------------------------ flash

FLASH_CASES = [
    # B, Hkv, G, S, Dh, causal, window, softcap
    (1, 2, 2, 256, 64, True, None, 0.0),
    (2, 1, 4, 256, 128, True, 64, 0.0),
    (1, 2, 1, 512, 64, True, None, 50.0),
    (1, 1, 2, 256, 64, False, None, 0.0),
    (1, 1, 1, 384, 64, True, 200, 30.0),
    (2, 2, 2, 128, 32, True, None, 0.0),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_oracle(case, dtype):
    B, Hkv, G, S, Dh, causal, window, cap = case
    ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, S, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, Dh), dtype)
    o = flash_attention_gqa(q, k, v, causal=causal, window=window,
                            logit_softcap=cap, block_q=128, block_k=128,
                            interpret=True)
    oref = kref.attention_ref(q.reshape(B, Hkv * G, S, Dh), k, v,
                              causal=causal, window=window,
                              logit_softcap=cap).reshape(q.shape)
    err = float(jnp.abs(o.astype(jnp.float32)
                        - oref.astype(jnp.float32)).max())
    assert err < _tol(dtype), err


def test_flash_block_shape_sweep():
    B, Hkv, G, S, Dh = 1, 1, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, Hkv, G, S, Dh))
    k = jax.random.normal(ks[1], (B, Hkv, S, Dh))
    v = jax.random.normal(ks[2], (B, Hkv, S, Dh))
    oref = kref.attention_ref(q.reshape(B, Hkv * G, S, Dh), k, v,
                              causal=True).reshape(q.shape)
    for bq, bk in [(64, 64), (128, 256), (256, 128), (512, 512)]:
        o = flash_attention_gqa(q, k, v, causal=True, block_q=bq,
                                block_k=bk, interpret=True)
        assert float(jnp.abs(o - oref).max()) < 3e-5, (bq, bk)


# -------------------------------------------------------------------- ssd

SSD_CASES = [
    # B, H, C, L, P, N
    (2, 3, 4, 32, 16, 8),
    (1, 2, 8, 64, 32, 16),
    (1, 1, 4, 128, 64, 128),
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_scan_vs_oracle(case, dtype):
    B, H, C, L, P, N = case
    ks = jax.random.split(jax.random.PRNGKey(sum(case)), 5)
    xh = jax.random.normal(ks[0], (B, H, C, L, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, H, C, L))
                         ).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, C, L, N), dtype)
    Cm = jax.random.normal(ks[4], (B, C, L, N), dtype)
    y, h = ssd_kernel(xh, dt, A, Bm, Cm, interpret=True)
    S = C * L
    yr, hr = kref.ssd_ref(
        jnp.moveaxis(xh.reshape(B, H, S, P), 1, 2).astype(jnp.float32),
        jnp.moveaxis(dt.reshape(B, H, S), 1, 2), A,
        Bm.reshape(B, S, N).astype(jnp.float32),
        Cm.reshape(B, S, N).astype(jnp.float32))
    yr = jnp.moveaxis(yr, 2, 1).reshape(B, H, C, L, P)
    scale = max(1.0, float(jnp.abs(yr).max()))
    assert float(jnp.abs(y.astype(jnp.float32) - yr).max()) / scale \
        < (2e-2 if dtype == jnp.bfloat16 else 1e-4)
    assert float(jnp.abs(h - hr).max()) < 1e-2


# --------------------------------------------------------------- moe gemm

@pytest.mark.parametrize("shape", [(4, 128, 128, 256), (2, 256, 128, 128),
                                   (8, 128, 256, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm_vs_oracle(shape, dtype):
    E, C, D, F = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(sum(shape)))
    x = jax.random.normal(k1, (E, C, D), dtype)
    w = jax.random.normal(k2, (E, D, F), dtype)
    y = moe_gemm(x, w, interpret=True)
    yr = kref.moe_gemm_ref(x, w)
    scale = max(1.0, float(jnp.abs(yr.astype(jnp.float32)).max()))
    err = float(jnp.abs(y.astype(jnp.float32)
                        - yr.astype(jnp.float32)).max()) / scale
    assert err < (3e-2 if dtype == jnp.bfloat16 else 1e-5), err


# ---------------------------------------------------------------- rmsnorm

@pytest.mark.parametrize("shape", [(256, 128), (512, 512), (64, 1024)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_vs_oracle(shape, dtype):
    R, D = shape
    k1, k2 = jax.random.split(jax.random.PRNGKey(R + D))
    x = jax.random.normal(k1, (R, D), dtype)
    w = (jax.random.normal(k2, (D,)) * 0.1).astype(dtype)
    y = rmsnorm_kernel(x, w, block_rows=min(256, R), interpret=True)
    yr = kref.rmsnorm_ref(x, w)
    err = float(jnp.abs(y.astype(jnp.float32)
                        - yr.astype(jnp.float32)).max())
    assert err < _tol(dtype), err


# ------------------------------------------------------- ops.py dispatch

def test_ops_auto_falls_back_to_ref_on_cpu():
    # on the CPU test container, impl="auto" must use the jnp oracle path
    assert jax.default_backend() == "cpu"
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 32))
    k = jax.random.normal(ks[1], (1, 128, 2, 32))
    v = jax.random.normal(ks[2], (1, 128, 2, 32))
    o_auto = ops.flash_attention(q, k, v, impl="auto")
    o_ref = ops.flash_attention(q, k, v, impl="ref")
    assert float(jnp.abs(o_auto - o_ref).max()) == 0.0


def test_ops_pallas_impl_needs_a_tpu():
    # Off a TPU, impl="pallas" raises; ops never picks interpret mode.
    x = jnp.ones((4, 128))
    with pytest.raises(RuntimeError, match="TPU"):
        ops.rmsnorm(x, jnp.ones(128), impl="pallas")


# -------------------------------------------------- placement (scheduler)

PLACE_SIZES = [8, 32, 256, 512, 1024, 8192]


def _np_fill(score, q, need):
    """numpy's stable argsort fill, the engine's reference order."""
    order = np.argsort(score, kind="stable")
    csum = np.minimum(np.cumsum(q[order]), need)
    counts = np.zeros_like(q)
    counts[order] = np.diff(np.concatenate(([0], csum)))
    return counts


def _sort_fill(score, q, need):
    from repro.kernels.placement import best_fit_counts
    with jax.enable_x64():
        return np.asarray(best_fit_counts(jnp.asarray(score),
                                          jnp.asarray(q, jnp.int32),
                                          jnp.int32(need)))


@pytest.mark.parametrize("b", PLACE_SIZES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_best_fit_counts_vs_oracle(b, dtype):
    from repro.kernels.placement import best_fit_counts, best_fit_counts_ref
    with jax.enable_x64():
        rng = np.random.default_rng(b)
        for trial in range(6):
            score = rng.uniform(-2.0, 4.0, size=b)
            if trial % 2:                      # force ties + infeasibles
                score = np.round(score, 1)
                score[rng.integers(b, size=max(b // 4, 1))] = np.inf
            q = rng.integers(0, 7, size=b).astype(np.int32)
            q[~np.isfinite(score)] = 0         # contract: infeasible q=0
            need = np.int32(rng.integers(1, int(q.sum()) + 2))
            q = np.minimum(q, need).astype(np.int32)
            s = jnp.asarray(score, dtype=dtype)
            got = best_fit_counts(s, jnp.asarray(q), jnp.asarray(need))
            ref = best_fit_counts_ref(s, jnp.asarray(q), jnp.asarray(need))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=f"b={b} trial={trial}")


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_best_fit_counts_signed_zeros_tie_by_slave(dtype):
    """-0.0 and +0.0 are one score: the fill takes them in slave order, as
    numpy's stable argsort does, whatever the sign bit."""
    rng = np.random.default_rng(3)
    b = 64
    score = np.where(rng.random(b) < 0.5, -0.0, 0.0).astype(dtype)
    score[::7] = 0.5                           # higher scores among them
    q = np.ones(b, np.int32)
    for need in (1, 5, 20, 40):
        got = _sort_fill(score, q, need)
        np.testing.assert_array_equal(got, _np_fill(score, q, need))
        # The first `need` zero-score slaves by index, signs ignored.
        zeros = np.flatnonzero(score == 0.0)
        assert got[zeros[:need]].sum() == need, need


@pytest.mark.parametrize("part", ["mid", "lo"])
def test_best_fit_counts_orders_f64_below_the_f32_part(part):
    """f64 scores that round to one f32 `hi` part, and for `lo` to one
    `mid` part as well, still fill in f64 order."""
    rng = np.random.default_rng(11)
    b = 48
    # Offsets of both signs, so the lower parts are negative for some.
    if part == "mid":                          # 48 distinct scores
        score = 1.0 + 2.0 ** -30 * (rng.permutation(b) - 24)
    else:                                      # 8 distinct, 6 slaves each
        score = 1.0 + 2.0 ** -25 + 2.0 ** -52 * (rng.permutation(b) % 8 - 3)
    hi = np.float32(score)
    assert np.all(hi == hi[0])                 # one f32 hi part
    if part == "lo":
        mid = np.float32(score - hi.astype(np.float64))
        assert np.all(mid == mid[0])           # and one mid part
    assert np.unique(score).size == (b if part == "mid" else 8)
    q = rng.integers(1, 4, size=b).astype(np.int32)
    for need in (1, 7, 30, int(q.sum())):
        got = _sort_fill(score, q, need)
        np.testing.assert_array_equal(got, _np_fill(score, q, need))
    # The best (lowest) score takes the first grant.
    assert _sort_fill(score, q, 1)[np.argmin(score)] == 1


def test_best_fit_counts_need_above_capacity_grants_every_q():
    rng = np.random.default_rng(5)
    b = 256
    score = rng.uniform(-1.0, 1.0, size=b)
    score[rng.integers(b, size=40)] = np.inf
    q = rng.integers(0, 5, size=b).astype(np.int32)
    q[~np.isfinite(score)] = 0
    got = _sort_fill(score, q, int(q.sum()) + 17)
    np.testing.assert_array_equal(got, q)


def test_best_fit_counts_all_infeasible_grants_nothing():
    b = 128
    score = np.full(b, np.inf)
    q = np.zeros(b, np.int32)
    for need in (0, 1, 1000):
        assert not _sort_fill(score, q, need).any(), need
