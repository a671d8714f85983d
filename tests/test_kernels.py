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

PLACE_SIZES = [8, 32, 256, 512]


@pytest.mark.parametrize("b", PLACE_SIZES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_best_fit_counts_vs_oracle(b, dtype):
    from repro.kernels.placement import best_fit_counts, best_fit_counts_ref
    with jax.enable_x64():
        rng = np.random.default_rng(b)
        for trial in range(6):
            score = rng.uniform(0.0, 4.0, size=b)
            if trial % 2:                      # force ties + infeasibles
                score = np.round(score, 1)
                score[rng.integers(b, size=max(b // 4, 1))] = np.inf
            q = rng.integers(0, 7, size=b).astype(np.int32)
            q[~np.isfinite(score)] = 0         # contract: infeasible q=0
            need = np.int32(rng.integers(1, int(q.sum()) + 2))
            q = np.minimum(q, need).astype(np.int32)
            s = jnp.asarray(score, dtype=dtype)
            got = best_fit_counts(s, jnp.asarray(q), jnp.asarray(need),
                                  block=256, interpret=True)
            ref = best_fit_counts_ref(s, jnp.asarray(q), jnp.asarray(need))
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                          err_msg=f"b={b} trial={trial}")


def test_best_fit_counts_rejects_ragged_block():
    from repro.kernels.placement import best_fit_counts
    with pytest.raises(ValueError):
        best_fit_counts(jnp.zeros(10), jnp.zeros(10, jnp.int32),
                        jnp.int32(1), block=4, interpret=True)
