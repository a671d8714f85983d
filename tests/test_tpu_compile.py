"""The scheduler's device programs compile for a TPU v5e, checked without a
chip: the chip's compiler builds each program for a described (not
attached) v5e topology. The Pallas placement kernel must come out as a
`tpu_custom_call`, alone and inside the fused `place_run` scan, under its
stable name.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.backend import _build_jax_fns
from repro.kernels.placement import KERNEL_NAME, best_fit_counts

SLAVES, RESOURCES, STEPS = 8192, 3, 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # A program compiled for a described chip cannot be read back from the
    # persistent cache without that chip: keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, one_chip, *shapes):
    with jax.enable_x64():
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return fn.lower(*args).compile().as_text()


def test_best_fit_counts_compiles_to_tpu_kernel(one_chip):
    fn = jax.jit(lambda s, q, n: best_fit_counts(s, q, n, interpret=False))
    text = _compile(fn, one_chip, ((SLAVES,), jnp.float64),
                    ((SLAVES,), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in text


def test_place_run_compiles_with_pallas_kernel(one_chip):
    b, m, k = SLAVES, RESOURCES, STEPS
    text = _compile(_build_jax_fns(True)["place_run"], one_chip,
                    ((b, m), jnp.float64), ((b, m), jnp.float64),
                    ((k, m), jnp.float64), ((k,), jnp.int64),
                    ((k,), jnp.int64), ((k,), jnp.int64))
    assert "tpu_custom_call" in text
    # The kernel keeps its stable name inside the fused program.
    assert KERNEL_NAME in text


def test_probe_compiles(one_chip):
    n, m = 2048, RESOURCES
    _compile(_build_jax_fns(True)["probe"], one_chip,
             ((n, m), jnp.float64), ((n,), jnp.float64),
             ((m,), jnp.float64))


def test_ladder_compiles(one_chip):
    # Small on purpose: the ladder's f64 argsort over n * L keys takes
    # minutes to compile for the TPU at n=1024, L=64.
    n, m, levels = 16, RESOURCES, 8
    _compile(_build_jax_fns(True)["ladder"], one_chip,
             ((n, m), jnp.float64), ((n,), jnp.int64), ((n,), jnp.int64),
             ((n,), jnp.float64), ((n,), jnp.bool_), ((m,), jnp.float64),
             ((levels,), jnp.int64))
