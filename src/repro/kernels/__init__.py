"""Pallas TPU kernels for the compute hot-spots, with jnp oracles.

Layout: <name>.py = pl.pallas_call + BlockSpec; ops.py = jit'd wrappers
adapting model layouts; ref.py = pure-jnp ground truth used in tests.
The scheduler's best-fit fill (placement.py) is a plain `lax.sort`
program, not a Pallas kernel.
"""
from . import ops, ref
from .flash_attention import flash_attention_gqa
from .moe_gemm import moe_gemm
from .placement import best_fit_counts, best_fit_counts_ref
from .rmsnorm import rmsnorm as rmsnorm_kernel
from .ssd_scan import ssd_scan as ssd_scan_kernel

__all__ = ["ops", "ref", "flash_attention_gqa", "moe_gemm",
           "best_fit_counts", "best_fit_counts_ref",
           "rmsnorm_kernel", "ssd_scan_kernel"]
