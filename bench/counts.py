"""Operations and bytes of the engine's kernels, from their shapes.

Each count is the least the algorithm needs for one call at the
packing's own shapes (p subdomains, m stacked rows, block width w),
without the kernels' lane padding.
"""
from __future__ import annotations


def schwarz_sweep(p: int, m: int, w: int, itemsize: int = 4) -> tuple:
    """(flops, bytes) of one fused Schwarz sweep: the forward pass makes
    two matvecs over each (m, w) block, the backward pass one transposed
    matvec, and each pass reads every block once."""
    return 6.0 * p * m * w, 2.0 * p * m * w * itemsize


def gram(p: int, m: int, w: int, itemsize: int = 4) -> tuple:
    """(flops, bytes) of the batched Gram N_i = A_i^T diag(r) A_i: one
    multiply-add per (row, column pair), reading each block once and
    writing each (w, w) result once."""
    return 2.0 * p * m * w * w, (p * m * w + p * w * w) * itemsize


def least_seconds(flops: float, nbytes: float, peaks: dict) -> tuple:
    """(least seconds, the bound that sets it): the larger of flops at
    the bf16 peak (the MXU runs float32 at the default precision as one
    bf16 pass) and bytes at the HBM bandwidth."""
    t_flop = flops / float(peaks["bf16_flops_per_s"])
    t_byte = nbytes / float(peaks["hbm_bytes_per_s"])
    return (t_flop, "compute") if t_flop >= t_byte else (t_byte, "memory")
