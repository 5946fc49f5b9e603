"""Weighted Gram-matrix Pallas TPU kernel — the DD-KF compute hot spot.

Every DyDD re-partition re-factorizes each subdomain's local normal matrix
N_i = A_i^T diag(r) A_i (paper eq. 27); with p subdomains this is a batch
of (m x w)^T (m x w) products, m ~ 5000+, w ~ n/p — the dominant FLOPs of
the setup phase.

TPU mapping: grid (p, w/bw, w/bw, m/bm) with the reduction (m) axis as
the sequential dimension; each (bw x bw) output tile's accumulator lives
in VMEM scratch; each step loads the two (bm x bw) column tiles of A_i,
scales one's rows by r, and issues a single MXU matmul-accumulate.  w
is padded to 128 lanes by the wrapper in ops.py; bm is a multiple of 128
(or all of m), the lane axis of the r tile.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Constant block index for the index maps: a bare 0 is traced as int64
# when x64 is on, and Mosaic takes only int32 block indices.
_ZERO = np.int32(0)


# Output tile edge: the (w, w) result is computed in (bw, bw) tiles so the
# kernel's VMEM (accumulator, double-buffered output and A tiles) stays
# bounded at any block width — DyDD's repartitions leave a few subdomains
# far wider than n / p, and a whole (w, w) accumulator overflows the
# TPU's scoped VMEM once the padded w passes ~1k lanes.
GRAM_BLOCK_W = 512


def _gram_kernel(ai_ref, aj_ref, r_ref, o_ref, acc_ref, *, block_m: int,
                 m_total: int):
    mi = pl.program_id(3)
    nm = pl.num_programs(3)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ai = ai_ref[...].astype(jnp.float32)          # (bm, bw) column tile i
    aj = aj_ref[...].astype(jnp.float32)          # (bm, bw) column tile j
    r = r_ref[0].astype(jnp.float32)              # (bm,)
    # mask padded rows of the final block
    row = mi * block_m + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_m, 1), 0)
    valid = row < m_total
    ar = jnp.where(valid, ai * r[:, None], 0.0)
    aj = jnp.where(valid, aj, 0.0)
    acc_ref[...] += jax.lax.dot_general(
        ar, aj, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(mi == nm - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def gram(A, r, *, block_m: int = 256, interpret: bool = False):
    """A: (p, m, w), r: (p, m)  ->  N: (p, w, w) with N = A^T diag(r) A.

    r enters lifted to (p, 1, m) so its (1, bm) block is a full unit row
    by a lane tile — legal under the TPU's (8, 128) tiling at any p.
    Output tiles past w (when bw does not divide it) only ever read
    A's out-of-range columns, and their rows and columns are dropped on
    writeback."""
    p, m, w = A.shape
    block_m = min(block_m, m)
    block_w = min(GRAM_BLOCK_W, w)
    nm = pl.cdiv(m, block_m)
    nw = pl.cdiv(w, block_w)
    kernel = functools.partial(_gram_kernel, block_m=block_m, m_total=m)
    return pl.pallas_call(
        kernel,
        grid=(p, nw, nw, nm),
        in_specs=[
            pl.BlockSpec((None, block_m, block_w),
                         lambda b, i, j, k: (b, k, i)),
            pl.BlockSpec((None, block_m, block_w),
                         lambda b, i, j, k: (b, k, j)),
            pl.BlockSpec((None, 1, block_m), lambda b, i, j, k: (b, _ZERO, k)),
        ],
        out_specs=pl.BlockSpec((None, block_w, block_w),
                               lambda b, i, j, k: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((p, w, w), A.dtype),
        scratch_shapes=[pltpu.VMEM((block_w, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(A, A, r[:, None])
