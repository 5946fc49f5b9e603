"""Fused additive-Schwarz iteration step — the DD-KF solve hot loop.

Each solver iteration applies, per subdomain i (paper eqs. 23-26):

    y_i    = A_i @ (x_i * wdiv_i)          # overlap-weighted local matvec
    Ax     = allreduce_p(y_i)              # the one unavoidable collective
    resid  = b - Ax + A_i @ x_i            # local residual correction
    rhs_i  = (A_i^T @ (r * resid) + muov_i * x_i) * mask_i

Written as three separate products, the step reads the (m x w) local
operator A_i from HBM three times per iteration (two forward matvecs +
one transposed reduction) and materializes ``resid`` as an (m,) HBM
round-trip.  The fused kernels cut that to two passes with no resid
materialization:

* :func:`schwarz_fwd` — ONE pass over A_i tiles computes both forward
  products as a single stacked (2, w) x (w, bm) MXU matmul per tile
  (``xs = [x * wdiv, x]``), emitting ``(y_i, u_i = A_i @ x_i)``.  The
  cross-subdomain ``Ax = psum(y)`` stays outside the kernel — it is the
  collective the decomposition exists to expose.
* :func:`schwarz_bwd` — the SECOND pass re-reads each (bm x w) A-tile,
  forms the matching resid tile ``b - Ax + u`` directly in VMEM
  (registers, never written back), and accumulates the transposed
  product ``A_tile^T @ (r * resid)`` into a (1, w) VMEM scratch; the
  ``+ muov * x`` / ``* mask`` epilogue runs once at the last m-block.

TPU mapping mirrors ``gram.py``: grid (p, m/bm) with the m axis
sequential, accumulator in VMEM scratch, and ``bm`` a multiple of 128
(or all of m) since it is the lane axis of the m-vector tiles.  Every
block spans the whole lane (w) axis, so w need not be a multiple of 128:
the kernels read A at its own width, with no padded copy (HBM already
stores the array in (8, 128) tiles, so the DMAs move the same tiles).
Unlike gram, f64 inputs keep an f64 accumulator (interpret mode must
stay ULP-comparable to the jnp path; the f32 accumulator is only used
for f32/bf16 inputs where the MXU accumulates in f32 anyway).
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


def _acc_dtype(dtype):
    return jnp.float64 if dtype == jnp.float64 else jnp.float32


def _fwd_kernel(a_ref, xs_ref, o_ref, *, acc_t):
    # One tile: (2, w) @ (bm, w)^T -> (2, bm) = [y_tile; u_tile].  Rows of
    # the output are independent dots over w, so padded m-rows need no
    # masking — out-of-range rows are dropped on writeback.
    o_ref[...] = jax.lax.dot_general(
        xs_ref[...], a_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=acc_t).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def schwarz_fwd(A, x, wdiv, *, block_m: int = 256, interpret: bool = False):
    """A: (p, m, w), x/wdiv: (p, w) -> (y, u) both (p, m) with
    y = A @ (x * wdiv) and u = A @ x, one HBM pass over A."""
    p, m, w = A.shape
    block_m = min(block_m, m)
    nm = pl.cdiv(m, block_m)
    xs = jnp.stack([x * wdiv, x], axis=1)          # (p, 2, w)
    kernel = functools.partial(_fwd_kernel, acc_t=_acc_dtype(A.dtype))
    out = pl.pallas_call(
        kernel,
        grid=(p, nm),
        in_specs=[
            pl.BlockSpec((None, block_m, w), lambda i, j: (i, j, _ZERO)),
            pl.BlockSpec((None, 2, w), lambda i, j: (i, _ZERO, _ZERO)),
        ],
        out_specs=pl.BlockSpec((None, 2, block_m), lambda i, j: (i, _ZERO, j)),
        out_shape=jax.ShapeDtypeStruct((p, 2, m), A.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(A, xs)
    return out[:, 0], out[:, 1]


def _bwd_kernel(a_ref, r_ref, b_ref, ax_ref, u_ref, x_ref, muov_ref,
                mask_ref, o_ref, acc_ref, *, block_m: int, m_total: int):
    mi = pl.program_id(1)
    nm = pl.num_programs(1)

    @pl.when(mi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_t = acc_ref.dtype
    # resid tile lives entirely in VMEM/registers — never written to HBM.
    resid = b_ref[...] - ax_ref[...] + u_ref[...]  # (1, bm)
    t = (r_ref[...] * resid).astype(acc_t)
    # mask padded rows of the final block (and the A tile, so garbage
    # padding can't poison the product via 0 * inf)
    col = mi * block_m + jax.lax.broadcasted_iota(jnp.int32,
                                                  (1, block_m), 1)
    row = mi * block_m + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_m, 1), 0)
    t = jnp.where(col < m_total, t, 0.0)
    a = jnp.where(row < m_total, a_ref[...], 0.0).astype(acc_t)
    acc_ref[...] += jax.lax.dot_general(
        t, a, (((1,), (0,)), ((), ())), preferred_element_type=acc_t)

    @pl.when(mi == nm - 1)
    def _done():
        acc = acc_ref[...] + (muov_ref[...].astype(acc_t)
                              * x_ref[...].astype(acc_t))
        o_ref[...] = (acc * mask_ref[...].astype(acc_t)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def schwarz_bwd(A, r, b, Ax, u, x, muov, mask, *, block_m: int = 256,
                interpret: bool = False):
    """A: (p, m, w), r/b/Ax: (m,), u: (p, m), x/muov/mask: (p, w) ->
    rhs: (p, w) = (A^T @ (r * (b - Ax + u)) + muov * x) * mask, one HBM
    pass over A with the resid tiles formed in VMEM.

    Every vector enters as a (1, len) row per grid step — the shared
    m-vectors as (1, m), the per-subdomain ones lifted to (p, 1, len) —
    so each block's last two dims are a full unit row by a lane tile,
    which the TPU's (8, 128) tiling accepts at any p."""
    p, m, w = A.shape
    block_m = min(block_m, m)
    nm = pl.cdiv(m, block_m)
    kernel = functools.partial(_bwd_kernel, block_m=block_m, m_total=m)
    vec_spec = pl.BlockSpec((1, block_m), lambda i, j: (_ZERO, j))
    loc_spec = pl.BlockSpec((None, 1, w), lambda i, j: (i, _ZERO, _ZERO))
    out = pl.pallas_call(
        kernel,
        grid=(p, nm),
        in_specs=[
            pl.BlockSpec((None, block_m, w), lambda i, j: (i, j, _ZERO)),
            vec_spec,                              # r
            vec_spec,                              # b
            vec_spec,                              # Ax
            pl.BlockSpec((None, 1, block_m), lambda i, j: (i, _ZERO, j)),  # u
            loc_spec,                              # x
            loc_spec,                              # muov
            loc_spec,                              # mask
        ],
        out_specs=loc_spec,
        out_shape=jax.ShapeDtypeStruct((p, 1, w), A.dtype),
        scratch_shapes=[pltpu.VMEM((1, w), _acc_dtype(A.dtype))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(A, r[None], b[None], Ax[None], u[:, None], x[:, None], muov[:, None],
      mask[:, None])
    return out[:, 0]
