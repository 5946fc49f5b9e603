"""RG-LRU linear-recurrence Pallas TPU kernel.

Computes h_t = a_t * h_{t-1} + b_t over the sequence axis, blocked over
(batch, width): the grid is (B, W/bw, S/bs) with the *sequence* axis as the
sequential TPU grid dimension; the running state h (bw lanes) persists in a
VMEM scratch across sequence blocks, and each block's scan is a short
unrolled/fori loop over bs steps entirely in VMEM.

TPU adaptation: lanes (width) are the vector dimension — blocks are
(bs, bw) with bw a multiple of 128 so the per-step multiply-add maps to
full VPU lanes; HBM traffic is exactly 2 reads + 1 write per element
(streaming), the roofline optimum for a memory-bound scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rglru_kernel(a_ref, b_ref, o_ref, h_ref, *, block_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[0].astype(jnp.float32)   # (bs, bw)
    b = b_ref[0].astype(jnp.float32)

    def step(t, h):
        h = a[t] * h + b[t]
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    h_ref[...] = h


@functools.partial(jax.jit, static_argnames=("block_s", "block_w",
                                             "interpret"))
def rglru_scan(a, b, *, block_s: int = 256, block_w: int = 128,
               interpret: bool = False):
    """a, b: (B, S, W) -> h: (B, S, W) with h_t = a_t h_{t-1} + b_t."""
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    ns = pl.cdiv(S, block_s)
    nw = pl.cdiv(W, block_w)

    kernel = functools.partial(_rglru_kernel, block_s=block_s)
    return pl.pallas_call(
        kernel,
        grid=(B, nw, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si:
                         (bi, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si:
                         (bi, si, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w), lambda bi, wi, si:
                               (bi, si, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((block_w,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
