"""Mamba-2 SSD chunked-scan Pallas TPU kernel.

Head-folded layout: x (BH, S, P), dt (BH, S), A (BH,), B/C (BH, S, N).
Grid is (BH, S/chunk) with the chunk axis sequential; the inter-chunk
ssm state (N, P) lives in VMEM scratch.  Per chunk (all in VMEM):

  intra:  y  = ((C B^T) .* L) (dt .* x)        two MXU matmuls + mask
  inter:  y += (C .* exp(cum)) S_prev          one MXU matmul
  state:  S  = exp(cum_last) S_prev + B^T (dt .* exp(cum_last - cum) .* x)

which is the state-space-duality algorithm with the quadratic part
confined to a (chunk x chunk) tile — sized so chunk, N, P are multiples
of the 128 MXU dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, o_ref, s_ref, *,
                chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    x = x_ref[0].astype(jnp.float32)          # (C, P)
    dt = dt_ref[0].astype(jnp.float32)        # (C,)
    A = a_ref[0].astype(jnp.float32)          # scalar (per head)
    Bm = b_ref[0].astype(jnp.float32)         # (C, N)
    Cm = c_ref[0].astype(jnp.float32)         # (C, N)

    dA = dt * A                                # (C,)
    cum = jnp.cumsum(dA)                       # (C,)
    last = cum[-1]

    # intra-chunk (dual form)
    diff = cum[:, None] - cum[None, :]         # (C, C)
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    L = jnp.where(mask, jnp.exp(diff), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = CB * L * dt[None, :]              # (C, C)
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk from carried state
    s_prev = s_ref[...]                        # (N, P)
    y += jax.lax.dot_general(Cm * jnp.exp(cum)[:, None], s_prev,
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)

    # state update
    w = dt * jnp.exp(last - cum)               # (C,)
    s_new = jnp.exp(last) * s_prev + jax.lax.dot_general(
        Bm * w[:, None], x, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = s_new
    o_ref[0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256,
             interpret: bool = False):
    """x: (BH,S,P), dt: (BH,S), A: (BH,), B/C: (BH,S,N) -> y: (BH,S,P)."""
    BH, S, P = x.shape
    N = B.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(BH, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk), lambda b, c: (b, c)),
            pl.BlockSpec((1,), lambda b, c: (b,)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, P), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, B, C)
