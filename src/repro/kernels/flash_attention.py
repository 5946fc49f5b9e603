"""Flash attention Pallas TPU kernel (causal + sliding-window).

TPU adaptation (DESIGN.md §3): blocks are sized for VMEM and MXU alignment
(q/k tiles 128-multiple, head_dim padded to 128/256); the kv-block grid
dimension is the *sequential* (arbitrary) TPU grid axis, so the online
softmax accumulators (m, l, acc) live in VMEM scratch across kv steps —
the HBM->VMEM streaming analogue of the CUDA shared-memory algorithm.

Layout: q, k, v are (BH, S, D) with heads pre-folded into batch and GQA
pre-expanded (the ops.py wrapper does both).  Sliding window w > 0 masks
kv positions <= q - w; causal masks kv > q.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int, block_q: int,
                  block_k: int, seq_len: int):
    qi = pl.program_id(1)          # q block index
    ki = pl.program_id(2)          # kv block index (sequential axis)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k

    # Skip fully-masked blocks (causal: kv block entirely after q block;
    # window: kv block entirely before the window opening).
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1
    if window > 0:
        run = jnp.logical_and(
            run, k_start + block_k - 1 > q_start - window)

    @pl.when(run)
    def _body():
        q = q_ref[0].astype(jnp.float32)          # (bq, d)
        k = k_ref[0].astype(jnp.float32)          # (bk, d)
        v = v_ref[0].astype(jnp.float32)          # (bk, d)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        in_bounds = kpos < seq_len
        # zero out padded k/v rows: out-of-bounds block slack is undefined
        # (NaN in interpret mode) and 0 * NaN = NaN otherwise
        row_ok = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, 1), 0)) < seq_len
        k = jnp.where(row_ok, k, 0.0)
        v = jnp.where(row_ok, v, 0.0)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale                              # (bq, bk)
        ok = in_bounds
        if causal:
            ok &= kpos <= qpos
        if window > 0:
            ok &= kpos > qpos - window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]                        # (bq,)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        # guard fully-masked rows (m_cur == NEG_INF): exp(-inf - -inf)
        # must contribute 0, not 1
        p = jnp.where(s > 0.5 * NEG_INF,
                      jnp.exp(s - m_cur[:, None]), 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
        m_ref[...] = m_cur
        acc_ref[...] = (acc_ref[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q, k, v: (BH, S, D) -> (BH, S, D).  window <= 0 means unbounded."""
    bh, s, d = q.shape
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq = pl.cdiv(s, block_q)
    nk = pl.cdiv(s, block_k)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_len=s)

    return pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            # acc, m, l accumulators persist across the sequential kv axis
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
