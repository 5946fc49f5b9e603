"""Kernel microbenchmarks: us_per_call of the jnp references (CPU wall
time) + interpret-mode correctness deltas vs the oracles.  On TPU the same
harness times the Pallas kernels natively (mode='kernel')."""
from __future__ import annotations

import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.compile_cache import use_compile_cache


def _time(fn, repeats=5):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / repeats * 1e6  # us


def bench_all(mode_fast: str = "ref"):
    rows = []
    # flash attention
    for (bh, s, d) in [(8, 512, 64), (4, 1024, 128)]:
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        q, k, v = (jax.random.normal(kk, (bh, s, d), jnp.float32)
                   for kk in keys)
        us = _time(lambda: ops.flash_attention(q, k, v, causal=True,
                                               mode=mode_fast))
        out_i = ops.flash_attention(q[:1, :256], k[:1, :256], v[:1, :256],
                                    mode="interpret", block_q=64,
                                    block_k=64)
        out_r = ref.attention_ref(q[:1, :256], k[:1, :256], v[:1, :256])
        err = float(jnp.max(jnp.abs(out_i - out_r)))
        rows.append((f"flash_attention_{bh}x{s}x{d}", us,
                     f"interp_err={err:.1e}"))
    # rglru
    a = jax.random.uniform(jax.random.PRNGKey(1), (8, 1024, 256),
                           jnp.float32, 0.8, 0.999)
    b = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (8, 1024, 256),
                                jnp.float32)
    us = _time(lambda: ops.rglru_scan(a, b, mode=mode_fast))
    out_i = ops.rglru_scan(a[:1, :128, :64], b[:1, :128, :64],
                           mode="interpret", block_s=64, block_w=32)
    err = float(jnp.max(jnp.abs(out_i - ref.rglru_scan_ref(
        a[:1, :128, :64], b[:1, :128, :64]))))
    rows.append((f"rglru_scan_8x1024x256", us, f"interp_err={err:.1e}"))
    # ssd
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    bh, s, p, n = 16, 512, 64, 64
    x = jax.random.normal(keys[0], (bh, s, p), jnp.float32)
    dt = jax.random.uniform(keys[1], (bh, s), jnp.float32, 0.001, 0.1)
    A = -jax.random.uniform(keys[2], (bh,), jnp.float32, 0.5, 2.0)
    B = jax.random.normal(keys[3], (bh, s, n), jnp.float32)
    C = jax.random.normal(keys[4], (bh, s, n), jnp.float32)
    # time the chunked ssd (kernel-shaped math) via the pallas interpret on
    # a small slice + jnp chunked path for wall time
    from repro.kernels.ssd_scan import ssd_scan as _ssd_kernel
    us = _time(lambda: ref.ssd_heads_ref(x[:2], dt[:2], A[:2], B[:2],
                                         C[:2], 128))
    out_i = _ssd_kernel(x[:2, :128], dt[:2, :128], A[:2], B[:2, :128],
                        C[:2, :128], chunk=64, interpret=True)
    err = float(jnp.max(jnp.abs(out_i - ref.ssd_heads_ref(
        x[:2, :128], dt[:2, :128], A[:2], B[:2, :128], C[:2, :128], 64))))
    rows.append((f"ssd_scan_{bh}x{s}x{p}x{n}", us, f"interp_err={err:.1e}"))
    # gram — the batched pack-phase reduction N_i = A_i^T diag(r) A_i
    # over all p subdomain blocks at once (the DD-KF pack's device side)
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    pg, mg, wg = 8, 768, 96
    Ag = jax.random.normal(keys[0], (pg, mg, wg), jnp.float32)
    rg = jax.random.uniform(keys[1], (pg, mg), jnp.float32, 0.5, 2.0)
    us = _time(lambda: ops.gram(Ag, rg, mode=mode_fast))
    out_i = ops.gram(Ag[:2, :256], rg[:2, :256], mode="interpret",
                     block_m=128)
    err = float(jnp.max(jnp.abs(out_i - ref.gram_ref(Ag[:2, :256],
                                                     rg[:2, :256]))))
    rows.append((f"gram_pack_{pg}x{mg}x{wg}", us, f"interp_err={err:.1e}"))
    # fused Schwarz step — fwd (stacked y/u matmat) and bwd (residual
    # formed in VMEM + transpose product), the solve phase's inner loop
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    xg = jax.random.normal(keys[0], (pg, wg), jnp.float32)
    wdiv = jax.random.uniform(keys[1], (pg, wg), jnp.float32, 0.5, 1.0)
    rv = jax.random.uniform(keys[2], (mg,), jnp.float32, 0.5, 2.0)
    bv = jax.random.normal(keys[3], (mg,), jnp.float32)
    muov = jax.random.uniform(keys[4], (pg, wg), jnp.float32, 0.0, 1.0)
    mask = jnp.ones((pg, wg), jnp.float32)
    us = _time(lambda: ops.schwarz_fwd(Ag, xg, wdiv, mode=mode_fast))
    yi, ui = ops.schwarz_fwd(Ag[:2, :256], xg[:2], wdiv[:2],
                             mode="interpret", block_m=128)
    yr, ur = ref.schwarz_fwd_ref(Ag[:2, :256], xg[:2], wdiv[:2])
    err = float(max(jnp.max(jnp.abs(yi - yr)), jnp.max(jnp.abs(ui - ur))))
    rows.append((f"schwarz_fwd_{pg}x{mg}x{wg}", us, f"interp_err={err:.1e}"))
    y, u = ref.schwarz_fwd_ref(Ag, xg, wdiv)
    Ax = jnp.sum(y, axis=0)
    us = _time(lambda: ops.schwarz_bwd(Ag, rv, bv, Ax, u, xg, muov, mask,
                                       mode=mode_fast))
    out_i = ops.schwarz_bwd(Ag[:2, :256], rv[:256], bv[:256], Ax[:256],
                            u[:2, :256], xg[:2], muov[:2], mask[:2],
                            mode="interpret", block_m=128)
    out_r = ref.schwarz_bwd_ref(Ag[:2, :256], rv[:256], bv[:256],
                                Ax[:256], u[:2, :256], xg[:2], muov[:2],
                                mask[:2])
    err = float(jnp.max(jnp.abs(out_i - out_r)))
    rows.append((f"schwarz_bwd_{pg}x{mg}x{wg}", us, f"interp_err={err:.1e}"))
    return rows


if __name__ == "__main__":
    use_compile_cache()
    for name, us, derived in bench_all():
        print(f"{name},{us:.1f},{derived}")
