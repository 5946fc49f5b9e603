"""The native Schwarz kernels on a TPU: they read A at its own width.

At Example 4's p = 8, m = 6094 and w = 1070 (the width DyDD gives the
Beta(2, 5) network), the kernels run on the raw (p, m, w) arrays and on an
explicitly zero-padded copy at 1152 lanes.  The two must agree: a kernel
that read the padding lanes of A's (8, 128) tiles would differ.  The test
also prints both variants' device times per call, read off a profiler
trace.  It skips without a TPU.

    python -m pytest -q -s tests/test_schwarz_chip.py
"""
import glob
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import schwarz_step

P8, M, W, BLOCK_M = 8, 6094, 1070, 512
REPS = 20


def _kernel_ms(trace_dir) -> dict:
    """Mean device time per call of each Schwarz kernel in the newest
    profiler trace under ``trace_dir``, chip 0's ``XLA Ops`` line."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))
    total = {"schwarz_fwd": 0.0, "schwarz_bwd": 0.0}
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for e in line.events:
                name = e.name.split(" = ", 1)[0].lstrip("%")
                kernel = name.split(".", 1)[0]
                if kernel in total:
                    total[kernel] += e.duration_ns / 1e6
    return {k: v / REPS for k, v in total.items()}


def test_full_width_kernels_match_zero_padded_on_chip(tmp_path):
    if jax.default_backend() != "tpu":
        pytest.skip("needs a TPU")
    f32 = jnp.float32
    ks = jax.random.split(jax.random.PRNGKey(20261022), 8)
    A = jax.random.normal(ks[0], (P8, M, W), f32)
    x = jax.random.normal(ks[1], (P8, W), f32)
    wdiv = jax.random.uniform(ks[2], (P8, W), f32, 0.5, 1.0)
    r = jax.random.uniform(ks[3], (M,), f32, 0.5, 2.0)
    b = jax.random.normal(ks[4], (M,), f32)
    muov = jax.random.uniform(ks[5], (P8, W), f32)
    mask = (jax.random.uniform(ks[6], (P8, W)) > 0.2).astype(f32)
    Ax = jax.random.normal(ks[7], (M,), f32)

    pad = -W % 128
    A_pad = jnp.pad(A, ((0, 0), (0, 0), (0, pad)))
    vpad = lambda v: jnp.pad(v, ((0, 0), (0, pad)))

    def run(A, x, wdiv, muov, mask):
        y, u = schwarz_step.schwarz_fwd(A, x, wdiv, block_m=BLOCK_M)
        rhs = schwarz_step.schwarz_bwd(A, r, b, Ax, u, x, muov, mask,
                                       block_m=BLOCK_M)
        return y, u, rhs

    full_args = (A, x, wdiv, muov, mask)
    pad_args = (A_pad, vpad(x), vpad(wdiv), vpad(muov), vpad(mask))
    full = jax.block_until_ready(run(*full_args))
    padded = jax.block_until_ready(run(*pad_args))
    gaps = {}
    for name, got, want in zip(("y", "u", "rhs"), full,
                               (padded[0], padded[1], padded[2][:, :W])):
        assert got.shape == want.shape
        got, want = np.asarray(got), np.asarray(want)
        assert np.isfinite(got).all()
        gaps[name] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    print(f"\nmax relative gap, full width against zero-padded: {gaps}")
    assert max(gaps.values()) <= 1e-5, gaps

    times = {}
    for label, args in (("full", full_args), ("padded", pad_args)):
        out = str(tmp_path / label)
        with jax.profiler.trace(out):
            for _ in range(REPS):
                jax.block_until_ready(run(*args))
        times[label] = _kernel_ms(out)
    print(f"device ms per call, p={P8} m={M} w={W} block_m={BLOCK_M}: "
          f"{times}")
    assert all(t > 0 for v in times.values() for t in v.values()), times
