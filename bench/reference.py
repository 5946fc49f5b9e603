"""Plain reference of the assimilation stream, and its low-precision control.

The engine's analysis of a cycle is the CLS estimate (paper eq. 17-19)

    x = argmin ||H0 (x - xb)||^2 + ||H1 x - y1||^2,

with H0 = [I; smooth * D2] (identity rows over second differences) and
H1 the 3-point Gaussian interpolation rows of the cycle's observation
positions, all weights 1.  Both operators are banded, so the normal
matrix N = H0^T H0 + H1^T H1 has bandwidth 2 and a banded Cholesky
solves it exactly in O(n).  The observation values y1 come from the
configuration's truth model, drawn here from the run's seed: a truth
that random-walks each cycle, seen through H1 with Gaussian noise.  The
background xb is the previous cycle's analysis (persistence forecast).
This module writes all of that out in numpy (and scipy's banded solve
in float64); it shares no code with ``repro`` and takes nothing the
program made.

``operand=...`` rounds both operands of every product to ``operand``
before multiplying and accumulates in float32: the control, i.e. this
reference computed one precision below the configuration's.
``operand=None`` is the reference itself, in float64 throughout.

The harness loads this module from the configuration's ``reference``
key and calls ``observations``, ``solve``, ``chain`` and
``relative_gap``.
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.linalg

STENCIL = 3
OPERANDS = {"float8_e4m3fn": ml_dtypes.float8_e4m3fn,
            "bfloat16": ml_dtypes.bfloat16}


def _rounder(operand):
    if operand is None:
        return np.float64, (lambda v: v)
    low = OPERANDS[operand]
    return np.float32, (lambda v: np.asarray(v, np.float32).astype(
        low).astype(np.float32))


def observation_rows(n: int, pos: np.ndarray):
    """(cols, vals), each (..., m, 3): the interpolation row of each
    observation at ``pos`` in [0, 1) — weights exp(-(j - pos n)^2 / 2)
    over the mesh points j next to the nearest one, normalised; points
    off the mesh get weight 0."""
    pos = np.asarray(pos, np.float64)
    centre = np.clip((pos * n).astype(np.int64), 0, n - 1)
    cols = centre[..., None] + np.arange(-(STENCIL // 2), STENCIL // 2 + 1)
    valid = (cols >= 0) & (cols < n)
    w = np.where(valid, np.exp(-0.5 * (cols - pos[..., None] * n) ** 2), 0.0)
    return cols, w / w.sum(axis=-1, keepdims=True)


def state_rows(n: int, smooth: float):
    """(cols, vals), each (2n - 2, 3): the rows of H0."""
    i = np.arange(n)
    eye_c = np.stack([i, i, i], axis=1)
    eye_v = np.zeros((n, 3))
    eye_v[:, 0] = 1.0
    j = np.arange(n - 2)
    d2_c = np.stack([j, j + 1, j + 2], axis=1)
    d2_v = np.tile(smooth * np.array([-1.0, 2.0, -1.0]), (n - 2, 1))
    return np.concatenate([eye_c, d2_c]), np.concatenate([eye_v, d2_v])


def _add_gram(band, cols, vals, q):
    """band[..., d, j + 1] += v_a v_b for every row's pair of slots with
    col_a - col_b = d >= 0 (the row's slots hold consecutive columns)."""
    qv = q(vals)
    lead = band.shape[:-2]
    for a in range(STENCIL):
        for b in range(a + 1):
            prod = qv[..., a] * qv[..., b]
            idx = np.broadcast_to(cols[..., b] + 1, prod.shape)
            if lead:
                for c in range(lead[0]):
                    np.add.at(band[c, a - b], idx[c], prod[c])
            else:
                np.add.at(band[a - b], idx, prod)


def _matvec(cols, vals, x, q):
    """y[..., r] = sum_k vals[r, k] x[..., cols[r, k]] (cols clipped: their
    weight is 0 off the mesh)."""
    xs = x[..., np.clip(cols, 0, x.shape[-1] - 1)]
    return np.sum(q(vals) * q(xs), axis=-1)


def _rmatvec(cols, vals, y, n, q, acc):
    """z[..., j] = sum over rows r and slots k with cols[r, k] == j of
    vals[r, k] y[..., r]."""
    prod = q(vals) * q(y)[..., None]
    out = np.zeros(prod.shape[:-2] + (n + 2,), acc)
    idx = np.clip(cols, -1, n) + 1
    for c in range(prod.shape[0]):
        np.add.at(out[c], idx[c] if idx.ndim == prod.ndim else idx, prod[c])
    return out[..., 1:n + 1]


def observations(config: dict, seed: int, positions: list) -> list:
    """Each cycle's observation values, in the order the cycles came: the
    truth starts N(0, 1) at every mesh point and each cycle moves to
    (1 - drift) truth + drift N(0, 1); y1 = H1 truth + noise N(0, 1), all
    drawn in that order from one generator seeded with ``seed``."""
    n = int(config["n"])
    drift, noise = float(config["truth_drift"]), float(config["obs_noise"])
    rng = np.random.default_rng(seed)
    truth = rng.normal(size=n)
    ys = []
    for pos in positions:
        truth = (1.0 - drift) * truth + drift * rng.normal(size=n)
        cols, vals = observation_rows(n, pos)
        y = _matvec(cols, vals, truth, lambda v: v)
        ys.append(y + noise * rng.normal(size=len(pos)))
    return ys


def _system(config, pos, operand):
    """(lower band of each cycle's Cholesky factor, H0 rows, H1 rows,
    rounding, accumulation type) for a batch of cycles."""
    acc, q = _rounder(operand)
    pos = np.atleast_2d(np.asarray(pos, np.float64))
    n = int(config["n"])
    h0 = state_rows(n, float(config["smooth"]))
    h1 = observation_rows(n, pos)
    band = np.zeros((len(pos), STENCIL, n + 2), acc)
    base = np.zeros((STENCIL, n + 2), acc)
    _add_gram(base, *h0, q)
    band += base
    _add_gram(band, *h1, q)
    return _factor(band[..., 1:n + 1], q, acc), h0, h1, q, acc


def _rhs(h0, h1, y1, xb, q, acc):
    """H0^T H0 xb + H1^T y1, one row per cycle."""
    n = xb.shape[-1]
    y0 = _matvec(*h0, xb, q).astype(acc)
    return _rmatvec(*h0, y0, n, q, acc) + _rmatvec(*h1, y1, n, q, acc)


def solve(config: dict, pos, y1, xb, operand: str | None = None):
    """Analyses of a batch of cycles, each from the background given.
    pos, y1: (C, m) observation positions and values; xb: (C, n)
    backgrounds.  Returns (C, n)."""
    L, h0, h1, q, acc = _system(config, pos, operand)
    y1 = np.atleast_2d(np.asarray(y1, np.float64))
    xb = np.atleast_2d(np.asarray(xb, np.float64))
    return _substitute(L, _rhs(h0, h1, y1, xb, q, acc), q, acc)


def chain(config: dict, positions: list, ys: list,
          operand: str | None = None) -> np.ndarray:
    """The whole stream's analyses, each cycle's background being this
    chain's own previous analysis (zeros before the first), as the engine
    carries its own.  Returns (C, n)."""
    L, h0, (cols, vals), q, acc = _system(config, np.stack(positions),
                                          operand)
    xb = np.zeros((1, int(config["n"])))
    out = []
    for c, y in enumerate(ys):
        rhs = _rhs(h0, (cols[c:c + 1], vals[c:c + 1]),
                   np.asarray(y, np.float64)[None], xb, q, acc)
        xb = _substitute(L[c:c + 1], rhs, q, acc)
        out.append(xb[0])
    return np.stack(out)


def _factor(band, q, acc):
    """Lower band of the Cholesky factor of N, given as its lower band:
    band[:, d, j] = N[j + d, j], d = 0..2, one matrix per leading row."""
    C, _, n = band.shape
    L = np.zeros_like(band)
    for j in range(n):
        s = band[:, 0, j].copy()
        if j >= 1:
            s -= q(L[:, 1, j - 1]) * q(L[:, 1, j - 1])
        if j >= 2:
            s -= q(L[:, 2, j - 2]) * q(L[:, 2, j - 2])
        d = np.sqrt(s).astype(acc)
        L[:, 0, j] = d
        if j + 1 < n:
            t = band[:, 1, j].copy()
            if j >= 1:
                t -= q(L[:, 2, j - 1]) * q(L[:, 1, j - 1])
            L[:, 1, j] = t / d
        if j + 2 < n:
            L[:, 2, j] = band[:, 2, j] / d
    return L


def _substitute(L, rhs, q, acc):
    """x with L L^T x = rhs, one system per leading row.  In float64 (no
    rounding) LAPACK's banded solve does the same substitution."""
    C, _, n = L.shape
    if acc is np.float64:
        return np.stack([scipy.linalg.cho_solve_banded((L[c], True), rhs[c])
                         for c in range(C)])
    z = np.zeros((C, n), acc)
    for j in range(n):
        s = rhs[:, j].copy()
        if j >= 1:
            s -= q(L[:, 1, j - 1]) * q(z[:, j - 1])
        if j >= 2:
            s -= q(L[:, 2, j - 2]) * q(z[:, j - 2])
        z[:, j] = s / L[:, 0, j]
    x = np.zeros((C, n), acc)
    for j in range(n - 1, -1, -1):
        s = z[:, j].copy()
        if j + 1 < n:
            s -= q(L[:, 1, j]) * q(x[:, j + 1])
        if j + 2 < n:
            s -= q(L[:, 2, j]) * q(x[:, j + 2])
        x[:, j] = s / L[:, 0, j]
    return x.astype(np.float64)


def relative_gap(x: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per cycle: max |x - ref| / max |ref| (max-norms over the mesh)."""
    x = np.atleast_2d(x)
    ref = np.atleast_2d(ref)
    return np.max(np.abs(x - ref), axis=-1) / np.max(np.abs(ref), axis=-1)
