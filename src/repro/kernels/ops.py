"""jit'd public wrappers for the Pallas kernels.

On TPU backends the Pallas kernels run natively; elsewhere (this CPU
container, and any backend without Mosaic) the wrappers either run the
kernels in interpret mode (tests) or fall back to the jnp references —
selected by ``mode``:

  "auto"      — kernel on TPU, reference otherwise (production default)
  "kernel"    — force the Pallas kernel (native)
  "interpret" — force the Pallas kernel in interpret mode (CPU validation)
  "ref"       — force the jnp oracle
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import gram as _gram
from repro.kernels import rglru_scan as _rg
from repro.kernels import schwarz_step as _sch
from repro.kernels import ssd_scan as _ssd


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(mode: str) -> str:
    if mode == "auto":
        return "kernel" if _on_tpu() else "ref"
    return mode


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    mode: str = "auto", block_q: int = 128,
                    block_k: int = 128):
    """q, k, v: (BH, S, D) -> (BH, S, D)."""
    m = _resolve(mode)
    if m == "ref":
        return _ref.attention_ref(q, k, v, causal=causal, window=window)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=(m == "interpret"))


def rglru_scan(a, b, *, mode: str = "auto", block_s: int = 256,
               block_w: int = 128):
    """h_t = a_t h_{t-1} + b_t; a, b: (B, S, W)."""
    m = _resolve(mode)
    if m == "ref":
        return _ref.rglru_scan_ref(a, b)
    return _rg.rglru_scan(a, b, block_s=block_s, block_w=block_w,
                          interpret=(m == "interpret"))


def ssd_scan(x, dt, A, B, C, *, chunk: int = 256, mode: str = "auto"):
    """Head-folded SSD: x (BH,S,P), dt (BH,S), A (BH,), B/C (BH,S,N)."""
    m = _resolve(mode)
    if m == "ref":
        return _ref.ssd_heads_ref(x, dt, A, B, C, chunk)
    return _ssd.ssd_scan(x, dt, A, B, C, chunk=chunk,
                         interpret=(m == "interpret"))


# -- gram block_m autotuning -------------------------------------------------
#
# The best reduction tile depends on (p, m, w) — a tall skinny A wants a
# bigger m-tile to amortize the accumulator writeback, a wide one is
# VMEM-bound earlier.  First call per (shape, dtype, path) runs a tiny
# timed sweep; every later call (and every jit retrace with the same
# shape) hits the cache.

# block_m is the lane axis of every m-vector tile the kernels load (the
# gram's r row, the Schwarz step's (2, bm) output and its r/b/Ax/u rows),
# so the TPU compiler takes only multiples of 128 — or the whole of m,
# which ``min(c, m)`` yields for short stacks.
GRAM_BLOCK_CANDIDATES = (128, 256, 512, 1024)

# VMEM budget for one grid step's working set: three quarters of the
# 16 MiB of scoped VMEM the TPU v5e compiler gives a Pallas kernel (the
# limit it reports when a kernel overflows it).  The tile models below
# were checked against the compiler's own need (the least
# ``vmem_limit_bytes`` that compiles, Example 4's p = 8, m = 6094 at
# w = 256, 640 and 1664): the gram model over-estimates it, the Schwarz
# model is within 4%, which the last quarter covers.  Candidates whose
# tile footprint exceeds this are rejected without being timed (a sweep
# that overflows VMEM does not compile) and recorded in the tuning
# report.
GRAM_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_GRAM_TUNE_CACHE: dict = {}


def gram_tile_bytes(block_m: int, w: int) -> int:
    """f32 VMEM working set of one gram grid step, with bw the output
    tile edge (``gram.GRAM_BLOCK_W``, or w when narrower): the
    double-buffered (bw, bw) output tile and its accumulator, and the
    two double-buffered (block_m, bw) A tiles with their row-scaled and
    masked copies."""
    bw = min(_gram.GRAM_BLOCK_W, w)
    return 4 * (3 * bw * bw + 6 * block_m * bw)


def autotune_gram_block(p: int, m: int, w: int, dtype,
                        interpret: bool = False) -> int:
    """Pick block_m for a (p, m, w) gram by timing the candidates once.

    Cached per (shape, dtype, path); the sweep costs two kernel launches
    per candidate (one compile+warmup, one timed).  Candidates whose
    VMEM tile footprint exceeds :data:`GRAM_VMEM_BUDGET_BYTES` are
    skipped (and recorded) rather than timed; the smallest candidate is
    always kept so the sweep cannot come up empty.
    """
    # Time exactly the shape the production path runs: the native kernel
    # sees the lane (w) axis zero-padded to the 128-lane tile (ops.gram
    # pads before calling it); interpret mode runs the raw width.
    if not interpret:
        w = w + (-w % 128)
    key = (int(p), int(m), int(w), jnp.dtype(dtype).name, bool(interpret))
    hit = _GRAM_TUNE_CACHE.get(key)
    if hit is not None:
        return hit["block_m"]
    candidates = sorted({min(c, m) for c in GRAM_BLOCK_CANDIDATES})
    rejected = {bm: gram_tile_bytes(bm, w) for bm in candidates
                if gram_tile_bytes(bm, w) > GRAM_VMEM_BUDGET_BYTES}
    kept = [bm for bm in candidates if bm not in rejected]
    if not kept:  # every candidate over budget: keep the narrowest
        kept = candidates[:1]
        rejected.pop(kept[0])
    A = jnp.ones((p, m, w), dtype)
    r = jnp.ones((p, m), dtype)
    sweep = {}
    for bm in kept:
        jax.block_until_ready(
            _gram.gram(A, r, block_m=bm, interpret=interpret))
        t0 = time.perf_counter()
        jax.block_until_ready(
            _gram.gram(A, r, block_m=bm, interpret=interpret))
        sweep[bm] = time.perf_counter() - t0
    best = min(sweep, key=sweep.get)
    _GRAM_TUNE_CACHE[key] = {"block_m": best, "time_s": sweep[best],
                             "sweep_s": sweep,
                             "rejected_vmem": {str(bm): int(fb) for bm, fb
                                               in rejected.items()}}
    # Journal the decision into the observability registry (first call
    # per shape only — the cache short-circuits repeats).
    from repro.obs import meters as meters_mod
    meters_mod.get_meters().event(
        "gram.autotune", shape=[int(p), int(m), int(w)],
        dtype=str(jnp.dtype(dtype)), block_m=int(best),
        candidates=sorted(int(b) for b in sweep),
        rejected_vmem=sorted(int(b) for b in rejected))
    return best


def gram_block_for(shape, dtype, mode: str = "auto"):
    """The block_m the gram path will use for this (p, m, w) shape —
    autotuned for the kernel paths, ``None`` when the shape resolves to
    the jnp reference (which has no blocking).  Call this *outside* jit
    (e.g. at operator-packing time) and pass the result through as a
    static argument."""
    m = _resolve(mode)
    if m == "ref" or (mode == "auto" and jnp.dtype(dtype) == jnp.float64):
        return None
    p, mm, w = shape
    return autotune_gram_block(p, mm, w, dtype, interpret=(m == "interpret"))


def gram_tuning_report() -> dict:
    """JSON-serializable snapshot of the autotune cache: per shape, the
    chosen block and the timed sweep (what the streaming benchmark
    records next to its pack times)."""
    return {
        f"p{p}_m{m}_w{w}_{dt}" + ("_interpret" if it else ""): dict(v)
        for (p, m, w, dt, it), v in _GRAM_TUNE_CACHE.items()
    }


# -- schwarz step block_m autotuning ----------------------------------------
#
# Same harness as gram, generalized to the fused solve kernel: first call
# per (p, m_loc, w, dtype, path) times the candidates once (fwd + bwd
# together — that is exactly what one solver iteration launches), with
# the same conservative VMEM budget.  ``pack_operator`` resolves the
# block host-side and threads it statically through the jitted solves.

SCHWARZ_BLOCK_CANDIDATES = GRAM_BLOCK_CANDIDATES
_SCHWARZ_TUNE_CACHE: dict = {}


def schwarz_tile_bytes(block_m: int, w: int) -> int:
    """f32 VMEM working set of one fused-step grid slot, priced at the
    union of both passes: the (block_m, w) A tile (+ its masked copy in
    the bwd pass), the four (block_m,) m-vectors (r, b, Ax, u), the
    stacked (2, w) xs operand, the (2, block_m) fwd output tile, and the
    (1, w) accumulator plus the four (w,) local vectors."""
    return 4 * (2 * block_m * w + 4 * block_m + 2 * w
                + 2 * block_m + 5 * w)


def autotune_schwarz_block(p: int, m: int, w: int, dtype,
                           interpret: bool = False) -> int:
    """Pick block_m for a (p, m, w) fused Schwarz step by timing the
    candidates once (one warmup + one timed launch of fwd+bwd each).
    Cached per (shape, dtype, path); over-VMEM candidates are rejected
    without being timed, keeping at least the narrowest."""
    if not interpret:
        w = w + (-w % 128)
    key = (int(p), int(m), int(w), jnp.dtype(dtype).name, bool(interpret))
    hit = _SCHWARZ_TUNE_CACHE.get(key)
    if hit is not None:
        return hit["block_m"]
    candidates = sorted({min(c, m) for c in SCHWARZ_BLOCK_CANDIDATES})
    rejected = {bm: schwarz_tile_bytes(bm, w) for bm in candidates
                if schwarz_tile_bytes(bm, w) > GRAM_VMEM_BUDGET_BYTES}
    kept = [bm for bm in candidates if bm not in rejected]
    if not kept:
        kept = candidates[:1]
        rejected.pop(kept[0])
    A = jnp.ones((p, m, w), dtype)
    xv = jnp.ones((p, w), dtype)
    mv = jnp.ones((m,), dtype)

    def run(bm):
        y, u = _sch.schwarz_fwd(A, xv, xv, block_m=bm, interpret=interpret)
        return _sch.schwarz_bwd(A, mv, mv, jnp.sum(y, 0), u, xv, xv, xv,
                                block_m=bm, interpret=interpret)

    sweep = {}
    for bm in kept:
        jax.block_until_ready(run(bm))
        t0 = time.perf_counter()
        jax.block_until_ready(run(bm))
        sweep[bm] = time.perf_counter() - t0
    best = min(sweep, key=sweep.get)
    _SCHWARZ_TUNE_CACHE[key] = {"block_m": best, "time_s": sweep[best],
                                "sweep_s": sweep,
                                "rejected_vmem": {str(bm): int(fb) for bm, fb
                                                  in rejected.items()}}
    from repro.obs import meters as meters_mod
    meters_mod.get_meters().event(
        "schwarz.autotune", shape=[int(p), int(m), int(w)],
        dtype=str(jnp.dtype(dtype)), block_m=int(best),
        candidates=sorted(int(b) for b in sweep),
        rejected_vmem=sorted(int(b) for b in rejected))
    return best


def schwarz_block_for(shape, dtype, mode: str = "auto"):
    """The block_m the fused solve path will use for this (p, m, w) —
    autotuned for the kernel paths, ``None`` when the shape resolves to
    the jnp reference.  Call outside jit (at operator-packing time) and
    pass through as a static argument."""
    m = _resolve(mode)
    if m == "ref" or (mode == "auto" and jnp.dtype(dtype) == jnp.float64):
        return None
    p, mm, w = shape
    return autotune_schwarz_block(p, mm, w, dtype,
                                  interpret=(m == "interpret"))


def schwarz_tuning_report() -> dict:
    """JSON-serializable snapshot of the schwarz autotune cache (same
    keying as :func:`gram_tuning_report`)."""
    return {
        f"p{p}_m{m}_w{w}_{dt}" + ("_interpret" if it else ""): dict(v)
        for (p, m, w, dt, it), v in _SCHWARZ_TUNE_CACHE.items()
    }


def export_tune_caches() -> dict:
    """Both autotune caches as one JSON-ready dict (checkpoint payload):
    a resumed engine re-imports them so the first post-restore solve
    doesn't re-run the candidate sweeps."""
    def dump(cache):
        return [{"key": list(k),
                 "block_m": int(v["block_m"]),
                 "time_s": float(v["time_s"]),
                 "sweep_s": {str(bm): float(t)
                             for bm, t in v["sweep_s"].items()},
                 "rejected_vmem": dict(v["rejected_vmem"])}
                for k, v in cache.items()]
    return {"gram": dump(_GRAM_TUNE_CACHE),
            "schwarz": dump(_SCHWARZ_TUNE_CACHE)}


def import_tune_caches(payload: dict) -> int:
    """Merge a previously exported cache payload in (existing entries
    win — they were timed on *this* host).  Returns entries added."""
    added = 0
    for name, cache in (("gram", _GRAM_TUNE_CACHE),
                        ("schwarz", _SCHWARZ_TUNE_CACHE)):
        for row in (payload or {}).get(name, []):
            p, m, w, dt, it = row["key"]
            key = (int(p), int(m), int(w), str(dt), bool(it))
            if key in cache:
                continue
            cache[key] = {"block_m": int(row["block_m"]),
                          "time_s": float(row["time_s"]),
                          "sweep_s": {int(bm): float(t) for bm, t
                                      in row["sweep_s"].items()},
                          "rejected_vmem": dict(row["rejected_vmem"])}
            added += 1
    return added


def schwarz_fwd(A, x, wdiv, *, mode: str = "auto",
                block_m: int | None = None):
    """Fused forward Schwarz half: (y, u) = (A @ (x * wdiv), A @ x) in
    one pass over A.  A: (p, m, w), x/wdiv: (p, w).

    float64 takes the jnp reference under mode="auto" (still single-pass
    — the reference uses the same stacked matmat); the native kernel
    pads the lane (w) axis to 128 with zero columns (extra columns
    contribute nothing to either product)."""
    m = _resolve(mode)
    if m == "ref" or (mode == "auto" and A.dtype == jnp.float64):
        return _ref.schwarz_fwd_ref(A, x, wdiv)
    if block_m is None:
        if isinstance(A, jax.core.Tracer):
            block_m = 256
        else:
            p, mm, w_ = A.shape
            block_m = autotune_schwarz_block(p, mm, w_, A.dtype,
                                             interpret=(m == "interpret"))
    w = A.shape[-1]
    wpad = -w % 128
    if m == "kernel" and wpad:
        A = jnp.pad(A, ((0, 0), (0, 0), (0, wpad)))
        x = jnp.pad(x, ((0, 0), (0, wpad)))
        wdiv = jnp.pad(wdiv, ((0, 0), (0, wpad)))
        return _sch.schwarz_fwd(A, x, wdiv, block_m=block_m,
                                interpret=False)
    return _sch.schwarz_fwd(A, x, wdiv, block_m=block_m,
                            interpret=(m == "interpret"))


def schwarz_bwd(A, r, b, Ax, u, x, muov, mask, *, mode: str = "auto",
                block_m: int | None = None):
    """Fused backward Schwarz half: rhs = (A^T @ (r * (b - Ax + u)) +
    muov * x) * mask in one pass over A with VMEM-resident residual
    tiles.  A: (p, m, w), r/b/Ax: (m,), u: (p, m), rest (p, w)."""
    m = _resolve(mode)
    if m == "ref" or (mode == "auto" and A.dtype == jnp.float64):
        return _ref.schwarz_bwd_ref(A, r, b, Ax, u, x, muov, mask)
    if block_m is None:
        if isinstance(A, jax.core.Tracer):
            block_m = 256
        else:
            p, mm, w_ = A.shape
            block_m = autotune_schwarz_block(p, mm, w_, A.dtype,
                                             interpret=(m == "interpret"))
    w = A.shape[-1]
    wpad = -w % 128
    if m == "kernel" and wpad:
        pad2 = ((0, 0), (0, wpad))
        out = _sch.schwarz_bwd(
            jnp.pad(A, ((0, 0), (0, 0), (0, wpad))), r, b, Ax, u,
            jnp.pad(x, pad2), jnp.pad(muov, pad2), jnp.pad(mask, pad2),
            block_m=block_m, interpret=False)
        return out[:, :w]
    return _sch.schwarz_bwd(A, r, b, Ax, u, x, muov, mask,
                            block_m=block_m, interpret=(m == "interpret"))


def gram(A, r, *, mode: str = "auto", block_m: int | None = None):
    """Batched weighted Gram N = A^T diag(r) A — the DD-KF normal-matrix
    assembly hot spot (paper eq. 27).  A: (p, m, w), r: (p, m).

    float64 inputs always take the jnp reference under mode="auto" (the
    MXU has no f64 path); for the native kernel the lane (w) axis is
    zero-padded to the 128-lane tile and the result sliced back.

    ``block_m=None`` autotunes the reduction tile on first call per shape
    (cached; see :func:`autotune_gram_block`) when the inputs are
    concrete, and falls back to 256 under tracing — jitted callers should
    resolve the block with :func:`gram_block_for` and pass it statically.
    """
    m = _resolve(mode)
    if m == "ref" or (mode == "auto" and A.dtype == jnp.float64):
        return _ref.gram_ref(A, r)
    if block_m is None:
        if isinstance(A, jax.core.Tracer):
            block_m = 256
        else:
            p, mm, w_ = A.shape
            block_m = autotune_gram_block(p, mm, w_, A.dtype,
                                          interpret=(m == "interpret"))
    w = A.shape[-1]
    wpad = -w % 128
    if m == "kernel" and wpad:
        A = jnp.pad(A, ((0, 0), (0, 0), (0, wpad)))
        out = _gram.gram(A, r, block_m=block_m, interpret=False)
        return out[:, :w, :w]
    return _gram.gram(A, r, block_m=block_m, interpret=(m == "interpret"))
