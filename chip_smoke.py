#!/usr/bin/env python3
"""Chip smoke: the streaming DyDD + DD-KF engine end to end on a TPU.

    python chip_smoke.py             # phases A and B, one chip
    python chip_smoke.py --chips 4   # sharded solve vs vmapped, four chips

Every phase drives ``AssimilationEngine.run`` (through ``run_scenario``)
on the paper's Example 4 (``repro.configs.cls_paper.EXAMPLE4``: n = 2048
mesh points, m = 2000 observations per cycle, chain graph) under the
``drifting_swarm`` network, with ``track_reference=True`` so each cycle
journals its error against the direct CLS solve (``cls.solve``).

* A — p = 8 in float64, as the entry points run today.  At least one
  DyDD repartition must fire, so the repack and re-factor run here.
* B — the same configuration in float32, where ``solver_kernel="auto"``
  must resolve to the native Pallas ``schwarz_fwd``/``schwarz_bwd`` and
  ``gram`` kernels; the compiled programs must contain them.
* ``--chips 4`` (only this) — p = 4, overlap 1, ``solver="shardmap"`` on
  the engine-built (4,) mesh with ``comm="allreduce"`` and
  ``comm="neighbour"``, each compared with ``solver="vmapped"`` on the
  same stream.

Each phase prints one line; the timings on it are smoke timings, not a
benchmark.  The last stdout line is ``{"ok": true, "device": {...}}``.
No TPU, a kernel that resolved to interpret mode or to the reference,
an error over its bound, or any exception exits non-zero before that
line.  One process; it starts no children.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N, M_OBS, SCENARIO = 2048, 2000, "drifting_swarm"
CYCLES = 4
# 60 Schwarz sweeps: a CPU rehearsal of phase A's stream reached the
# float64 rounding floor (max error 4.0e-15 over 4 cycles); 24 sweeps
# still left 8.6e-9 and 32 left 2.0e-11.
ITERS = 60
# Phase A bound: what 24 sweeps reach, so a solve that stopped
# converging fails it, with room over the CPU floor for the TPU's
# float64 emulation and its different reduction order (a v5e measured
# 1.8e-13).
TOL_F64 = 1e-9
# Float32 bound (phase B and --chips 4).  On a v5e phase B measured a
# max error of 0.0213 against the float32 direct solve (|x| ~ 20, so
# ~1e-3 relative): float32 dots there run at the TPU's default precision
# in the engine and in the direct solve alike (phase B prints the
# relative error of one such dot, and of the Pallas gram, against
# float64).  0.1 sits ~5x above that; a wrong tile, block or layout
# gives errors of the order of |x|.
TOL_F32 = 0.1
# --chips 4: shardmap and vmapped run the same float32 program but for
# the order of the cross-device sums, and the Schwarz map contracts, so
# that rounding does not grow over the sweeps.  Four v5e chips measured
# a max difference of 3.0e-8; 1e-5 leaves 300x of room for other sum
# orders and is 2000 times under the error both share against the
# direct solve.
TOL_AGREE = 1e-5


def device_or_exit(chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU — JAX found platform "
                 f"{devs[0].platform!r} ({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX found {len(devs)}")
    return devs


def run_stream(cfg):
    """Run one engine over the phase's stream; returns (journal, the
    packings it built, its per-cycle analyses)."""
    from repro.assim import AssimilationEngine
    eng = AssimilationEngine(cfg)
    packs, analyses = [], {}
    prepare = eng.prepare

    def observed_prepare(*args, **kw):
        prep = prepare(*args, **kw)
        packs.append(prep.packed_op)
        return prep

    eng.prepare = observed_prepare
    eng.on_analysis = lambda c, x: analyses.__setitem__(c, np.asarray(x))
    journal = eng.run_scenario(SCENARIO, m=M_OBS, cycles=CYCLES)
    return journal, packs, analyses


def pallas_calls(fn, *args, **kw) -> int:
    """Native Pallas kernels in fn's compiled program (interpret mode
    and the jnp reference leave none)."""
    return jax.jit(fn, static_argnames=tuple(kw)).lower(
        *args, **kw).compile().as_text().count("tpu_custom_call")


def f32_precision() -> dict:
    """Relative error, against a float64 product on the host, of one
    float32 product on the chip: an XLA dot at the default precision and
    the Pallas gram kernel."""
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    a = rng.standard_normal((1, 1024, 256))
    r = rng.uniform(0.5, 2.0, (1, 1024))
    want = np.einsum("pmw,pm,pmv->pwv", a, r, a)
    a32, r32 = jax.numpy.asarray(a, "float32"), jax.numpy.asarray(r, "float32")
    got = {"dot_default": jax.jit(lambda a, r: jax.numpy.einsum(
               "pmw,pm,pmv->pwv", a, r, a))(a32, r32),
           "gram_kernel": ops.gram(a32, r32, mode="kernel", block_m=512)}
    scale = np.abs(want).max()
    return {f"{k}_rel_err": float(np.abs(np.asarray(v) - want).max() / scale)
            for k, v in got.items()}


def phase_line(name, devs, cfg, journal, packs, **extra) -> dict:
    from repro.kernels import ops
    pk = packs[-1]
    dtype = pk.A_loc.dtype
    recs = journal.records
    steady = [r.cycle_time for r in recs[1:]]
    row = {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "devices": len(devs),
        "dtype": str(dtype),
        "solver": cfg.solver,
        "solve_kernel": pk.solve_kernel,
        "solve_block": pk.solve_block,
        "gram_block": ops.gram_block_for(pk.A_loc.shape, dtype),
        "cycles": len(recs),
        "repartitions": sum(r.repartitioned for r in recs),
        "max_error_vs_direct": max(r.error_vs_direct for r in recs),
        "smoke_p50_cycle_s": float(np.median(steady)) if steady else None,
        "peak_bytes_in_use": (devs[0].memory_stats() or {}).get(
            "peak_bytes_in_use"),
        **extra,
    }
    print(f"phase {name}: " + " ".join(f"{k}={v}" for k, v in row.items())
          + "  (smoke timing, not a benchmark)", flush=True)
    return row


def check(ok: bool, what: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED — {what}")


def phase_a(devs) -> None:
    from repro.assim import EngineConfig
    jax.config.update("jax_enable_x64", True)
    cfg = EngineConfig(n=N, p=8, iters=ITERS, track_reference=True)
    journal, packs, _ = run_stream(cfg)
    row = phase_line("A", devs, cfg, journal, packs, tol=TOL_F64)
    check(row["dtype"] == "float64", f"phase A ran in {row['dtype']}")
    check(row["cycles"] == CYCLES, f"phase A ran {row['cycles']} cycles")
    check(row["repartitions"] >= 1, "phase A fired no DyDD repartition")
    check(row["max_error_vs_direct"] <= TOL_F64,
          f"phase A error {row['max_error_vs_direct']} > {TOL_F64}")


def phase_b(devs) -> None:
    from repro.assim import EngineConfig
    from repro.core import ddkf
    from repro.kernels import ops
    jax.config.update("jax_enable_x64", False)
    cfg = EngineConfig(n=N, p=8, iters=ITERS, track_reference=True)
    journal, packs, _ = run_stream(cfg)
    pk = ddkf.with_rhs(packs[-1], np.zeros((packs[-1].m,), np.float32))
    gram_block = ops.gram_block_for(pk.A_loc.shape, pk.A_loc.dtype)
    solve_calls = pallas_calls(ddkf.solve_vmapped, pk, iters=ITERS)
    r = jax.numpy.broadcast_to(pk.r, (pk.p, pk.m))
    gram_calls = pallas_calls(lambda a, r: ops.gram(
        a, r, block_m=gram_block), pk.A_loc, r)
    row = phase_line("B", devs, cfg, journal, packs, tol=TOL_F32,
                     pallas_calls_solve=solve_calls,
                     pallas_calls_gram=gram_calls, **f32_precision())
    check(row["dtype"] == "float32", f"phase B ran in {row['dtype']}")
    check(row["solve_kernel"] == "fused",
          f"solve kernel resolved to {row['solve_kernel']!r}, not 'fused'")
    check(row["solve_block"] is not None and gram_block is not None,
          "a fused or gram block is None: the jnp reference ran")
    check(solve_calls >= 2 and gram_calls >= 1,
          f"native Pallas kernels missing from the compiled programs "
          f"(solve {solve_calls}, gram {gram_calls}): interpret mode or "
          f"the reference ran")
    check(row["cycles"] == CYCLES, f"phase B ran {row['cycles']} cycles")
    check(row["max_error_vs_direct"] <= TOL_F32,
          f"phase B error {row['max_error_vs_direct']} > {TOL_F32}")


def phase_sharded(devs) -> None:
    from repro.assim import EngineConfig
    jax.config.update("jax_enable_x64", False)
    base = dict(n=N, p=4, overlap=1, iters=ITERS, track_reference=True)
    cfg = EngineConfig(solver="vmapped", **base)
    journal, packs, ref = run_stream(cfg)
    row = phase_line("4chip-vmapped", devs, cfg, journal, packs,
                     tol=TOL_F32)
    check(row["max_error_vs_direct"] <= TOL_F32,
          f"vmapped error {row['max_error_vs_direct']} > {TOL_F32}")
    for comm in ("allreduce", "neighbour"):
        cfg = EngineConfig(solver="shardmap", comm=comm, **base)
        journal, packs, got = run_stream(cfg)
        agree = max(float(np.max(np.abs(got[c] - ref[c]))) for c in ref)
        row = phase_line(f"4chip-shardmap-{comm}", devs, cfg, journal,
                         packs, tol=TOL_F32,
                         max_abs_diff_vs_vmapped=agree,
                         agree_tol=TOL_AGREE)
        check(sorted(got) == sorted(ref), f"{comm}: cycles differ")
        check(agree <= TOL_AGREE,
              f"shardmap/{comm} differs from vmapped by {agree}")
        check(row["max_error_vs_direct"] <= TOL_F32,
              f"shardmap/{comm} error {row['max_error_vs_direct']} > "
              f"{TOL_F32}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded solve on four chips")
    args = ap.parse_args()
    devs = device_or_exit(args.chips)
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(devs)
    else:
        phase_a(devs)
        phase_b(devs)
    print(f"chip_smoke: all phases passed in "
          f"{time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
