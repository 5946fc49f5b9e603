"""Fault-tolerance benchmark: snapshot overhead, resume latency, elastic
remesh quality, and chaos-injection accounting.

Four sections, one JSON report (gated by ``benchmarks/regress.py``):

* **snapshot_overhead** — the streaming engine run at several snapshot
  cadences vs uncheckpointed: per-snapshot p50 wall time, its share of
  the mean cycle time (the machine-normalized ratio the gate watches),
  and the end-to-end wall-time overhead;
* **resume** — restore latency from a mid-stream checkpoint and a
  bitwise check that the resumed journal equals the uninterrupted run
  (the determinism contract, measured end-to-end);
* **remesh_quality** — scale-down p=8 -> p=4 for the shelf and k-d tree
  domains on the slowly-drifting ``coastal_band`` network: the first
  resumed cycle's load imbalance under the elastically re-derived tiling
  vs a cold default tiling at the new p (both deterministic given the
  stream seed — the elastic path's whole reason to exist is that ratio
  staying below 1; a fast-moving network like ``rotating_swarm`` would
  make any load history stale by construction);
* **fault_injection** — a chaos run (scheduled transient pack/solve
  faults, retried with backoff) vs a clean run: retry counts and a
  bitwise journal comparison (retries must not perturb numerics).

``--kill-resume`` switches to the CI smoke orchestration: spawn a child
process (``--child-run``) that SIGKILLs itself mid-stream via a chaos
kill point, resume from the surviving checkpoint in the parent, and
exit non-zero unless the concatenated journal is bitwise identical to
an uninterrupted run.

  PYTHONPATH=src python benchmarks/chaos_bench.py --out chaos.json
  PYTHONPATH=src python benchmarks/chaos_bench.py --kill-resume
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.assim import AssimilationEngine, EngineConfig, streams  # noqa: E402
from repro.assim.metrics import imbalance_ratio  # noqa: E402
from repro.checkpoint import manager as ckpt  # noqa: E402
from repro.core import domain as domain_mod  # noqa: E402
from repro.core import kdtree as kdtree_mod  # noqa: E402
from repro.obs import meters as obs_meters  # noqa: E402
from repro.runtime import elastic  # noqa: E402
from repro.runtime.chaos import ChaosConfig, ChaosInjector  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402

# The kill-and-resume smoke's shared shape: the child and the parent's
# uninterrupted reference must build the exact same run.
KILL_CFG = dict(n=48, p=3, iters=10)
KILL_STREAM = dict(name="drifting_swarm", m=80, cycles=10, seed=2)
KILL_AT_CYCLE = 5
KILL_SNAPSHOT_EVERY = 2


def _stream(args, seed_off: int = 0):
    return streams.ResumableStream("drifting_swarm", args.m, args.cycles,
                                   seed=args.seed + seed_off)


def _engine(args, **kw):
    return AssimilationEngine(
        EngineConfig(n=args.n, p=args.p, iters=args.iters), **kw)


def _timed_run(args, **run_kw):
    """(journal, wall, meters snapshot) of one engine run on fresh
    meters."""
    prev = obs_meters.set_meters(obs_meters.Meters())
    try:
        eng = _engine(args)
        t0 = time.perf_counter()
        j = eng.run(_stream(args), **run_kw)
        wall = time.perf_counter() - t0
        snap = obs_meters.get_meters().snapshot()
    finally:
        obs_meters.set_meters(prev)
    return j, wall, snap


def bench_snapshot_overhead(args, workdir: str) -> tuple:
    """Per-cadence snapshot cost; returns (rows, the cadence runs'
    checkpoint dirs) so the resume section can reuse a saved state."""
    # Warm compile, then the measured uncheckpointed reference.
    _timed_run(args)
    base_j, base_wall, _ = _timed_run(args)
    rows = {"baseline": {"wall_time": base_wall,
                         "cycle_time_mean": float(np.mean(
                             [r.cycle_time for r in base_j.records]))}}
    dirs = {}
    for cadence in args.cadences:
        ck = os.path.join(workdir, f"cadence_{cadence}")
        j, wall, snap = _timed_run(args, checkpoint_dir=ck,
                                   snapshot_every=cadence)
        times = snap["series"].get("engine.snapshot_time", [])
        cyc_mean = float(np.mean([r.cycle_time for r in j.records]))
        p50 = float(np.percentile(times, 50)) if times else 0.0
        rows[f"cadence_{cadence}"] = {
            "snapshots": len(times),
            "snapshot_p50_ms": p50 * 1e3,
            "snapshot_over_cycle_ratio": (p50 / cyc_mean if cyc_mean
                                          else 0.0),
            "wall_time": wall,
            "wall_overhead_ratio": (wall / base_wall - 1.0 if base_wall
                                    else 0.0),
            "cycle_time_mean": cyc_mean,
        }
        dirs[cadence] = ck
        print(f"cadence={cadence:3d}  {len(times):3d} snapshots  "
              f"p50 {p50*1e3:7.2f} ms  "
              f"({rows[f'cadence_{cadence}']['snapshot_over_cycle_ratio']:.3f} "
              f"of a cycle)  wall overhead "
              f"{rows[f'cadence_{cadence}']['wall_overhead_ratio']:+.1%}")
    return rows, (base_j, dirs)


def bench_resume(args, base_j, dirs) -> dict:
    """Restore latency from a mid-stream checkpoint + bitwise check."""
    cadence = args.cadences[0]
    ck = dirs[cadence]
    # A mid-stream step (not the final one): half the cycles, rounded to
    # the cadence grid.
    mid = max(cadence, (args.cycles // 2) // cadence * cadence)
    path = os.path.join(ck, f"step_{mid:08d}")
    t0 = time.perf_counter()
    eng, stream = elastic.resume_assim_engine(path)
    restore_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    j = eng.run(stream)
    replay_s = time.perf_counter() - t0
    bitwise = j.deterministic_json() == base_j.deterministic_json()
    row = {
        "resumed_from_cycle": mid,
        "restore_latency_s": restore_s,
        "remaining_cycles": args.cycles - mid,
        "resumed_run_s": replay_s,
        "restore_bitwise": float(bitwise),
    }
    print(f"resume from cycle {mid}: restore {restore_s*1e3:.1f} ms, "
          f"{args.cycles - mid} cycles in {replay_s:.2f} s, "
          f"bitwise={bitwise}")
    return row


def bench_remesh_quality(args, workdir: str) -> dict:
    """p=8 -> p=4 scale-down: first-cycle imbalance of the elastically
    re-derived tiling vs a cold default tiling, shelf and kdtree."""
    out = {}
    specs = {
        "shelf": (EngineConfig(n=64, ndim=2, nx=8, ny=8, pr=4, pc=2,
                               iters=args.iters),
                  lambda: domain_mod.ShelfTiling2D(nx=8, ny=8, pr=2,
                                                   pc=2)),
        "kdtree": (EngineConfig(n=64, domain_kind="kdtree", p=8, nx=8,
                                ny=8, iters=args.iters),
                   lambda: kdtree_mod.KDTreeDomain(nx=8, ny=8, p=4)),
    }
    for kind, (cfg, cold_domain) in specs.items():
        ck = os.path.join(workdir, f"remesh_{kind}")
        eng = AssimilationEngine(cfg)
        eng.run(streams.ResumableStream("coastal_band", args.m, 6,
                                        seed=args.seed),
                checkpoint_dir=ck, snapshot_every=3)
        eng2, stream2 = elastic.resume_assim_engine(
            os.path.join(ck, "step_00000003"), p=4)
        # The first resumed cycle's observations, against the elastic vs
        # the cold tiling — before any rebalance can repair either.
        obs = next(iter(streams.ResumableStream.from_cursor(
            stream2.cursor)))
        imb_elastic = imbalance_ratio(eng2.domain.counts(obs))
        imb_cold = imbalance_ratio(cold_domain().counts(obs))
        out[kind] = {
            "p_from": 8, "p_to": 4,
            "first_cycle_imbalance_elastic": float(imb_elastic),
            "first_cycle_imbalance_cold": float(imb_cold),
            "elastic_over_cold": (float(imb_elastic / imb_cold)
                                  if imb_cold else 0.0),
        }
        print(f"remesh {kind:7s} p8->p4: imbalance elastic "
              f"{imb_elastic:.3f} vs cold {imb_cold:.3f} "
              f"(ratio {out[kind]['elastic_over_cold']:.3f})")
    return out


def bench_fault_injection(args) -> dict:
    """Chaos run vs clean run: retries journalled, numerics untouched."""
    clean = _engine(args).run(_stream(args, seed_off=1))
    inj = ChaosInjector(ChaosConfig(
        pack_fault_cycles=(1, 3), solve_fault_cycles=(2,)))
    prev = obs_meters.set_meters(obs_meters.Meters())
    try:
        chaotic = _engine(args, chaos=inj).run(_stream(args, seed_off=1))
        snap = obs_meters.get_meters().snapshot()
    finally:
        obs_meters.set_meters(prev)
    bitwise = chaotic.deterministic_json() == clean.deterministic_json()
    row = {
        "injected_pack": snap["counters"].get("chaos.injected.pack", 0.0),
        "injected_solve": snap["counters"].get("chaos.injected.solve",
                                               0.0),
        "retries": snap["counters"].get("chaos.retries", 0.0),
        "journal_bitwise": float(bitwise),
        "schedule": inj.schedule(),
    }
    print(f"fault injection: {row['injected_pack']:.0f} pack + "
          f"{row['injected_solve']:.0f} solve faults, "
          f"{row['retries']:.0f} retries, bitwise={bitwise}")
    return row


# ---------------------------------------------------------------------------
# Kill-and-resume smoke (CI): child SIGKILLs itself, parent resumes.
# ---------------------------------------------------------------------------

def child_run(checkpoint_dir: str) -> None:
    inj = ChaosInjector(ChaosConfig(kill_cycles=(KILL_AT_CYCLE,)))
    eng = AssimilationEngine(EngineConfig(**KILL_CFG), chaos=inj)
    eng.run(streams.ResumableStream(**KILL_STREAM),
            checkpoint_dir=checkpoint_dir,
            snapshot_every=KILL_SNAPSHOT_EVERY)
    print("UNREACHABLE: kill point did not fire", file=sys.stderr)
    sys.exit(3)


def kill_resume_smoke() -> None:
    with tempfile.TemporaryDirectory() as workdir:
        ck = os.path.join(workdir, "ck")
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child-run",
             "--checkpoint-dir", ck],
            env=dict(os.environ), capture_output=True, text=True,
            timeout=600)
        if proc.returncode != -signal.SIGKILL:
            print(f"[chaos] child exited {proc.returncode}, expected "
                  f"SIGKILL ({-signal.SIGKILL})\n{proc.stderr[-2000:]}",
                  file=sys.stderr)
            sys.exit(1)
        latest = ckpt.latest_checkpoint(ck)
        if latest is None:
            print("[chaos] no surviving checkpoint after kill",
                  file=sys.stderr)
            sys.exit(1)
        print(f"[chaos] child SIGKILLed after cycle {KILL_AT_CYCLE}; "
              f"resuming from {os.path.basename(latest)}")
        base = AssimilationEngine(EngineConfig(**KILL_CFG)).run(
            streams.ResumableStream(**KILL_STREAM))
        eng, stream = elastic.resume_assim_engine(ck)
        j = eng.run(stream)
        if j.deterministic_json() != base.deterministic_json():
            print("[chaos] resumed journal is NOT bitwise identical to "
                  "the uninterrupted run", file=sys.stderr)
            sys.exit(1)
        print(f"[chaos] kill-and-resume OK: {len(j.records)} cycles, "
              f"journal bitwise identical across the kill")


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--cycles", type=int, default=12)
    ap.add_argument("--m", type=int, default=120)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--n", type=int, default=64)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--cadences", type=int, nargs="+", default=[2, 6],
                    help="snapshot_every values to sweep")
    ap.add_argument("--kill-resume", action="store_true",
                    help="run the kill-and-resume CI smoke instead of "
                         "the benchmark")
    ap.add_argument("--child-run", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--checkpoint-dir", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    if args.child_run:
        child_run(args.checkpoint_dir)
        return
    if args.kill_resume:
        kill_resume_smoke()
        return

    report = {
        "bench_config": {k: v for k, v in vars(args).items()
                         if k not in ("out", "child_run",
                                      "checkpoint_dir", "kill_resume")},
        "devices": len(jax.devices()),
        "chaos": {},
    }
    with tempfile.TemporaryDirectory() as workdir:
        rows, (base_j, dirs) = bench_snapshot_overhead(args, workdir)
        report["chaos"]["snapshot_overhead"] = rows
        report["chaos"]["resume"] = bench_resume(args, base_j, dirs)
        report["chaos"]["remesh_quality"] = \
            bench_remesh_quality(args, workdir)
    report["chaos"]["fault_injection"] = bench_fault_injection(args)

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
