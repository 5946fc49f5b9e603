"""Multi-tenant serving benchmark: fleet-batched vs sequential streams.

For each fleet size S (default 16 and 64; the full curve in the paper
runs 16/64/256/1024) build S independent assimilation streams from a
mixed scenario pool (1D interval, 2D shelf, 2D adaptive k-d tree —
different shapes land in different compiled cohorts, exercising the
shape bucketing) and run them twice:

* **sequential** — one ``AssimilationEngine.run`` per stream, back to
  back: the per-engine loop a tenant would run alone;
* **fleet** — all S streams through one :class:`FleetServer`
  (continuous batching on the shared slot scheduler, cohort-stacked
  ``lax.map`` solves, host packing on a thread pool).

Reported per fleet size: sustained cycles/sec, per-cycle latency
p50/p99 (from the journals' measured ``cycle_time``), the
``fleet_over_sequential_throughput`` ratio the CI smoke gate asserts
``> 1``, and a ``bitwise_identical`` flag comparing every stream's
final analysis across the two arms (the determinism contract,
end-to-end).  The fleet arm's telemetry (queue-depth gauge,
admission/retirement events, per-cohort dispatch counters) is snapshot
from :mod:`repro.obs.meters` into the report.

  PYTHONPATH=src python benchmarks/serving_bench.py --out serving.json
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python benchmarks/serving_bench.py --streams 64 --cycles 3 \
      --out serving.json                              # CI smoke shape
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.assim import (  # noqa: E402
    AssimilationEngine, EngineConfig, FleetServer, streams)
from repro.obs import meters as obs_meters  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402


def scenario_pool(args):
    """(name, config, scenario) templates cycled over the fleet: three
    domain kinds so a mixed fleet always spans several shape cohorts."""
    return [
        ("drifting_swarm",
         EngineConfig(n=args.n, p=args.p, iters=args.iters)),
        ("bursty_clusters",
         EngineConfig(n=args.n, p=args.p, iters=args.iters)),
        ("rotating_swarm",
         EngineConfig(ndim=2, nx=args.nx, ny=args.ny, pr=args.pr,
                      pc=args.pc, iters=args.iters)),
        ("satellite_track",
         EngineConfig(ndim=2, domain_kind="kdtree", nx=args.nx2,
                      ny=args.ny2, p=args.p, iters=args.iters)),
    ]


def build_specs(count: int, args):
    pool = scenario_pool(args)
    return [(f"s{i}",) + pool[i % len(pool)] + (i,)
            for i in range(count)]


def latency_stats(journals) -> dict:
    lat = np.array([rec.cycle_time for j in journals.values()
                    for rec in j.records])
    return {
        "cycles": int(lat.size),
        "latency_p50": float(np.percentile(lat, 50)) if lat.size else 0.0,
        "latency_p99": float(np.percentile(lat, 99)) if lat.size else 0.0,
    }


def run_sequential(specs, args) -> tuple:
    journals, finals = {}, {}
    t0 = time.perf_counter()
    for sid, name, cfg, seed in specs:
        eng = AssimilationEngine(cfg)
        journals[sid] = eng.run(
            streams.make_stream(name, args.m, args.cycles, seed=seed))
        finals[sid] = np.asarray(eng.analysis)
    wall = time.perf_counter() - t0
    row = {"wall_time": wall, **latency_stats(journals)}
    row["cycles_per_sec"] = row["cycles"] / wall if wall else 0.0
    return row, finals


def run_fleet(specs, args, mesh, solver=None) -> tuple:
    prev = obs_meters.set_meters(obs_meters.Meters())
    try:
        server = FleetServer(mesh=mesh, max_active=args.max_active,
                             pack_workers=args.pack_workers,
                             solver=solver)
        for sid, name, cfg, seed in specs:
            server.add_stream(sid, cfg, streams.make_stream(
                name, args.m, args.cycles, seed=seed))
        journals = server.serve()
        finals = {sid: np.asarray(eng.analysis)
                  for sid, eng in server.engines.items()}
        snap = obs_meters.get_meters().snapshot()
    finally:
        obs_meters.set_meters(prev)
    names = [e["name"] for e in snap["events"]]
    row = {"wall_time": server.stats["wall_time"],
           "rounds": server.stats["rounds"],
           **latency_stats(journals)}
    row["cycles_per_sec"] = (row["cycles"] / row["wall_time"]
                             if row["wall_time"] else 0.0)
    row["telemetry"] = {
        "cohort_dispatches": snap["counters"].get(
            "fleet.cohort.dispatches", 0.0),
        "cohort_members": snap["counters"].get("fleet.cohort.members",
                                               0.0),
        "padded_slots": snap["counters"].get("fleet.cohort.padded_slots",
                                             0.0),
        "admit_events": names.count("fleet.admit"),
        "retire_events": names.count("fleet.retire"),
        "dydd_repacks": names.count("fleet.dydd.repack"),
        "queue_depth_final": snap["gauges"].get("fleet.queue_depth"),
    }
    return row, finals


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--streams", type=int, nargs="+", default=[16, 64],
                    help="fleet sizes to sweep (paper curve: 16 64 256 "
                         "1024)")
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--m", type=int, default=120)
    ap.add_argument("--iters", type=int, default=25)
    ap.add_argument("--n", type=int, default=48)
    ap.add_argument("--p", type=int, default=4)
    ap.add_argument("--nx", type=int, default=12)
    ap.add_argument("--ny", type=int, default=8)
    ap.add_argument("--pr", type=int, default=2)
    ap.add_argument("--pc", type=int, default=2)
    ap.add_argument("--nx2", type=int, default=16,
                    help="kdtree raster width")
    ap.add_argument("--ny2", type=int, default=12,
                    help="kdtree raster height")
    ap.add_argument("--max-active", type=int, default=64,
                    help="fleet slot-table capacity (streams beyond it "
                         "queue FIFO)")
    ap.add_argument("--pack-workers", type=int, default=4)
    ap.add_argument("--no-mesh", action="store_true",
                    help="keep the fleet on one device even when more "
                         "are visible")
    ap.add_argument("--warmup", type=int, default=1,
                    help="unmeasured full passes per arm before the "
                         "measured one, so cycles/sec is *sustained* "
                         "throughput (compiled programs warm; the same "
                         "streams re-run hit the same shape cohorts). "
                         "0 = include compile time")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    n_dev = len(jax.devices())
    mesh = None
    if not args.no_mesh and n_dev > 1:
        mesh = jax.make_mesh((n_dev,), ("fleet",),
                             axis_types=(jax.sharding.AxisType.Auto,))

    report = {
        "bench_config": {k: v for k, v in vars(args).items()
                         if k != "out"},
        "devices": n_dev,
        "fleet_mesh": None if mesh is None else n_dev,
        "fleet_counts": {},
    }
    for count in args.streams:
        specs = build_specs(count, args)
        # One CohortSolver across warmup + measured passes: its pinned
        # cohort capacities (and the jitted programs keyed off them)
        # are what the warmup exists to stabilize.
        from repro.assim import fleet as fleet_lib
        solver = fleet_lib.CohortSolver(mesh=mesh)
        for _ in range(args.warmup):
            run_sequential(specs, args)
            run_fleet(specs, args, mesh, solver=solver)
        seq_row, seq_finals = run_sequential(specs, args)
        fleet_row, fleet_finals = run_fleet(specs, args, mesh,
                                            solver=solver)
        bitwise = all(np.array_equal(seq_finals[sid], fleet_finals[sid])
                      for sid, *_ in specs)
        ratio = (fleet_row["cycles_per_sec"] / seq_row["cycles_per_sec"]
                 if seq_row["cycles_per_sec"] else 0.0)
        report["fleet_counts"][str(count)] = {
            "sequential": seq_row,
            "fleet": fleet_row,
            "fleet_over_sequential_throughput": ratio,
            "bitwise_identical": bool(bitwise),
        }
        print(f"S={count:5d}  seq {seq_row['cycles_per_sec']:8.2f} cyc/s "
              f"(p50 {seq_row['latency_p50']*1e3:7.1f} ms, "
              f"p99 {seq_row['latency_p99']*1e3:7.1f} ms)  "
              f"fleet {fleet_row['cycles_per_sec']:8.2f} cyc/s "
              f"(p50 {fleet_row['latency_p50']*1e3:7.1f} ms, "
              f"p99 {fleet_row['latency_p99']*1e3:7.1f} ms)  "
              f"ratio {ratio:5.2f}x  bitwise={bitwise}")
        sys.stdout.flush()

    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
