"""Streaming assimilation benchmark: static DD vs online DyDD, 1D and 2D.

For every registered observation-stream scenario — 1D interval domains and
2D shelf tilings alike — run the multi-cycle engine twice:
``rebalance=False`` (the paper's static decomposition, left to degrade as
the network moves) and ``rebalance=True`` (online DyDD with the default
threshold/hysteresis policy) — and emit a JSON comparison of per-cycle
latency (split into host+device *pack* vs device *solve*, so the batched
``kernels.ops.gram`` packing win is visible) and the imbalance trajectory.

The report also carries the communication accounting: per-arm modelled
``comm_bytes_per_cycle`` + ``halo_fraction``, a ``comm_sweep`` section
pricing the allreduce vs neighbour (halo-only ppermute) state exchange
across overlap widths s = 0..3, and — with ``--compare-comm`` on a
sharded run — measured wall-clock for both paths side by side plus the
max-abs difference of their final analyses (the ULP-parity evidence).
``--compare-kernels`` does the same for the Schwarz sweep's kernel: the
jnp reference vs the fused kernel (``solver_kernel=``), recording
solve-phase wall-clock shares and the final-analysis parity.

``--compare-domains`` additionally runs every 2D scenario's DyDD arm on
both the shelf tiling and the adaptive k-d tree domain at equal p
(pr*pc cells vs pr*pc leaves) and records final imbalance, migration
volume and comm bytes side by side — on the anisotropic station-network
scenarios (``satellite_track``, ``river_gauges``) the kdtree's final
imbalance sits strictly below the shelf's.

  PYTHONPATH=src python benchmarks/streaming_bench.py --out streaming.json
  PYTHONPATH=src python benchmarks/streaming_bench.py \
      --n 96 --m 200 --cycles 4 --scenarios drifting_swarm    # smoke
  PYTHONPATH=src python benchmarks/streaming_bench.py \
      --nx 12 --ny 8 --pr 2 --pc 2 --scenarios rotating_swarm # 2D smoke
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.assim import AssimilationEngine, EngineConfig, streams  # noqa: E402
from repro.core import ddkf, domain, kdtree  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.obs import meters as obs_meters  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402


def make_config(ndim: int, rebalance: bool, args,
                comm: str | None = None,
                domain_kind: str | None = None,
                solver_kernel: str | None = None) -> EngineConfig:
    common = dict(iters=args.iters, rebalance=rebalance,
                  imbalance_threshold=args.threshold,
                  track_reference=args.track_reference,
                  solver=args.solver, overlap=args.overlap,
                  comm=comm or args.comm, halo_weight=args.halo_weight,
                  record_residuals=not args.no_residuals,
                  solver_kernel=solver_kernel or args.solver_kernel)
    if ndim == 1:
        return EngineConfig(n=args.n, p=args.p, **common)
    kind = domain_kind or args.domain
    if kind == "kdtree":
        # Equal p: the k-d tree gets exactly as many leaves as the shelf
        # has cells, so the comparison is like for like.
        return EngineConfig(ndim=2, domain_kind="kdtree",
                            p=args.pr * args.pc, nx=args.nx, ny=args.ny,
                            damping=args.damping_2d, **common)
    return EngineConfig(ndim=2, nx=args.nx, ny=args.ny,
                        pr=args.pr, pc=args.pc, damping=args.damping_2d,
                        **common)


_WALL_CLOCK_S: list = []   # measured per-arm wall-clock, for the trace
                           # coverage figure (sum of journal cycle times)


def run_arm(name: str, rebalance: bool, args, comm: str | None = None,
            domain_kind: str | None = None,
            solver_kernel: str | None = None):
    """Run one engine arm; returns (record_dict, final_analysis)."""
    ndim = streams.get(name).ndim
    eng = AssimilationEngine(make_config(ndim, rebalance, args, comm=comm,
                                         domain_kind=domain_kind,
                                         solver_kernel=solver_kernel))
    journal = eng.run_scenario(name, m=args.m, cycles=args.cycles,
                               seed=args.seed)
    cycle_times = journal.cycle_times
    _WALL_CLOCK_S.append(float(np.sum(cycle_times)))
    pack_times = [r.pack_time for r in journal.records]
    solve_times = [r.solve_time for r in journal.records]
    imb = journal.imbalance_trajectory
    return {
        "rebalance": rebalance,
        "solver": args.solver,
        "solver_kernel": solver_kernel or args.solver_kernel,
        "overlap": args.overlap,
        "comm": comm or args.comm,
        "halo_weight": args.halo_weight,
        "domain": journal.meta,
        # Modelled per-cycle communication volume of the configured comm
        # path plus the decomposition's shared-slot fraction (both from
        # the journal; the model prices solve_shardmap traffic even when
        # the arm ran the vmapped solver).
        "comm_bytes_per_cycle": [r.comm_bytes_per_cycle
                                 for r in journal.records],
        "halo_fraction": [r.halo_fraction for r in journal.records],
        "loads_weighted_final": journal.records[-1].loads_weighted,
        "imbalance_trajectory": imb,
        "imbalance_final": imb[-1],
        "efficiency_trajectory": [r.efficiency for r in journal.records],
        "cycle_latency_s": cycle_times,
        "cycle_latency_mean_s": float(np.mean(cycle_times)),
        # Steady-state latency: drop the first cycles, which pay the jit
        # specialization for each new padded block width.
        "cycle_latency_steady_s": float(np.mean(
            cycle_times[len(cycle_times) // 2:])),
        # Pack (host slicing + batched device gram/cholesky) vs solve
        # (device DD-KF iteration) — the per-cycle split.
        "pack_time_s": pack_times,
        "solve_time_s": solve_times,
        "pack_time_mean_s": float(np.mean(pack_times)),
        "solve_time_mean_s": float(np.mean(solve_times)),
        "repartitions": journal.repartition_count,
        "migrated_total": journal.migrated_total,
        # Telemetry: per-cycle Schwarz residual histories (empty with
        # --no-residuals), per-phase p50/p99, per-edge comm bytes + the
        # assembled (p, p) matrix of the final cycle, per-device solve
        # times and any straggler flags.
        "residual_history": [r.residual_history for r in journal.records],
        "phases": journal.phase_stats(),
        "comm_edge_bytes_per_cycle": [r.comm_edge_bytes_per_cycle
                                      for r in journal.records],
        "comm_matrix_final": obs_meters.comm_matrix(
            eng.p,
            journal.records[-1].comm_edge_bytes_per_cycle).tolist(),
        "comm_mvec_bytes_per_cycle": [r.comm_mvec_bytes_per_cycle
                                      for r in journal.records],
        "device_solve_times": [r.device_solve_times
                               for r in journal.records],
        "straggler_flags": [r.straggler_flags for r in journal.records],
        "summary": journal.summary(),
    }, (None if eng.analysis is None else np.asarray(eng.analysis))


def comm_sweep(args) -> dict:
    """Modelled per-iteration state-exchange bytes vs overlap width s.

    For the benchmark's 1D and 2D domain shapes (uniform boundaries —
    the model depends only on the decomposition geometry), price both
    communication paths at s = 0..3: the allreduce path is flat in s
    (it always moves the full n-vector), the neighbour path grows
    linearly with s and never depends on n — the scaling regime the
    paper's T^p_oh overhead term assumes.
    """
    itemsize = 8  # the benchmark engines run under jax_enable_x64
    out = {}
    domains = {
        "1d": domain.Interval1D(n=args.n, p=args.p),
        "2d": domain.ShelfTiling2D(nx=args.nx, ny=args.ny,
                                   pr=args.pr, pc=args.pc),
        "kdtree": kdtree.KDTreeDomain(nx=args.nx, ny=args.ny,
                                      p=args.pr * args.pc),
    }
    for key, dom in domains.items():
        rows = {}
        # stacked rows: the background block (dom.n) + observations
        m = dom.n + args.m
        mesh_shape = dom.mesh_axes()[1]
        for s in range(4):
            dec = dom.decomposition(overlap=s)
            halo = dec.halo_exchange
            alla = ddkf.comm_model(dom.n, m, dom.p, itemsize,
                                   comm="allreduce",
                                   mesh_shape=mesh_shape)
            neigh = ddkf.comm_model(dom.n, m, dom.p, itemsize,
                                    halo=halo, comm="neighbour",
                                    mesh_shape=mesh_shape)
            rows[f"s{s}"] = {
                "halo_fraction": dec.halo_fraction,
                "allreduce_state_bytes_per_device":
                    alla["state_bytes_per_device_mean"],
                "neighbour_state_bytes_per_device":
                    neigh["state_bytes_per_device_mean"],
                "neighbour_per_edge_bytes": neigh["per_edge_bytes"],
                "permute_rounds": neigh["permute_rounds"],
            }
        out[key] = rows
    return out


def pint_section(args) -> dict:
    """Parallel-in-time arm: one long 1D stream run sequentially and
    under the Parareal window engine (``repro.assim.timepar``), with the
    wall-clock cycles/sec ratio, the Parareal iteration evidence and the
    analysis-chain parity recorded side by side.

    Both arms are warmed up on the *same full stream* first so jit
    compilation does not land in either measurement: the window-stacked
    program (and the padded solver programs) are specific to the
    stream-wide max block width, which a short prefix would not
    reproduce — DyDD drifts the widths over the stream.
    """
    from repro.assim.timepar import TimeParEngine

    name, cycles = "drifting_swarm", args.pint_cycles
    cfg_kw = dict(n=args.n, p=args.p, iters=args.iters,
                  record_residuals=False)
    pint_cfg = EngineConfig(time_windows=args.time_windows,
                            pint_tol=args.pint_tol,
                            pint_fine_iters=args.pint_fine_iters,
                            pint_coarse_iters=args.pint_coarse_iters,
                            **cfg_kw)

    print(f"[streaming_bench] pint warmup ({cycles} cycles, both arms)"
          f" ...", file=sys.stderr)
    AssimilationEngine(EngineConfig(**cfg_kw)).run(
        streams.make_stream(name, args.m, cycles, seed=args.seed))
    TimeParEngine(pint_cfg).run(
        streams.make_stream(name, args.m, cycles, seed=args.seed))

    print(f"[streaming_bench] pint sequential arm ({cycles} cycles) ...",
          file=sys.stderr)
    seq = AssimilationEngine(EngineConfig(**cfg_kw))
    chain: list = []
    seq.on_analysis = lambda c, x: chain.append(np.asarray(x))
    t0 = time.perf_counter()
    seq.run(streams.make_stream(name, args.m, cycles, seed=args.seed))
    seq_wall = time.perf_counter() - t0

    print(f"[streaming_bench] pint windowed arm (W={args.time_windows})"
          f" ...", file=sys.stderr)
    tp = TimeParEngine(pint_cfg)
    t0 = time.perf_counter()
    journal = tp.run(streams.make_stream(name, args.m, cycles,
                                         seed=args.seed))
    pint_wall = time.perf_counter() - t0

    meta = journal.meta["pint"]
    diff = max(float(np.max(np.abs(a - b)))
               for a, b in zip(tp.analyses, chain))
    return {
        "scenario": name,
        "cycles": cycles,
        "time_windows": meta["time_windows"],
        "window_sizes": meta["window_sizes"],
        "mesh": meta["mesh"],
        "coarse_iters": meta["coarse_iters"],
        "fine_iters": meta["fine_iters"],
        "warm_start": meta["warm_start"],
        "pint_iters": meta["iters"],
        "converged": bool(meta["converged"]),
        "correction_norms": meta["correction_norms"],
        "tol": meta["tol"],
        "sequential_wall_s": seq_wall,
        "pint_wall_s": pint_wall,
        "sequential_cycles_per_sec": cycles / max(seq_wall, 1e-12),
        "pint_cycles_per_sec": cycles / max(pint_wall, 1e-12),
        # The headline: windowed throughput over sequential on the same
        # stream (> 1 means the time axis bought real wall-clock).
        "pint_over_sequential_cycles_per_sec":
            seq_wall / max(pint_wall, 1e-12),
        "analysis_chain_max_abs_diff": diff,
    }


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n", type=int, default=256, help="1D state dimension")
    ap.add_argument("--p", type=int, default=8, help="1D subdomains")
    ap.add_argument("--nx", type=int, default=24, help="2D mesh width")
    ap.add_argument("--ny", type=int, default=12, help="2D mesh height")
    ap.add_argument("--pr", type=int, default=2, help="2D strip count")
    ap.add_argument("--pc", type=int, default=4, help="2D cells per strip")
    ap.add_argument("--damping-2d", type=float, default=0.7,
                    help="additive-Schwarz damping for the 2D tiling")
    ap.add_argument("--m", type=int, default=600)
    ap.add_argument("--cycles", type=int, default=8)
    ap.add_argument("--iters", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--threshold", type=float, default=1.5)
    ap.add_argument("--track-reference", action="store_true",
                    help="also journal per-cycle error vs one-shot solve")
    ap.add_argument("--solver", default="vmapped",
                    choices=("vmapped", "shardmap"),
                    help="shardmap needs one device per subdomain")
    ap.add_argument("--overlap", type=int, default=0,
                    help="Schwarz halo width")
    ap.add_argument("--comm", default="allreduce",
                    choices=("allreduce", "neighbour"),
                    help="sharded state-exchange path (neighbour = "
                    "halo-only ppermute rounds)")
    ap.add_argument("--halo-weight", type=float, default=0.0,
                    help="overlap-aware DyDD: work units per halo column "
                    "added to the scheduled loads")
    ap.add_argument("--solver-kernel", default="auto",
                    choices=ddkf.SOLVER_KERNELS,
                    help="local Schwarz step implementation (auto = "
                    "fused Pallas on TPU, jnp elsewhere)")
    ap.add_argument("--domain", default="shelf",
                    choices=("shelf", "kdtree"),
                    help="2D domain of the main arms: shelf tiling or "
                    "adaptive k-d tree (pr*pc leaves)")
    ap.add_argument("--compare-domains", action="store_true",
                    help="also run the DyDD arm of every 2D scenario "
                    "with both the shelf and the kdtree domain at equal "
                    "p and record final imbalance / migration volume / "
                    "comm bytes side by side")
    ap.add_argument("--compare-comm", action="store_true",
                    help="also run the DyDD arm with both comm paths and "
                    "record wall-clock + modelled bytes side by side "
                    "(meaningful with --solver shardmap)")
    ap.add_argument("--compare-kernels", action="store_true",
                    help="also run the DyDD arm with the jnp and the "
                    "fused Schwarz-step kernel and record wall-clock + "
                    "solve phase ratio side by side (the fused kernel "
                    "resolves to its interpret/reference path off-TPU)")
    ap.add_argument("--time-windows", type=int, default=0,
                    help="run the parallel-in-time section: a long "
                    "drifting_swarm stream sequentially and under the "
                    "Parareal window engine with this many windows "
                    "(sharded over a ('time','sub') mesh when the "
                    "device count factors); 0 = off")
    ap.add_argument("--pint-cycles", type=int, default=32,
                    help="stream length of the parallel-in-time section")
    ap.add_argument("--pint-tol", type=float, default=1e-8,
                    help="Parareal correction-norm stopping tolerance")
    ap.add_argument("--pint-coarse-iters", type=int, default=0,
                    help="Schwarz iterations of the coarse propagator "
                    "(0 = --iters // 10)")
    ap.add_argument("--pint-fine-iters", type=int, default=0,
                    help="Schwarz iterations of the warm-started fine "
                    "sweeps (0 = cold full --iters solves); the "
                    "work-optimal Parareal setting — coarse + fine "
                    "iterations together buy the accuracy, so the "
                    "windowed arm spends fewer total iterations per "
                    "cycle than the sequential arm at the same "
                    "analysis-chain tolerance")
    ap.add_argument("--scenarios", nargs="*", default=None,
                    choices=streams.available(),
                    help="subset of the registered scenarios "
                    "(default: all, 1D and 2D)")
    ap.add_argument("--out", default=None, help="write JSON here "
                    "(default: stdout)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome/Perfetto trace_events timeline "
                    "of every engine run here (open at ui.perfetto.dev)")
    ap.add_argument("--profile", default=None, metavar="LOGDIR",
                    help="wrap the runs in jax.profiler.trace into this "
                    "directory (TensorBoard XPlane; kernel-level)")
    ap.add_argument("--no-residuals", action="store_true",
                    help="skip the per-iteration Schwarz residual "
                    "histories (drops the lax.scan solve variant)")
    args = ap.parse_args()

    # Fresh telemetry sinks for this run: a meters registry (always — the
    # snapshot lands in the report) and a span tracer when --trace asks
    # for a timeline.  ExitStack keeps the scenario loop un-indented.
    obs_meters.set_meters(obs_meters.Meters())
    tracer = obs_trace.Tracer("streaming_bench") if args.trace else None
    ctx = contextlib.ExitStack()
    ctx.enter_context(obs_trace.tracing(tracer))
    ctx.enter_context(obs_trace.jax_profile(args.profile))

    names = args.scenarios or streams.available()
    report = {
        "config": {"n": args.n, "p": args.p, "nx": args.nx, "ny": args.ny,
                   "pr": args.pr, "pc": args.pc, "m": args.m,
                   "cycles": args.cycles, "iters": args.iters,
                   "seed": args.seed, "threshold": args.threshold,
                   "solver": args.solver, "overlap": args.overlap,
                   "comm": args.comm, "halo_weight": args.halo_weight,
                   "domain": args.domain,
                   "solver_kernel": args.solver_kernel,
                   "time_windows": args.time_windows,
                   "pint_cycles": args.pint_cycles,
                   "pint_fine_iters": args.pint_fine_iters},
        "scenarios": {},
        # Modelled bytes vs overlap width for both comm paths (no runs
        # needed — the model depends only on the decomposition).
        "comm_sweep": comm_sweep(args),
    }
    for name in names:
        ndim = streams.get(name).ndim
        print(f"[streaming_bench] {name} ({ndim}D) ...", file=sys.stderr)
        static, _ = run_arm(name, rebalance=False, args=args)
        dydd, x_dydd = run_arm(name, rebalance=True, args=args)
        report["scenarios"][name] = {
            "ndim": ndim,
            "static": static,
            "dydd": dydd,
            "imbalance_reduction": float(
                np.mean(static["imbalance_trajectory"])
                / max(np.mean(dydd["imbalance_trajectory"]), 1e-12)),
            "final_imbalance_reduction": float(
                static["imbalance_final"]
                / max(dydd["imbalance_final"], 1e-12)),
        }
        if args.compare_domains and ndim == 2:
            # Shelf-vs-kdtree at equal p (pr*pc cells vs pr*pc leaves):
            # final imbalance, migration volume, modelled comm bytes on
            # the anisotropic station networks.  Since tie-aware 2D
            # counting the shelf rank-splits tied coordinate groups and
            # lands on the m/p rounding floor even here; the kdtree's
            # geometric median cuts cannot, so the ratio below now
            # favours the shelf on count balance (the kdtree keeps
            # arbitrary-p support and strip-free geometry).
            compare_d = {}
            for kind in ("shelf", "kdtree"):
                if kind == args.domain:
                    arm = dydd
                else:
                    print(f"[streaming_bench]   domain={kind} ...",
                          file=sys.stderr)
                    arm, _ = run_arm(name, rebalance=True, args=args,
                                     domain_kind=kind)
                compare_d[kind] = {
                    "imbalance_final": arm["imbalance_final"],
                    "imbalance_mean": float(
                        np.mean(arm["imbalance_trajectory"])),
                    "migrated_total": arm["migrated_total"],
                    "repartitions": arm["repartitions"],
                    "comm_bytes_per_cycle_mean": float(
                        np.mean(arm["comm_bytes_per_cycle"])),
                    "p": arm["domain"]["p"],
                }
            assert compare_d["shelf"]["p"] == compare_d["kdtree"]["p"]
            compare_d["final_imbalance_ratio_shelf_over_kdtree"] = float(
                compare_d["shelf"]["imbalance_final"]
                / max(compare_d["kdtree"]["imbalance_final"], 1e-12))
            report["scenarios"][name]["domain_compare"] = compare_d
        if args.compare_comm:
            # Allreduce-vs-neighbour on the same scenario: measured
            # wall-clock next to modelled per-cycle bytes.  The dydd arm
            # above already ran with args.comm — only the other path
            # needs a fresh run.
            compare = {}
            analyses = {args.comm: x_dydd}
            for comm in ("allreduce", "neighbour"):
                if comm == args.comm:
                    arm = dydd
                else:
                    print(f"[streaming_bench]   comm={comm} ...",
                          file=sys.stderr)
                    arm, analyses[comm] = run_arm(name, rebalance=True,
                                                  args=args, comm=comm)
                compare[comm] = {
                    "solve_time_mean_s": arm["solve_time_mean_s"],
                    "cycle_latency_steady_s": arm["cycle_latency_steady_s"],
                    "comm_bytes_per_cycle_mean": float(
                        np.mean(arm["comm_bytes_per_cycle"])),
                }
            compare["modelled_bytes_ratio"] = float(
                compare["allreduce"]["comm_bytes_per_cycle_mean"]
                / max(compare["neighbour"]["comm_bytes_per_cycle_mean"],
                      1e-12))
            # The two comm paths iterate the identical update; their
            # final analyses may differ only by collective reduction
            # order (ULPs) — recorded so the CI artifact carries the
            # parity evidence.
            compare["analysis_max_abs_diff"] = float(np.max(np.abs(
                analyses["allreduce"] - analyses["neighbour"])))
            report["scenarios"][name]["comm_compare"] = compare
        if args.compare_kernels:
            # Jnp-vs-fused Schwarz sweep on the same scenario: measured
            # wall-clock and the solve phase's share of the cycle for
            # both kernels.  Off-TPU "fused" resolves to the same jnp
            # reference, so the two arms run one program there.
            kcompare = {}
            kanalyses = {}
            for kern in ("jnp", "fused"):
                if kern == args.solver_kernel:
                    arm, kanalyses[kern] = dydd, x_dydd
                else:
                    print(f"[streaming_bench]   kernel={kern} ...",
                          file=sys.stderr)
                    arm, kanalyses[kern] = run_arm(
                        name, rebalance=True, args=args,
                        solver_kernel=kern)
                summ = arm["summary"]
                solve_p50 = summ["phases"].get("solve", {}).get("p50", 0.0)
                kcompare[kern] = {
                    "solve_time_mean_s": arm["solve_time_mean_s"],
                    "cycle_latency_steady_s": arm["cycle_latency_steady_s"],
                    "solve_phase_ratio": float(
                        solve_p50 / max(summ["cycle_time_mean"], 1e-12)),
                }
            kcompare["fused_over_jnp_solve_ratio"] = float(
                kcompare["fused"]["solve_time_mean_s"]
                / max(kcompare["jnp"]["solve_time_mean_s"], 1e-12))
            # Both kernels iterate the identical update; the final
            # analyses may differ only by reduction order (ULPs) — the
            # CI artifact's parity evidence.
            kcompare["analysis_max_abs_diff"] = float(np.max(np.abs(
                kanalyses["jnp"] - kanalyses["fused"])))
            report["scenarios"][name]["kernel_compare"] = kcompare

    if args.time_windows > 0:
        report["pint"] = pint_section(args)

    # Autotuned gram reduction tiles (chosen block_m + timed sweep per
    # packed shape; empty when every pack took the jnp reference path).
    report["gram_autotune"] = ops.gram_tuning_report()
    # Same for the fused Schwarz-step kernel (empty when every solve ran
    # the jnp or reference path).
    report["schwarz_autotune"] = ops.schwarz_tuning_report()

    ctx.close()   # stop profiling, restore the previous tracer
    # Counter/gauge/series registry the engines and core layers reported
    # into (comm bytes, halo builds, CG residuals, straggler flags ...).
    report["meters"] = obs_meters.get_meters().snapshot()
    if tracer is not None:
        wall = float(np.sum(_WALL_CLOCK_S))
        report["trace"] = {
            "path": args.trace,
            "wall_clock_s": wall,
            # Fraction of the measured cycle wall-clock covered by the
            # engine's "cycle" spans — the acceptance metric (>= 0.95).
            "cycle_coverage": tracer.coverage("cycle", wall),
            "events": len(tracer.events),
        }
        tracer.save(args.trace)
        print(f"[streaming_bench] wrote trace {args.trace} "
              f"(coverage {report['trace']['cycle_coverage']:.3f})",
              file=sys.stderr)

    text = json.dumps(report, indent=2)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text)
        print(f"[streaming_bench] wrote {args.out}", file=sys.stderr)
    else:
        print(text)


if __name__ == "__main__":
    main()
