"""Streaming DD-KF assimilation with online DyDD — thin engine driver.

Runs registered observation-stream scenarios through the
:class:`repro.assim.AssimilationEngine`: multi-cycle DD-KF with the
analysis carried forward as the next background and DyDD repartitioning
the subdomains whenever the moving observation network unbalances them —
the configuration the paper's conclusion names as future work ("each
subdomain to move independently with time").

``--ndim 1`` (default) drives an Interval1D domain; ``--ndim 2`` drives a
ShelfTiling2D (the paper's Ω ⊂ R² setting) and prints the per-cell load
table before/after each rebalance.  ``--ndim 2 --domain kdtree`` swaps
the shelf for the adaptive k-d tree domain (pr*pc median-split leaves —
the right choice for strongly anisotropic networks such as
``satellite_track`` / ``river_gauges``).

  PYTHONPATH=src python examples/dydd_assimilation.py
  PYTHONPATH=src python examples/dydd_assimilation.py \
      --n 96 --m 200 --cycles 4 --scenarios drifting_swarm   # CI smoke
  PYTHONPATH=src python examples/dydd_assimilation.py \
      --ndim 2 --nx 12 --ny 8 --pr 2 --pc 2 --m 200 --cycles 2 \
      --scenarios rotating_swarm                             # 2D CI smoke
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python examples/dydd_assimilation.py \
      --ndim 2 --pr 2 --pc 4 --overlap 1 --solver shardmap \
      --scenarios rotating_swarm    # sharded: one device per tiling cell
  PYTHONPATH=src python examples/dydd_assimilation.py \
      --ndim 2 --domain kdtree --pr 2 --pc 4 --m 300 --cycles 3 \
      --scenarios satellite_track river_gauges  # anisotropic k-d domain
"""
import argparse

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402

from repro.assim import AssimilationEngine, EngineConfig, streams  # noqa: E402
from repro.core import ddkf  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402


def make_config(args) -> EngineConfig:
    common = dict(iters=args.iters, rebalance=not args.static,
                  imbalance_threshold=args.threshold,
                  hysteresis=args.hysteresis, track_reference=True,
                  solver=args.solver, overlap=args.overlap,
                  comm=args.comm, halo_weight=args.halo_weight,
                  record_residuals=args.residuals,
                  solver_kernel=args.solver_kernel)
    if args.ndim == 1:
        return EngineConfig(n=args.n, p=args.p, **common)
    if args.domain == "kdtree":
        # Equal p to the shelf at the same flags: pr*pc leaves.
        return EngineConfig(ndim=2, domain_kind="kdtree",
                            p=args.pr * args.pc, nx=args.nx, ny=args.ny,
                            damping=args.damping, **common)
    return EngineConfig(ndim=2, nx=args.nx, ny=args.ny, pr=args.pr,
                        pc=args.pc, damping=args.damping, **common)


def print_load_table(eng, rec) -> None:
    """Per-cell loads before/after the cycle's rebalance, as pr x pc grids."""
    before = eng.domain.load_table(rec.loads_before)
    after = eng.domain.load_table(rec.loads)
    rows = []
    for rb, ra in zip(np.atleast_2d(before), np.atleast_2d(after)):
        rows.append("  " + " ".join(f"{v:5d}" for v in rb)
                    + "   ->   " + " ".join(f"{v:5d}" for v in ra))
    print(f"  cycle {rec.cycle} cell loads (before -> after rebalance):")
    print("\n".join(rows))


def run_scenario(name: str, args) -> None:
    cfg = make_config(args)
    eng = AssimilationEngine(cfg)
    dom = eng.journal.meta
    if args.ndim == 1:
        shape = f"p={dom['p']}"
    elif dom["kind"] == "kdtree":
        shape = (f"{dom['p']}-leaf k-d tree on a "
                 f"{dom['nx']}x{dom['ny']} mesh")
    else:
        shape = (f"{dom['pr']}x{dom['pc']} cells on a "
                 f"{dom['nx']}x{dom['ny']} mesh")
    solver = cfg.solver + (f" on mesh {dict(eng.mesh.shape)}"
                           if eng.mesh is not None else "")
    if cfg.solver == "shardmap":
        solver += f", comm={cfg.comm}"
    print(f"\n=== {name} ({'static DD' if args.static else 'DyDD'}, "
          f"{shape}, overlap={cfg.overlap}, {solver}, m={args.m}, "
          f"{args.cycles} cycles) ===")
    print(f"{'cycle':>5s} {'imb_in':>7s} {'imb_out':>7s} {'E':>6s} "
          f"{'rep':>4s} {'moved':>6s} {'t_cycle':>8s} {'err_DD-DA':>10s}")
    journal = eng.run_scenario(name, m=args.m, cycles=args.cycles,
                               seed=args.seed)
    for r in journal.records:
        print(f"{r.cycle:5d} {r.imbalance_before:7.2f} {r.imbalance:7.2f} "
              f"{r.efficiency:6.3f} {'yes' if r.repartitioned else '-':>4s} "
              f"{r.migrated:6d} {r.cycle_time * 1e3:7.1f}ms "
              f"{r.error_vs_direct:10.2e}")
        if args.ndim == 2 and r.repartitioned:
            print_load_table(eng, r)
    s = journal.summary()
    print(f"summary: {s['repartitions']} repartitions, "
          f"{s['migrated_total']} observations migrated, "
          f"max imbalance {s['imbalance_max']:.3f}, "
          f"max error vs one-shot solve {s['error_max']:.2e}")
    if cfg.overlap > 0:
        print(f"comm ({cfg.comm}): "
              f"{s['comm_bytes_per_cycle_mean'] / 1e3:.1f} kB/cycle "
              f"modelled, halo fraction "
              f"{s['halo_fraction_mean']:.3f}")
    if s.get("phases"):
        split = ", ".join(f"{k} {v['p50'] * 1e3:.1f}ms"
                          for k, v in sorted(s["phases"].items()))
        print(f"phase p50: {split}")
    if cfg.record_residuals and s.get("residual_final_mean") is not None:
        print(f"Schwarz residual (final iter, mean over cycles): "
              f"{s['residual_final_mean']:.2e}")


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--ndim", type=int, default=1, choices=(1, 2),
                    help="domain dimension: 1 = interval, 2 = shelf tiling "
                    "or k-d tree (see --domain)")
    ap.add_argument("--domain", default="shelf",
                    choices=("shelf", "kdtree"),
                    help="2D domain kind: shelf tiling (pr x pc cells) or "
                    "adaptive k-d tree (pr*pc median-split leaves — for "
                    "strongly anisotropic networks)")
    ap.add_argument("--n", type=int, default=512, help="1D state dimension")
    ap.add_argument("--p", type=int, default=8, help="1D subdomains")
    ap.add_argument("--nx", type=int, default=24, help="2D mesh width")
    ap.add_argument("--ny", type=int, default=12, help="2D mesh height")
    ap.add_argument("--pr", type=int, default=2, help="2D strip count")
    ap.add_argument("--pc", type=int, default=4, help="2D cells per strip")
    ap.add_argument("--damping", type=float, default=0.7,
                    help="additive-Schwarz damping (2D tilings converge "
                    "with under-relaxation)")
    ap.add_argument("--m", type=int, default=800, help="observations/cycle")
    ap.add_argument("--cycles", type=int, default=6)
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--threshold", type=float, default=1.5,
                    help="max/mean imbalance ratio arming the rebalance")
    ap.add_argument("--hysteresis", type=int, default=1,
                    help="consecutive over-threshold cycles before firing")
    ap.add_argument("--static", action="store_true",
                    help="disable DyDD (static-DD baseline)")
    ap.add_argument("--solver", default="vmapped",
                    choices=("vmapped", "shardmap"),
                    help="shardmap needs one device per subdomain "
                    "(set XLA_FLAGS=--xla_force_host_platform_device_"
                    "count=<p> on CPU)")
    ap.add_argument("--overlap", type=int, default=0,
                    help="Schwarz halo width (mesh columns/rows absorbed "
                    "from each grid-graph neighbour)")
    ap.add_argument("--comm", default="allreduce",
                    choices=("allreduce", "neighbour"),
                    help="sharded state exchange: full n-vector allreduce "
                    "or halo-only neighbour ppermute rounds")
    ap.add_argument("--halo-weight", type=float, default=0.0,
                    help="overlap-aware DyDD: work units per halo column "
                    "added to the loads the schedule balances")
    ap.add_argument("--solver-kernel", default="auto",
                    choices=ddkf.SOLVER_KERNELS,
                    help="local Schwarz step: auto (fused Pallas on TPU, "
                    "jnp elsewhere), jnp, fused, fused_interpret, "
                    "fused_ref")
    ap.add_argument("--scenarios", nargs="*", default=None,
                    choices=streams.available(),
                    help="subset of the registered scenarios "
                    "(default: all of this --ndim)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write a Chrome/Perfetto trace_events timeline "
                    "of the runs here (open at ui.perfetto.dev)")
    ap.add_argument("--profile", default=None, metavar="LOGDIR",
                    help="wrap the runs in jax.profiler.trace into this "
                    "directory (TensorBoard XPlane; kernel-level)")
    ap.add_argument("--residuals", action="store_true",
                    help="journal per-iteration Schwarz residual "
                    "histories (lax.scan solve variant)")
    args = ap.parse_args()

    names = args.scenarios or streams.available(ndim=args.ndim)
    tracer = obs_trace.Tracer("dydd_assimilation") if args.trace else None
    with obs_trace.tracing(tracer), obs_trace.jax_profile(args.profile):
        for name in names:
            if streams.get(name).ndim != args.ndim:
                raise SystemExit(
                    f"scenario {name!r} is {streams.get(name).ndim}D; "
                    f"pass --ndim {streams.get(name).ndim}")
            run_scenario(name, args)
    if tracer is not None:
        tracer.save(args.trace)
        print(f"\nwrote trace {args.trace} "
              f"({len(tracer.events)} events)")


if __name__ == "__main__":
    main()
