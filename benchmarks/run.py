"""Benchmark entrypoint: one function per paper table + beyond-paper
benches + the roofline table.  Prints ``name,us_per_call,derived`` CSV at
the end.

  PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0] + "/src")


def main() -> None:
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller p-sweeps (CI mode)")
    ap.add_argument("--n", type=int, default=2048, help="mesh size")
    args, _ = ap.parse_known_args()

    from benchmarks import beyond_paper, kernels_bench, paper_tables, \
        roofline
    from benchmarks.common import csv_row

    csv = []
    t_all = time.time()

    print("=" * 72)
    print("Example 1 (paper Tables 1-3)")
    rows = paper_tables.example1(n=args.n, quick=args.quick)
    for r in rows:
        csv.append(csv_row(f"dydd_{r.name}", r.t_dydd * 1e6,
                           f"E={r.dydd.efficiency:.3f};err={r.err:.1e}"))

    print("=" * 72)
    print("Example 2 (paper Tables 4-8, Table 9)")
    rows = paper_tables.example2(n=args.n, quick=args.quick)
    for r in rows:
        csv.append(csv_row(f"dydd_{r.name}", r.t_dydd * 1e6,
                           f"E={r.dydd.efficiency:.3f};err={r.err:.1e}"))

    print("=" * 72)
    print("Example 3 (paper Table 10)")
    for p, t, E, _ in paper_tables.example3(n=args.n, quick=args.quick):
        csv.append(csv_row(f"dydd_star_p{p}", t * 1e6, f"E={E:.3f}"))

    print("=" * 72)
    print("Example 4 (paper Table 12)")
    rows = paper_tables.example4(n=args.n, quick=args.quick)
    for r in rows:
        csv.append(csv_row(
            f"ddkf_chain_p{r.p}", r.tp_model * 1e6,
            f"S_kf={r.speedup_kf:.2f};E_kf={r.efficiency_kf:.3f};"
            f"S_dd={r.speedup:.2f}"))

    print("=" * 72)
    print("Table 11 / Figure 5 (error_DD-DA)")
    for p, err in paper_tables.table11_accuracy(n=args.n,
                                                quick=args.quick):
        csv.append(csv_row(f"err_dd_da_p{p}", 0.0, f"err={err:.2e}"))

    print("=" * 72)
    print("Beyond paper: DyDD in the LM framework")
    print("[MoE expert balance]")
    for bal, er, et, mass in beyond_paper.moe_expert_balance():
        csv.append(csv_row(f"moe_balance_{bal}", 0.0,
                           f"E_router={er:.3f};E_sched={et:.3f}"))
    print("[DP loader balance]")
    for bal, emean, emin in beyond_paper.loader_balance(
            windows=5 if args.quick else 20):
        csv.append(csv_row(f"loader_balance_{bal}", 0.0,
                           f"Emean={emean:.3f};Emin={emin:.3f}"))
    print("[Scheduling scalability]")
    for p, t, E in beyond_paper.scheduling_scalability():
        csv.append(csv_row(f"dydd_sched_p{p}", t * 1e6, f"E={E:.3f}"))
    print("[2D DyDD (paper Figures 1-4 setting)]")
    r2 = beyond_paper.dydd_2d_figures()
    csv.append(csv_row("dydd_2d_2x4", 0.0, f"E={r2.efficiency:.3f}"))

    print("=" * 72)
    print("Kernel microbenchmarks")
    for name, us, derived in kernels_bench.bench_all():
        csv.append(csv_row(name, us, derived))

    print("=" * 72)
    print("Roofline (from dry-run artifacts)")
    roofline.print_table()
    roofline.summarize()

    print("=" * 72)
    print(f"total bench time {time.time() - t_all:.0f}s")
    print("\nname,us_per_call,derived")
    for line in csv:
        print(line)


if __name__ == "__main__":
    main()
