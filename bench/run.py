#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 bench/run.py --workload ex4_p8.beta_network --seed 7 \
        --seconds 45 --trace 0

Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.  The last stdout line is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and
``breakdown`` with ``--trace 1``), its last key ``checks``: each number
compared for ``correct`` with its limit, also printed as the last lines
of stderr.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def devices_or_exit(chips: int) -> list:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU: JAX found platform {devs[0].platform!r} "
                 f"({len(devs)} device(s))")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    return devs


def result(run, positions, xs, *, seed, limits, metrics, trace, n_setup,
           devices_used, all_devices) -> dict:
    """The result line's object, the checks last."""
    from bench import harness
    peak = harness.memory_peak(devices_used)
    checks = harness.check(run.config, seed, positions, xs, limits,
                           n_setup)
    out = {}
    for m in metrics:
        v = harness.reader(m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = all_devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(all_devices), "memory_peak_bytes": peak}
    line = {"correct": harness.correct(checks), "attempted": len(run.handed),
            "failed": (checks["cycles_over"]["value"]
                       + checks["cycles_unanswered"]["value"]),
            "metrics": out, "device": device}
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = {"device_ops": run.trace.top_ops(),
                             "idle_gaps": run.trace.idle_gaps()}
    line["checks"] = checks
    return line


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness
    cell, config, mix, e2e, layers = harness.load_cell(args.workload)
    limits = harness.load_limits(args.workload)
    devs = devices_or_exit(int(cell["chips"]))
    used = devs[:int(cell["chips"])]
    peaks = harness.peaks_for(devs[0].device_kind)
    run, positions, xs = harness.run_cell(
        cell, config, mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, devices=used, peaks=peaks)
    from repro.kernels import ops
    info = {"workload": args.workload, "seed": args.seed,
            "setup_cycles": int(mix["setup_cycles"]),
            "window_cycles": run.done,
            "window_compiles": len(run.compiles),
            "window_compile_s": sum(d for _, d in run.compiles),
            "widths": sorted({c["w"] for c in run.cycles}),
            "solve_blocks": sorted({str(c["solve_block"])
                                    for c in run.cycles}),
            "gram_blocks": sorted({str(ops.gram_block_for(
                (c["p"], c["m"], c["w"]), c["dtype"]))
                for c in run.cycles}),
            "solve_kernels": sorted({c["solve_kernel"] for c in run.cycles}),
            "repartitions_in_window": sum(c["repartitioned"]
                                          for c in run.cycles)}
    print(json.dumps({"info": info}), flush=True)
    line = result(run, positions, xs, seed=args.seed, limits=limits,
                  metrics=layers if args.trace else e2e,
                  trace=bool(args.trace), n_setup=int(mix["setup_cycles"]),
                  devices_used=used, all_devices=devs)
    for k, v in line["checks"].items():
        print(f"check {k}: {v['value']} (limit {v['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
