#!/usr/bin/env python3
"""Readings that a cell's correctness limits are set from.

    python3 bench/control.py --workload ex4_p8.beta_network \
        --seeds 101-112 --seconds 20

For each seed, in this one process, it runs the cell's set-up and window
as ``bench/run.py`` does and reads the numbers compared for ``correct``
twice: for the program, and for the control, i.e. the reference put in
the program's place over the same cycles and computed one precision
below the configuration's (the operands of every product rounded to
float8_e4m3fn, float32 accumulation: the program's float32 dots already
run as one bfloat16 pass).  The control's numbers go through the same
``check`` as the program's, and its ``correct`` is printed beside them;
the bfloat16 control's numbers are printed for the record.  One JSON
line per seed, then a summary line.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import harness  # noqa: E402

CONTROL = "float8_e4m3fn"
GAPS = ("analysis_gap", "chain_gap")


def readings(cell, config, mix, seed, seconds, devices, peaks,
             t_start=None) -> dict:
    """The program's and the controls' numbers over one run."""
    run, positions, xs = harness.run_cell(
        cell, config, mix, seed=seed, seconds=seconds, trace=False,
        t_start=time.perf_counter() if t_start is None else t_start,
        devices=devices, peaks=peaks)
    limits = harness.load_limits(cell["name"])
    n_setup = int(mix["setup_cycles"])
    ref = harness.reference_module(config)
    answered = positions[:len(xs)]
    ys = ref.observations(config, seed, answered)
    out = {"seed": seed, "cycles": len(xs), "window_cycles": run.done}
    for who in ("program", CONTROL, "bfloat16"):
        ans = xs if who == "program" else list(
            ref.chain(config, answered, ys, operand=who))
        checks = harness.check(config, seed, positions, ans, limits,
                               n_setup)
        out[who] = {k: checks[k]["value"] for k in GAPS}
        out[who]["correct"] = harness.correct(checks)
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    from bench.run import devices_or_exit
    cell, config, mix, _, _ = harness.load_cell(args.workload)
    devs = devices_or_exit(int(cell["chips"]))
    used = devs[:int(cell["chips"])]
    peaks = harness.peaks_for(devs[0].device_kind)
    lo, hi = (int(s) for s in args.seeds.split("-"))
    rows = []
    for seed in range(lo, hi + 1):
        rows.append(readings(cell, config, mix, seed, args.seconds, used,
                             peaks, t_start=T_START if not rows else None))
        print(json.dumps(rows[-1]), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in GAPS:
        summary[f"program_max.{k}"] = max(r["program"][k] for r in rows)
        summary[f"control_min.{k}"] = min(r[CONTROL][k] for r in rows)
        summary[f"bfloat16_min.{k}"] = min(r["bfloat16"][k] for r in rows)
    summary["program_correct"] = all(r["program"]["correct"] for r in rows)
    summary["control_correct_any"] = any(r[CONTROL]["correct"]
                                         for r in rows)
    summary["device"] = {"platform": devs[0].platform,
                         "kind": devs[0].device_kind, "count": len(devs)}
    print(json.dumps(summary), flush=True)


if __name__ == "__main__":
    main()
