"""The readers of the program's timed steps (pack.*, solve.input) and of
the chip's idle time outside prepare, on hand-made runs and on the
recorded v5e slice with the program's own repro.* host spans added."""
import copy
import json

import numpy as np
import pytest

from bench import harness, tracing
from bench.tests.test_metrics import fake_run
from bench.tests.test_tracing import DATA, made

STEPS = {"pack.h1": 0.03, "pack.concat": 0.04, "pack.roundtrip": 0.12,
         "pack.fill": 0.13, "pack.h2d": 0.25, "pack.factor": 0.01,
         "solve.input": 0.02}
STEP_READERS = ("pack_build_ms", "pack_fill_ms", "pack_h2d_ms",
                "pack_factor_ms", "solve_host_ms")
OLD_READERS = ("analyses_per_s", "setup_s", "dydd_ms", "pack_ms",
               "compile_s_per_cycle", "compiles_per_cycle", "solve_ms",
               "schwarz_roofline", "gram_roofline", "device_idle_frac")


def read(name, run):
    return harness.reader(name)(run)


def stepped_run(trace=None):
    """fake_run's four cycles, cycle k timing each step at k times
    ``STEPS``: means are 2.5 times ``STEPS``."""
    run = fake_run(trace)
    for k, c in enumerate(run.cycles, start=1):
        c["phases"].update({n: v * k for n, v in STEPS.items()})
    return run


def test_step_readers():
    run = stepped_run()
    ms = {n: 1e3 * 2.5 * v for n, v in STEPS.items()}
    assert read("pack_build_ms", run) == pytest.approx(
        ms["pack.h1"] + ms["pack.concat"] + ms["pack.roundtrip"])
    assert read("pack_fill_ms", run) == pytest.approx(ms["pack.fill"])
    assert read("pack_h2d_ms", run) == pytest.approx(ms["pack.h2d"])
    assert read("pack_factor_ms", run) == pytest.approx(ms["pack.factor"])
    assert read("solve_host_ms", run) == pytest.approx(ms["solve.input"])


def test_step_readers_are_silent_for_a_program_without_steps():
    """A program that journals pack and solve whole (no pack.* or
    solve.* steps) reads nothing, and nothing raises."""
    run = fake_run()
    for name in STEP_READERS:
        assert read(name, run) is None
    run.cycles = []
    for name in STEP_READERS:
        assert read(name, run) is None


def test_idle_outside_prepare_on_a_made_trace():
    """Chip 0 idles over [0, 110), [130, 420), [620, 650) and
    [700, 1000); prepare runs over [0, 400) on the worker: 20 + 30 + 300
    of the 1000 ns window are idle with no prepare running."""
    run = fake_run(tracing.reduce(made(), n_devices=1))
    assert read("idle_outside_prepare_frac", run) == pytest.approx(0.35)
    assert read("idle_outside_prepare_frac", fake_run()) is None


def slice_events():
    return json.loads((DATA / "v5e_trace_slice.json").read_text())["events"]


def with_repro_spans(events):
    """The slice with the program's own spans on the host, as a run of
    the program writes them beside the harness's: repro.prepare and its
    steps on a worker line, repro.solve and its steps on the main one."""
    ev = copy.deepcopy(events)
    host = ev[tracing.HOST_PLANE]
    main = host["python3"]
    worker, extra = [], []
    for name, s, d in main:
        if name == "bench.prepare":
            worker.append(["repro.prepare", s + 1e3, d - 2e3])
            t = s + 2e3
            for step in ("pack.h1", "pack.concat", "pack.roundtrip",
                         "pack.fill", "pack.h2d", "pack.factor"):
                worker.append(["repro." + step, t, d / 8])
                t += d / 8
        elif name == "bench.solve":
            extra += [["repro.solve", s + 1e3, d - 2e3],
                      ["repro.solve.input", s + 2e3, 2e6],
                      ["repro.solve.device", s + 2e3 + 2e6, d - 5e6]]
    host["python3"] = main + extra
    host["pack_0"] = worker
    return ev


def _numbers(r):
    return {"window_s": r.window_s, "busy_s": r.busy_s, "host": r.host,
            "top_ops": r.top_ops(), "idle_gaps": r.idle_gaps(),
            "ops": [d.ops for d in r.devices],
            "gaps": [d.gaps for d in r.devices]}


def test_program_spans_leave_the_reduction_unchanged():
    """Every number and label the accepted readers and the breakdown
    compute from the slice is the same with the program's repro.* spans
    in the trace."""
    before = tracing.reduce(slice_events(), n_devices=1)
    after = tracing.reduce(with_repro_spans(slice_events()), n_devices=1)
    assert _numbers(after) == _numbers(before)
    assert [g[0] for g in after.idle_gaps(5)] == [
        "prepare+solve", "prepare+solve", "prepare", "prepare+solve",
        "prepare"]
    for name in OLD_READERS:
        assert read(name, fake_run(after)) == read(name, fake_run(before))


def test_idle_outside_prepare_on_the_recorded_slice():
    """Against a brute-force count on a 1 us grid of the slice's window."""
    r = tracing.reduce(with_repro_spans(slice_events()), n_devices=1)
    lo, hi = r.window_ns
    t = np.arange(lo, hi, 1e3) + 500.0
    busy = np.zeros(t.shape, bool)
    for _, _, s, d, _ in r.devices[0].ops:
        busy |= (t >= s) & (t < s + d)
    prep = np.zeros(t.shape, bool)
    for name, s, e in r.host:
        if name == "bench.prepare":
            prep |= (t >= s) & (t < e)
    want = float(np.mean(~busy & ~prep))
    got = read("idle_outside_prepare_frac", fake_run(r))
    assert got == pytest.approx(want, abs=2e-3)
    assert 0 < got < read("device_idle_frac", fake_run(r))


def test_new_metrics_are_in_the_benchmark_with_their_cell():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    layers = {m["name"]: m for m in spec["per_layer"]}
    for name in STEP_READERS + ("idle_outside_prepare_frac",):
        assert layers[name]["moves"] == "analyses_per_s"
        assert layers[name]["workloads"] == ["ex4_p8.beta_network"]
