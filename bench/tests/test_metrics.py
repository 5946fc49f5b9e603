"""Each metric reader on a hand-made run with known answers."""
import json

import pytest

from bench import counts, harness, tracing
from bench.tests.test_tracing import made

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def fake_run(trace=None):
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = spec["workloads"][0]
    run = harness.Run(cell=cell, config={"iters": 60}, devices=[None],
                      peaks=PEAKS, setup_s=30.0, t0=100.0,
                      handed=[100.0, 100.5, 101.0, 101.5],
                      ready=[100.8, 101.2, 101.7, 102.0],
                      compiles=[(100.2, 0.5), (101.1, 1.5)], trace=trace)
    run.cycles = [{"p": 8, "m": 6094, "w": 1070, "solve_time": 0.1 * k,
                   "phases": {"pack": 0.2, "halo": 0.001, "dydd": 0.002}}
                  for k in (1, 2, 3, 4)]
    return run


def read(name, run):
    return harness.reader(name)(run)


def test_host_clock_and_journal_readers():
    run = fake_run()
    assert read("analyses_per_s", run) == pytest.approx(4 / 2.0)
    assert read("setup_s", run) == 30.0
    assert read("solve_ms", run) == pytest.approx(250.0)
    assert read("pack_ms", run) == pytest.approx(200.0)
    assert read("dydd_ms", run) == pytest.approx(3.0)
    assert read("compiles_per_cycle", run) == pytest.approx(0.5)
    assert read("compile_s_per_cycle", run) == pytest.approx(0.5)


def test_trace_readers_stay_silent_without_a_trace():
    run = fake_run()
    for name in ("schwarz_roofline", "gram_roofline", "device_idle_frac"):
        assert read(name, run) is None


def test_trace_readers():
    run = fake_run(tracing.reduce(made(), n_devices=1))
    assert read("device_idle_frac", run) == pytest.approx(0.73)
    least = 4 * 60 * counts.least_seconds(
        *counts.schwarz_sweep(8, 6094, 1070), PEAKS)[0]
    assert read("schwarz_roofline", run) == pytest.approx(
        100 * least / 200e-9)
    assert read("gram_roofline", run) is None     # no gram op in the trace


def test_roofline_bounds():
    assert counts.least_seconds(*counts.schwarz_sweep(8, 6094, 1070),
                                PEAKS)[1] == "memory"
    assert counts.least_seconds(*counts.gram(8, 6094, 1070),
                                PEAKS)[1] == "compute"
