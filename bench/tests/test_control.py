"""The control, at a size a test run holds: the program reads under the
cell's limit and the control (the reference one precision down) over
it."""
import time

import jax
import pytest

import repro.compile_cache
from bench import control, harness
from bench.tests.test_faults import CELLS, SMALL


@pytest.mark.parametrize("workload", CELLS)
def test_control_fails_where_the_program_passes(workload, monkeypatch):
    monkeypatch.setattr(repro.compile_cache, "use_compile_cache",
                        lambda: "")
    cell, config, mix, _, _ = harness.load_cell(workload)
    peaks = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
    r = control.readings(cell, dict(config, **SMALL), mix, 2**31 + 3, 2.0,
                         jax.devices()[:1], peaks, t_start=time.perf_counter())
    limits = harness.load_limits(workload)
    assert r["window_cycles"] >= 1
    assert r["program"]["correct"] and not r[control.CONTROL]["correct"]
    for k in control.GAPS:
        assert r["program"][k] < limits[k]["limit"]
