"""A whole run, without the look for a chip, at a size a test run holds:
sound, it comes out correct; with the timed path broken underneath in
each way the cell can break, ``correct`` comes out false."""
import dataclasses
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench import run as bench_run

# Small enough for the CPU; every other setting is the cell's own.
SMALL = {"n": 128, "m_obs": 200}
SEED = 2**31 + 77


def run_small(workload: str, fault: str = "none", seconds: float = 2.0):
    """The result line of one small run of ``workload`` on this host's
    devices, with ``fault`` planted in the program."""
    import repro.compile_cache
    from repro.assim import engine as engine_mod
    from repro.core import cls, ddkf

    undo = []

    def patch(obj, name, new):
        undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, new)

    # The test compiles nothing into the checkout's cache.
    patch(repro.compile_cache, "use_compile_cache", lambda: "")
    if fault == "unchanged_state":
        solve = engine_mod.AssimilationEngine._solve

        def stale(self, prep):
            x, background, hist, times = solve(self, prep)
            return jnp.asarray(background, x.dtype), background, hist, times
        patch(engine_mod.AssimilationEngine, "_solve", stale)
    elif fault == "half_left_out":
        build = cls.observation_operator

        def half(*a, **kw):
            H1 = build(*a, **kw)
            H1[1::2] = 0.0
            return H1
        patch(cls, "observation_operator", half)
    elif fault == "observations_altered":
        prepare = engine_mod.AssimilationEngine.prepare

        def shifted(self, *a, **kw):
            prep = prepare(self, *a, **kw)
            return dataclasses.replace(prep, y1=prep.y1 + 0.05)
        patch(engine_mod.AssimilationEngine, "prepare", shifted)
    elif fault == "answer_altered":
        solver = ddkf.solve_vmapped

        def altered(*a, **kw):
            x = solver(*a, **kw)
            return x.at[x.shape[-1] // 3].add(1.0)
        patch(ddkf, "solve_vmapped", altered)
    else:
        assert fault == "none", fault
    try:
        cell, config, mix, e2e, _ = harness.load_cell(workload)
        config = dict(config, **SMALL)
        devs = jax.devices()
        used = devs[:int(cell["chips"])]
        peaks = harness.load_json(harness.BENCH / "peaks.json")[
            "TPU v5 lite"]
        run, pos, xs = harness.run_cell(
            cell, config, mix, seed=SEED, seconds=seconds,
            trace=False, t_start=time.perf_counter(), devices=used,
            peaks=peaks)
        return bench_run.result(
            run, pos, xs, seed=SEED, limits=harness.load_limits(workload),
            metrics=e2e, trace=False, n_setup=int(mix["setup_cycles"]),
            devices_used=used, all_devices=devs)
    finally:
        for obj, name, old in reversed(undo):
            setattr(obj, name, old)


CELLS = ["ex4_p8.beta_network"]
# A one-chip cell has no exchange between chips to leave out.
FAULTS = ["unchanged_state", "half_left_out", "observations_altered",
          "answer_altered"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    line = run_small(workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(workload, fault):
    line = run_small(workload, fault)
    assert not line["correct"], line["checks"]
    assert line["failed"] >= 1
