"""The trace reduction, on a hand-made trace with known answers (its
planes, lines and naming stats as JAX writes them for a TPU)."""
import json
from pathlib import Path

import pytest

from bench import kernels, tracing

DATA = Path(__file__).resolve().parent / "data"

HOST = tracing.HOST_PLANE
DEV = "/device:TPU:{}"


def made(n_devices=1):
    ev = {HOST: {"python": [["bench.window", 0.0, 1000.0],
                            ["bench.solve", 400.0, 500.0],
                            ["other", 0.0, 5.0]],
                 "pack_0": [["bench.prepare", 0.0, 400.0]]}}
    for d in range(n_devices):
        ev[DEV.format(d)] = {
            tracing.MODULES_LINE: [["jit_solve_vmapped(42)", 400.0, 400.0],
                                   ["jit_schwarz_fwd(7)", 100.0, 50.0]],
            tracing.OPS_LINE: [
                ["%schwarz_fwd.1 = f32[8,2,6094] custom-call(%pad.3)",
                 420.0, 100.0],
                ["%schwarz_bwd.1 = f32[8,1,1152] custom-call(%pad.3)",
                 520.0, 100.0],
                ["%custom-call.5 = f32[8,9,128,128] custom-call("
                 "%schwarz_fwd.1)", 650.0, 50.0],
                ["%schwarz_fwd.2 = f32[8,2,6094] custom-call(%p)",
                 110.0, 20.0],
                ["%fusion.1 = f32[8] fusion(%x)", 1500.0, 10.0]]}
    return ev


def test_busy_idle_and_op_time():
    r = tracing.reduce(made(), n_devices=1)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.busy_s == pytest.approx(270e-9)      # [110,130] [420,620] [650,700]
    assert r.op_seconds(kernels.SCHWARZ_OPS,
                        kernels.SOLVE_MODULES) == pytest.approx(200e-9)
    assert r.op_seconds(kernels.SCHWARZ_OPS) == pytest.approx(220e-9)
    assert sorted(r.top_ops(2)) == [["schwarz_bwd.1", pytest.approx(100e-9)],
                                    ["schwarz_fwd.1", pytest.approx(100e-9)]]


def test_idle_gaps_are_labelled_by_the_host():
    r = tracing.reduce(made(), n_devices=1, compiles=[(9e-7, 1e-6)])
    gaps = r.idle_gaps()
    assert [g[1] for g in gaps] == pytest.approx(
        [300e-9, 290e-9, 110e-9, 30e-9])
    assert gaps[0][0] == "solve+compile"          # [700, 1000)
    assert gaps[1][0] == "prepare+solve"          # [130, 420)
    assert gaps[2][0] == "prepare"                # [0, 110)


def test_busy_is_averaged_over_chips_and_chips_are_required():
    r = tracing.reduce(made(2), n_devices=2)
    assert r.busy_s == pytest.approx(270e-9)
    assert r.op_seconds(kernels.SCHWARZ_OPS) == pytest.approx(440e-9)
    with pytest.raises(RuntimeError):
        tracing.reduce(made(1), n_devices=2)


def test_module_and_kernel_from_naming_stats():
    """Ops that carry their module as a stat are put in it."""
    ev = made()
    ev[DEV.format(0)] = {tracing.OPS_LINE: [
        ["%schwarz_fwd.3 = f32[8] custom-call(%a)", 420.0, 100.0,
         "hlo_module=jit_solve_vmapped(1)"],
        ["%gram.1 = f32[8] custom-call(%a)", 110.0, 20.0,
         "hlo_module=jit_gram(2)"],
        ["%gram.2 = f32[8] custom-call(%a)", 700.0, 50.0,
         "hlo_module=jit__factor_batched(3)"]]}
    r = tracing.reduce(ev, n_devices=1)
    assert r.busy_s == pytest.approx(170e-9)
    assert r.op_seconds(kernels.SCHWARZ_OPS,
                        kernels.SOLVE_MODULES) == pytest.approx(100e-9)
    assert r.op_seconds(kernels.GRAM_OPS,
                        kernels.FACTOR_MODULES) == pytest.approx(50e-9)


def test_recorded_v5e_slice():
    """A slice of a real v5e trace: the kernels are found by their own
    names inside the timed modules, and the other custom calls of the
    solve (the triangular blocks) and its loop are not counted."""
    rec = json.loads((DATA / "v5e_trace_slice.json").read_text())
    r = tracing.reduce(rec["events"], n_devices=1)
    ops = r.devices[0].ops
    fwd = [o for o in ops if o[0].startswith("schwarz_fwd")]
    assert len(fwd) == 60 and all(o[1].startswith("jit_solve_vmapped")
                                  for o in fwd)
    solve = r.op_seconds(kernels.SCHWARZ_OPS, kernels.SOLVE_MODULES)
    kernels_only = sum(o[3] for o in ops if o[0].startswith("schwarz_")
                       and o[1].startswith("jit_solve_vmapped")) * 1e-9
    assert solve == pytest.approx(kernels_only) and solve > 0
    others = sum(o[3] for o in ops if o[0].startswith(("custom-call",
                                                        "while"))) * 1e-9
    assert others > solve        # what the old pattern swept in
    assert 0 < r.op_seconds(kernels.GRAM_OPS, kernels.FACTOR_MODULES)
    assert 0 < r.busy_s < r.window_s


def test_a_trace_it_cannot_attribute_is_an_error():
    """No line of XLA ops, or ops that no module holds: the reduction
    fails rather than count another kernel's time."""
    ev = made()
    ev[DEV.format(0)] = {"Ops": ev[DEV.format(0)][tracing.OPS_LINE]}
    with pytest.raises(RuntimeError, match="XLA Ops"):
        tracing.reduce(ev, n_devices=1)
    ev = made()
    del ev[DEV.format(0)][tracing.MODULES_LINE]
    with pytest.raises(RuntimeError, match="module"):
        tracing.reduce(ev, n_devices=1)
