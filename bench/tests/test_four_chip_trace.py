"""The four-chip cell's readers on a slice of its recorded TPU v5e trace
(``data/v5e_4chip_trace_slice.json``: one cycle's sharded solve and
packing on each of four chips), and on hand-made runs."""
import json
import re
from pathlib import Path

import pytest

from bench import harness, kernels, tracing

DATA = Path(__file__).resolve().parent / "data"
CELL = "ex4_p4_4chip.beta_network"
COLLECTIVE = re.compile(r"^%?(all-reduce|collective-permute-start|"
                        r"collective-permute-done)\.?\d*$")


def slice_events(name="v5e_4chip_trace_slice.json"):
    return json.loads((DATA / name).read_text())["events"]


def fake_run(trace=None, phases=None, cycles=1):
    run = harness.Run(cell={"name": CELL}, config={"iters": 60},
                      devices=[None] * 4, peaks={}, t0=0.0,
                      handed=[0.0] * cycles,
                      ready=[0.1 * (k + 1) for k in range(cycles)],
                      trace=trace)
    run.cycles = [{"p": 4, "m": 6094, "w": 1257, "solve_time": 0.05,
                   "phases": dict(phases or {})} for _ in range(cycles)]
    return run


def read(name, run):
    return harness.reader(name)(run)


def test_the_sharded_solve_module_is_the_timed_solve():
    mods = {m[0] for plane, lines in slice_events().items()
            if plane.startswith("/device:") for m in lines["XLA Modules"]}
    solve = [m for m in mods if re.search(kernels.SOLVE_MODULES, m)]
    assert solve and all(m.startswith("jit__solve_shard_map(")
                         for m in solve)
    # The packing's programs are not taken for the solve.
    assert {m.split("(")[0] for m in mods} >= {
        "jit__gather_blocks", "jit__factor_batched"}


def test_collective_ms_reads_the_recorded_trace():
    events = slice_events()
    r = tracing.reduce(events, n_devices=4)
    want = 0.0
    for plane, lines in events.items():
        if plane.startswith("/device:"):
            want += sum(dur for name, _, dur in lines["XLA Ops"]
                        if COLLECTIVE.match(name))
    got = read("collective_ms", fake_run(r))
    # every collective of the slice lies inside the solve's module
    assert got == pytest.approx(1e-6 * want / 4)
    assert 0.1 < got < 5.0
    # per window cycle: two cycles over the same ops read half
    assert read("collective_ms", fake_run(r, cycles=2)) == \
        pytest.approx(got / 2)


def test_collective_names_are_the_ones_on_the_chip():
    pattern = harness._module(harness.BENCH / "metrics" /
                              "collective_ms.py", "collective").COLLECTIVE_OPS
    for name in ("all-reduce.18", "collective-permute-start.1",
                 "collective-permute-done", "all-reduce-start.3",
                 "reduce-scatter.2", "all-gather-done.7"):
        assert re.search(pattern, tracing.op_name(name) + " "), name
    for name in ("fusion.3", "schwarz_fwd.5", "custom-call.79",
                 "copy-start.6", "all-reducer.1"):
        assert not re.search(pattern, name + " "), name


def test_collective_ms_is_silent_without_collectives():
    """No trace, or a one-chip trace with no collective in its solve (the
    parent's sharded solve, jit_run, is no solve module either)."""
    assert read("collective_ms", fake_run()) is None
    one_chip = tracing.reduce(slice_events("v5e_trace_slice.json"),
                              n_devices=1)
    assert read("collective_ms", fake_run(one_chip)) is None


def test_pack_place_ms_reads_the_journal_step():
    run = fake_run(phases={"pack.place": 0.023, "pack": 0.09}, cycles=3)
    assert read("pack_place_ms", run) == pytest.approx(23.0)
    # a program that does not time the step: nothing
    assert read("pack_place_ms", fake_run(phases={"pack": 0.09})) is None


def test_the_cell_and_its_metrics_are_in_the_benchmark():
    spec = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in spec["workloads"]}[CELL]
    assert cell["chips"] == 4 and cell["traffic"] == "beta_network"
    layers = {m["name"]: m for m in spec["per_layer"]}
    assert layers["collective_ms"]["layer"] == "cross-chip exchange"
    assert layers["pack_place_ms"]["layer"] == "operator packing"
    for name in ("collective_ms", "pack_place_ms"):
        assert layers[name]["workloads"] == [CELL]
        assert layers[name]["moves"] == "analyses_per_s"
    _, config, _, e2e, mine = harness.load_cell(CELL)
    assert {m["name"] for m in mine} == {"collective_ms", "pack_place_ms"}
    assert {m["name"] for m in e2e} == {"analyses_per_s", "setup_s"}
    assert (config["p"], config["overlap"], config["solver"],
            config["comm"]) == (4, 1, "shardmap", "neighbour")
