"""Elastic fault tolerance: deterministic chaos injection, engine
snapshot/resume (bitwise journal continuation), remesh-on-p-change, and
the fleet's failure paths (transient retry, crashed-stream retirement
with slot reclamation)."""
import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.assim import AssimilationEngine, EngineConfig, FleetServer, streams
from repro.core import domain as domain_mod
from repro.core import kdtree as kdtree_mod
from repro.obs import meters as obs_meters
from repro.runtime import chaos
from repro.runtime import elastic
from repro.runtime.straggler import StragglerConfig


@pytest.fixture()
def fresh_meters():
    prev = obs_meters.get_meters()
    m = obs_meters.Meters()
    obs_meters.set_meters(m)
    yield m
    obs_meters.set_meters(prev)


# ---------------------------------------------------------------------------
# Injector determinism and retry mechanics.
# ---------------------------------------------------------------------------

def test_injector_schedule_and_replay_deterministic(fresh_meters):
    cfg = chaos.ChaosConfig(seed=7, max_cycle=64, pack_fault_rate=0.1,
                            solve_fault_rate=0.05, kill_cycles=(9,),
                            straggle_cycles=(3,))
    a, b = chaos.ChaosInjector(cfg), chaos.ChaosInjector(cfg)
    assert a.schedule() == b.schedule()
    json.dumps(a.schedule())   # JSON-ready
    for inj in (a, b):
        for c in range(64):
            for site in ("pack", "solve"):
                try:
                    inj.check(site, c)
                except chaos.TransientFault:
                    pass
    assert a.injections == b.injections and a.injections
    other = chaos.ChaosInjector(
        chaos.ChaosConfig(seed=8, max_cycle=64, pack_fault_rate=0.1,
                          solve_fault_rate=0.05))
    assert other.schedule()["pack_fault_cycles"] != \
        a.schedule()["pack_fault_cycles"]


def test_fault_fires_once_unless_fail_every_attempt(fresh_meters):
    inj = chaos.ChaosInjector(chaos.ChaosConfig(pack_fault_cycles=(2,)))
    with pytest.raises(chaos.TransientFault):
        inj.check("pack", 2)
    inj.check("pack", 2)          # second attempt passes
    inj.check("pack", 1)          # unscheduled cycle never fires

    hard = chaos.ChaosInjector(
        chaos.ChaosConfig(pack_fault_cycles=(2,), fail_every_attempt=True))
    with pytest.raises(chaos.TransientFault):
        chaos.retry_transient(lambda: hard.check("pack", 2), retries=2,
                              backoff=0.0, site="pack", cycle=2,
                              sleep=lambda s: None)
    assert len(hard.injections) == 3   # initial + both retries


def test_retry_transient_backoff_sequence(fresh_meters):
    delays, calls = [], {"n": 0}

    def fn():
        calls["n"] += 1
        if calls["n"] < 3:
            raise chaos.TransientFault("flaky")
        return "ok"

    out = chaos.retry_transient(fn, retries=3, backoff=0.05, site="solve",
                                cycle=1, sleep=delays.append)
    assert out == "ok"
    assert delays == [0.05, 0.1]   # exponential
    snap = fresh_meters.snapshot()
    assert snap["counters"]["chaos.retries"] == 2
    assert [e["attempt"] for e in snap["events"]
            if e["name"] == "chaos.retry"] == [1, 2]


def test_retry_transient_does_not_catch_fatal():
    with pytest.raises(ZeroDivisionError):
        chaos.retry_transient(lambda: 1 / 0, retries=5, backoff=0.0,
                              sleep=lambda s: None)


# ---------------------------------------------------------------------------
# Engine-level chaos: retried faults leave the journal bitwise identical.
# ---------------------------------------------------------------------------

def _cfg(**kw):
    return EngineConfig(n=48, p=3, iters=6, **kw)


def _stream(cycles=6, seed=3, m=60):
    return streams.make_stream("drifting_swarm", m, cycles, seed=seed)


@pytest.mark.parametrize("double_buffer", [True, False])
def test_engine_transient_faults_retry_bitwise(fresh_meters, double_buffer):
    base = AssimilationEngine(_cfg(double_buffer=double_buffer)) \
        .run(_stream())
    inj = chaos.ChaosInjector(
        chaos.ChaosConfig(pack_fault_cycles=(1, 3), solve_fault_cycles=(2,)))
    eng = AssimilationEngine(_cfg(double_buffer=double_buffer), chaos=inj)
    j = eng.run(_stream())
    assert j.deterministic_json() == base.deterministic_json()
    assert {(r["site"], r["cycle"]) for r in inj.injections} == \
        {("pack", 1), ("pack", 3), ("solve", 2)}
    assert fresh_meters.snapshot()["counters"]["chaos.retries"] == 3


def test_engine_fault_outliving_retries_is_fatal(fresh_meters):
    inj = chaos.ChaosInjector(
        chaos.ChaosConfig(solve_fault_cycles=(1,), fail_every_attempt=True))
    eng = AssimilationEngine(_cfg(solve_retries=1), chaos=inj)
    with pytest.raises(chaos.TransientFault):
        eng.run(_stream())


def test_forced_straggler_flags_without_touching_numerics(fresh_meters):
    base = AssimilationEngine(_cfg()).run(_stream())
    scfg = StragglerConfig(grace_steps=1, consecutive_trigger=1,
                           deadline_factor=10.0)
    inj = chaos.ChaosInjector(
        chaos.ChaosConfig(straggle_cycles=(4,), straggle_device=0,
                          straggle_factor=1e6))
    eng = AssimilationEngine(_cfg(), straggler_config=scfg, chaos=inj)
    j = eng.run(_stream())
    # The inflated report flags the device...
    assert j.records[4].straggler_flags == [0]
    snap = fresh_meters.snapshot()
    assert snap["counters"]["engine.straggler.flags"] >= 1
    assert any(r["site"] == "straggle" for r in inj.injections)
    # ...while the analyses/journal stay bitwise (only reported timing
    # changed; straggler_flags are excluded from the deterministic view
    # by design — they are chaos evidence).
    assert j.deterministic_json() == base.deterministic_json()


# ---------------------------------------------------------------------------
# Snapshot / restore: bitwise journal continuation on every domain kind.
# ---------------------------------------------------------------------------

KINDS = {
    "interval": (dict(n=48, p=3, iters=6), ("drifting_swarm", 60)),
    "shelf": (dict(n=64, ndim=2, nx=8, ny=8, pr=2, pc=2, iters=6),
              ("rotating_swarm", 80)),
    "kdtree": (dict(n=64, domain_kind="kdtree", p=4, nx=8, ny=8, iters=6),
               ("rotating_swarm", 80)),
}
_CYCLES = 8


def _kind_run(kind, tmp_path, **run_kw):
    cfg_kw, (scen, m) = KINDS[kind]
    eng = AssimilationEngine(EngineConfig(**cfg_kw))
    j = eng.run(streams.ResumableStream(scen, m, _CYCLES, seed=11),
                **run_kw)
    return eng, j


@pytest.mark.parametrize("kind", list(KINDS))
def test_snapshot_resume_bitwise(tmp_path, kind):
    base_eng, base = _kind_run(kind, tmp_path)
    ck = str(tmp_path / kind)
    _kind_run(kind, tmp_path, checkpoint_dir=ck, snapshot_every=4)
    eng2, stream2 = elastic.resume_assim_engine(
        os.path.join(ck, "step_00000004"))
    assert stream2 is not None and stream2.pos == 4
    assert stream2.remaining() == _CYCLES - 4
    j = eng2.run(stream2)
    assert j.deterministic_json() == base.deterministic_json()
    np.testing.assert_array_equal(np.asarray(eng2.analysis),
                                  np.asarray(base_eng.analysis))
    assert j.meta["resume"] == [
        {"at_cycle": 4, "p": eng2.p, "remeshed": False}]


@pytest.mark.parametrize("kind", list(KINDS))
def test_elastic_remesh_in_process(tmp_path, kind):
    new_p = 2
    ck = str(tmp_path / kind)
    _kind_run(kind, tmp_path, checkpoint_dir=ck, snapshot_every=4)
    eng2, stream2 = elastic.resume_assim_engine(
        os.path.join(ck, "step_00000004"), p=new_p)
    assert eng2.p == new_p and stream2.pos == 4
    j = eng2.run(stream2)
    # Continues without replaying: cycles 4.._CYCLES-1 on the new p.
    assert [r.cycle for r in j.records] == list(range(_CYCLES))
    assert all(len(r.loads) == new_p for r in j.records[4:])
    assert all(len(r.loads) > new_p for r in j.records[:4])
    assert j.meta["resume"][-1] == \
        {"at_cycle": 4, "p": new_p, "remeshed": True}


def test_restore_rejects_unknown_snapshot_version(tmp_path):
    from repro.checkpoint import manager as ckpt
    path = ckpt.save_pytree({"truth": np.zeros(4)}, str(tmp_path), step=1,
                            metadata={"snapshot_version": 99})
    with pytest.raises(ValueError, match="snapshot version"):
        AssimilationEngine.restore(path)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000),
       kind=st.sampled_from(["interval", "shelf", "kdtree"]))
def test_domain_state_roundtrip(seed, kind):
    """state_dict/load_state round-trips the boundary state bitwise for
    all three domain kinds, across arbitrary rebalance histories."""
    rng = np.random.default_rng(seed)
    if kind == "interval":
        dom, fresh = (domain_mod.Interval1D(n=32, p=4),
                      domain_mod.Interval1D(n=32, p=4))
        obs = np.sort(rng.random(50))
    elif kind == "shelf":
        dom, fresh = (domain_mod.ShelfTiling2D(nx=8, ny=8, pr=2, pc=2),
                      domain_mod.ShelfTiling2D(nx=8, ny=8, pr=2, pc=2))
        obs = rng.random((50, 2))
    else:
        dom, fresh = (kdtree_mod.KDTreeDomain(nx=8, ny=8, p=4),
                      kdtree_mod.KDTreeDomain(nx=8, ny=8, p=4))
        obs = rng.random((50, 2))
    dom.rebalance(obs)
    state = dom.state_dict()
    fresh.load_state({k: np.array(v) for k, v in state.items()})
    for k, v in fresh.state_dict().items():
        np.testing.assert_array_equal(v, state[k])
    np.testing.assert_array_equal(fresh.counts(obs), dom.counts(obs))


# ---------------------------------------------------------------------------
# Remesh derivation helpers.
# ---------------------------------------------------------------------------

def test_rebalanced_edges_quantile_cut():
    edges = np.array([0.0, 1.0, 2.0, 3.0, 4.0])
    out = elastic.rebalanced_edges(edges, [0, 0, 4, 4], new_p=2)
    np.testing.assert_allclose(out, [0.0, 3.0, 4.0])
    # Zero mass -> uniform; endpoints always pinned.
    np.testing.assert_allclose(
        elastic.rebalanced_edges(edges, [0, 0, 0, 0], new_p=4),
        np.linspace(0.0, 4.0, 5))


def test_shelf_grid_selection():
    assert elastic._shelf_grid(4, pr_old=2, pr=None, pc=None) == (2, 2)
    assert elastic._shelf_grid(2, pr_old=2, pr=None, pc=None) == (2, 1)
    assert elastic._shelf_grid(6, pr_old=4, pr=None, pc=None) == (3, 2)
    assert elastic._shelf_grid(8, pr_old=2, pr=4, pc=None) == (4, 2)
    with pytest.raises(ValueError):
        elastic._shelf_grid(8, pr_old=2, pr=3, pc=3)


# ---------------------------------------------------------------------------
# Fleet failure paths.
# ---------------------------------------------------------------------------

def test_fleet_prepare_failure_reclaims_slot(fresh_meters):
    cfg = _cfg()
    server = FleetServer(max_active=1, pack_workers=2, gather_window=0.0)
    # np.asarray("boom", float64) raises inside prepare on the pool.
    server.add_stream("bad", cfg, iter(["boom"]))
    server.add_stream("good", cfg, _stream(cycles=4, seed=1))
    journals = server.serve()
    assert len(journals["bad"]) == 0
    assert len(journals["good"]) == 4   # got the reclaimed slot
    assert server.scheduler.idle()
    snap = fresh_meters.snapshot()
    assert snap["counters"]["fleet.streams_failed"] == 1
    assert any(e["name"] == "fleet.stream_failed" and e["sid"] == "bad"
               for e in snap["events"])


def test_fleet_transient_pack_fault_retry_bitwise(fresh_meters):
    cfg = _cfg()

    def run_fleet(with_chaos):
        server = FleetServer(pack_workers=2, gather_window=0.0,
                             retry_backoff=0.001)
        for i in range(2):
            inj = (chaos.ChaosInjector(
                chaos.ChaosConfig(pack_fault_cycles=(1, 3)))
                if with_chaos else None)
            server.add_stream(f"s{i}", cfg, _stream(cycles=5, seed=i),
                              chaos=inj)
        return server.serve()

    a, b = run_fleet(False), run_fleet(True)
    for sid in a:
        assert a[sid].deterministic_json() == b[sid].deterministic_json()
    assert fresh_meters.snapshot()["counters"]["chaos.retries"] >= 4


def test_fleet_cohort_solve_retry_bitwise(fresh_meters):
    cfg = _cfg()

    def run_fleet(inj):
        server = FleetServer(pack_workers=2, gather_window=0.0,
                             retry_backoff=0.001, chaos=inj)
        for i in range(2):
            server.add_stream(f"s{i}", cfg, _stream(cycles=5, seed=i))
        return server.serve()

    a = run_fleet(None)
    b = run_fleet(chaos.ChaosInjector(
        chaos.ChaosConfig(solve_fault_cycles=(0, 2))))
    for sid in a:
        assert a[sid].deterministic_json() == b[sid].deterministic_json()
    assert fresh_meters.snapshot()["counters"]["chaos.retries"] >= 2


def test_fleet_snapshot_resume_bitwise(tmp_path, fresh_meters):
    cfg = _cfg()
    cycles = 7
    base = AssimilationEngine(cfg).run(
        streams.ResumableStream("drifting_swarm", 60, cycles, seed=4))
    ck = str(tmp_path / "fleet")
    server = FleetServer(pack_workers=2, gather_window=0.0)
    server.add_stream("s", cfg,
                      streams.ResumableStream("drifting_swarm", 60, cycles,
                                              seed=4),
                      checkpoint_dir=ck, snapshot_every=3)
    fleet_j = server.serve()["s"]
    assert fleet_j.deterministic_json() == base.deterministic_json()
    # Cross-path resume: a fleet-taken snapshot continues bitwise under
    # the single-engine run loop.
    eng2, stream2 = elastic.resume_assim_engine(
        os.path.join(ck, "step_00000003"))
    assert stream2.pos == 3
    j = eng2.run(stream2)
    assert j.deterministic_json() == base.deterministic_json()


# ---------------------------------------------------------------------------
# Subprocess integration: SIGKILL mid-stream + elastic restart under a
# forced-host CPU mesh.
# ---------------------------------------------------------------------------

_CHILD_PRELUDE = """
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.assim.engine import AssimilationEngine, EngineConfig
from repro.assim import streams
from repro.runtime import elastic
from repro.runtime.chaos import ChaosConfig, ChaosInjector
"""


def _run_child(script, devices=None, timeout=300):
    env = dict(os.environ)
    if devices:
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    return subprocess.run([sys.executable, "-c",
                           _CHILD_PRELUDE + script], env=env,
                          capture_output=True, text=True, timeout=timeout)


@pytest.mark.slow
def test_kill_and_resume_bitwise_subprocess(tmp_path):
    """SIGKILL the engine mid-stream (after the cycle-6 snapshot), resume
    in this process from the surviving checkpoint, and require the
    concatenated journal to be bitwise identical to an uninterrupted
    run."""
    ck = str(tmp_path / "ck")
    out = _run_child(f"""
inj = ChaosInjector(ChaosConfig(kill_cycles=(5,)))
eng = AssimilationEngine(EngineConfig(n=48, p=3, iters=6), chaos=inj)
eng.run(streams.ResumableStream("drifting_swarm", 60, 10, seed=2),
        checkpoint_dir=r"{ck}", snapshot_every=2)
print("UNREACHABLE")
""")
    assert out.returncode == -signal.SIGKILL, out.stderr[-2000:]
    assert "UNREACHABLE" not in out.stdout

    from repro.checkpoint import manager as ckpt
    latest = ckpt.latest_checkpoint(ck)
    assert latest is not None and latest.endswith("step_00000006")

    base = AssimilationEngine(EngineConfig(n=48, p=3, iters=6)).run(
        streams.ResumableStream("drifting_swarm", 60, 10, seed=2))
    eng2, stream2 = elastic.resume_assim_engine(ck)
    assert stream2.pos == 6
    j = eng2.run(stream2)
    assert j.deterministic_json() == base.deterministic_json()


def test_kill_resume_parent_holds_no_backend_at_spawn():
    """The kill-and-resume smoke's parent must not hold an accelerator
    while its child runs: a chip belongs to one process at a time, so a
    parent that had initialised a backend would starve the child."""
    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    out = _run_child(f"""
import sys
sys.path.insert(0, r"{bench}")
import chaos_bench
from jax._src import xla_bridge

def spawn(*args, **kwargs):
    print("BACKEND_AT_SPAWN", xla_bridge.backends_are_initialized())
    sys.exit(0)

chaos_bench.subprocess.run = spawn
chaos_bench.kill_resume_smoke()
""")
    assert out.returncode == 0, out.stderr[-2000:]
    assert "BACKEND_AT_SPAWN False" in out.stdout, out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["shelf", "kdtree"])
@pytest.mark.parametrize("new_p", [4, 2])
def test_elastic_restart_forced_host_subprocess(tmp_path, kind, new_p):
    """Save at p=8 under an 8-device forced-host CPU mesh; restart at
    p=4 / p=2 under a matching smaller mesh — the stream continues
    without replaying any completed cycle (acceptance criterion)."""
    ck = str(tmp_path / kind)
    cfg_src = {
        "shelf": "EngineConfig(n=64, ndim=2, nx=8, ny=8, pr=4, pc=2, "
                 "iters=6)",
        "kdtree": "EngineConfig(n=64, domain_kind='kdtree', p=8, nx=8, "
                  "ny=8, iters=6)",
    }[kind]
    out = _run_child(f"""
eng = AssimilationEngine({cfg_src})
assert eng.p == 8
eng.run(streams.ResumableStream("rotating_swarm", 80, 6, seed=9),
        checkpoint_dir=r"{ck}", snapshot_every=3)
print("SAVED")
""", devices=8)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SAVED" in out.stdout

    out = _run_child(f"""
import os
eng, stream = elastic.resume_assim_engine(
    os.path.join(r"{ck}", "step_00000003"), p={new_p})
assert eng.p == {new_p}, eng.p
assert stream.pos == 3, stream.pos
j = eng.run(stream)
assert [r.cycle for r in j.records] == list(range(6))
assert all(len(r.loads) == {new_p} for r in j.records[3:])
assert all(len(r.loads) == 8 for r in j.records[:3])
assert j.meta["resume"][-1]["remeshed"] is True
print("RESUMED")
""", devices=new_p)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "RESUMED" in out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["shelf", "kdtree"])
@pytest.mark.parametrize("p_old,p_new", [(2, 4), (4, 8)])
def test_elastic_growth_forced_host_subprocess(tmp_path, kind, p_old,
                                               p_new):
    """The growth direction: save at p under a forced-host mesh matching
    p, restart at 2p under a doubled mesh.  The stream continues without
    replaying any completed cycle, and the re-derived load-aware cut
    beats a cold default decomposition of the same shape on the first
    post-restart cycle's incoming imbalance (the point of carrying the
    load history through the remesh; satellite_track's anisotropic swath
    structure persists across cycles, so the journalled density is
    informative)."""
    ck = str(tmp_path / f"{kind}{p_old}")
    shelf_grid = {2: "pr=2, pc=1", 4: "pr=2, pc=2"}
    cfg_src = (
        f"EngineConfig(n=128, ndim=2, nx=16, ny=8, {shelf_grid[p_old]}, "
        f"iters=6)" if kind == "shelf"
        else f"EngineConfig(n=128, domain_kind='kdtree', p={p_old}, "
             f"nx=16, ny=8, iters=6)")
    out = _run_child(f"""
eng = AssimilationEngine({cfg_src})
assert eng.p == {p_old}
eng.run(streams.ResumableStream("satellite_track", 240, 8, seed=3),
        checkpoint_dir=r"{ck}", snapshot_every=4)
print("SAVED")
""", devices=p_old)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "SAVED" in out.stdout

    out = _run_child(f"""
import os
from repro.assim.metrics import imbalance_ratio
from repro.core import domain as domain_mod
from repro.core import kdtree as kdtree_mod
eng, stream = elastic.resume_assim_engine(
    os.path.join(r"{ck}", "step_00000004"), p={p_new})
assert eng.p == {p_new}, eng.p
assert stream.pos == 4, stream.pos
if "{kind}" == "shelf":
    cold_dom = domain_mod.ShelfTiling2D(nx=16, ny=8, pr=eng.domain.pr,
                                        pc=eng.domain.pc)
else:
    cold_dom = kdtree_mod.KDTreeDomain(nx=16, ny=8, p={p_new})
j = eng.run(stream)
assert [r.cycle for r in j.records] == list(range(8))
assert all(len(r.loads) == {p_new} for r in j.records[4:])
assert all(len(r.loads) == {p_old} for r in j.records[:4])
assert j.meta["resume"][-1] == {{"at_cycle": 4, "p": {p_new},
                                 "remeshed": True}}
it = streams.make_stream("satellite_track", 240, 8, seed=3)
obs4 = [next(it) for _ in range(5)][4]
warm = j.records[4].imbalance_before
cold = imbalance_ratio(cold_dom.counts(obs4))
assert warm < cold, (warm, cold)
print("GROWN", warm, cold)
""", devices=p_new)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "GROWN" in out.stdout
