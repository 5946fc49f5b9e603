"""Multi-tenant fleet serving: slot scheduler, cohort bucketing, and the
bitwise-determinism contract — per-stream journals and analyses from a
FleetServer must be bit-identical to running each engine's sequential
``run`` loop, on every domain kind, including under a forced 8-device
fleet mesh (subprocess) where cohorts are padded with dummy slots."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.assim import AssimilationEngine, EngineConfig, FleetServer, streams
from repro.assim import fleet as fleet_mod
from repro.core import cls, dd, ddkf, dydd
from repro.obs import meters as obs_meters
from repro.runtime.scheduler import SlotScheduler

import jax


@pytest.fixture()
def fresh_meters():
    prev = obs_meters.get_meters()
    m = obs_meters.Meters()
    obs_meters.set_meters(m)
    yield m
    obs_meters.set_meters(prev)


# ---------------------------------------------------------------------------
# SlotScheduler.
# ---------------------------------------------------------------------------

def test_scheduler_fifo_capacity_and_recycling(fresh_meters):
    s = SlotScheduler(capacity=2, meters_prefix="t.")
    for name in "abcd":
        s.submit(name)
    assert s.queue_depth() == 4 and s.idle() is False
    first = s.admit()
    assert first == [(0, "a"), (1, "b")]          # FIFO, capacity-bounded
    assert s.admit() == []                        # table full
    assert s.retire(0) == "a"
    assert s.admit() == [(0, "c")]                # lowest slot recycled
    s.retire(1)
    s.retire(0)
    assert s.admit() == [(0, "d")]                # lowest-first recycle
    s.retire(0)
    assert s.idle()
    assert s.stats() == {"submitted": 4, "retired": 4,
                         "active": 0, "queued": 0}
    snap = fresh_meters.snapshot()
    assert snap["gauges"]["t.queue_depth"] == 0
    assert snap["gauges"]["t.active"] == 0
    names = [e["name"] for e in snap["events"]]
    assert names.count("t.admit") == 4 and names.count("t.retire") == 4


def test_scheduler_unbounded_and_max_new(fresh_meters):
    s = SlotScheduler()                            # capacity=None
    for i in range(5):
        s.submit(i)
    assert [p for _, p in s.admit(max_new=2)] == [0, 1]
    assert [p for _, p in s.admit()] == [2, 3, 4]
    with pytest.raises(KeyError):
        s.retire(99)
    with pytest.raises(ValueError):
        SlotScheduler(capacity=0)


def test_serve_queue_waves_use_shared_scheduler(monkeypatch):
    """The LM driver's serve_queue rides the same SlotScheduler: waves
    of at most ``slots`` requests, FIFO, every request served once."""
    from repro.launch import serve as serve_drv

    waves = []

    def fake_serve_batch(cfg, params, requests, *, max_seq, greedy=True,
                         seed=0, mesh=None):
        waves.append([r.rid for r in requests])
        for r in requests:
            r.out = [r.rid]
        return requests, {"prefill_s": 0.0, "decode_s": 0.5}

    monkeypatch.setattr(serve_drv, "serve_batch", fake_serve_batch)
    reqs = [serve_drv.Request(rid=i, prompt=np.arange(3, dtype=np.int32),
                              max_new=1) for i in range(5)]
    done, stats = serve_drv.serve_queue(None, None, reqs, slots=2,
                                        max_seq=8)
    assert waves == [[0, 1], [2, 3], [4]]
    assert sorted(r.rid for r in done) == list(range(5))
    assert stats["waves"] == 3 and stats["decode_s"] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# Cohort machinery.
# ---------------------------------------------------------------------------

def test_quantize_capacity():
    assert fleet_mod.quantize_capacity(1) == 1
    assert fleet_mod.quantize_capacity(3) == 4
    assert fleet_mod.quantize_capacity(5, mult=8) == 8
    assert fleet_mod.quantize_capacity(9, mult=8) == 16
    with pytest.raises(ValueError):
        fleet_mod.quantize_capacity(0)


def _pack_problem(n=48, p=4, m=96, seed=0, overlap=0, obs_seed=0):
    rng = np.random.default_rng(obs_seed)
    obs = np.sort(rng.beta(2, 5, size=m))
    prob = cls.local_problem(jax.random.PRNGKey(seed), n, obs)
    res = dydd.dydd_1d(obs, p)
    dec = dd.decompose_1d(n, res.boundaries, overlap=overlap)
    return ddkf.pack(prob, dec)


def test_cohort_key_separates_shapes_and_statics():
    pk1 = _pack_problem(seed=0)
    pk2 = _pack_problem(seed=1)          # same shapes, different data
    pk3 = _pack_problem(n=64, seed=0)    # different n (and w)
    k = lambda pk, **kw: fleet_mod.cohort_key(
        pk, kw.get("iters", 40), kw.get("damping", 1.0),
        kw.get("rec", False))
    assert k(pk1) == k(pk2)
    assert k(pk1) != k(pk3)
    assert k(pk1) != k(pk1, iters=60)
    assert k(pk1) != k(pk1, damping=0.7)
    assert k(pk1) != k(pk1, rec=True)


def test_stack_packed_rejects_mixed_shapes():
    with pytest.raises(ValueError, match="stack"):
        ddkf.stack_packed([])
    with pytest.raises(ValueError):
        ddkf.stack_packed([_pack_problem(n=48), _pack_problem(n=64)])


def test_solve_fleet_bitwise_vs_sequential_vmapped():
    """The fleet map is the same program per member: stacking K problems
    and solving once gives bit-identical results to K separate
    solve_vmapped calls — including with dummy padding copies whose
    results are discarded (CohortSolver's quantization)."""
    packs = [_pack_problem(seed=s) for s in range(3)]
    seq = [np.asarray(ddkf.solve_vmapped(pk, iters=40, damping=0.8))
           for pk in packs]
    res = fleet_mod.CohortSolver().solve(
        fleet_mod.cohort_key(packs[0], 40, 0.8, False), packs)
    assert res.size == 3 and res.capacity == 4       # padded to 2**j
    for a, b in zip(res.xs, seq):
        assert np.array_equal(np.asarray(a), b)


# ---------------------------------------------------------------------------
# FleetServer determinism vs sequential engines.
# ---------------------------------------------------------------------------

def _recorder(store):
    def forecast(x):
        store.append(np.asarray(x).copy())
        return x
    return forecast


def _sequential(specs):
    out = {}
    for sid, cfg, (name, m, cycles, seed) in specs:
        rec = []
        eng = AssimilationEngine(cfg, forecast=_recorder(rec))
        eng.run(streams.make_stream(name, m, cycles, seed=seed))
        out[sid] = (rec, np.asarray(eng.analysis), eng.journal)
    return out


def _fleet(specs, **server_kw):
    server = FleetServer(**server_kw)
    recs = {}
    for sid, cfg, (name, m, cycles, seed) in specs:
        recs[sid] = []
        server.add_stream(sid, cfg,
                          streams.make_stream(name, m, cycles, seed=seed),
                          forecast=_recorder(recs[sid]))
    journals = server.serve()
    return recs, journals, server


def _assert_stream_parity(specs, seq, recs, journals):
    for sid, _, (_, _, cycles, _) in specs:
        rec_s, final_s, j_s = seq[sid]
        j_f = journals[sid]
        assert len(j_f) == len(j_s) == cycles
        # Bitwise per-cycle analyses (the forecast wrapper sees every
        # carried analysis) ...
        assert len(recs[sid]) == len(rec_s)
        for a, b in zip(recs[sid], rec_s):
            assert np.array_equal(a, b), sid
        # ... and bit-identical journalled decisions/numerics (timing
        # fields naturally differ).
        for rf, rs in zip(j_f.records, j_s.records):
            assert rf.loads == rs.loads
            assert rf.loads_before == rs.loads_before
            assert rf.repartitioned == rs.repartitioned
            assert rf.migrated == rs.migrated
            assert rf.imbalance == rs.imbalance
            assert rf.residual_history == rs.residual_history
            assert rf.comm_bytes_per_cycle == rs.comm_bytes_per_cycle


def test_fleet_two_streams_bitwise_1d(fresh_meters):
    specs = [
        ("s0", EngineConfig(n=48, p=4, iters=30),
         ("drifting_swarm", 120, 3, 0)),
        ("s1", EngineConfig(n=48, p=4, iters=30),
         ("bursty_clusters", 120, 3, 1)),
    ]
    seq = _sequential(specs)
    recs, journals, server = _fleet(specs, max_active=2)
    _assert_stream_parity(specs, seq, recs, journals)
    assert server.stats["cycles"] == 6
    snap = fresh_meters.snapshot()
    assert snap["counters"]["fleet.cohort.dispatches"] >= 3
    assert "fleet.queue_depth" in snap["gauges"]


def test_fleet_mixed_domains_bitwise_with_churn(fresh_meters):
    """2D shelf + kdtree + 1D with residual recording, more streams than
    slots: admission/retirement churn and per-stream DyDD repacks leave
    every stream bit-identical to its sequential run."""
    specs = [
        ("shelf", EngineConfig(ndim=2, nx=12, ny=8, pr=2, pc=2, iters=25),
         ("rotating_swarm", 200, 3, 1)),
        ("kdtree", EngineConfig(ndim=2, nx=16, ny=12,
                                domain_kind="kdtree", p=4, iters=25),
         ("satellite_track", 240, 3, 2)),
        ("hist", EngineConfig(n=64, p=4, iters=25, record_residuals=True),
         ("storm_front", 150, 3, 4)),
        ("line", EngineConfig(n=48, p=4, iters=25),
         ("drifting_swarm", 120, 4, 5)),
    ]
    seq = _sequential(specs)
    recs, journals, server = _fleet(specs, max_active=2, pack_workers=2)
    _assert_stream_parity(specs, seq, recs, journals)
    snap = fresh_meters.snapshot()
    repacks = [e for e in snap["events"]
               if e["name"] == "fleet.dydd.repack"]
    assert repacks, "expected at least one DyDD repack in these streams"
    assert snap["counters"]["fleet.rounds"] == server.stats["rounds"]


def test_fleet_add_stream_validation():
    server = FleetServer()
    cfg = EngineConfig(n=32, p=2, iters=10)
    server.add_stream("a", cfg, [])
    with pytest.raises(ValueError, match="duplicate"):
        server.add_stream("a", cfg, [])
    with pytest.raises(ValueError, match="vmapped"):
        server.add_stream("b", EngineConfig(n=32, p=2, solver="shardmap"),
                          [])
    journals = server.serve()          # empty stream retires immediately
    assert len(journals["a"]) == 0
    assert server.stats["cycles"] == 0


# ---------------------------------------------------------------------------
# Readmit: crash -> snapshot -> slot re-acquisition -> bitwise completion.
# ---------------------------------------------------------------------------

def test_fleet_readmit_crashed_stream(fresh_meters, tmp_path):
    """A stream that crashes mid-serve (injected pack faults exhausting
    the retry budget) is retired as failed; readmit() rebuilds it from
    its latest per-stream snapshot, re-queues it through the
    SlotScheduler, and the completed journal is bitwise-identical
    (modulo wall-clock fields) to an uninterrupted run."""
    from repro.runtime import chaos as chaos_mod

    cfg = EngineConfig(n=48, p=4, iters=25)
    name, m, cycles, seed = "drifting_swarm", 120, 6, 0

    eng_ref = AssimilationEngine(cfg)
    eng_ref.run(streams.make_stream(name, m, cycles, seed=seed))
    ref_json = eng_ref.journal.deterministic_json()

    # Crash s0 at cycle 3 (the fault re-fires on every retry) with a
    # snapshot at every cycle boundary; a healthy companion stream
    # keeps the server round loop honest.
    ckpt = str(tmp_path / "s0")
    inj = chaos_mod.ChaosInjector(chaos_mod.ChaosConfig(
        pack_fault_cycles=(3,), fail_every_attempt=True))
    server = FleetServer(max_active=2, max_retries=1, retry_backoff=0.0)
    server.add_stream("s0", cfg,
                      streams.ResumableStream(name, m, cycles, seed=seed),
                      checkpoint_dir=ckpt, snapshot_every=1, chaos=inj)
    server.add_stream("side", cfg,
                      streams.make_stream("bursty_clusters", 120, 4,
                                          seed=1))
    journals = server.serve()
    assert len(journals["s0"]) == 3          # crashed before cycle 3
    assert len(journals["side"]) == 4

    with pytest.raises(ValueError, match="checkpoint_dir"):
        server.readmit("side")               # no snapshots configured
    with pytest.raises(KeyError):
        server.readmit("nope")

    server.readmit("s0")                     # fresh engine, no chaos
    with pytest.raises(ValueError, match="active or queued"):
        server.readmit("s0")                 # already back in the queue
    journals = server.serve()
    assert len(journals["s0"]) == cycles
    assert journals["s0"].deterministic_json() == ref_json

    snap = fresh_meters.snapshot()
    names = [e["name"] for e in snap["events"]]
    assert "fleet.stream_failed" in names
    assert "fleet.stream_readmitted" in names
    assert snap["counters"]["fleet.streams_readmitted"] == 1
    re_ev = [e for e in snap["events"]
             if e["name"] == "fleet.stream_readmitted"][0]
    assert re_ev["sid"] == "s0" and re_ev["resume_cycle"] == 3


# ---------------------------------------------------------------------------
# Forced 8-device fleet mesh (subprocess, like test_ddkf_multidevice).
# ---------------------------------------------------------------------------

SCRIPT_FLEET_8DEV = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.assim import AssimilationEngine, EngineConfig, FleetServer, streams

def recorder(store):
    def f(x):
        store.append(np.asarray(x).copy())
        return x
    return f

specs = [(f"s{i}", EngineConfig(n=48, p=4, iters=25),
          ("drifting_swarm", 120, 3, i)) for i in range(3)]

seq = {}
for sid, cfg, (name, m, cycles, seed) in specs:
    rec = []
    eng = AssimilationEngine(cfg, forecast=recorder(rec))
    eng.run(streams.make_stream(name, m, cycles, seed=seed))
    seq[sid] = (rec, eng.journal)

mesh = jax.make_mesh((8,), ("fleet",),
                     axis_types=(jax.sharding.AxisType.Auto,))
server = FleetServer(mesh=mesh, mesh_axis="fleet")
recs = {}
for sid, cfg, (name, m, cycles, seed) in specs:
    recs[sid] = []
    server.add_stream(sid, cfg,
                      streams.make_stream(name, m, cycles, seed=seed),
                      forecast=recorder(recs[sid]))
journals = server.serve()
for sid, cfg, (name, m, cycles, seed) in specs:
    rec_s, j_s = seq[sid]
    assert len(journals[sid]) == len(j_s) == cycles
    assert len(recs[sid]) == len(rec_s)
    for a, b in zip(recs[sid], rec_s):
        assert np.array_equal(a, b), sid
    for rf, rs in zip(journals[sid].records, j_s.records):
        assert rf.loads == rs.loads and rf.migrated == rs.migrated
print("OK", server.stats["cycles"])
"""


@pytest.mark.slow
def test_fleet_8_device_cohort_bitwise():
    """3 live streams on an 8-device fleet mesh: the cohort pads to 8
    with dummy copies, shards members across devices, and still returns
    bit-identical per-stream analyses to sequential single-device
    runs."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..",
                                     "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT_FLEET_8DEV],
                         env=env, capture_output=True, text=True,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
