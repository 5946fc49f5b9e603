"""Observability layer: span tracer, meters registry, engine telemetry.

Covers the telemetry PR's acceptance surface: span nesting and thread
attribution, Chrome trace_events schema, zero-overhead disabled tracing,
residual-history monotonicity on a converging solve, comm-matrix totals
against the journalled per-cycle bytes, journal round-trips with the new
fields, and the straggler monitor wired through the engine cycle loop.
"""
import glob
import json
import os
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from repro.assim import AssimilationEngine, EngineConfig
from repro.assim.metrics import CycleMetrics, Journal
from repro.core import cls, dd, ddkf
from repro.obs import meters as obs_meters
from repro.obs import trace as obs_trace
from repro.runtime.straggler import StragglerConfig


@pytest.fixture()
def fresh_meters():
    prev = obs_meters.get_meters()
    m = obs_meters.Meters()
    obs_meters.set_meters(m)
    yield m
    obs_meters.set_meters(prev)


# ---------------------------------------------------------------------------
# Tracer primitives.
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_parent():
    tr = obs_trace.Tracer()
    with obs_trace.tracing(tr):
        with obs_trace.span("outer"):
            with obs_trace.span("inner"):
                time.sleep(0.002)
    outer, = tr.spans("outer")
    inner, = tr.spans("inner")
    assert outer["args"]["depth"] == 0 and "parent" not in outer["args"]
    assert inner["args"]["depth"] == 1
    assert inner["args"]["parent"] == "outer"
    # The child closes first and lies inside the parent's window.
    assert inner["t0"] >= outer["t0"]
    assert inner["t0"] + inner["dur"] <= outer["t0"] + outer["dur"] + 1e-9
    assert outer["dur"] >= 0.002


def test_span_thread_attribution():
    """Spans land on the opening thread's track; nesting stacks are
    per-thread (a worker's span is never a child of the main thread's)."""
    tr = obs_trace.Tracer()

    def worker():
        with tr.span("work"):
            time.sleep(0.001)

    with tr.span("main-span"):
        t = threading.Thread(target=worker, name="worker-1")
        t.start()
        t.join()
    work, = tr.spans("work")
    main, = tr.spans("main-span")
    assert work["track"] == "worker-1"
    assert main["track"] != "worker-1"
    assert work["args"]["depth"] == 0       # not nested under main-span
    assert "parent" not in work["args"]


def test_span_fence_blocks_device_work():
    """A fenced span's duration includes the device work that produced
    the fenced value (block_until_ready runs before the span closes)."""
    tr = obs_trace.Tracer()
    x = np.random.default_rng(0).normal(size=(200, 200))
    with obs_trace.tracing(tr):
        with obs_trace.span("matmul") as sp:
            y = jax.numpy.asarray(x) @ jax.numpy.asarray(x)
            sp.fence(y)
    sp_rec, = tr.spans("matmul")
    assert sp_rec["dur"] > 0
    assert np.isfinite(np.asarray(y)).all()


def test_chrome_trace_schema():
    tr = obs_trace.Tracer(process_name="test-proc")
    with tr.span("a", cycle=3):
        pass
    tr.emit("dev-span", time.perf_counter() - 0.01, 0.01,
            track="device 0")
    doc = tr.to_chrome_trace()
    # Round-trips through JSON (the export is what --trace writes).
    doc = json.loads(json.dumps(doc))
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert all(e["ph"] in ("X", "M") for e in evs)
    xs = [e for e in evs if e["ph"] == "X"]
    assert {e["name"] for e in xs} == {"a", "dev-span"}
    for e in xs:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0 and e["pid"] == 0
    # Metadata: a process_name row and one thread_name row per track,
    # with device rows sorted after host threads.
    metas = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"]: e["tid"] for e in metas
             if e["name"] == "thread_name"}
    assert "device 0" in names
    host_tids = [tid for t, tid in names.items()
                 if not t.startswith("device")]
    assert all(names["device 0"] > tid for tid in host_tids)
    assert any(e["name"] == "process_name"
               and e["args"]["name"] == "test-proc" for e in metas)
    # X events reference declared tids only.
    assert {e["tid"] for e in xs} <= set(names.values())


def test_null_tracer_is_shared_noop():
    prev = obs_trace.set_tracer(None)
    try:
        s1 = obs_trace.span("anything", key="val")
        s2 = obs_trace.span("other")
        assert s1 is s2                      # shared singleton, no alloc
        with s1 as s:
            assert s.fence(123) == 123
            s.annotate(a=1)                  # no-op, no error
    finally:
        obs_trace.set_tracer(prev)


def test_disabled_tracing_overhead_micro_bench():
    """The disabled span path must stay allocation-free and cheap: 50k
    disabled spans in well under a second even on a loaded CI box (the
    real figure is tens of nanoseconds each)."""
    prev = obs_trace.set_tracer(None)
    try:
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with obs_trace.span("hot"):
                pass
        dt = time.perf_counter() - t0
    finally:
        obs_trace.set_tracer(prev)
    assert dt < 1.0, f"disabled tracing cost {dt / n * 1e6:.2f}us/span"


def test_jax_profile_raises_when_profiler_fails(monkeypatch):
    """--profile DIR with a profiler that cannot start fails the run
    instead of finishing without a profile; no DIR is a no-op."""
    def broken(logdir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "trace", broken)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with obs_trace.jax_profile("/nonexistent/profile"):
            pass
    with obs_trace.jax_profile(None) as got:
        assert got is None


def test_tracing_context_restores_previous():
    tr = obs_trace.Tracer()
    base = obs_trace.get_tracer()
    with obs_trace.tracing(tr):
        assert obs_trace.get_tracer() is tr
    assert obs_trace.get_tracer() is base


# ---------------------------------------------------------------------------
# Meters registry.
# ---------------------------------------------------------------------------

def test_meters_counters_series_events(fresh_meters):
    m = fresh_meters
    m.inc("a")
    m.inc("a", 2.5)
    m.gauge("g", 7)
    m.observe("s", 1.0)
    m.extend("s", [2.0, 3.0])
    m.event("e", foo="bar")
    snap = m.snapshot()
    assert snap["counters"]["a"] == 3.5
    assert snap["gauges"]["g"] == 7
    assert snap["series"]["s"] == [1.0, 2.0, 3.0]
    assert snap["events"][0]["name"] == "e"
    assert snap["events"][0]["foo"] == "bar"
    json.dumps(snap)                         # JSON-serializable
    m.reset()
    assert not m.counters and not m.series and not m.events


def test_meters_thread_hammer(fresh_meters):
    """Concurrent inc/observe/event from many threads lose nothing: the
    registry serializes every mutation behind one lock (``counters[k] +=
    v`` is a read-modify-write, not atomic under the GIL), which is what
    lets the fleet's packing pool and serving loop share one Meters."""
    m = fresh_meters
    threads, per = 8, 500
    barrier = threading.Barrier(threads)

    def hammer(tid):
        barrier.wait()
        for i in range(per):
            m.inc("h.count")
            m.inc("h.weighted", 0.5)
            m.observe("h.series", float(tid))
            m.gauge(f"h.gauge.{tid}", i)
            if i % 100 == 0:
                m.event("h.event", tid=tid, i=i)

    ts = [threading.Thread(target=hammer, args=(t,)) for t in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    snap = m.snapshot()
    assert snap["counters"]["h.count"] == threads * per
    assert snap["counters"]["h.weighted"] == pytest.approx(
        threads * per * 0.5)
    assert len(snap["series"]["h.series"]) == threads * per
    assert len(snap["events"]) == threads * (per // 100)
    for t in range(threads):
        assert snap["gauges"][f"h.gauge.{t}"] == per - 1


def test_comm_matrix_symmetric_and_total():
    per_edge = {"0-1": 100.0, "1-2": 50.0}
    M = obs_meters.comm_matrix(3, per_edge)
    assert M.shape == (3, 3)
    np.testing.assert_array_equal(M, M.T)
    # Each endpoint sends the edge's bytes: total = 2 * sum(edges).
    assert M.sum() == 2 * (100.0 + 50.0)
    assert M[0, 1] == 100.0 and M[1, 2] == 50.0 and M[0, 2] == 0.0


# ---------------------------------------------------------------------------
# Residual histories.
# ---------------------------------------------------------------------------

def _packed_problem(n=48, p=4, overlap=1, m=150):
    rng = np.random.default_rng(0)
    obs = np.sort(rng.beta(2, 5, m))
    prob = cls.local_problem(jax.random.PRNGKey(0), n, obs)
    dec = dd.decompose_1d(n, dd.uniform_boundaries(p), overlap=overlap)
    return ddkf.pack(prob, dec)


def test_residual_history_converges_and_matches_default_path():
    packed = _packed_problem()
    x_plain = ddkf.solve_vmapped(packed, iters=150)
    x_hist, hist = ddkf.solve_vmapped(packed, iters=150,
                                      residual_history=True)
    np.testing.assert_allclose(np.asarray(x_hist), np.asarray(x_plain),
                               rtol=0, atol=1e-12)
    hist = np.asarray(hist)
    assert hist.shape == (150,)
    # Converging Schwarz iteration: the update norm collapses by orders
    # of magnitude, and the tail is (weakly) monotone non-increasing.
    assert hist[-1] < 1e-8 * max(hist[0], 1e-30)
    tail = hist[len(hist) // 2:]
    assert np.all(np.diff(tail) <= 1e-12 + tail[:-1] * 1e-6)


# ---------------------------------------------------------------------------
# Engine telemetry end to end.
# ---------------------------------------------------------------------------

def _run_engine(tracer=None, cycles=3, **cfg_kw):
    kw = dict(n=48, p=4, iters=60, overlap=1, comm="neighbour",
              record_residuals=True, double_buffer=True)
    kw.update(cfg_kw)
    eng = AssimilationEngine(EngineConfig(**kw))
    with obs_trace.tracing(tracer):
        journal = eng.run_scenario("drifting_swarm", m=160, cycles=cycles)
    return eng, journal


def test_engine_phases_and_trace_coverage(fresh_meters):
    tr = obs_trace.Tracer()
    eng, journal = _run_engine(tracer=tr)
    for rec in journal.records:
        assert {"count", "halo", "pack", "data", "solve"} <= set(
            rec.phases)
        assert all(v >= 0 for v in rec.phases.values())
    # The cycle spans cover the measured wall-clock (acceptance: >=95%).
    wall = sum(journal.cycle_times)
    assert tr.coverage("cycle", wall) >= 0.95
    # Packing ran on the double-buffer worker thread from cycle 1 on.
    pack_tracks = {s["track"] for s in tr.spans("pack")}
    assert any(t.startswith("pack") for t in pack_tracks)
    # Summary aggregates per-phase percentiles.
    stats = journal.summary()["phases"]
    assert stats["solve"]["p99"] >= stats["solve"]["p50"] > 0
    # Meters got the engine-level counters.
    assert fresh_meters.counters["engine.cycles"] == len(journal)


def test_engine_residual_history_journalled():
    _, journal = _run_engine(cycles=2)
    for rec in journal.records:
        assert len(rec.residual_history) == 60
        assert rec.residual_history[-1] < rec.residual_history[0]


def test_comm_matrix_total_matches_journalled_bytes():
    """matrix.sum() + mvec bytes == comm_bytes_per_cycle on the
    neighbour path (the per-edge dict is the same model, itemized)."""
    _, journal = _run_engine(cycles=2)
    p = journal.meta["p"]
    for rec in journal.records:
        M = obs_meters.comm_matrix(p, rec.comm_edge_bytes_per_cycle)
        np.testing.assert_array_equal(M, M.T)
        assert np.isclose(M.sum() + rec.comm_mvec_bytes_per_cycle,
                          rec.comm_bytes_per_cycle)


def test_journal_round_trip_with_telemetry_fields():
    _, journal = _run_engine(cycles=2)
    doc = json.loads(journal.to_json())
    j2 = Journal.from_dict(doc)
    assert len(j2) == len(journal)
    for a, b in zip(journal.records, j2.records):
        assert b.phases == {k: float(v) for k, v in a.phases.items()}
        assert b.residual_history == [float(v)
                                      for v in a.residual_history]
        assert b.comm_edge_bytes_per_cycle == a.comm_edge_bytes_per_cycle
        assert b.device_solve_times == a.device_solve_times
        assert b.straggler_flags == a.straggler_flags
        assert b.loads == a.loads
    # Old-journal compatibility: records without the new keys load with
    # the defaults, and unknown future keys are ignored.
    legacy = {k: v for k, v in doc["records"][0].items()
              if k not in ("phases", "residual_history",
                           "comm_edge_bytes_per_cycle",
                           "comm_mvec_bytes_per_cycle",
                           "device_solve_times", "straggler_flags")}
    legacy["some_future_field"] = 1
    rec = CycleMetrics.from_dict(legacy)
    assert rec.phases == {} and rec.residual_history == []


def test_straggler_monitor_wired_into_cycle_loop(fresh_meters):
    """With a pathological deadline config every post-grace cycle is
    flagged; the flags land in the journal and the meters."""
    cfg = StragglerConfig(grace_steps=0, consecutive_trigger=1,
                          deadline_factor=1e-9)
    eng = AssimilationEngine(
        EngineConfig(n=48, p=4, iters=40, record_residuals=False),
        straggler_config=cfg)
    journal = eng.run_scenario("drifting_swarm", m=160, cycles=3)
    # record() seeds the EWMA on the first post-grace step, so flags
    # start at the second cycle (the vmapped solve is device 0).
    assert journal.records[0].straggler_flags == []
    for rec in journal.records[1:]:
        assert rec.straggler_flags == [0]
        assert rec.device_solve_times and len(rec.device_solve_times) == 1
    assert fresh_meters.counters["engine.straggler.flags"] == 2
    assert journal.summary()["straggler_flags_total"] == 2


def test_engine_disabled_tracing_by_default(fresh_meters):
    """No tracer installed: the engine runs clean and records phases in
    the journal anyway (the dict timing is tracer-independent)."""
    assert isinstance(obs_trace.get_tracer(), obs_trace.NullTracer)
    _, journal = _run_engine(tracer=None, cycles=2,
                             record_residuals=False)
    assert all(r.phases["solve"] > 0 for r in journal.records)
    assert all(r.residual_history == [] for r in journal.records)


# ---------------------------------------------------------------------------
# Journal phases: pack and solve in timed steps, compiles per phase, the
# profiler's timeline.
# ---------------------------------------------------------------------------

PACK_STEPS = ("pack.h1", "pack.concat", "pack.roundtrip", "pack.fill",
              "pack.h2d", "pack.factor")
SOLVE_STEPS = ("solve.input", "solve.device")


def test_phase_writes_nested_steps_and_accumulates():
    phases = obs_trace.Phases()
    with obs_trace.phase(phases, "outer"):
        with obs_trace.phase(phases, "outer.step"):
            time.sleep(0.002)
        with obs_trace.phase(phases, "outer.step"):
            time.sleep(0.002)
    assert phases["outer.step"] >= 0.004
    assert phases["outer"] >= phases["outer.step"]
    assert phases.compiles == {}
    with obs_trace.phase(None, "nowhere") as ph:   # writes nothing
        assert ph.fence(7) == 7
    assert "nowhere" not in phases


def test_engine_pack_and_solve_steps_cover_their_phases():
    """Every cycle's pack.* steps cover >= 95% of pack and its solve.*
    steps >= 95% of solve; pack stays the total.  (Cycles of a few ms
    each, one thread: what lies between the steps is the instrumentation
    and the packing's return, tens of us.)"""
    eng = AssimilationEngine(EngineConfig(n=128, p=4, iters=200, overlap=1,
                                          double_buffer=False))
    journal = eng.run_scenario("drifting_swarm", m=300, cycles=4)
    for rec in journal.records:
        ph = rec.phases
        assert set(PACK_STEPS) <= set(ph) and set(SOLVE_STEPS) <= set(ph)
        pack = sum(ph[k] for k in PACK_STEPS)
        solve = sum(ph[k] for k in SOLVE_STEPS)
        assert 0.95 * ph["pack"] <= pack <= ph["pack"]
        assert 0.95 * ph["solve"] <= solve <= ph["solve"]


def test_engine_writes_six_pack_steps_and_copies_h0_once():
    """Each cycle of a run writes all six pack.* steps, summing to within
    1% of pack; the engine copies H0 to the device once, on the first
    prepare, and every later cycle packs from that copy."""
    eng = AssimilationEngine(EngineConfig(n=512, p=4, iters=20, overlap=1,
                                          track_reference=False))
    assert eng._H0_dev is None
    h0s = []
    eng.on_analysis = lambda c, x: h0s.append(eng._H0_dev)
    journal = eng.run_scenario("drifting_swarm", m=1200, cycles=4)
    assert len(h0s) == 4 and h0s[0] is not None
    assert all(h is h0s[0] for h in h0s)
    np.testing.assert_array_equal(np.asarray(h0s[0]), eng._H0)
    for rec in journal.records:
        ph = rec.phases
        assert set(PACK_STEPS) <= set(ph)
        pack = sum(ph[k] for k in PACK_STEPS)
        assert 0.99 * ph["pack"] <= pack <= ph["pack"]


def test_engine_analyses_bitwise_with_and_without_tracer():
    runs = []
    for tracer in (None, obs_trace.Tracer()):
        xs = []
        eng = AssimilationEngine(EngineConfig(
            n=48, p=4, iters=60, overlap=1, comm="neighbour",
            record_residuals=True, double_buffer=True))
        eng.on_analysis = lambda c, x: xs.append(np.asarray(x))
        with obs_trace.tracing(tracer):
            eng.run_scenario("drifting_swarm", m=160, cycles=3)
        runs.append(np.stack(xs))
    np.testing.assert_array_equal(runs[0], runs[1])


def test_compiles_are_journalled_under_the_phase_that_caused_them(
        fresh_meters):
    """A cycle at a width this process has not compiled records its
    compiles under pack.factor and solve.device; a repeat cycle on the
    same network records none."""
    obs = np.sort(np.random.default_rng(5).uniform(0.05, 0.95, 97))
    eng = AssimilationEngine(EngineConfig(n=44, p=3, iters=20,
                                          rebalance=False))
    journal = eng.run([obs, obs])
    first, repeat = journal.records
    assert first.compiles["pack.factor"][0] >= 1
    assert first.compiles["solve.device"][0] >= 1
    assert all(c >= 1 and s > 0 for c, s in first.compiles.values())
    assert repeat.compiles == {}
    # The per-cycle series the journal already holds are no meters.
    assert not {"engine.imbalance", "engine.halo_fraction",
                "engine.residual_final", "dydd.cg_residual"} & set(
                    fresh_meters.series)
    doc = CycleMetrics.from_dict(json.loads(json.dumps(first.to_dict())))
    assert doc.compiles == first.compiles


def _host_lines(logdir):
    """[[event names] per line] of the host plane of the newest profile
    under ``logdir``."""
    path = max(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    data = jax.profiler.ProfileData.from_file(path)
    host, = [pl for pl in data.planes if pl.name == "/host:CPU"]
    return [[e.name for e in line.events] for line in host.lines]


def test_profiler_trace_holds_engine_phases_without_a_tracer(tmp_path):
    """With no Tracer and no wrapper, a jax.profiler trace holds the
    engine's phases as repro.* host events on the thread that ran them:
    prepare and the pack steps on the packing worker's line, the solve
    and its steps on the main thread's."""
    assert isinstance(obs_trace.get_tracer(), obs_trace.NullTracer)
    eng = AssimilationEngine(EngineConfig(n=48, p=4, iters=30,
                                          double_buffer=True))
    with jax.profiler.trace(str(tmp_path)):
        eng.run_scenario("drifting_swarm", m=160, cycles=2)
    lines = [set(n for n in names if n.startswith("repro."))
             for names in _host_lines(str(tmp_path))]
    worker = [ln for ln in lines if "repro.prepare" in ln]
    main = [ln for ln in lines if "repro.solve" in ln]
    assert len(worker) == 1 and len(main) == 1 and worker != main
    assert {"repro.prepare", "repro.count", "repro.pack", "repro.data"} | {
        "repro." + k for k in PACK_STEPS} <= worker[0]
    assert {"repro.solve"} | {"repro." + k for k in SOLVE_STEPS} <= main[0]
    # Outside a trace the disabled path is the shared no-op again.
    assert obs_trace.span("a") is obs_trace.span("b")


def _fresh_compile():
    """Compile a function jax has not seen before; returns its value."""
    return jax.jit(lambda v: v * 3.0 + 1.0)(np.arange(3.0))


def test_compiles_go_to_the_innermost_journal_phase_only():
    """A compile lands under the innermost open phase whose dict is a
    Phases, passing over a phase without a dict; under a plain dict or
    no phase at all it is journalled nowhere."""
    phases = obs_trace.Phases()
    plain: dict = {}
    with obs_trace.phase(phases, "outer"):
        with obs_trace.phase(None, "no-dict"):
            _fresh_compile()
    with obs_trace.phase(plain, "plain"):
        _fresh_compile()
    _fresh_compile()
    assert set(phases.compiles) == {"outer"}
    count, seconds = phases.compiles["outer"]
    assert count >= 1 and seconds > 0
    assert set(plain) == {"plain"} and not hasattr(plain, "compiles")


def test_phase_without_a_dict_never_fences(monkeypatch):
    """Only a phase that records blocks on its fence, Tracer or not."""
    blocked = []
    monkeypatch.setattr(obs_trace, "_block", blocked.append)
    with obs_trace.tracing(obs_trace.Tracer()):
        with obs_trace.phase(None, "unrecorded") as ph:
            ph.fence("a")
        phases = obs_trace.Phases()
        with obs_trace.phase(phases, "recorded") as ph:
            ph.fence("b")
    with obs_trace.phase(None, "untraced") as ph:
        ph.fence("c")
    assert blocked == ["b"] and set(phases) == {"recorded"}


def test_importing_obs_loads_no_jax_and_registers_no_listener():
    """repro.obs stays importable from every layer without jax; the
    compile listener is registered by the first Phases."""
    code = ("import sys; import repro.obs as o; "
            "assert 'jax' not in sys.modules, 'jax'; "
            "assert not o.trace._LISTENING; "
            "o.Phases(); assert o.trace._LISTENING; "
            "assert 'jax' in sys.modules")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src")]
        + [v for v in [os.environ.get("PYTHONPATH")] if v]))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


@pytest.mark.parametrize("overlap", [0, 1])
def test_pack_operator_steps_leave_the_packing_unchanged(overlap):
    """pack_operator with a phases dict writes its three steps and packs
    the same arrays, bit for bit, as without one (which writes
    nothing)."""
    rng = np.random.default_rng(7)
    obs = np.sort(rng.beta(2, 5, 150))
    prob = cls.local_problem(jax.random.PRNGKey(0), 48, obs)
    dec = dd.decompose_1d(48, dd.uniform_boundaries(4), overlap=overlap)
    A, _, r = prob.stacked()
    phases = obs_trace.Phases()
    timed = ddkf.pack_operator(A, r, dec, phases=phases)
    plain = ddkf.pack_operator(A, r, dec)
    assert set(phases) == {"pack.fill", "pack.h2d", "pack.factor"}
    assert all(v > 0 for v in phases.values())
    for f in ("A_loc", "Ninv_loc", "cols", "mask", "muov", "wdiv", "mult",
              "mult_loc", "scatter_cols", "gather_cols", "r", "b"):
        np.testing.assert_array_equal(np.asarray(getattr(timed, f)),
                                      np.asarray(getattr(plain, f)))
