"""Observability layer: span tracer, meters registry, engine telemetry.

Covers the telemetry PR's acceptance surface: span nesting and thread
attribution, Chrome trace_events schema, zero-overhead disabled tracing,
residual-history monotonicity on a converging solve, comm-matrix totals
against the journalled per-cycle bytes, journal round-trips with the new
fields, and the straggler monitor wired through the engine cycle loop.
"""
import json
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
