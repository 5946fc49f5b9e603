"""Streaming assimilation engine: scenario registry (1D + 2D), rebalance
policy, double-buffered pipelining, agreement with the one-shot DD-KF
solve, and the dimension-agnostic Domain layer (degenerate 2D == 1D)."""
import json

import numpy as np
import pytest

from repro.assim import (AssimilationEngine, EngineConfig, Journal,
                         imbalance_ratio, streams)
from repro.core import domain as domain_mod

THRESHOLD = 1.5
CYCLES = 6


def small_config(**kw):
    base = dict(n=64, p=4, iters=80, imbalance_threshold=THRESHOLD,
                track_reference=True)
    base.update(kw)
    return EngineConfig(**base)


def small_config_2d(**kw):
    base = dict(ndim=2, nx=12, ny=8, pr=2, pc=2, iters=600, damping=0.7,
                imbalance_threshold=THRESHOLD, track_reference=True)
    base.update(kw)
    return EngineConfig(**base)


# ---------------------------------------------------------------------------
# Stream registry.
# ---------------------------------------------------------------------------

def test_registry_has_the_five_scenarios():
    names = streams.available(ndim=1)
    assert len(names) >= 5
    for required in ("drifting_swarm", "bursty_clusters", "sensor_dropout",
                     "diurnal", "storm_front"):
        assert required in names


def test_registry_has_four_2d_scenarios():
    names = streams.available(ndim=2)
    assert len(names) >= 4
    for required in ("storm_front_2d", "rotating_swarm", "coastal_band",
                     "grid_dropout"):
        assert required in names
    # the unfiltered listing carries both dimensions
    assert set(streams.available()) >= set(names) | set(
        streams.available(ndim=1))


def test_unknown_scenario_raises():
    with pytest.raises(ValueError, match="unknown stream scenario"):
        streams.make_stream("nope", 10, 2)


def test_duplicate_registration_raises():
    with pytest.raises(ValueError, match="already registered"):
        streams.register("diurnal")(lambda m, cycles, seed: iter(()))


@pytest.mark.parametrize("name", streams.available())
def test_stream_determinism_and_shapes(name):
    m, cycles = 120, 5
    ndim = streams.get(name).ndim
    a = list(streams.make_stream(name, m, cycles, seed=7))
    b = list(streams.make_stream(name, m, cycles, seed=7))
    c = list(streams.make_stream(name, m, cycles, seed=8))
    assert len(a) == cycles
    for xa, xb in zip(a, b):
        np.testing.assert_array_equal(xa, xb)
    assert any(not np.array_equal(xa, xc) for xa, xc in zip(a, c))
    for obs in a:
        assert (obs >= 0).all() and (obs < 1).all()
        if ndim == 1:
            assert obs.shape == (m,)
            assert (np.diff(obs) >= 0).all()
        else:
            assert obs.shape == (m, 2)
            # lex-sorted by (y, x)
            order = np.lexsort((obs[:, 0], obs[:, 1]))
            np.testing.assert_array_equal(order, np.arange(m))


# ---------------------------------------------------------------------------
# Engine: every scenario, >= 6 cycles, correctness + rebalance invariants.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", streams.available(ndim=1))
def test_engine_runs_scenario_and_matches_one_shot(name):
    # Additive Schwarz converges slowly on cycles where the observation
    # mass is split across far-apart subdomain interfaces (bursty_clusters
    # mid-run, storm_front's post-storm bimodal network); 1500 iterations
    # covers the worst registered scenario at this size.
    eng = AssimilationEngine(small_config(iters=1500))
    journal = eng.run_scenario(name, m=160, cycles=CYCLES, seed=0)
    assert len(journal) == CYCLES
    for r in journal.records:
        # Engine analysis == per-cycle one-shot solve to tolerance.
        assert r.error_vs_direct < 1e-8, (name, r.cycle, r.error_vs_direct)
        assert sum(r.loads) == 160
        # Wherever a repartition fired, post-migration imbalance is under
        # the configured threshold.
        if r.repartitioned:
            assert r.imbalance <= THRESHOLD, (name, r.cycle, r.loads)
    assert eng.analysis is not None and eng.analysis.shape == (64,)


def test_rebalancing_beats_static_on_drifting_swarm():
    runs = {}
    for rebalance in (True, False):
        eng = AssimilationEngine(small_config(rebalance=rebalance,
                                              track_reference=False))
        runs[rebalance] = eng.run_scenario("drifting_swarm", m=160,
                                           cycles=CYCLES, seed=0)
    imb_dydd = np.mean(runs[True].imbalance_trajectory)
    imb_static = np.mean(runs[False].imbalance_trajectory)
    assert runs[False].repartition_count == 0
    assert runs[True].repartition_count >= 1
    assert imb_dydd < imb_static


def test_double_buffer_matches_serial_execution():
    outs = {}
    for db in (True, False):
        eng = AssimilationEngine(small_config(double_buffer=db,
                                              track_reference=False))
        journal = eng.run_scenario("bursty_clusters", m=160, cycles=CYCLES,
                                   seed=3)
        outs[db] = (np.asarray(eng.analysis), journal)
    np.testing.assert_array_equal(outs[True][0], outs[False][0])
    for a, b in zip(outs[True][1].records, outs[False][1].records):
        assert a.loads == b.loads
        assert a.repartitioned == b.repartitioned
        assert a.migrated == b.migrated
        assert a.imbalance == b.imbalance


def test_empty_subdomain_always_fires_dd_step():
    """All observations in the right half: subdomains 0-1 are empty, so the
    DD step must fire immediately even with an enormous threshold."""
    def half_domain(m, cycles, seed):
        rng = np.random.default_rng(seed)
        for _ in range(cycles):
            yield np.sort(rng.uniform(0.5, 1.0, m))

    eng = AssimilationEngine(small_config(imbalance_threshold=1e9,
                                          track_reference=False))
    journal = eng.run(half_domain(160, 3, seed=0))
    assert journal.records[0].repartitioned
    assert all(v > 0 for v in journal.records[0].loads)


def test_hysteresis_defers_repartition():
    """A skewed-but-nowhere-empty network over threshold every cycle: with
    hysteresis=3 the first repartition fires on cycle 2 (third cycle)."""
    def skewed(m, cycles, seed):
        rng = np.random.default_rng(seed)
        for _ in range(cycles):
            hot = rng.uniform(0.0, 0.25, (4 * m) // 5)
            cold = rng.uniform(0.25, 1.0, m - len(hot))
            yield np.sort(np.concatenate([hot, cold]))

    eng = AssimilationEngine(small_config(hysteresis=3,
                                          track_reference=False))
    journal = eng.run(skewed(160, 5, seed=0))
    fired = [r.cycle for r in journal.records if r.repartitioned]
    assert journal.records[0].imbalance_before > THRESHOLD
    assert fired and fired[0] == 2, fired


def test_no_thrash_on_static_tied_clustered_stream():
    """ISSUE 5 regression: a static clustered stream with heavily tied
    coordinates used to repartition every cycle (the tie bug left an
    'empty' subdomain that re-fired the DD step).  With the rank-split
    migration the first repartition balances exactly; no further cycle
    may fire."""
    def clustered(m, cycles, seed):
        obs = np.sort(np.concatenate([np.full(3 * m // 4, 0.1),
                                      np.full(m - 3 * m // 4, 0.9)]))
        for _ in range(cycles):
            yield obs

    eng = AssimilationEngine(small_config(track_reference=False))
    journal = eng.run(clustered(160, 6, seed=0))
    assert journal.records[0].repartitioned
    assert journal.repartition_count == 1
    assert journal.records[0].loads == [40, 40, 40, 40]
    for r in journal.records[1:]:
        assert not r.repartitioned
        assert not r.rebalance_suppressed  # balanced, trigger never arms


def test_unpopulatable_empty_subdomain_suppressed_and_journalled():
    """Fewer observations than subdomains: the empty trigger fires once,
    the rebalance cannot populate every subdomain, and every later cycle
    of the static stream suppresses the re-fire (journalled) instead of
    thrashing the DD step."""
    def tiny(m, cycles, seed):
        for _ in range(cycles):
            yield np.array([0.5, 0.5])

    eng = AssimilationEngine(small_config(track_reference=False,
                                          double_buffer=False))
    journal = eng.run(tiny(2, 5, seed=0))
    assert journal.records[0].repartitioned
    assert journal.repartition_count == 1
    for r in journal.records[1:]:
        assert r.rebalance_suppressed and not r.repartitioned
    assert journal.summary()["repartitions_suppressed"] == 4
    d = json.loads(journal.to_json())
    assert d["records"][1]["rebalance_suppressed"] is True


def test_suppression_lifts_when_the_stream_moves():
    """Suppression keys on exact load equality: once the stream shifts
    the counts, the trigger fires again."""
    def shifting(m, cycles, seed):
        yield np.array([0.5, 0.5])
        yield np.array([0.5, 0.5])
        yield np.array([0.05, 0.06])   # different loads -> re-fire

    eng = AssimilationEngine(small_config(track_reference=False,
                                          double_buffer=False))
    journal = eng.run(shifting(2, 3, seed=0))
    fired = [r.cycle for r in journal.records if r.repartitioned]
    assert fired[0] == 0 and len(fired) >= 2 and 2 in fired
    assert journal.records[1].rebalance_suppressed


def test_static_mode_never_repartitions():
    eng = AssimilationEngine(small_config(rebalance=False,
                                          track_reference=False))
    journal = eng.run_scenario("storm_front", m=160, cycles=CYCLES, seed=0)
    assert journal.repartition_count == 0
    assert journal.migrated_total == 0


# ---------------------------------------------------------------------------
# 2D domain: ShelfTiling2D engine runs, rebalance wins, degenerate parity.
# ---------------------------------------------------------------------------

# Station-network scenarios with quantized (tied) coordinates: a shelf
# tiling cannot cut inside a tie group, so its post-rebalance imbalance
# carries a tie-group floor above the trigger threshold (the gap the
# KDTreeDomain closes — see test_kdtree.py).
TIED_2D = frozenset({"satellite_track", "river_gauges"})


@pytest.mark.parametrize("name", streams.available(ndim=2))
def test_engine_runs_2d_scenario_and_matches_one_shot(name):
    eng = AssimilationEngine(small_config_2d())
    journal = eng.run_scenario(name, m=160, cycles=4, seed=0)
    assert len(journal) == 4
    assert journal.meta["ndim"] == 2
    bound = 2.5 if name in TIED_2D else THRESHOLD
    for r in journal.records:
        assert r.error_vs_direct < 1e-8, (name, r.cycle, r.error_vs_direct)
        assert sum(r.loads) == 160
        if r.repartitioned:
            assert r.imbalance <= bound, (name, r.cycle, r.loads)
    assert eng.analysis is not None and eng.analysis.shape == (96,)


@pytest.mark.parametrize("name", streams.available(ndim=2))
def test_2d_rebalancing_beats_static(name):
    runs = {}
    for rebalance in (True, False):
        eng = AssimilationEngine(small_config_2d(rebalance=rebalance,
                                                 iters=150,
                                                 track_reference=False))
        runs[rebalance] = eng.run_scenario(name, m=160, cycles=4, seed=0)
    assert runs[False].repartition_count == 0
    assert runs[True].repartition_count >= 1
    assert (np.mean(runs[True].imbalance_trajectory)
            < np.mean(runs[False].imbalance_trajectory))
    # final-cycle imbalance also improves (the benchmark's acceptance bar)
    assert (runs[True].imbalance_trajectory[-1]
            < runs[False].imbalance_trajectory[-1])


def test_engine_rejects_dimension_mismatch():
    with pytest.raises(ValueError, match="1D"):
        AssimilationEngine(small_config_2d()).run_scenario(
            "drifting_swarm", m=40, cycles=2)
    with pytest.raises(ValueError, match="2D"):
        AssimilationEngine(small_config()).run_scenario(
            "rotating_swarm", m=40, cycles=2)


def test_2d_overlap_converges_to_same_fixed_point():
    """Schwarz halo on the shelf tiling: overlap > 0 reaches the same
    fixed point (the one-shot CLS estimate, so also the overlap=0
    block-exact solve) on a seeded 2D scenario, and the halo-augmented
    decomposition genuinely overlaps."""
    eng = AssimilationEngine(small_config_2d(overlap=2))
    dec = eng.domain.decomposition(overlap=2)
    assert dec.boundaries is None
    assert dec.has_overlap and dec.column_multiplicity.max() > 1
    journal = eng.run_scenario("rotating_swarm", m=160, cycles=3, seed=0)
    for r in journal.records:
        assert r.error_vs_direct < 1e-8, (r.cycle, r.error_vs_direct)

    eng0 = AssimilationEngine(small_config_2d(overlap=0))
    eng0.run_scenario("rotating_swarm", m=160, cycles=3, seed=0)
    assert float(np.linalg.norm(np.asarray(eng.analysis)
                                - np.asarray(eng0.analysis))) < 1e-8


def test_negative_overlap_rejected():
    with pytest.raises(ValueError, match="overlap"):
        AssimilationEngine(small_config_2d(overlap=-1))
    with pytest.raises(ValueError, match="overlap"):
        AssimilationEngine(small_config(overlap=-2))


def test_grid_dropout_fires_empty_cell_dd_step():
    """grid_dropout empties whole tiling cells mid-run: the DD-step must
    fire even with an enormous threshold, and leave no cell empty."""
    eng = AssimilationEngine(small_config_2d(imbalance_threshold=1e9,
                                             iters=150,
                                             track_reference=False))
    journal = eng.run_scenario("grid_dropout", m=200, cycles=5, seed=0)
    outage = [r for r in journal.records if 0 in r.loads_before]
    assert outage, "scenario never emptied a cell"
    for r in outage:
        assert r.repartitioned
        assert all(v > 0 for v in r.loads), (r.cycle, r.loads)


@pytest.mark.parametrize("overlap", [0, 2])
def test_shelf_pr1_degenerates_to_interval1d_bitwise(overlap):
    """A ShelfTiling2D with pr=1, ny=1 is exactly the 1D engine: same
    analyses and same journal loads, bit for bit — including the halo
    path (overlap=s reduces to the 1D interval overlap of eq. 21)."""
    n, p, m, cycles = 48, 4, 120, 5
    one_d = list(streams.make_stream("drifting_swarm", m, cycles, seed=5))

    eng1 = AssimilationEngine(EngineConfig(n=n, p=p, iters=120,
                                           overlap=overlap))
    j1 = eng1.run(iter(one_d))

    def lifted():
        for obs in one_d:
            yield np.stack([obs, np.full_like(obs, 0.5)], axis=1)

    eng2 = AssimilationEngine(EngineConfig(ndim=2, nx=n, ny=1, pr=1, pc=p,
                                           iters=120, overlap=overlap))
    j2 = eng2.run(lifted())

    np.testing.assert_array_equal(np.asarray(eng1.analysis),
                                  np.asarray(eng2.analysis))
    for a, b in zip(j1.records, j2.records):
        assert a.loads == b.loads
        assert a.loads_before == b.loads_before
        assert a.repartitioned == b.repartitioned
        assert a.migrated == b.migrated
    np.testing.assert_array_equal(eng1.domain.boundaries,
                                  eng2.domain.x_edges[0])


def test_explicit_domain_overrides_config():
    dom = domain_mod.ShelfTiling2D(nx=8, ny=8, pr=2, pc=2)
    eng = AssimilationEngine(small_config(), domain=dom)
    assert eng.domain is dom
    assert eng.n == 64 and eng.p == 4
    assert eng.journal.meta["kind"] == "shelf2d"


def test_domain_kind_kdtree_config():
    """domain_kind='kdtree' builds a p-leaf KDTreeDomain over the nx x ny
    mesh and runs 2D scenarios end to end."""
    cfg = EngineConfig(ndim=2, domain_kind="kdtree", p=4, nx=12, ny=8,
                      iters=40, track_reference=False)
    eng = AssimilationEngine(cfg)
    assert eng.journal.meta["kind"] == "kdtree"
    assert eng.p == 4 and eng.n == 96
    journal = eng.run_scenario("satellite_track", m=80, cycles=2, seed=0)
    assert len(journal) == 2
    for r in journal.records:
        assert sum(r.loads) == 80
    # a 1D scenario is rejected like any other 2D domain
    with pytest.raises(ValueError, match="1D"):
        AssimilationEngine(cfg).run_scenario("drifting_swarm", m=40,
                                             cycles=2)


def test_unknown_domain_kind_raises():
    with pytest.raises(ValueError, match="domain_kind"):
        AssimilationEngine(EngineConfig(domain_kind="voronoi"))


# ---------------------------------------------------------------------------
# Metrics journal.
# ---------------------------------------------------------------------------

def test_imbalance_ratio():
    assert imbalance_ratio([4, 4, 4, 4]) == 1.0
    assert imbalance_ratio([8, 0, 0, 0]) == 4.0
    assert imbalance_ratio([0, 0]) == 1.0


def test_journal_json_roundtrip(tmp_path):
    eng = AssimilationEngine(small_config(track_reference=False))
    journal = eng.run_scenario("diurnal", m=120, cycles=3, seed=0)
    d = json.loads(journal.to_json())
    assert len(d["records"]) == 3
    assert d["summary"]["cycles"] == 3
    for key in ("repartitions", "migrated_total", "imbalance_max",
                "cycle_time_mean"):
        assert key in d["summary"]
    path = tmp_path / "journal.json"
    journal.save(str(path))
    assert json.loads(path.read_text())["summary"]["cycles"] == 3


def test_empty_journal_summary():
    assert Journal().summary() == {"cycles": 0}


# ---------------------------------------------------------------------------
# Config validation.
# ---------------------------------------------------------------------------

def test_shardmap_without_mesh_raises():
    # On this single-device test session the device count cannot match
    # p=4, so auto-building the mesh is rejected with the fix spelled out.
    with pytest.raises(ValueError, match="requires a mesh"):
        AssimilationEngine(EngineConfig(solver="shardmap"))


def test_shardmap_mesh_device_count_mismatch_raises():
    """p != mesh device count must fail up front with an actionable
    message, not as an opaque shard_map shape error mid-solve."""
    import jax
    mesh = jax.make_mesh((1,), ("sub",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with pytest.raises(ValueError, match="one device per subdomain"):
        AssimilationEngine(EngineConfig(solver="shardmap", p=4), mesh=mesh)


def test_shardmap_single_device_mesh_runs():
    """p=1 matches the 1-device test session: the engine auto-builds the
    (1,) mesh and the sharded path solves a cycle end to end."""
    cfg = EngineConfig(n=32, p=1, iters=60, solver="shardmap",
                       track_reference=True)
    eng = AssimilationEngine(cfg)
    assert eng.mesh is not None and eng.mesh_axis == "sub"
    journal = eng.run_scenario("drifting_swarm", m=60, cycles=2, seed=0)
    for r in journal.records:
        assert r.error_vs_direct < 1e-8


def test_unknown_solver_raises():
    with pytest.raises(ValueError, match="unknown solver"):
        AssimilationEngine(EngineConfig(solver="quantum"))


def test_zero_hysteresis_raises():
    with pytest.raises(ValueError, match="hysteresis"):
        AssimilationEngine(EngineConfig(hysteresis=0))


def test_sub_unity_threshold_raises():
    with pytest.raises(ValueError, match="imbalance_threshold"):
        AssimilationEngine(EngineConfig(imbalance_threshold=0.5))
