"""Neighbour-only halo exchange: the precomputed ppermute edge schedule
(`dd.HaloExchange`), the comm-volume model, and the overlap-aware DyDD
weighting — everything the sharded solve's `comm="neighbour"` path rides
on, validated host-side (the device-path ULP parity lives in
test_ddkf_multidevice.py under forced multi-device XLA)."""
import numpy as np
import pytest

from repro.assim import AssimilationEngine, EngineConfig
from repro.core import dd, ddkf, domain, dydd, dydd2d


def _tiling_dec(pr, pc, nx=16, ny=8, overlap=1, seed=4, balance=True):
    dom = domain.ShelfTiling2D(nx=nx, ny=ny, pr=pr, pc=pc)
    if balance:
        obs = dydd2d.make_observations_2d(400, kind="clustered", seed=seed)
        dom.rebalance(obs)
    return dom.decomposition(overlap=overlap)


# ---------------------------------------------------------------------------
# Edge discovery + graph colouring.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pr,pc", [(1, 8), (2, 4), (4, 2), (1, 2), (2, 2)])
def test_edge_schedule_rounds_are_matchings(pr, pc):
    """Every round's permutation is a directed matching: no device sends
    twice or receives twice in one ppermute round, the rounds cover both
    directed arcs of every edge exactly once, and the König colouring
    achieves exactly max-degree rounds (the optimum — every device must
    send to each of its deg neighbours in distinct rounds)."""
    dec = _tiling_dec(pr, pc, overlap=1)
    he = dec.halo_exchange
    arcs = []
    for perm in he.perms:
        srcs = [s for s, _ in perm]
        dsts = [d for _, d in perm]
        assert len(set(srcs)) == len(srcs)
        assert len(set(dsts)) == len(dsts)
        arcs.extend((int(s), int(d)) for s, d in perm)
    expect = [a for i, j in he.edges for a in ((i, j), (j, i))]
    assert sorted(arcs) == sorted(expect)
    deg = np.zeros(dec.p, np.int64)
    for i, j in he.edges:
        deg[i] += 1
        deg[j] += 1
    assert he.rounds == (int(deg.max()) if he.edges else 0)


def test_chain_schedule_is_two_rounds():
    """A 1D chain (pr=1 degenerate) schedules into two rounds regardless
    of p (interior max degree 2), with int32 pack/unpack maps shaped
    (p, rounds, h)."""
    dec = dd.decompose_1d(64, dd.uniform_boundaries(8), overlap=2)
    he = dec.halo_exchange
    assert he.edges == tuple((i, i + 1) for i in range(7))
    assert he.rounds == 2
    assert he.pack_idx.shape == (8, 2, he.h)
    assert he.unpack_idx.shape == (8, 2, he.h)
    assert he.pack_idx.dtype == np.int32
    assert he.unpack_idx.dtype == np.int32


def test_triangle_graph_needs_only_two_rounds():
    """Pairwise-overlapping triangle of subdomains: max degree 2, so the
    directed bipartite colouring schedules it in 2 rounds.  (An
    undirected edge colouring cannot — an odd cycle needs 3 colours —
    which is exactly why the schedule colours arcs, not edges.)"""
    col_sets = (np.array([0, 1, 2, 3]), np.array([2, 3, 4, 5]),
                np.array([0, 1, 4, 5]))
    dec = dd.Decomposition(n=6, col_sets=col_sets, overlap=1)
    he = dec.halo_exchange
    assert set(he.edges) == {(0, 1), (0, 2), (1, 2)}
    assert he.rounds == 2


def test_grid_schedule_includes_corner_halo_pairs():
    """A wide 2D overlap makes diagonal cells share halo∩halo columns;
    the intersection-derived edge set catches those pairs (a pure
    grid-edges schedule would silently drop their contributions)."""
    dec = _tiling_dec(2, 4, overlap=1)
    he = dec.halo_exchange
    grid = set(dydd.grid_edges(2, 4, torus=False))
    assert grid <= set(he.edges)            # grid neighbours always there
    # shared columns really are shared, ascending, in both endpoints
    sets = [np.asarray(c) for c in dec.col_sets]
    for (i, j), s, (si, sj) in zip(he.edges, he.shared, he.send_slots):
        assert (np.diff(s) > 0).all()
        np.testing.assert_array_equal(sets[i][si], s)
        np.testing.assert_array_equal(sets[j][sj], s)


def test_no_overlap_means_no_edges():
    dec = dd.decompose_1d(48, dd.uniform_boundaries(4), overlap=0)
    he = dec.halo_exchange
    assert he.edges == () and he.rounds == 0 and he.h == 0
    assert dec.halo_fraction == 0.0
    np.testing.assert_array_equal(dec.halo_sizes, np.zeros(4, np.int64))


def test_empty_core_cells_exchange_nothing():
    """A cell with an empty core owns no columns, so it acquires no
    edges and its slot map is all dump."""
    y = np.linspace(0, 1, 2)
    x = np.array([[0.0, 0.001, 1.0]])     # cell (0,0) owns no column
    col_sets = dydd2d.cell_col_sets(8, 4, y, x, overlap=2)
    dec = dd.Decomposition(n=32, col_sets=tuple(col_sets), overlap=2)
    he = dec.halo_exchange
    assert all(0 not in e for e in he.edges)
    if he.rounds:
        assert (he.pack_idx[0] == he.w).all()
        assert (he.unpack_idx[0] == he.w).all()


# ---------------------------------------------------------------------------
# Index-map round trip: the neighbour exchange reproduces the global
# multiplicity-weighted assembly exactly.
# ---------------------------------------------------------------------------

def _simulate_neighbour_exchange(dec, x_loc):
    """Host-side replay of the device exchange: pack the outgoing arc's
    slots at pack_idx, swap over each round's perm, scatter-add the
    incoming payload at unpack_idx (the dump slot absorbs padding),
    divide by the local multiplicity."""
    he = dec.halo_exchange
    sets = [np.asarray(c) for c in dec.col_sets]
    w = dec.pad_width
    mult = np.maximum(dec.column_multiplicity, 1)
    out = np.zeros_like(x_loc)
    pad = np.concatenate([x_loc, np.zeros((dec.p, 1))], axis=1)
    for i in range(dec.p):
        acc = pad[i].copy()
        for r in range(he.rounds):
            for s, d in he.perms[r]:
                if d == i:
                    np.add.at(acc, he.unpack_idx[i, r],
                              pad[s][he.pack_idx[s, r]])
        mloc = np.ones(w)
        k = sets[i].size
        mloc[:k] = mult[sets[i]]
        out[i] = acc[:w] / mloc
    return out


@pytest.mark.parametrize("make", [
    lambda: dd.decompose_1d(64, dd.uniform_boundaries(8), overlap=3),
    lambda: _tiling_dec(2, 4, overlap=1),
    lambda: _tiling_dec(2, 2, nx=12, ny=10, overlap=2),
    # triangle graph: odd cycle, exercises the alternating-path recolour
    lambda: dd.Decomposition(n=6, col_sets=(
        np.array([0, 1, 2, 3]), np.array([2, 3, 4, 5]),
        np.array([0, 1, 4, 5])), overlap=1),
])
def test_neighbour_exchange_matches_global_average(make):
    dec = make()
    rng = np.random.default_rng(7)
    sets = [np.asarray(c) for c in dec.col_sets]
    w = dec.pad_width
    x_loc = np.zeros((dec.p, w))
    for i, c in enumerate(sets):
        x_loc[i, :c.size] = rng.normal(size=c.size)
    # global reference: scatter-add everyone, divide by multiplicity
    acc = np.zeros(dec.n)
    for i, c in enumerate(sets):
        acc[c] += x_loc[i, :c.size]
    ref = acc / np.maximum(dec.column_multiplicity, 1)
    out = _simulate_neighbour_exchange(dec, x_loc)
    for i, c in enumerate(sets):
        np.testing.assert_allclose(out[i, :c.size], ref[c], atol=1e-14)
        np.testing.assert_array_equal(out[i, c.size:], 0.0)


# ---------------------------------------------------------------------------
# Comm-volume accounting.
# ---------------------------------------------------------------------------

def test_comm_model_neighbour_scales_with_overlap_not_n():
    """The acceptance property: neighbour-path state bytes grow with the
    overlap width s and are flat in n; allreduce-path bytes grow with n
    and are flat in s."""
    def state_bytes(n, s, comm):
        dec = dd.decompose_1d(n, dd.uniform_boundaries(8), overlap=s)
        model = ddkf.comm_model(n, 2 * n, 8, 8, halo=dec.halo_exchange,
                                comm=comm)
        return model["state_bytes_per_device_mean"]

    # flat in n at fixed s, linear in s at fixed n
    assert state_bytes(256, 2, "neighbour") == \
        state_bytes(1024, 2, "neighbour")
    assert state_bytes(256, 4, "neighbour") == \
        2 * state_bytes(256, 2, "neighbour")
    # the allreduce path is the opposite regime
    assert state_bytes(1024, 2, "allreduce") == \
        4 * state_bytes(256, 2, "allreduce")
    assert state_bytes(256, 4, "allreduce") == \
        state_bytes(256, 2, "allreduce")


def test_packed_edge_send_bytes():
    import jax
    from repro.core import cls
    rng = np.random.default_rng(3)
    obs = rng.beta(2, 5, 200)
    prob = cls.local_problem(jax.random.PRNGKey(0), 64, obs)
    dec = dd.decompose_1d(64, dd.uniform_boundaries(4), overlap=2)
    packed = ddkf.pack(prob, dec)
    he = dec.halo_exchange
    per_edge = packed.edge_send_bytes(he)
    itemsize = np.dtype(np.asarray(packed.A_loc).dtype).itemsize
    assert set(per_edge) == {f"{i}-{j}" for i, j in he.edges}
    for (i, j), s in zip(he.edges, he.shared):
        assert per_edge[f"{i}-{j}"] == s.size * itemsize
    stats = packed.comm_stats(halo=he, comm="neighbour")
    assert stats["per_edge_bytes"] == per_edge
    assert stats["permute_rounds"] == he.rounds


def test_solve_shardmap_guards():
    """The neighbour path validates its inputs before touching devices:
    a missing or shape-mismatched halo schedule fails loudly."""
    import jax
    from repro.core import cls
    obs = np.sort(np.random.default_rng(5).uniform(0, 1, 80))
    prob = cls.local_problem(jax.random.PRNGKey(0), 32, obs)
    dec = dd.decompose_1d(32, dd.uniform_boundaries(1), overlap=0)
    packed = ddkf.pack(prob, dec)
    mesh = jax.make_mesh((1,), ("sub",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    with pytest.raises(ValueError, match="halo_exchange"):
        ddkf.solve_shardmap(packed, mesh, comm="neighbour", halo=None)
    other = dd.decompose_1d(32, dd.uniform_boundaries(2), overlap=2)
    with pytest.raises(ValueError, match="does not match the packing"):
        ddkf.solve_shardmap(packed, mesh, comm="neighbour",
                            halo=other.halo_exchange)
    with pytest.raises(ValueError, match="comm must be"):
        ddkf.solve_shardmap(packed, mesh, comm="smoke-signals")
    with pytest.raises(ValueError, match="mvec must be"):
        ddkf.solve_shardmap(packed, mesh, mvec="bucket-brigade")


# ---------------------------------------------------------------------------
# Overlap-aware DyDD weighting.
# ---------------------------------------------------------------------------

def test_dydd_1d_none_offsets_bit_for_bit():
    rng = np.random.default_rng(0)
    obs = rng.beta(2, 5, 500)
    a = dydd.dydd_1d(obs, 6)
    b = dydd.dydd_1d(obs, 6, cost_offsets=None)
    np.testing.assert_array_equal(a.boundaries, b.boundaries)
    np.testing.assert_array_equal(a.loads_final, b.loads_final)


def test_dydd_1d_offsets_shift_loads_away_from_costly_subdomains():
    """A subdomain carrying fixed halo cost should end up with fewer
    observations: weighted loads (obs + offsets) balance instead."""
    rng = np.random.default_rng(1)
    obs = np.sort(rng.uniform(0, 1, 600))
    off = np.array([0, 120, 0, 0], np.int64)
    res = dydd.dydd_1d(obs, 4, cost_offsets=off)
    base = dydd.dydd_1d(obs, 4)
    assert res.loads_final.sum() == 600        # conservation
    assert res.loads_final[1] < base.loads_final[1]
    work = res.loads_final + off
    assert np.abs(work - work.mean()).max() <= \
        np.abs(base.loads_final + off
               - (base.loads_final + off).mean()).max()


def test_dydd_1d_offsets_validate_shape():
    with pytest.raises(ValueError, match="cost_offsets"):
        dydd.dydd_1d(np.linspace(0, 0.9, 50), 4,
                     cost_offsets=np.zeros(3))


def test_dydd_2d_none_offsets_bit_for_bit():
    obs = dydd2d.make_observations_2d(800, kind="clustered", seed=2)
    a = dydd2d.dydd_2d(obs, pr=2, pc=3)
    b = dydd2d.dydd_2d(obs, pr=2, pc=3, cost_offsets=None)
    np.testing.assert_array_equal(a.y_edges, b.y_edges)
    np.testing.assert_array_equal(a.x_edges, b.x_edges)
    np.testing.assert_array_equal(a.loads_final, b.loads_final)


def test_dydd_2d_offsets_balance_weighted_loads():
    obs = dydd2d.make_observations_2d(900, kind="uniform", seed=5)
    off = np.zeros((2, 3), np.int64)
    off[0, 0] = 150
    res = dydd2d.dydd_2d(obs, pr=2, pc=3, cost_offsets=off)
    base = dydd2d.dydd_2d(obs, pr=2, pc=3)
    assert res.loads_final.sum() == 900
    assert res.loads_final[0, 0] < base.loads_final[0, 0]


def test_domain_rebalance_forwards_offsets():
    dom = domain.Interval1D(n=64, p=4)
    rng = np.random.default_rng(3)
    obs = np.sort(rng.uniform(0, 1, 400))
    off = np.array([0, 0, 0, 100], np.float64)
    dom.rebalance(obs, cost_offsets=off)
    assert dom.counts(obs)[3] < 100 + 400 // 4
    dom2 = domain.ShelfTiling2D(nx=12, ny=8, pr=2, pc=2)
    obs2 = dydd2d.make_observations_2d(400, kind="uniform", seed=1)
    dom2.rebalance(obs2, cost_offsets=np.array([0, 0, 0, 80]))
    assert dom2.counts(obs2).sum() == 400


# ---------------------------------------------------------------------------
# Engine integration: journal fields + the weighted trigger path.
# ---------------------------------------------------------------------------

def test_engine_journals_comm_accounting():
    cfg = EngineConfig(n=64, p=4, overlap=2, iters=40, halo_weight=1.0,
                       comm="neighbour", double_buffer=False)
    eng = AssimilationEngine(cfg)
    journal = eng.run_scenario("drifting_swarm", m=200, cycles=3, seed=0)
    for rec in journal.records:
        assert rec.comm_bytes_per_cycle > 0
        assert 0.0 < rec.halo_fraction < 1.0
        assert len(rec.loads_weighted) == 4
        # weighted = loads + halo cost, so never below the raw loads
        assert all(wv >= lv for wv, lv
                   in zip(rec.loads_weighted, rec.loads))
    s = journal.summary()
    assert s["comm_bytes_per_cycle_mean"] > 0
    assert s["halo_fraction_mean"] > 0
    d = journal.to_dict()
    assert d["records"][0]["loads_weighted"] == \
        journal.records[0].loads_weighted


def test_engine_neighbour_comm_model_beats_allreduce():
    """On a small-overlap decomposition the modelled neighbour traffic is
    strictly below the allreduce traffic (the point of the path)."""
    kw = dict(n=128, p=4, overlap=1, iters=40, double_buffer=False)
    j_all = AssimilationEngine(EngineConfig(comm="allreduce", **kw)) \
        .run_scenario("drifting_swarm", m=200, cycles=2, seed=0)
    j_nei = AssimilationEngine(EngineConfig(comm="neighbour", **kw)) \
        .run_scenario("drifting_swarm", m=200, cycles=2, seed=0)
    for ra, rn in zip(j_all.records, j_nei.records):
        assert rn.comm_bytes_per_cycle < ra.comm_bytes_per_cycle
        # identical decomposition trajectory: comm mode must not change
        # the rebalance decisions (vmapped solver ignores comm entirely)
        assert ra.loads == rn.loads


def test_engine_rejects_bad_comm_config():
    with pytest.raises(ValueError, match="comm"):
        AssimilationEngine(EngineConfig(comm="carrier-pigeon"))
    with pytest.raises(ValueError, match="halo_weight"):
        AssimilationEngine(EngineConfig(halo_weight=-1.0))
