"""2D DyDD — the paper's Ω ⊂ R² setting (Figures 1-4) — plus the gram
kernel (DD-KF normal-matrix hot spot)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_shim import given, settings, strategies as st

from repro.core import cls, dd, ddkf, dydd, dydd2d
from repro.kernels import ops, ref


# ---------------------------------------------------------------------------
# 2D DyDD.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["uniform", "beta", "clustered"])
def test_dydd_2d_balances(kind):
    obs = dydd2d.make_observations_2d(1600, kind=kind, seed=3)
    res = dydd2d.dydd_2d(obs, pr=4, pc=4)
    assert res.loads_final.sum() == 1600
    assert res.efficiency > 0.95, res.loads_final
    # figures 1-4 structure: the initial clustered tiling is unbalanced
    if kind == "clustered":
        assert dydd.balance_ratio(res.loads_initial.reshape(-1)) < 0.5


def test_dydd_2d_empty_cells():
    """Figure 1's configuration: whole regions without observations."""
    rng = np.random.default_rng(0)
    obs = np.stack([rng.uniform(0, 0.45, 900),
                    rng.uniform(0.55, 1.0, 900)], axis=1)  # top-left only
    res = dydd2d.dydd_2d(obs, pr=2, pc=4)
    assert (res.loads_initial == 0).any()
    assert res.loads_final.min() > 0
    assert res.efficiency > 0.95


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000), pr=st.integers(2, 4),
       pc=st.integers(2, 5))
def test_dydd_2d_properties(seed, pr, pc):
    obs = dydd2d.make_observations_2d(800, kind="clustered", seed=seed)
    res = dydd2d.dydd_2d(obs, pr=pr, pc=pc)
    assert res.loads_final.sum() == 800                  # conservation
    lbar = 800 / (pr * pc)
    assert np.abs(res.loads_final - lbar).max() <= max(2.0, 0.05 * lbar)
    # y-edges monotone; x-edges monotone per strip
    assert (np.diff(res.y_edges) >= 0).all()
    assert (np.diff(res.x_edges, axis=1) >= 0).all()


def test_dydd_2d_matches_grid_graph_schedule_floor():
    """The geometric result is at least as balanced as the grid-graph
    diffusion schedule's fixed point."""
    obs = dydd2d.make_observations_2d(1024, kind="beta", seed=9)
    res = dydd2d.dydd_2d(obs, pr=4, pc=4)
    graph_final, _ = dydd.balance(res.loads_initial.reshape(-1),
                                  dydd.grid_edges(4, 4, torus=False))
    assert res.efficiency >= dydd.balance_ratio(graph_final) - 0.02


def test_cell_col_sets_partition_mesh():
    obs = dydd2d.make_observations_2d(500, seed=1)
    res = dydd2d.dydd_2d(obs, pr=2, pc=3)
    sets = dydd2d.cell_col_sets(12, 10, res.y_edges, res.x_edges)
    allc = np.concatenate(sets)
    np.testing.assert_array_equal(np.sort(allc), np.arange(120))


# ---------------------------------------------------------------------------
# Halo (Schwarz overlap) column sets on the shelf tiling.
# ---------------------------------------------------------------------------

def test_cell_col_sets_halo_covers_and_overlaps():
    """overlap=s supersets the core partition; every column is still
    covered; interior seams carry multiplicity > 1."""
    obs = dydd2d.make_observations_2d(500, seed=1)
    res = dydd2d.dydd_2d(obs, pr=2, pc=3)
    core = dydd2d.cell_col_sets(12, 10, res.y_edges, res.x_edges)
    halo = dydd2d.cell_col_sets(12, 10, res.y_edges, res.x_edges,
                                overlap=2)
    counts = np.zeros(120, np.int64)
    for cset, hset in zip(core, halo):
        assert set(np.asarray(cset)) <= set(np.asarray(hset))
        assert (np.diff(hset) > 0).all()      # ascending, unique
        counts[hset] += 1
    assert counts.min() >= 1                   # full coverage
    assert counts.max() > 1                    # halos actually overlap


def test_cell_col_sets_halo_is_cross_shaped():
    """On a uniform 2x2 tiling of an 8x8 mesh with overlap=1, cell (0,0)
    absorbs one column from its right neighbour and one row from the
    strip below — but not the diagonal corner point (4,4)."""
    y = np.linspace(0, 1, 3)
    x = np.tile(np.linspace(0, 1, 3), (2, 1))
    halo = dydd2d.cell_col_sets(8, 8, y, x, overlap=1)
    cell00 = set(np.asarray(halo[0]).tolist())
    assert 0 * 8 + 4 in cell00        # right halo column, own rows
    assert 4 * 8 + 0 in cell00        # bottom halo row, own columns
    assert 4 * 8 + 4 not in cell00    # diagonal corner: not a neighbour
    # boundary clipping: nothing outside the mesh, nothing left of x=0
    assert min(cell00) == 0 and max(cell00) < 64


def test_cell_col_sets_empty_core_gets_no_halo():
    """A cell whose x-window holds no mesh column stays empty even with
    overlap > 0 (a halo without a core would break load accounting)."""
    y = np.linspace(0, 1, 2)
    x = np.array([[0.0, 0.001, 1.0]])     # cell (0,0) owns no column
    halo = dydd2d.cell_col_sets(8, 4, y, x, overlap=2)
    assert halo[0].size == 0
    assert halo[1].size == 32


def test_cell_col_sets_ny1_pr1_matches_decompose_1d():
    """Degenerate mesh: the halo construction reproduces the 1D interval
    overlap (eq. 21) exactly."""
    for s in (0, 1, 3):
        halo = dydd2d.cell_col_sets(
            48, 1, np.linspace(0, 1, 2),
            np.tile(np.linspace(0, 1, 5), (1, 1)), overlap=s)
        dec = dd.decompose_1d(48, dd.uniform_boundaries(4), overlap=s)
        for a, b in zip(halo, dec.col_sets):
            np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("overlap", [1, 2])
def test_ddkf_2d_overlap_converges_to_direct(overlap):
    """Multiplicity-weighted halo assembly: the overlapping 2D Schwarz
    solve reaches the same fixed point (the direct CLS estimate) as the
    overlap=0 block-exact decomposition."""
    nx, ny = 12, 8
    n = nx * ny
    obs2 = dydd2d.make_observations_2d(400, kind="clustered", seed=4)
    obs_raster = (np.clip((obs2[:, 1] * ny).astype(int), 0, ny - 1) * nx
                  + np.clip((obs2[:, 0] * nx).astype(int), 0, nx - 1)
                  + 0.5) / n
    prob = cls.local_problem(jax.random.PRNGKey(0), n, np.sort(obs_raster))
    res = dydd2d.dydd_2d(obs2, pr=2, pc=2)
    col_sets = dydd2d.cell_col_sets(nx, ny, res.y_edges, res.x_edges,
                                    overlap=overlap)
    dec = dd.Decomposition(n=n, col_sets=tuple(col_sets), overlap=overlap)
    assert dec.boundaries is None and dec.has_overlap
    packed = ddkf.pack(prob, dec)
    x = ddkf.solve_vmapped(packed, iters=300, damping=0.7)
    err = float(jnp.linalg.norm(x - cls.solve(prob)))
    assert err < 1e-6, err


def test_ddkf_on_2d_decomposition():
    """End-to-end: 2D DyDD tiling -> DD-KF solve == direct CLS (the 2D
    analogue of the paper's pipeline; Remark 4's I x J decomposition)."""
    nx, ny = 12, 8
    n = nx * ny
    obs2 = dydd2d.make_observations_2d(400, kind="clustered", seed=4)
    # project obs to 1D raster position for the spatially-local operator
    obs_raster = (np.clip((obs2[:, 1] * ny).astype(int), 0, ny - 1) * nx
                  + np.clip((obs2[:, 0] * nx).astype(int), 0, nx - 1)
                  + 0.5) / n
    prob = cls.local_problem(jax.random.PRNGKey(0), n, np.sort(obs_raster))
    res = dydd2d.dydd_2d(obs2, pr=2, pc=2)
    col_sets = dydd2d.cell_col_sets(nx, ny, res.y_edges, res.x_edges)
    col_sets = [c for c in col_sets if c.size]
    dec = dd.Decomposition(n=n, col_sets=tuple(col_sets),
                           boundaries=np.linspace(0, 1, len(col_sets) + 1),
                           overlap=0)
    packed = ddkf.pack(prob, dec)
    x = ddkf.solve_vmapped(packed, iters=250, damping=0.7)
    err = float(jnp.linalg.norm(x - cls.solve(prob)))
    assert err < 1e-6, err


def test_dydd_2d_reports_rounds_and_respects_cap():
    obs = dydd2d.make_observations_2d(1200, kind="clustered", seed=7)
    res = dydd2d.dydd_2d(obs, pr=3, pc=3, max_rounds=8)
    assert 1 <= res.rounds <= 8
    capped = dydd2d.dydd_2d(obs, pr=3, pc=3, max_rounds=1)
    assert capped.rounds == 1
    # more rounds can only do at least as well as the 1-round cap
    assert res.efficiency >= capped.efficiency - 1e-12


def test_dydd_2d_iterates_until_no_improvement():
    """The y-pass/x-pass pair is iterated: the returned loads are within
    integer rounding of the mean OR a further round would not improve."""
    obs = dydd2d.make_observations_2d(900, kind="beta", seed=11)
    res = dydd2d.dydd_2d(obs, pr=4, pc=4, max_rounds=8)
    lbar = 900 / 16
    dev = np.abs(res.loads_final - lbar).max()
    if dev >= 1.0:
        again = dydd2d.dydd_2d(obs, pr=4, pc=4,
                               y_edges=res.y_edges, x_edges=res.x_edges,
                               max_rounds=1)
        dev2 = np.abs(again.loads_final - lbar).max()
        assert dev2 >= dev - 1e-12


def test_dydd_2d_warm_start_boundaries():
    """Passing current shelf edges warm-starts the rebalance: the initial
    loads are counted against them, and an already-balanced tiling needs
    no movement."""
    obs = dydd2d.make_observations_2d(800, kind="clustered", seed=2)
    first = dydd2d.dydd_2d(obs, pr=2, pc=3)
    warm = dydd2d.dydd_2d(obs, pr=2, pc=3,
                          y_edges=first.y_edges, x_edges=first.x_edges)
    np.testing.assert_array_equal(warm.loads_initial, first.loads_final)
    assert warm.efficiency >= first.efficiency - 1e-12
    assert warm.total_movement <= first.total_movement


def test_dydd_2d_pr1_is_exactly_dydd_1d():
    """Degenerate dimension: a 1 x pc shelf on 2D points with constant y
    reproduces dydd_1d on the x coordinates exactly."""
    rng = np.random.default_rng(5)
    xs = np.sort(rng.beta(2, 5, 500))
    obs2 = np.stack([xs, np.full_like(xs, 0.5)], axis=1)
    res2 = dydd2d.dydd_2d(obs2, pr=1, pc=6)
    res1 = dydd.dydd_1d(xs, 6)
    np.testing.assert_array_equal(res2.x_edges[0], res1.boundaries)
    np.testing.assert_array_equal(res2.loads_final.reshape(-1),
                                  res1.loads_final)
    assert res2.total_movement == res1.total_movement


def test_counts_2d_none_ranks_match_historic_rule():
    """tie_ranks=None reproduces the searchsorted(side='right') + clip
    counting bit for bit (the pre-tie-fix behaviour, random inputs)."""
    obs = dydd2d.make_observations_2d(700, kind="clustered", seed=9)
    y_edges = np.linspace(0.0, 1.0, 4)
    x_edges = np.tile(np.linspace(0.0, 1.0, 5), (3, 1))
    rows = np.clip(np.searchsorted(y_edges, obs[:, 1], side="right") - 1,
                   0, 2)
    want = np.zeros((3, 4), np.int64)
    for r in range(3):
        xs = obs[rows == r, 0]
        cols = np.clip(np.searchsorted(x_edges[r], xs, side="right") - 1,
                       0, 3)
        want[r] = np.bincount(cols, minlength=4)
    got = dydd2d._counts_2d(obs, y_edges, x_edges)
    np.testing.assert_array_equal(got, want)


def test_dydd_2d_quantized_ties_split_across_boundaries():
    """The carried-over ROADMAP bug: a quantized stream whose y values
    all sit exactly on the strip boundary used to count wholesale into
    the lower strip (historic all-right tie rule), so the recount never
    saw the loads the migration realized and the result stayed [m, 0]
    per column.  With the rank-split recount (the 2D analogue of the 1D
    tie_ranks fix) the schedule's targets are realized exactly."""
    m = 16
    obs = np.stack([np.linspace(0.03, 0.97, m), np.full(m, 0.5)], axis=1)
    res = dydd2d.dydd_2d(obs, pr=2, pc=2)
    assert res.loads_final.sum() == m
    # Perfect split: every cell gets m/4 despite every y being tied.
    np.testing.assert_array_equal(res.loads_final,
                                  np.full((2, 2), m // 4))
    assert res.y_tie_ranks is not None and res.y_tie_ranks[0] == m // 2
    # The counting rule itself honours the returned ranks.
    np.testing.assert_array_equal(
        dydd2d._counts_2d(obs, res.y_edges, res.x_edges,
                          res.y_tie_ranks, res.x_tie_ranks),
        res.loads_final)


def test_dydd_2d_x_ties_within_strip_split():
    """Per-strip x ties: quantized x coordinates tied on a cell edge
    split by rank inside each strip independently."""
    rng = np.random.default_rng(11)
    # Two strips, 12 obs each, every x equal to 0.5 (the pc=2 cell edge).
    ys = np.concatenate([rng.uniform(0.0, 0.45, 12),
                         rng.uniform(0.55, 1.0, 12)])
    obs = np.stack([np.full(24, 0.5), ys], axis=1)
    res = dydd2d.dydd_2d(obs, pr=2, pc=2)
    np.testing.assert_array_equal(res.loads_final, np.full((2, 2), 6))
    assert res.x_tie_ranks is not None
    np.testing.assert_array_equal(res.x_tie_ranks, np.full((2, 1), 6))


def test_dydd_2d_tie_ranks_thread_through_warm_start():
    """DyDD2DResult's tie ranks carry into the next online rebalance the
    same way boundaries do — the warm-started recount sees the realized
    loads, so an already-balanced quantized stream needs no movement."""
    m = 16
    obs = np.stack([np.linspace(0.03, 0.97, m), np.full(m, 0.5)], axis=1)
    first = dydd2d.dydd_2d(obs, pr=2, pc=2)
    warm = dydd2d.dydd_2d(obs, pr=2, pc=2,
                          y_edges=first.y_edges, x_edges=first.x_edges,
                          y_tie_ranks=first.y_tie_ranks,
                          x_tie_ranks=first.x_tie_ranks)
    np.testing.assert_array_equal(warm.loads_initial, first.loads_final)
    assert warm.total_movement == 0


# ---------------------------------------------------------------------------
# gram kernel.
# ---------------------------------------------------------------------------

# (1, 200, 600) spans two output tiles of GRAM_BLOCK_W = 512 lanes, the
# second one ragged.
@pytest.mark.parametrize("p,m,w", [(4, 300, 32), (2, 512, 64), (1, 64, 16),
                                   (1, 200, 600)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gram_kernel_sweep(p, m, w, dtype):
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(p, m, w)), jnp.float32).astype(dtype)
    r = jnp.asarray(rng.uniform(0.5, 2.0, (p, m)),
                    jnp.float32).astype(dtype)
    out = ops.gram(A, r, mode="interpret", block_m=128)
    want = ref.gram_ref(A.astype(jnp.float32), r.astype(jnp.float32))
    tol = 1e-3 if dtype == jnp.float32 else 3e-1
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), atol=tol * float(
                                   jnp.max(jnp.abs(want))) / 100 + tol,
                               rtol=tol)


def test_gram_autotune_picks_and_caches_block():
    """First call per shape sweeps the block_m candidates and caches the
    winner; the tuning report exposes the chosen block + timed sweep."""
    shape = (2, 320, 16)
    b1 = ops.autotune_gram_block(*shape, jnp.float32, interpret=True)
    assert b1 in {min(c, shape[1]) for c in ops.GRAM_BLOCK_CANDIDATES}
    b2 = ops.autotune_gram_block(*shape, jnp.float32, interpret=True)
    assert b2 == b1
    report = ops.gram_tuning_report()
    key = "p2_m320_w16_float32_interpret"
    assert key in report
    assert report[key]["block_m"] == b1
    assert set(report[key]["sweep_s"]) == {min(c, shape[1])
                                           for c in ops.GRAM_BLOCK_CANDIDATES}
    # the ref path has no blocking to tune
    assert ops.gram_block_for(shape, jnp.float64, mode="auto") is None


def test_gram_autotune_rejects_over_vmem_candidates(monkeypatch):
    """Candidates whose tile footprint exceeds the VMEM budget are
    skipped without being timed and recorded in the tuning report; the
    narrowest candidate survives even under an absurdly small budget."""
    shape = (2, 2048, 24)
    # Budget between the smallest and largest candidate footprints
    # (candidates are clipped to min(c, m), so the widest here is 1024).
    budget = (ops.gram_tile_bytes(64, 24)
              + ops.gram_tile_bytes(1024, 24)) // 2
    monkeypatch.setattr(ops, "GRAM_VMEM_BUDGET_BYTES", budget)
    b = ops.autotune_gram_block(*shape, jnp.float32, interpret=True)
    key = "p2_m2048_w24_float32_interpret"
    report = ops.gram_tuning_report()
    assert key in report
    rej = report[key]["rejected_vmem"]
    assert rej, "expected at least one over-budget candidate"
    assert str(b) not in rej
    assert all(int(v) > budget for v in rej.values())
    # rejected candidates were never timed
    assert not (set(map(int, rej)) & set(report[key]["sweep_s"]))
    # under a budget below every candidate, the narrowest one is kept
    monkeypatch.setattr(ops, "GRAM_VMEM_BUDGET_BYTES", 1)
    b2 = ops.autotune_gram_block(2, 512, 24, jnp.float32, interpret=True)
    assert b2 == min(min(c, 512) for c in ops.GRAM_BLOCK_CANDIDATES)


@pytest.mark.parametrize("p,m,w", [(4, 300, 32), (2, 512, 64), (1, 64, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.float64])
def test_schwarz_kernel_sweep(p, m, w, dtype):
    """Interpret-mode fused Schwarz step vs the jnp oracles, both halves,
    across shapes that exercise ragged last tiles and both dtypes."""
    rng = np.random.default_rng(0)
    A = jnp.asarray(rng.normal(size=(p, m, w)), dtype)
    x = jnp.asarray(rng.normal(size=(p, w)), dtype)
    wdiv = jnp.asarray(rng.uniform(0.5, 1.0, (p, w)), dtype)
    rv = jnp.asarray(rng.uniform(0.5, 2.0, m), dtype)
    bv = jnp.asarray(rng.normal(size=m), dtype)
    muov = jnp.asarray(rng.uniform(0.0, 1.0, (p, w)), dtype)
    mask = jnp.asarray(rng.uniform(size=(p, w)) > 0.2, dtype)
    tol = 1e-5 if dtype == jnp.float32 else 1e-12

    y_i, u_i = ops.schwarz_fwd(A, x, wdiv, mode="interpret", block_m=128)
    y_r, u_r = ref.schwarz_fwd_ref(A, x, wdiv)
    sc = float(jnp.max(jnp.abs(y_r)))
    np.testing.assert_allclose(np.asarray(y_i), np.asarray(y_r),
                               atol=tol * sc, rtol=tol)
    np.testing.assert_allclose(np.asarray(u_i), np.asarray(u_r),
                               atol=tol * sc, rtol=tol)

    Ax = jnp.sum(y_r, axis=0)
    rhs_i = ops.schwarz_bwd(A, rv, bv, Ax, u_r, x, muov, mask,
                            mode="interpret", block_m=128)
    rhs_r = ref.schwarz_bwd_ref(A, rv, bv, Ax, u_r, x, muov, mask)
    sc = float(jnp.max(jnp.abs(rhs_r)))
    np.testing.assert_allclose(np.asarray(rhs_i), np.asarray(rhs_r),
                               atol=tol * sc, rtol=tol)
    # masked slots come out exactly zero on both paths
    np.testing.assert_array_equal(
        np.asarray(rhs_i)[np.asarray(mask) == 0], 0.0)


def test_schwarz_autotune_picks_and_caches_block():
    """First call per shape sweeps the block_m candidates (fwd + bwd
    timed together — one solver iteration's launches) and caches the
    winner; the tuning report exposes the chosen block + timed sweep."""
    shape = (2, 320, 16)
    b1 = ops.autotune_schwarz_block(*shape, jnp.float32, interpret=True)
    assert b1 in {min(c, shape[1]) for c in ops.SCHWARZ_BLOCK_CANDIDATES}
    b2 = ops.autotune_schwarz_block(*shape, jnp.float32, interpret=True)
    assert b2 == b1
    report = ops.schwarz_tuning_report()
    key = "p2_m320_w16_float32_interpret"
    assert key in report
    assert report[key]["block_m"] == b1
    assert set(report[key]["sweep_s"]) == \
        {min(c, shape[1]) for c in ops.SCHWARZ_BLOCK_CANDIDATES}
    # f64 under mode="auto" resolves to the jnp reference — no block
    assert ops.schwarz_block_for(shape, jnp.float64, mode="auto") is None
    # but the interpret path tunes a block even for f64 (CI parity runs)
    assert ops.schwarz_block_for(shape, jnp.float64,
                                 mode="interpret") is not None


def test_schwarz_autotune_rejects_over_vmem_candidates(monkeypatch):
    """Candidates whose fused-step tile footprint exceeds the VMEM budget
    are skipped without being timed; the narrowest survives even under
    an absurdly small budget."""
    shape = (2, 2048, 24)
    budget = (ops.schwarz_tile_bytes(64, 24)
              + ops.schwarz_tile_bytes(1024, 24)) // 2
    monkeypatch.setattr(ops, "GRAM_VMEM_BUDGET_BYTES", budget)
    b = ops.autotune_schwarz_block(*shape, jnp.float32, interpret=True)
    key = "p2_m2048_w24_float32_interpret"
    report = ops.schwarz_tuning_report()
    assert key in report
    rej = report[key]["rejected_vmem"]
    assert rej, "expected at least one over-budget candidate"
    assert str(b) not in rej
    assert all(int(v) > budget for v in rej.values())
    assert not (set(map(int, rej)) & set(report[key]["sweep_s"]))
    monkeypatch.setattr(ops, "GRAM_VMEM_BUDGET_BYTES", 1)
    b2 = ops.autotune_schwarz_block(2, 640, 24, jnp.float32,
                                    interpret=True)
    assert b2 == min(min(c, 640) for c in ops.SCHWARZ_BLOCK_CANDIDATES)


def test_gram_matches_ddkf_pack_normal_matrix():
    """The kernel computes exactly the normal matrices ddkf.pack builds."""
    rng = np.random.default_rng(1)
    obs = rng.beta(2, 5, 200)
    prob = cls.local_problem(jax.random.PRNGKey(0), 64, obs)
    dec = dd.decompose_1d(64, dd.uniform_boundaries(4))
    packed = ddkf.pack(prob, dec)
    N = ops.gram(packed.A_loc.astype(jnp.float32),
                 jnp.tile(packed.r.astype(jnp.float32), (4, 1)),
                 mode="interpret", block_m=128)
    # pack stores inv(N + pad-identity): compare with the inverse of the
    # kernel's normal matrix
    for i in range(4):
        got = np.asarray(packed.Ninv_loc[i], np.float64)
        k = int(np.asarray(packed.mask[i]).sum())
        want = np.asarray(N[i], np.float64)
        want[np.arange(k, packed.w), np.arange(k, packed.w)] += 1.0
        np.testing.assert_allclose(got, np.linalg.inv(want), atol=1e-3)
