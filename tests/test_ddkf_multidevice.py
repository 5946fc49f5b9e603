"""shard_map DD-KF under real (forced) multi-device XLA — the production
communication path, exercised in a subprocess so the main test session
keeps its single-device view — plus parity of the device-side batched
operator packing (kernels.ops.gram, Cholesky, inverse) against a
per-subdomain numpy loop."""
import os
import subprocess
import sys

import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import cls, dd, ddkf, dydd
from repro.core.domain import ShelfTiling2D

SCRIPT = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import cls, dd, ddkf, dydd

rng = np.random.default_rng(0)
obs = rng.beta(2, 5, size=400)
prob = cls.local_problem(jax.random.PRNGKey(0), 128, obs)
x_direct = cls.solve(prob)
res = dydd.dydd_1d(obs, 8)
dec = dd.decompose_1d(prob.n, res.boundaries, overlap=0)
packed = ddkf.pack(prob, dec)
mesh = jax.make_mesh((8,), ("sub",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x_s = ddkf.solve_shardmap(packed, mesh, axis="sub", iters=120)
err = float(jnp.linalg.norm(x_s - x_direct))
assert err < 1e-9, err
# the (m,) product reduce-scatter path (dense-network regime; here the
# auto switch picks it since m = 528 >= 2 * n) matches the plain psum
x_sc = ddkf.solve_shardmap(packed, mesh, axis="sub", iters=120,
                           mvec="scatter")
x_ps = ddkf.solve_shardmap(packed, mesh, axis="sub", iters=120,
                           mvec="psum")
d_m = float(np.abs(np.asarray(x_sc) - np.asarray(x_ps)).max())
assert d_m < 1e-13, d_m
# neighbour-only halo exchange (with overlap) matches allreduce to ULPs
dec2 = dd.decompose_1d(prob.n, res.boundaries, overlap=2)
packed2 = ddkf.pack(prob, dec2)
x_a = ddkf.solve_shardmap(packed2, mesh, axis="sub", iters=120)
x_n = ddkf.solve_shardmap(packed2, mesh, axis="sub", iters=120,
                          comm="neighbour", halo=dec2.halo_exchange)
d_c = float(np.abs(np.asarray(x_a) - np.asarray(x_n)).max())
assert d_c < 1e-13, d_c
err_n = float(jnp.linalg.norm(x_n - x_direct))
assert err_n < 1e-9, err_n
# fused Schwarz-step kernel (interpret path off-TPU): ULP parity with
# the jnp local step on both solvers
packed2f = ddkf.pack(prob, dec2, solver_kernel="fused_interpret")
assert packed2f.solve_kernel == "fused_interpret"
assert packed2f.solve_block is not None
x_vj = ddkf.solve_vmapped(packed2, iters=60, damping=0.7)
x_vf = ddkf.solve_vmapped(packed2f, iters=60, damping=0.7)
d_v = float(np.abs(np.asarray(x_vj) - np.asarray(x_vf)).max())
assert d_v < 1e-13, d_v
x_sj = ddkf.solve_shardmap(packed2, mesh, axis="sub", iters=60,
                           damping=0.7, comm="neighbour",
                           halo=dec2.halo_exchange)
x_sf = ddkf.solve_shardmap(packed2f, mesh, axis="sub", iters=60,
                           damping=0.7, comm="neighbour",
                           halo=dec2.halo_exchange)
d_f = float(np.abs(np.asarray(x_sj) - np.asarray(x_sf)).max())
assert d_f < 1e-13, d_f
# the packed buffer exchange issues exactly halo.rounds ppermutes per
# iteration (the fori_loop body is traced once) regardless of per-pair
# edge multiplicity
jaxpr = str(jax.make_jaxpr(lambda pk: ddkf.solve_shardmap(
    pk, mesh, axis="sub", iters=60, damping=0.7, comm="neighbour",
    halo=dec2.halo_exchange))(packed2))
n_pp = jaxpr.count("ppermute")
assert n_pp == dec2.halo_exchange.rounds, (n_pp,
                                           dec2.halo_exchange.rounds)
print("OK", err, d_m, d_c, d_v, d_f)
"""

SCRIPT_2D = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import cls, dd, ddkf, dydd2d, domain

ny, nx = 8, 16
n = nx * ny
dom = domain.ShelfTiling2D(nx=nx, ny=ny, pr=2, pc=4)
obs2 = dydd2d.make_observations_2d(400, kind="clustered", seed=4)
dom.rebalance(obs2)
dec = dom.decomposition(overlap=1)
obs_raster = (np.clip((obs2[:, 1] * ny).astype(int), 0, ny - 1) * nx
              + np.clip((obs2[:, 0] * nx).astype(int), 0, nx - 1)
              + 0.5) / n
prob = cls.local_problem(jax.random.PRNGKey(0), n, np.sort(obs_raster))
packed = ddkf.pack(prob, dec)
x_v = ddkf.solve_vmapped(packed, iters=200, damping=0.7)
mesh = jax.make_mesh((2, 4), ("row", "col"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
x_s = ddkf.solve_shardmap(packed, mesh, axis=("row", "col"), iters=200,
                          damping=0.7)
# The grid-sharded solve runs the identical iteration; the collective
# reduction order differs from the batched einsum by a few ULPs, nothing
# more (bitwise-equal up to reduction associativity).
d = float(np.abs(np.asarray(x_v) - np.asarray(x_s)).max())
assert d < 1e-13, d
err = float(jnp.linalg.norm(x_s - cls.solve(prob)))
assert err < 1e-9, err
# neighbour-only halo exchange on the 2D mesh: ppermute rounds over the
# coloured edge schedule (grid neighbours + the corner halo∩halo pairs)
# reproduce the allreduce exchange to reduction-order ULPs.
x_n = ddkf.solve_shardmap(packed, mesh, axis=("row", "col"), iters=200,
                          damping=0.7, comm="neighbour",
                          halo=dec.halo_exchange)
d_n = float(np.abs(np.asarray(x_s) - np.asarray(x_n)).max())
assert d_n < 1e-13, d_n
err_n = float(jnp.linalg.norm(x_n - cls.solve(prob)))
assert err_n < 1e-9, err_n
# fused local step on the 2D device mesh: parity with the jnp path
packedf = ddkf.pack(prob, dec, solver_kernel="fused_interpret")
x_fj = ddkf.solve_shardmap(packed, mesh, axis=("row", "col"), iters=60,
                           damping=0.7, comm="neighbour",
                           halo=dec.halo_exchange)
x_ff = ddkf.solve_shardmap(packedf, mesh, axis=("row", "col"), iters=60,
                           damping=0.7, comm="neighbour",
                           halo=dec.halo_exchange)
d_f = float(np.abs(np.asarray(x_fj) - np.asarray(x_ff)).max())
assert d_f < 1e-13, d_f
print("OK", d, err, d_n, d_f)
"""

SCRIPT_ENGINE = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.assim import AssimilationEngine, EngineConfig

kw = dict(ndim=2, nx=16, ny=8, pr=2, pc=4, iters=200, damping=0.7,
          overlap=1, imbalance_threshold=1.5)
js = AssimilationEngine(EngineConfig(solver="shardmap", **kw)).run_scenario(
    "rotating_swarm", m=160, cycles=2, seed=0)
jv = AssimilationEngine(EngineConfig(solver="vmapped", **kw)).run_scenario(
    "rotating_swarm", m=160, cycles=2, seed=0)
jn = AssimilationEngine(EngineConfig(solver="shardmap", comm="neighbour",
                                     **kw)).run_scenario(
    "rotating_swarm", m=160, cycles=2, seed=0)
for a, b, c in zip(js.records, jv.records, jn.records):
    assert a.loads == b.loads == c.loads
    assert a.repartitioned == b.repartitioned == c.repartitioned
    # neighbour path journals strictly less modelled traffic
    assert c.comm_bytes_per_cycle < a.comm_bytes_per_cycle
print("OK")
"""


SCRIPT_KDTREE = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import cls, ddkf, kdtree
from repro.assim import streams, AssimilationEngine, EngineConfig

# Irregular-graph halo exchange: a rebalanced 8-leaf k-d tree's face
# adjacency is NOT a grid, so the coloured ppermute schedule runs between
# arbitrary device pairs of the flat ("sub",) mesh.
dom = kdtree.KDTreeDomain(nx=16, ny=8, p=8)
obs2 = next(iter(streams.make_stream("satellite_track", 400, 1, seed=3)))
dom.rebalance(obs2)
dec = dom.decomposition(overlap=1)
he = dec.halo_exchange
assert len(he.edges) > 7, he.edges            # more than a chain
prob = cls.local_problem(jax.random.PRNGKey(0), dom.n,
                         np.sort(dom.obs_positions(obs2)))
packed = ddkf.pack(prob, dec)
mesh = jax.make_mesh((8,), ("sub",),
                     axis_types=(jax.sharding.AxisType.Auto,))
x_a = ddkf.solve_shardmap(packed, mesh, axis="sub", iters=200, damping=0.7)
x_n = ddkf.solve_shardmap(packed, mesh, axis="sub", iters=200, damping=0.7,
                          comm="neighbour", halo=he)
d = float(np.abs(np.asarray(x_a) - np.asarray(x_n)).max())
assert d < 1e-13, d
err = float(jnp.linalg.norm(x_n - cls.solve(prob)))
assert err < 1e-9, err
# fused local step over the irregular leaf graph: parity with jnp
packedf = ddkf.pack(prob, dec, solver_kernel="fused_interpret")
x_fj = ddkf.solve_shardmap(packed, mesh, axis="sub", iters=60,
                           damping=0.7, comm="neighbour", halo=he)
x_ff = ddkf.solve_shardmap(packedf, mesh, axis="sub", iters=60,
                           damping=0.7, comm="neighbour", halo=he)
d_f = float(np.abs(np.asarray(x_fj) - np.asarray(x_ff)).max())
assert d_f < 1e-13, d_f
# engine end to end on the leaf graph, both comm paths + vmapped parity
kw = dict(ndim=2, domain_kind="kdtree", p=8, nx=16, ny=8, iters=200,
          damping=0.7, overlap=1, imbalance_threshold=1.5)
js = AssimilationEngine(EngineConfig(solver="shardmap", **kw)).run_scenario(
    "satellite_track", m=160, cycles=2, seed=0)
jn = AssimilationEngine(EngineConfig(solver="shardmap", comm="neighbour",
                                     **kw)).run_scenario(
    "satellite_track", m=160, cycles=2, seed=0)
jv = AssimilationEngine(EngineConfig(solver="vmapped", **kw)).run_scenario(
    "satellite_track", m=160, cycles=2, seed=0)
for a, b, c in zip(js.records, jn.records, jv.records):
    assert a.loads == b.loads == c.loads
    assert a.repartitioned == b.repartitioned == c.repartitioned
    # neighbour path journals strictly less modelled traffic
    assert b.comm_bytes_per_cycle < a.comm_bytes_per_cycle
print("OK", d, err)
"""


SCRIPT_TIMEPAR = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.assim import AssimilationEngine, EngineConfig, streams
from repro.assim.timepar import TimeParEngine

name, m, cycles, seed = "drifting_swarm", 160, 12, 0
kw = dict(n=64, p=2, iters=60)

seq = AssimilationEngine(EngineConfig(**kw))
chain = []
seq.on_analysis = lambda cycle, x: chain.append(np.asarray(x))
seq.run(streams.make_stream(name, m, cycles, seed=seed))

# 8 devices, W=4 windows, p=2 -> the auto mesh factors as
# ("time": 4, "sub": 2): windows shard over time, subdomains over sub.
cfg = EngineConfig(time_windows=4, pint_tol=1e-8, **kw)
tp = TimeParEngine(cfg)
journal = tp.run(streams.make_stream(name, m, cycles, seed=seed))
pint = journal.meta["pint"]
assert pint["mesh"] == {"time": 4, "sub": 2}, pint["mesh"]
assert pint["converged"], pint
assert len(tp.analyses) == cycles
diff = max(float(np.max(np.abs(a - b)))
           for a, b in zip(tp.analyses, chain))
assert diff < 1e-6, diff
for rw, rs in zip(journal.records, seq.journal.records):
    assert rw.loads == rs.loads
    assert rw.repartitioned == rs.repartitioned
print("OK", pint["iters"], diff)
"""


def _run_forced_8dev(script: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout


@pytest.mark.slow
def test_shardmap_ddkf_8_devices():
    _run_forced_8dev(SCRIPT)


@pytest.mark.slow
def test_shardmap_ddkf_2d_mesh_matches_vmapped():
    """2D shelf tiling with halo overlap on a real 2 x 4 device mesh:
    grid axes map onto mesh axes; result matches solve_vmapped to
    reduction-order ULPs and the direct CLS solve to 1e-9."""
    _run_forced_8dev(SCRIPT_2D)


@pytest.mark.slow
def test_engine_shardmap_journal_matches_vmapped():
    """AssimilationEngine with solver='shardmap' auto-builds the pr x pc
    mesh and journals the same loads/repartitions as the vmapped run."""
    _run_forced_8dev(SCRIPT_ENGINE)


@pytest.mark.slow
def test_kdtree_shardmap_irregular_graph_8_devices():
    """KDTreeDomain end to end on a forced 8-device mesh: the leaf
    face-adjacency graph is irregular (first real exercise of the
    graph-general halo machinery beyond chains and grids), and the
    neighbour-only ppermute exchange matches allreduce to ULPs."""
    _run_forced_8dev(SCRIPT_KDTREE)


@pytest.mark.slow
def test_timepar_time_sub_mesh_8_devices():
    """Parareal engine on a forced 8-device ("time", "sub") mesh:
    windows shard over the time axis, subdomains over sub, and the
    converged analysis chain matches the sequential engine within the
    Parareal tolerance."""
    _run_forced_8dev(SCRIPT_TIMEPAR)


# ---------------------------------------------------------------------------
# Device-side operator packing parity vs a per-subdomain numpy loop.
# ---------------------------------------------------------------------------

def _pack_inverses_numpy(A, r, dec, mu):
    """The host reference: per-subdomain numpy normal matrices, with the
    identity on padded slots, and their inverses (what
    ddkf.pack_operator builds on the device)."""
    A = np.asarray(A)
    r = np.asarray(r)
    m, n = A.shape
    w = max(int(np.asarray(c).shape[0]) for c in dec.col_sets)
    counts = np.zeros(n, dtype=np.int64)
    for c in dec.col_sets:
        counts[np.asarray(c)] += 1
    Ninv_ref = np.zeros((dec.p, w, w), dtype=A.dtype)
    for i, c in enumerate(dec.col_sets):
        c = np.asarray(c)
        k = c.shape[0]
        A_i = np.zeros((m, w), dtype=A.dtype)
        A_i[:, :k] = A[:, c]
        N = (A_i.T * r) @ A_i
        if dec.overlap > 0 and mu > 0.0:
            ov = (counts[c] > 1).astype(N.dtype)
            N[:k, :k] += mu * np.diag(ov)
        pad = np.arange(k, w)
        N[pad, pad] = 1.0
        Ninv_ref[i] = np.linalg.inv(N)
    return Ninv_ref


def _fill_blocks_numpy(A, dec):
    """The padded (p, m, w) local blocks filled on the host, column block
    by column block, with +0.0 in the padded slots."""
    A = np.asarray(A)
    w = max(int(np.asarray(c).shape[0]) for c in dec.col_sets)
    A_loc = np.zeros((dec.p, A.shape[0], w), dtype=A.dtype)
    for i, c in enumerate(dec.col_sets):
        c = np.asarray(c)
        A_loc[i, :, :c.shape[0]] = A[:, c]
    return A_loc


def _bits(x):
    x = np.asarray(x)
    return x.view(np.dtype(f"u{x.dtype.itemsize}"))


_BLOCK_DECS = {
    "chain_overlap0": lambda: dd.decompose_1d(
        40, dydd.dydd_1d(np.random.default_rng(2).beta(2, 5, 120),
                         5).boundaries, overlap=0),
    "chain_overlap1": lambda: dd.decompose_1d(
        40, dd.uniform_boundaries(4), overlap=1),
    "shelf_2d": lambda: ShelfTiling2D(8, 6, 2, 3).decomposition(overlap=1),
}


@pytest.mark.parametrize("as_device", [False, True],
                         ids=["numpy_A", "device_A"])
@pytest.mark.parametrize("dec_name", sorted(_BLOCK_DECS))
def test_pack_operator_gathers_the_host_fill_bit_for_bit(
        monkeypatch, dec_name, as_device):
    """The device-built A_loc equals a numpy fill bit for bit, signed
    zeros included, with +0.0 in every padded slot; a device A is never
    read back to the host."""
    from jax._src.array import ArrayImpl

    dec = _BLOCK_DECS[dec_name]()
    n = dec.n
    if dec_name == "shelf_2d":   # raster-ordered cells: gaps in a column set
        assert any(np.any(np.diff(np.asarray(c)) > 1) for c in dec.col_sets)
    rng = np.random.default_rng(11)
    A_np = rng.normal(size=(3 * n, n)).astype(np.float32)
    A_np[rng.random(A_np.shape) < 0.2] = -0.0
    r = np.ones((A_np.shape[0],))
    expect = _fill_blocks_numpy(A_np, dec)

    A = jnp.asarray(A_np) if as_device else A_np
    read_back = []
    if as_device:
        # A CPU "device" array reads back with no transfer, so the guard
        # alone cannot see it there: every host read of A is recorded.
        def spy(read):
            return lambda x, *a, **kw: (read_back.append(x is A)
                                        or read(x, *a, **kw))

        value = ArrayImpl._value
        monkeypatch.setattr(ArrayImpl, "_value", property(spy(value.fget)))
        monkeypatch.setattr(np, "asarray", spy(np.asarray))
        monkeypatch.setattr(np, "array", spy(np.array))
    with jax.transfer_guard_device_to_host("disallow"):
        packed = ddkf.pack_operator(A, r, dec)
        jax.block_until_ready(packed)
    monkeypatch.undo()
    assert not any(read_back)

    got = np.asarray(packed.A_loc)
    assert got.dtype == np.float32 and got.shape == expect.shape
    np.testing.assert_array_equal(_bits(got), _bits(expect))
    pad = np.asarray(packed.mask) == 0
    assert pad.any()
    assert not np.signbit(got.transpose(0, 2, 1)[pad]).any()


@pytest.mark.parametrize("overlap,mu", [(0, 1.0), (2, 0.7)])
def test_pack_operator_gram_matches_numpy_loop(overlap, mu):
    rng = np.random.default_rng(3)
    obs = rng.beta(2, 5, 300)
    prob = cls.local_problem(jax.random.PRNGKey(0), 96, obs)
    res = dydd.dydd_1d(obs, 6)
    dec = dd.decompose_1d(prob.n, res.boundaries, overlap=overlap)
    A, b, r = prob.stacked()

    packed = ddkf.pack_operator(A, r, dec, mu=mu)
    Ninv_ref = _pack_inverses_numpy(A, r, dec, mu)
    np.testing.assert_allclose(np.asarray(packed.Ninv_loc), Ninv_ref,
                               rtol=1e-10, atol=1e-10)
    # and the packed solve still matches the direct CLS estimate
    x = ddkf.solve_vmapped(ddkf.with_rhs(packed, b), iters=150)
    err = float(jnp.linalg.norm(x - cls.solve(prob)))
    assert err < 1e-8, err


def test_pack_operator_gram_interpret_mode_close():
    """Forcing the Pallas gram kernel (interpret mode, f32 accumulation)
    keeps the inverses within kernel tolerance of the f64 reference."""
    rng = np.random.default_rng(4)
    obs = np.sort(rng.uniform(0, 1, 200))
    prob = cls.local_problem(jax.random.PRNGKey(1), 64, obs)
    dec = dd.decompose_1d(prob.n, dd.uniform_boundaries(4))
    A, _, r = prob.stacked()
    ref = ddkf.pack_operator(A, r, dec, gram_mode="ref")
    ker = ddkf.pack_operator(A, r, dec, gram_mode="interpret")
    Ninv_ref = _pack_inverses_numpy(A, r, dec, 1.0)
    np.testing.assert_allclose(np.asarray(ref.Ninv_loc), Ninv_ref,
                               rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(np.asarray(ker.Ninv_loc), Ninv_ref,
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# The local solve applies the stored inverse: no triangular solve per sweep.
# ---------------------------------------------------------------------------

SCRIPT_LOCAL_SOLVE = r"""
import re
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np, jax.numpy as jnp
from repro.core import cls, dd, ddkf, dydd

case = {case!r}
rng = np.random.default_rng(0)
obs = rng.beta(2, 5, size=400)
prob = cls.local_problem(jax.random.PRNGKey(0), 128, obs)
x_direct = np.asarray(cls.solve(prob))
res = dydd.dydd_1d(obs, 8)
dec = dd.decompose_1d(prob.n, res.boundaries, overlap=2)
packed = ddkf.pack(prob, dec, solver_kernel="jnp")
x_directs = [x_direct]
if case == "vmapped_jnp":
    solve = lambda pk: ddkf.solve_vmapped(pk, iters=120)
    xs = [solve(packed)]
elif case == "fleet":
    # two problems on one operator (other data), stacked
    prob2 = cls.local_problem(jax.random.PRNGKey(1), 128, obs)
    x_directs.append(np.asarray(cls.solve(prob2)))
    packed = ddkf.stack_packed([packed, ddkf.pack(prob2, dec,
                                                  solver_kernel="jnp")])
    solve = lambda pk: ddkf.solve_fleet(pk, iters=120)
    xs = list(solve(packed))
elif case == "shardmap":
    mesh = jax.make_mesh((8,), ("sub",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    solve = lambda pk: ddkf.solve_shardmap(pk, mesh, iters=120,
                                           comm="neighbour",
                                           halo=dec.halo_exchange)
    xs = [solve(packed)]
else:
    # two windows (the same problem) over ("time": 2, "sub": 4): two
    # subdomains per device
    mesh = jax.make_mesh((2, 4), ("time", "sub"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    packed = ddkf.stack_packed([packed, packed])
    solve = lambda pk: ddkf.solve_window_stack(pk, mesh, iters=120)
    xs = list(solve(packed))
    x_directs.append(x_direct)
# A triangular solve lowers to the HLO op triangular-solve, or on the CPU
# to a LAPACK trsm custom call named after the primitive.
hlo = jax.jit(solve).lower(packed).as_text(dialect="hlo")
assert not re.search(r"triangular|trsm", hlo, re.I), case
assert "dot(" in hlo, case
assert len(xs) == len(x_directs), case
for x, xd in zip(xs, x_directs):
    err = float(np.linalg.norm(np.asarray(x) - xd))
    assert err < 1e-9, (case, err)
print("OK", case, err)
"""


@pytest.mark.parametrize("case", ["vmapped_jnp", "fleet", "shardmap",
                                  "window_stack"])
def test_solves_apply_the_inverse_with_no_triangular_solve(case):
    """Every layout of the Schwarz step applies the packing's stored
    inverse as a matvec: the lowered solve holds no triangular solve,
    and the estimate matches the direct CLS solve as the shard_map
    equivalence test requires."""
    _run_forced_8dev(SCRIPT_LOCAL_SOLVE.format(case=case))


SCRIPT_WINDOW_KERNEL = r"""
import jax
jax.config.update("jax_enable_x64", True)
import numpy as np
from repro.core import cls, dd, ddkf, dydd

rng = np.random.default_rng(0)
obs = rng.beta(2, 5, size=400)
dec = dd.decompose_1d(128, dydd.dydd_1d(obs, 8).boundaries, overlap=2)
packs = [ddkf.pack(cls.local_problem(jax.random.PRNGKey(k), 128, obs), dec,
                   solver_kernel="fused_interpret") for k in (0, 1)]
assert all(pk.solve_kernel == "fused_interpret" for pk in packs)
# two windows over ("time": 2, "sub": 4): two subdomains per device
mesh = jax.make_mesh((2, 4), ("time", "sub"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
stacked = ddkf.stack_packed(packs)
solve = lambda pk: ddkf.solve_window_stack(pk, mesh, iters=60, damping=0.7)
# the sweep's two passes are the Pallas kernels
assert str(jax.make_jaxpr(solve)(stacked)).count("pallas_call") == 2
xs = solve(stacked)
for pk, x in zip(packs, np.asarray(xs)):
    x_v = np.asarray(ddkf.solve_vmapped(pk, iters=60, damping=0.7))
    d = float(np.abs(x - x_v).max())
    assert d < 1e-13, d
print("OK", d)
"""


def test_window_stack_runs_the_packings_kernel():
    """The window-stacked solve runs the packing's Schwarz-step kernel
    (here the Pallas kernel in interpret mode) and matches per-window
    ``solve_vmapped`` on the same packings to reduction-order ULPs."""
    _run_forced_8dev(SCRIPT_WINDOW_KERNEL)


def test_pad_packed_width_inverse_solves_padding_to_exactly_zero():
    """Widening a packing puts the identity on the new slots of the
    stored inverse, so an rhs that is zero there solves to exactly 0
    there (without the mask), and the padded solve still matches the
    unpadded one."""
    rng = np.random.default_rng(5)
    obs = rng.beta(2, 5, 200)
    prob = cls.local_problem(jax.random.PRNGKey(2), 64, obs)
    dec = dd.decompose_1d(prob.n, dydd.dydd_1d(obs, 4).boundaries,
                          overlap=1)
    A, b, r = prob.stacked()
    packed = ddkf.pack_operator(A, r, dec)
    padded = ddkf.pad_packed_width(packed, packed.w + 5)
    w = packed.w
    Ninv = np.asarray(padded.Ninv_loc)
    np.testing.assert_array_equal(Ninv[:, :w, :w],
                                  np.asarray(packed.Ninv_loc))
    np.testing.assert_array_equal(Ninv[:, w:, w:],
                                  np.broadcast_to(np.eye(5), (dec.p, 5, 5)))
    assert not Ninv[:, w:, :w].any() and not Ninv[:, :w, w:].any()

    pad = np.asarray(padded.mask) == 0
    assert pad[:, w:].all() and pad.sum() > 5 * dec.p
    rhs = rng.normal(size=pad.shape) * np.asarray(padded.mask)
    out = np.asarray(ddkf._local_solve(padded.Ninv_loc, jnp.asarray(rhs)))
    assert (out[pad] == 0.0).all()
    np.testing.assert_allclose(
        out, np.einsum("pij,pj->pi", Ninv, rhs), rtol=1e-12, atol=1e-12)

    x_pad = ddkf.solve_vmapped(ddkf.with_rhs(padded, b), iters=120)
    x = ddkf.solve_vmapped(ddkf.with_rhs(packed, b), iters=120)
    np.testing.assert_allclose(np.asarray(x_pad), np.asarray(x),
                               rtol=1e-12, atol=1e-12)
    assert float(jnp.linalg.norm(x_pad - cls.solve(prob))) < 1e-9
