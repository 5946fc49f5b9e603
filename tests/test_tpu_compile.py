"""Compile the DD-KF kernels and the sharded solve for a TPU v5e that is
described, not attached.

These ask the chip's own compiler what interpret mode cannot show: block
layouts the (8, 128) tiling refuses, kernels that overflow scoped VMEM,
programs that cannot be partitioned.  Shapes are the paper's Example 4
(n = 2048, m_obs = 2000, so m = 2n - 2 + m_obs = 6094 stacked rows) at
p = 8 — w = 256 for uniform blocks and w = 1664, the padded width DyDD's
repartitions reach on the ``drifting_swarm`` stream — and at p = 4 for the
sharded solve.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time, and every
test worker imports this file.
"""
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import dd, ddkf
from repro.kernels import gram as gram_mod
from repro.kernels import ops
from repro.kernels import schwarz_step

P8, M, N = 8, 6094, 2048
F32 = jnp.float32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _kept(tile_bytes, w):
    """The block_m candidates the autotune times at width w (the rest it
    rejects on its VMEM model without compiling them)."""
    return [bm for bm in ops.GRAM_BLOCK_CANDIDATES
            if tile_bytes(bm, w) <= ops.GRAM_VMEM_BUDGET_BYTES]


def _cases(tile_bytes):
    return [(w, bm) for w in (256, 1664) for bm in _kept(tile_bytes, w)]


def _sds(sharding, *shape, dtype=F32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernels(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def _triangular_solves(compiled) -> list:
    """The triangular-solve ops of a compiled program, or the custom
    calls the chip's compiler expands one into."""
    return re.findall(r"triangular-solve\(|custom_call_target=\"[^\"]*"
                      r"Triangular", compiled.as_text())


@pytest.mark.parametrize("w,block_m", _cases(ops.schwarz_tile_bytes))
def test_schwarz_fwd_compiles(one_chip, w, block_m):
    s = lambda *sh: _sds(one_chip, *sh)
    c = jax.jit(lambda A, x, wd: schwarz_step.schwarz_fwd(
        A, x, wd, block_m=block_m)).lower(
            s(P8, M, w), s(P8, w), s(P8, w)).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("w,block_m", _cases(ops.schwarz_tile_bytes))
def test_schwarz_bwd_compiles(one_chip, w, block_m):
    s = lambda *sh: _sds(one_chip, *sh)
    c = jax.jit(lambda A, r, b, ax, u, x, mu, mk: schwarz_step.schwarz_bwd(
        A, r, b, ax, u, x, mu, mk, block_m=block_m)).lower(
            s(P8, M, w), s(M), s(M), s(M), s(P8, M), s(P8, w), s(P8, w),
            s(P8, w)).compile()
    assert _kernels(c) == 1


@pytest.mark.parametrize("w,block_m", _cases(ops.gram_tile_bytes))
def test_gram_compiles(one_chip, w, block_m):
    s = lambda *sh: _sds(one_chip, *sh)
    c = jax.jit(lambda A, r: gram_mod.gram(A, r, block_m=block_m)).lower(
        s(P8, M, w), s(P8, M)).compile()
    assert _kernels(c) == 1


def test_every_candidate_fits_example4_uniform_width():
    """At Example 4's uniform width every candidate is under the VMEM
    budget, so the sweep times all of them (and the tests above compile
    all of them)."""
    assert _kept(ops.gram_tile_bytes, 256) == list(ops.GRAM_BLOCK_CANDIDATES)
    assert _kept(ops.schwarz_tile_bytes, 256) == \
        list(ops.SCHWARZ_BLOCK_CANDIDATES)


@pytest.mark.parametrize("kernel", ["schwarz_fwd", "schwarz_bwd", "gram"])
def test_ops_pads_unaligned_width(one_chip, kernel):
    """w = 250 is no multiple of 128: ``ops`` pads the lane axis to 256
    for the native kernel and slices the result back."""
    w = 250
    s = lambda *sh: _sds(one_chip, *sh)
    if kernel == "schwarz_fwd":
        fn = lambda A, x, wd: ops.schwarz_fwd(A, x, wd, mode="kernel",
                                              block_m=256)
        args, want = (s(P8, M, w), s(P8, w), s(P8, w)), [(P8, M)] * 2
    elif kernel == "schwarz_bwd":
        fn = lambda A, r, b, ax, u, x, mu, mk: ops.schwarz_bwd(
            A, r, b, ax, u, x, mu, mk, mode="kernel", block_m=256)
        args = (s(P8, M, w), s(M), s(M), s(M), s(P8, M), s(P8, w),
                s(P8, w), s(P8, w))
        want = [(P8, w)]
    else:
        fn = lambda A, r: ops.gram(A, r, mode="kernel", block_m=256)
        args, want = (s(P8, M, w), s(P8, M)), [(P8, w, w)]
    lowered = jax.jit(fn).lower(*args)
    got = [o.shape for o in jax.tree.leaves(lowered.out_info)]
    assert got == want
    assert _kernels(lowered.compile()) == 1


def test_block_gather_compiles_at_example4_width(one_chip):
    """The device gather of the padded local blocks at w = 1070, the
    width DyDD gives Example 4 on the Beta(2, 5) network: one gather,
    the (p, m, w) blocks out, and at most one more copy of them in
    temporaries."""
    w, i32 = 1070, jnp.int32
    lowered = ddkf._gather_blocks.lower(
        _sds(one_chip, M, N), _sds(one_chip, P8, w, dtype=i32),
        _sds(one_chip, P8, w, dtype=i32))
    assert lowered.out_info.shape == (P8, M, w)
    compiled = lowered.compile()
    assert " gather(" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes <= 2 * mem.output_size_in_bytes


@pytest.mark.parametrize("comm", ["allreduce", "neighbour"])
def test_solve_shardmap_compiles_on_4_chips(topo, monkeypatch, comm):
    """The jitted sharded solve at Example 4's p = 4, overlap-1 shapes on
    a (4,) mesh of the described chips: the fused Pallas step runs in
    each shard, the sums become all-reduces, and the neighbour path's
    halo rounds become collective permutes."""
    # The kernel path is picked from jax.default_backend(), which is the
    # CPU here; steer it to the TPU branch for this compile.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("sub",))
    dec = dd.decompose_1d(N, dd.uniform_boundaries(4), overlap=1)
    halo = dec.halo_exchange
    p, w = dec.p, halo.w
    sub, rep = NamedSharding(mesh, P("sub")), NamedSharding(mesh, P())
    i32 = jnp.int32
    packed = ddkf.PackedDD(
        A_loc=_sds(sub, p, M, w), Ninv_loc=_sds(sub, p, w, w),
        cols=_sds(sub, p, w, dtype=i32), mask=_sds(sub, p, w),
        muov=_sds(sub, p, w), wdiv=_sds(sub, p, w), mult=_sds(rep, N),
        mult_loc=_sds(sub, p, w), scatter_cols=_sds(sub, p, w, dtype=i32),
        gather_cols=_sds(sub, p, w, dtype=i32), r=_sds(rep, M),
        b=_sds(rep, M), n=N, p=p, w=w, solve_kernel="fused", solve_block=512)
    idx = (halo.pack_idx.shape if comm == "neighbour" else (p, 0, 0))
    mvec = "scatter"                # m >= MVEC_SCATTER_RATIO * n here
    assert M >= ddkf.MVEC_SCATTER_RATIO * N
    fn = ddkf._shardmap_fn(mesh, ("sub",), 60, comm, mvec,
                           halo.perms if comm == "neighbour" else (),
                           residual_history=False, return_per_device=True)
    compiled = fn.lower(packed, _sds(sub, *idx, dtype=i32),
                        _sds(sub, *idx, dtype=i32),
                        _sds(rep, dtype=F32)).compile()
    text = compiled.as_text()
    assert text.startswith("HloModule jit__solve_shard_map,")
    assert _kernels(compiled) == 2          # schwarz_fwd + schwarz_bwd
    # The compiler fuses the reduce-scatter + all-gather pairs back into
    # all-reduces on this mesh.
    assert "all-reduce" in text
    if comm == "neighbour":
        assert "collective-permute" in text
    # each sweep applies the stored inverse: no triangular solve
    assert not _triangular_solves(compiled)


def test_solve_vmapped_compiles_with_no_triangular_solve(one_chip,
                                                        monkeypatch):
    """The one-chip solve at Example 4's p = 8, w = 1070 (the width DyDD
    gives the Beta(2, 5) network): the two fused kernels per sweep, and
    the local solve a matvec against the stored inverse, with no
    triangular solve (the chip's compiler expands one into
    ``Invert*Triangular`` custom calls)."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    w, i32 = 1070, jnp.int32
    s = lambda *sh, dtype=F32: _sds(one_chip, *sh, dtype=dtype)
    packed = ddkf.PackedDD(
        A_loc=s(P8, M, w), Ninv_loc=s(P8, w, w), cols=s(P8, w, dtype=i32),
        mask=s(P8, w), muov=s(P8, w), wdiv=s(P8, w), mult=s(N),
        mult_loc=s(P8, w), scatter_cols=s(P8, w, dtype=i32),
        gather_cols=s(P8, w, dtype=i32), r=s(M), b=s(M), n=N, p=P8, w=w,
        solve_kernel="fused", solve_block=512)
    compiled = ddkf.solve_vmapped.lower(packed, iters=60).compile()
    assert _kernels(compiled) == 2          # schwarz_fwd + schwarz_bwd
    assert not _triangular_solves(compiled)


def test_solve_window_stack_compiles_with_the_fused_kernels(topo,
                                                           monkeypatch):
    """The window-stacked Parareal solve on a (2, 2) ("time", "sub")
    mesh of the described chips, two windows of Example 4's p = 4,
    overlap-1 packing (w = 1257): each device's sweep runs the two fused
    kernels over its windows and subdomains, with no triangular
    solve."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]).reshape(2, 2),
                             ("time", "sub"))
    ws = NamedSharding(mesh, P("time", "sub"))
    wt = NamedSharding(mesh, P("time"))
    K, p, w, i32 = 2, 4, 1257, jnp.int32
    stacked = ddkf.PackedDD(
        A_loc=_sds(ws, K, p, M, w), Ninv_loc=_sds(ws, K, p, w, w),
        cols=_sds(ws, K, p, w, dtype=i32), mask=_sds(ws, K, p, w),
        muov=_sds(ws, K, p, w), wdiv=_sds(ws, K, p, w),
        mult=_sds(wt, K, N), mult_loc=_sds(ws, K, p, w),
        scatter_cols=_sds(ws, K, p, w, dtype=i32),
        gather_cols=_sds(ws, K, p, w, dtype=i32), r=_sds(wt, K, M),
        b=_sds(wt, K, M), n=N, p=p, w=w, solve_kernel="fused",
        solve_block=512)
    compiled = jax.jit(lambda pk: ddkf.solve_window_stack(
        pk, mesh, iters=60)).lower(stacked).compile()
    assert _kernels(compiled) == 2          # schwarz_fwd + schwarz_bwd
    assert not _triangular_solves(compiled)


COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def test_sharded_pack_compiles_on_4_chips(topo, monkeypatch):
    """Each chip gathers and factors its own block at Example 4's p = 4,
    overlap-1 width (w = 1257, the width DyDD gives the Beta(2, 5)
    network): from an A whole on every chip, the two programs hold no
    collective, so no block crosses between chips, and each chip's
    gather writes its one (1, m, w) block with no temporaries."""
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    mesh = jax.sharding.Mesh(np.asarray(topo.devices[:4]), ("sub",))
    sub, rep = NamedSharding(mesh, P("sub")), NamedSharding(mesh, P())
    p, w, i32 = 4, 1257, jnp.int32
    gather = ddkf._gather_blocks.lower(
        _sds(rep, M, N), _sds(sub, p, w, dtype=i32),
        _sds(sub, p, w, dtype=i32), mesh=mesh, axis="sub").compile()
    factor = ddkf._factor_batched.lower(
        _sds(sub, p, M, w), _sds(rep, M), _sds(sub, p, w),
        gram_block=512, mesh=mesh, axis="sub").compile()
    for compiled, module in ((gather, "jit__gather_blocks"),
                             (factor, "jit__factor_batched")):
        text = compiled.as_text()
        assert text.startswith(f"HloModule {module},")
        assert not any(c in text for c in COLLECTIVES), module
    assert " gather(" in gather.as_text()
    assert _kernels(factor) == 1            # the gram, per chip
    mem = gather.memory_analysis()
    # one block, not four, up to its tile padding
    assert M * w * 4 <= mem.output_size_in_bytes < 1.1 * M * w * 4
    assert mem.temp_size_in_bytes == 0
