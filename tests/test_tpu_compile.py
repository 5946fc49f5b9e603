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
        A_loc=_sds(sub, p, M, w), L_loc=_sds(sub, p, w, w),
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
    assert _kernels(compiled) == 2          # schwarz_fwd + schwarz_bwd
    # The compiler fuses the reduce-scatter + all-gather pairs back into
    # all-reduces on this mesh.
    assert "all-reduce" in text
    if comm == "neighbour":
        assert "collective-permute" in text
