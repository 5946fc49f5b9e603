"""DD-KF — the distributed Kalman-Filter solve of a decomposed CLS problem.

Each subdomain (= processor) iterates the *additive* Schwarz update of
``repro.core.dd``: given the current global iterate, it solves its local
regularized VAR-KF problem (eq. 25/27) and the updates are assembled
(eq. 28).  The only inter-processor communication per iteration is

    Ax = sum_j A_j x_j            (one all-reduce of an m-vector)

plus the boundary/overlap exchange folded into the assembly — exactly the
communication structure the paper counts in its overhead T^p_oh.

Two execution paths share the same step function:
  * ``solve_vmapped``   — subdomains on the leading axis of a batch
                          (single-device correctness/reference path);
  * ``solve_shardmap``  — one device per subdomain on a 1D chain or a
                          2D ``pr x pc`` grid mesh (the production path,
                          exercised under forced multi-device XLA in
                          tests and by the launch dry-run).  The m-vector
                          all-reduce is a ``psum`` or — in the dense-
                          network regime m >> n — a ``psum_scatter`` +
                          ``all_gather`` pair; the overlap exchange is
                          either the same reduce-scatter pair on the
                          (n,) assembly (``comm="allreduce"``) or
                          neighbour-only ``ppermute`` rounds of just the
                          halo slots over the decomposition's coloured
                          edge schedule (``comm="neighbour"`` — the
                          paper's T^p_oh pattern: per-iteration traffic
                          proportional to the overlap width s, not n).
                          :func:`comm_model` prices both paths.

Static shapes: local blocks are padded to the max block width; padded
columns carry an identity diagonal in the local normal matrix and zero
right-hand side, so their solution stays exactly zero.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import cls as cls_mod
from repro.core import dd as dd_mod
from repro.kernels import ops as ops_mod


@partial(jax.tree_util.register_dataclass,
         data_fields=("A_loc", "L_loc", "cols", "mask", "muov", "wdiv",
                      "mult", "mult_loc", "scatter_cols", "gather_cols",
                      "r", "b"),
         meta_fields=("n", "p", "w", "solve_kernel", "solve_block"))
@dataclasses.dataclass(frozen=True)
class PackedDD:
    """Host-side packing of a Decomposition into padded device arrays."""

    A_loc: jax.Array      # (p, m, w) local column blocks, zero-padded
    L_loc: jax.Array      # (p, w, w) Cholesky of local normal matrices
    cols: jax.Array       # (p, w) global column index per local slot (or -1)
    mask: jax.Array       # (p, w) 1.0 for real columns, 0.0 for padding
    muov: jax.Array       # (p, w) mu on overlap slots (regularization)
    wdiv: jax.Array       # (p, w) mask / column-multiplicity: partition of
                          # unity so sum_i A_i (x_i * wdiv_i) == A x_glob
    mult: jax.Array       # (n,) column multiplicity (overlap counting)
    mult_loc: jax.Array   # (p, w) multiplicity gathered to local slots
                          # (1.0 on padding) — the neighbour-exchange
                          # assembly divisor
    scatter_cols: jax.Array  # (p, w) cols with padding redirected to the
                             # dump slot n — precomputed scatter map
    gather_cols: jax.Array   # (p, w) cols with padding clipped to 0 —
                             # precomputed (mask-guarded) gather map
    r: jax.Array          # (m,) weight diagonal
    b: jax.Array          # (m,) stacked data
    n: int
    p: int
    w: int
    solve_kernel: str = "jnp"   # resolved iteration-kernel path: "jnp" |
                                # "fused" | "fused_interpret" | "fused_ref"
    solve_block: int | None = None  # autotuned fused-kernel m-tile (None
                                    # when the path has no blocking)

    @property
    def m(self) -> int:
        """Stacked row count (background + observation rows)."""
        return int(self.r.shape[0])

    def edge_send_bytes(self, halo: "dd_mod.HaloExchange") -> dict:
        """Per-iteration bytes each endpoint of each halo edge sends on
        the ``comm='neighbour'`` path, priced at this packing's dtype."""
        return halo.edge_send_bytes(np.dtype(self.A_loc.dtype).itemsize)

    def comm_stats(self, halo: "dd_mod.HaloExchange | None" = None,
                   comm: str = "allreduce",
                   mesh_shape: tuple | None = None) -> dict:
        """Modelled per-iteration communication volume for this packing
        (see :func:`comm_model`)."""
        return comm_model(self.n, self.m, self.p,
                          np.dtype(self.A_loc.dtype).itemsize,
                          halo=halo, comm=comm, mesh_shape=mesh_shape)


# Dense-network regime switch: when the stacked row count m is at least
# this multiple of n, the (m,) observation-space product dominates the
# per-iteration traffic and the solve reduce-scatters it along the
# innermost mesh axis (bandwidth-optimal all-reduce) instead of a plain
# psum — the ROADMAP "psum_scatter the (m,) product when m >> n" item.
MVEC_SCATTER_RATIO = 2.0


def _axis_allreduce_elems(length: int, mesh_shape: tuple) -> list:
    """Per-device element sends of the hierarchical all-reduce
    ``solve_shardmap.axis_allreduce`` actually runs, per mesh axis.

    Outer axes take a *plain psum* of the full vector — on a torus that
    is a neighbour-hop ring without a scatter, so each of the (k - 1)
    hops moves the whole ``length``-vector: ``(k - 1) * length`` element
    sends per device.  Only the innermost axis gets the
    bandwidth-optimal reduce-scatter + all-gather pair at
    ``2 * (k - 1) / k * length``.  Pricing them identically (the old
    single-ring model) understates outer-axis cost on any mesh with
    more than one axis.
    """
    per_axis = []
    for i, k in enumerate(mesh_shape):
        k = int(k)
        if k <= 1:
            per_axis.append(0.0)
        elif i == len(mesh_shape) - 1:
            per_axis.append(2.0 * (k - 1) / k * length)
        else:
            per_axis.append(float(k - 1) * length)
    return per_axis


def comm_model(n: int, m: int, p: int, itemsize: int,
               halo: "dd_mod.HaloExchange | None" = None,
               comm: str = "allreduce",
               mesh_shape: tuple | None = None) -> dict:
    """Modelled per-iteration send volume of one ``solve_shardmap`` sweep.

    The model counts payload bytes leaving each device per Schwarz
    iteration, the quantity the paper's overhead term T^p_oh charges:

      * ``mvec`` — the (m,) observation-space product every path
        all-reduces, priced per mesh axis (``mesh_shape``, outer to
        inner; default ``(p,)``): outer axes pay full-vector psum hops,
        the innermost the bandwidth-optimal reduce-scatter + all-gather
        ring — see :func:`_axis_allreduce_elems`.
      * state exchange — ``comm="allreduce"``: the (n,)-assembled
        estimate through the same per-axis hierarchy, *independent of
        the overlap width*; ``comm="neighbour"``: only the halo slots,
        ``sum(|shared|)`` elements per edge endpoint — proportional to
        the overlap width s and to nothing else.

    Returns a JSON-ready dict with per-device and total bytes, the
    per-axis mvec breakdown, and the per-edge breakdown (empty for the
    allreduce path).
    """
    if comm not in ("allreduce", "neighbour"):
        raise ValueError(f"comm must be 'allreduce' or 'neighbour' "
                         f"(got {comm!r})")
    mesh_shape = tuple(int(k) for k in (mesh_shape or (p,)))
    if int(np.prod(mesh_shape)) != p:
        raise ValueError(f"mesh_shape {mesh_shape} does not factor "
                         f"p={p} devices")
    mvec_axis = [e * itemsize for e in _axis_allreduce_elems(m, mesh_shape)]
    mvec_dev = float(sum(mvec_axis))
    if comm == "allreduce":
        state_axis = [e * itemsize
                      for e in _axis_allreduce_elems(n, mesh_shape)]
        state_dev = np.full((p,), sum(state_axis))
        per_edge: dict = {}
        rounds = 0
    else:
        if halo is None:
            raise ValueError("comm='neighbour' needs the decomposition's "
                             "halo_exchange metadata")
        state_dev = halo.device_send_bytes(itemsize).astype(np.float64)
        per_edge = halo.edge_send_bytes(itemsize)
        rounds = halo.rounds
    return {
        "comm": comm,
        "mesh_shape": list(mesh_shape),
        "mvec_bytes_per_device": mvec_dev,
        "mvec_bytes_per_device_per_axis": [float(b) for b in mvec_axis],
        "state_bytes_per_device_max": float(state_dev.max(initial=0.0)),
        "state_bytes_per_device_mean": float(state_dev.mean()
                                             if p else 0.0),
        "state_bytes_total": float(state_dev.sum()),
        "bytes_per_iter_total": float(state_dev.sum() + p * mvec_dev),
        "per_edge_bytes": per_edge,
        "permute_rounds": rounds,
    }


def pack(prob: cls_mod.CLSProblem, dec: dd_mod.Decomposition,
         mu: float = 1.0, solver_kernel: str = "auto") -> PackedDD:
    A = jnp.concatenate([prob.H0, prob.H1], axis=0)
    r = jnp.concatenate([prob.R0, prob.R1])
    b = jnp.concatenate([prob.y0, prob.y1])
    return with_rhs(pack_operator(A, r, dec, mu=mu,
                                  solver_kernel=solver_kernel), b)


# Iteration-kernel selection: how the per-iteration local step runs.
# "jnp" is the historic composition (three HBM passes over A_loc per
# iteration, bit-identical to every previous release); the "fused_*"
# variants run the two-pass fused step of ``kernels/schwarz_step.py``
# through the matching ops-mode ("fused" resolves per backend: the
# native Pallas kernel on TPU, the single-pass stacked-matmat jnp
# reference elsewhere; "fused_interpret" forces the kernel in interpret
# mode — the CPU-CI ULP-parity path; "fused_ref" forces the reference).
SOLVER_KERNELS = ("auto", "jnp", "fused", "fused_interpret", "fused_ref")
_KERNEL_OPS_MODE = {"fused": "auto", "fused_interpret": "interpret",
                    "fused_ref": "ref"}


def _resolve_solver_kernel(solver_kernel: str) -> str:
    if solver_kernel not in SOLVER_KERNELS:
        raise ValueError(f"solver_kernel must be one of {SOLVER_KERNELS} "
                         f"(got {solver_kernel!r})")
    if solver_kernel == "auto":
        # Default to the fused kernel only where it is a different (and
        # faster) program: on TPU.  Elsewhere "auto" keeps the historic
        # jnp composition so default numerics stay bit-identical.
        return "fused" if jax.default_backend() == "tpu" else "jnp"
    return solver_kernel


@partial(jax.jit, static_argnames=("gram_mode", "gram_block"))
def _factor_batched(A_loc: jax.Array, r: jax.Array, diag_add: jax.Array,
                    gram_mode: str = "auto",
                    gram_block: int | None = None) -> jax.Array:
    """Batched local normal matrices + Cholesky factors, on device.

    N_i = A_i^T diag(r) A_i comes from the ``kernels.ops.gram`` kernel
    (Pallas on TPU, jnp reference elsewhere); ``diag_add`` carries the
    mu-regularization on overlap slots plus the identity on padded slots
    that keeps every factor nonsingular.  ``gram_block`` is the autotuned
    reduction tile, resolved by the caller outside jit
    (``ops.gram_block_for``).
    """
    p = A_loc.shape[0]
    N = ops_mod.gram(A_loc, jnp.broadcast_to(r, (p, r.shape[0])),
                     mode=gram_mode, block_m=gram_block)
    N = N + jax.vmap(jnp.diag)(diag_add.astype(N.dtype))
    return jax.vmap(jnp.linalg.cholesky)(N)


def pack_operator(A: jax.Array, r: jax.Array, dec: dd_mod.Decomposition,
                  mu: float = 1.0, gram_mode: str = "auto",
                  solver_kernel: str = "auto") -> PackedDD:
    """Pack the *operator* part of a decomposed CLS problem.

    The host slices the p column blocks into the padded (p, m, w) layout;
    the p local normal matrices N_i = A_i^T diag(r) A_i and their Cholesky
    factors are then built *on device* in one batched shot
    (:func:`_factor_batched`: ``kernels.ops.gram`` + ``vmap(cholesky)``)
    instead of a per-subdomain ``np.linalg.cholesky`` Python loop.  The
    packing depends only on (A, r, dec), not on the data vector b, so the
    streaming engine runs it for cycle t+1 while the device is solving
    cycle t, then injects the cycle's rhs with :func:`with_rhs` (a cheap
    ``dataclasses.replace``).

    ``gram_mode`` selects the kernel path ("auto": Pallas on TPU, jnp
    reference elsewhere — see :mod:`repro.kernels.ops`).
    ``solver_kernel`` selects the per-iteration step path the solves will
    run (:data:`SOLVER_KERNELS`); it is resolved here, host-side — the
    fused paths autotune their ``block_m`` once per shape
    (``ops.schwarz_block_for``) and the choice rides along statically in
    the packing's meta fields.

    The returned ``PackedDD`` carries a zero rhs; pass it through
    :func:`with_rhs` before solving.
    """
    m, n = A.shape
    p = dec.p
    w = max(1, max(int(np.asarray(c).shape[0]) for c in dec.col_sets))

    # Per-column multiplicity is the decomposition's source of truth: the
    # halo columns (multiplicity > 1) carry the mu-regularization and the
    # 1/multiplicity partition-of-unity assembly weight, on any graph.
    counts = dec.column_multiplicity
    halo_mu = dec.has_overlap and mu > 0.0

    A_loc = np.zeros((p, m, w), dtype=np.asarray(A).dtype)
    cols = -np.ones((p, w), dtype=np.int64)
    mask = np.zeros((p, w), dtype=np.asarray(A).dtype)
    muov = np.zeros((p, w), dtype=np.asarray(A).dtype)
    A_np = np.asarray(A)
    for i, c in enumerate(dec.col_sets):
        c = np.asarray(c)
        k = c.shape[0]
        A_loc[i, :, :k] = A_np[:, c]
        cols[i, :k] = c
        mask[i, :k] = 1.0
        if halo_mu:
            muov[i, :k] = mu * (counts[c] > 1).astype(muov.dtype)
    A_loc = jnp.asarray(A_loc)
    r = jnp.asarray(r, A_loc.dtype)
    # mu on overlap slots; identity on padded slots (mask == 0).  The
    # gram reduction tile is autotuned host-side (first call per shape,
    # cached) and handed to the jitted factor build as a static arg.
    gram_block = ops_mod.gram_block_for((p, m, w), A_loc.dtype,
                                        mode=gram_mode)
    L_loc = _factor_batched(A_loc, r, jnp.asarray(muov + (1.0 - mask)),
                            gram_mode=gram_mode, gram_block=gram_block)
    solve_kernel = _resolve_solver_kernel(solver_kernel)
    solve_block = (ops_mod.schwarz_block_for(
        (p, m, w), A_loc.dtype, mode=_KERNEL_OPS_MODE[solve_kernel])
        if solve_kernel != "jnp" else None)
    mult_at = np.maximum(counts, 1)[np.clip(cols, 0, n - 1)]
    wdiv = mask / mult_at
    # Precomputed index maps: scatter redirects padding to the dump slot
    # n, gather clips it to 0 (mask kills the value) — built once here
    # instead of a where(cols >= 0, ...) membership mask per call.
    mult_loc = np.where(cols >= 0, mult_at, 1.0)
    scatter_cols = np.where(cols >= 0, cols, n)
    gather_cols = np.where(cols >= 0, cols, 0)
    return PackedDD(A_loc=A_loc, L_loc=L_loc,
                    cols=jnp.asarray(cols), mask=jnp.asarray(mask),
                    muov=jnp.asarray(muov), wdiv=jnp.asarray(wdiv),
                    mult=jnp.asarray(np.maximum(counts, 1)).astype(A.dtype),
                    mult_loc=jnp.asarray(mult_loc, A_loc.dtype),
                    scatter_cols=jnp.asarray(scatter_cols),
                    gather_cols=jnp.asarray(gather_cols),
                    r=r, b=jnp.zeros((m,), dtype=A_loc.dtype), n=n, p=p,
                    w=w, solve_kernel=solve_kernel, solve_block=solve_block)


def with_rhs(packed: PackedDD, b: jax.Array) -> PackedDD:
    """Inject the data vector b = [y0; y1] into an operator-only packing."""
    return dataclasses.replace(packed, b=jnp.asarray(b, packed.A_loc.dtype))


def pad_packed_width(packed: PackedDD, w_new: int) -> PackedDD:
    """Re-pad a packing to a larger local block width ``w_new``.

    Different cycles of a stream decompose with different max block
    widths (DyDD moves boundaries), so their packings cannot be stacked
    (:func:`stack_packed` requires equal ``w``).  Padding widens every
    per-slot field with the same conventions ``pack_operator`` uses for
    its own padding — zero columns in ``A_loc``, identity diagonal in
    ``L_loc``, ``cols=-1``/``mask=0``, multiplicity 1, scatter to the
    dump slot ``n`` — so the padded slots solve to exactly zero and the
    assembled estimate is unchanged up to reduction order.  This is a
    *tolerance-path* helper (the window-stacked Parareal fine solves):
    widening changes the einsum reduction extents, so results agree with
    the unpadded solve to ULPs, not bitwise.
    """
    if w_new < packed.w:
        raise ValueError(f"cannot shrink a packing: w={packed.w} -> "
                         f"{w_new}")
    if w_new == packed.w:
        return packed
    pad = w_new - packed.w
    p, w = packed.p, packed.w
    L = jnp.zeros((p, w_new, w_new), packed.L_loc.dtype)
    L = L.at[:, :w, :w].set(packed.L_loc)
    diag = jnp.arange(w, w_new)
    L = L.at[:, diag, diag].set(1.0)
    pad2 = ((0, 0), (0, pad))
    return dataclasses.replace(
        packed,
        A_loc=jnp.pad(packed.A_loc, ((0, 0), (0, 0), (0, pad))),
        L_loc=L,
        cols=jnp.pad(packed.cols, pad2, constant_values=-1),
        mask=jnp.pad(packed.mask, pad2),
        muov=jnp.pad(packed.muov, pad2),
        wdiv=jnp.pad(packed.wdiv, pad2),
        mult_loc=jnp.pad(packed.mult_loc, pad2, constant_values=1.0),
        scatter_cols=jnp.pad(packed.scatter_cols, pad2,
                             constant_values=packed.n),
        gather_cols=jnp.pad(packed.gather_cols, pad2),
        w=w_new)


def _chol_solve(L, rhs):
    z = jax.scipy.linalg.solve_triangular(L, rhs, lower=True)
    return jax.scipy.linalg.solve_triangular(L.T, z, lower=False)


def _local_update(A_i, L_i, mask_i, muov_i, x_i, Ax, r, b):
    """One local regularized VAR-KF solve given the global product Ax
    (eq. 25/27): the mu-term anchors the overlap slots to the current
    consistent global iterate x_i (= x_glob gathered)."""
    resid = b - Ax + A_i @ x_i
    rhs = (A_i.T @ (r * resid) + muov_i * x_i) * mask_i
    return _chol_solve(L_i, rhs) * mask_i


# ---------------------------------------------------------------------------
# Reference path: subdomains on a batch axis.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("iters", "residual_history"))
def solve_vmapped(packed: PackedDD, iters: int = 60,
                  damping: float = 1.0,
                  residual_history: bool = False,
                  x0=None):
    """Additive-Schwarz DD-KF; returns the assembled global estimate.

    With ``residual_history=True`` the iteration runs under ``lax.scan``
    and the call returns ``(x, hist)`` where ``hist[k]`` is the global
    update norm ``||x_loc^{k+1} - x_loc^k||_F`` — the per-iteration
    Schwarz residual history the observability layer journals.  The
    default path is the historic ``fori_loop`` (identical numerics, no
    per-iteration output).

    ``x0`` is an optional (n,) global warm start: the iteration begins
    from its local gather instead of zeros.  The Schwarz map contracts to
    the same fixed point from any start, so a warm start from a nearby
    estimate (e.g. a coarse Parareal trajectory) buys the same accuracy
    in fewer iterations; ``x0=None`` keeps the historic zero start
    bitwise.

    The per-iteration local step follows the packing's resolved
    ``solve_kernel``: the historic jnp composition, or the fused
    two-pass step of :mod:`repro.kernels.schwarz_step` (reduction-order
    ULP parity with the jnp path).
    """
    kern = packed.solve_kernel

    def step(x_loc):
        if kern == "jnp":
            # partition of unity: overlap columns contribute once to
            # A x_glob
            Ax_parts = jnp.einsum("pmw,pw->pm", packed.A_loc,
                                  x_loc * packed.wdiv)
            Ax = jnp.sum(Ax_parts, axis=0)
            new = jax.vmap(
                lambda A_i, L_i, m_i, mu_i, x_i: _local_update(
                    A_i, L_i, m_i, mu_i, x_i, Ax, packed.r, packed.b)
            )(packed.A_loc, packed.L_loc, packed.mask, packed.muov, x_loc)
        else:
            mode = _KERNEL_OPS_MODE[kern]
            y, u = ops_mod.schwarz_fwd(packed.A_loc, x_loc, packed.wdiv,
                                       mode=mode,
                                       block_m=packed.solve_block)
            Ax = jnp.sum(y, axis=0)
            rhs = ops_mod.schwarz_bwd(packed.A_loc, packed.r, packed.b,
                                      Ax, u, x_loc, packed.muov,
                                      packed.mask, mode=mode,
                                      block_m=packed.solve_block)
            new = jax.vmap(_chol_solve)(packed.L_loc, rhs) * packed.mask
        x_loc2 = (1.0 - damping) * x_loc + damping * new
        # Overlap consistency: average duplicated columns globally, then
        # gather back (eq. 28).
        x_glob = assemble(packed, x_loc2)
        return gather_local(packed, x_glob)

    if x0 is None:
        x_init = jnp.zeros((packed.p, packed.w), dtype=packed.A_loc.dtype)
    else:
        x_init = gather_local(packed, jnp.asarray(x0, packed.A_loc.dtype))
    if not residual_history:
        x_loc = jax.lax.fori_loop(0, iters, lambda _, x: step(x), x_init)
        return assemble(packed, x_loc)

    def body(x_loc, _):
        nxt = step(x_loc)
        return nxt, jnp.linalg.norm(nxt - x_loc)

    x_loc, hist = jax.lax.scan(body, x_init, None, length=iters)
    return assemble(packed, x_loc), hist


def assemble(packed: PackedDD, x_loc: jax.Array) -> jax.Array:
    """Scatter local iterates into the global vector, averaging overlaps.

    Uses the scatter map precomputed at pack time (padding lands on the
    dump slot n) — no per-call membership mask rebuild."""
    acc = jnp.zeros((packed.n + 1,), dtype=x_loc.dtype)
    acc = acc.at[packed.scatter_cols.reshape(-1)].add(
        (x_loc * packed.mask).reshape(-1))
    return acc[:packed.n] / packed.mult


def gather_local(packed: PackedDD, x_glob: jax.Array) -> jax.Array:
    return x_glob[packed.gather_cols] * packed.mask


# ---------------------------------------------------------------------------
# Fleet path: independent *problems* on a leading batch axis.
# ---------------------------------------------------------------------------

def stack_packed(packs) -> PackedDD:
    """Stack same-shape packings onto a leading *problem* axis.

    Every data field gains a leading axis of size ``len(packs)`` (the
    fleet/cohort axis); the meta fields — which must agree exactly across
    the stack, including the resolved ``solve_kernel``/``solve_block`` —
    are carried through unchanged.  The result is what
    :func:`solve_fleet` consumes: one device dispatch advancing every
    problem in the cohort.

    Shape agreement is a *cohort key* responsibility of the caller
    (``repro.assim.fleet`` buckets streams by it); a mismatch here is a
    programming error and raises.
    """
    packs = list(packs)
    if not packs:
        raise ValueError("stack_packed needs at least one packing")
    ref = packs[0]
    key0 = (ref.n, ref.p, ref.w, ref.m, ref.solve_kernel, ref.solve_block,
            ref.A_loc.dtype)
    for pk in packs[1:]:
        key = (pk.n, pk.p, pk.w, pk.m, pk.solve_kernel, pk.solve_block,
               pk.A_loc.dtype)
        if key != key0:
            raise ValueError(
                f"cannot stack packings with different shapes/kernels: "
                f"{key} vs {key0} — bucket them into separate cohorts")
    # One jitted dispatch for all ~12 field stacks (cached per pytree
    # structure/shape, i.e. per (cohort shape, capacity) — bounded by the
    # serving layer's capacity quantization).  Eager per-field jnp.stack
    # costs a device dispatch per field per round, which dominated the
    # fleet's round overhead.
    return _stack_jit(tuple(packs))


@jax.jit
def _stack_jit(packs):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *packs)


@partial(jax.jit, static_argnames=("iters", "residual_history"))
def _solve_fleet_map(stacked: PackedDD, iters: int, damping,
                     residual_history: bool):
    return jax.lax.map(
        lambda pk: solve_vmapped(pk, iters=iters, damping=damping,
                                 residual_history=residual_history),
        stacked)


@partial(jax.jit, static_argnames=("iters", "residual_history"))
def _solve_fleet_map_warm(stacked: PackedDD, x0, iters: int, damping,
                          residual_history: bool):
    # Separate jit from the cold path so x0=None callers keep their
    # historic trace (and bitwise output) untouched.
    return jax.lax.map(
        lambda arg: solve_vmapped(arg[0], iters=iters, damping=damping,
                                  residual_history=residual_history,
                                  x0=arg[1]),
        (stacked, x0))


def _fleet_sharded_fn(mesh, axis: str, iters: int, residual_history: bool):
    """Jitted shard_map of the per-problem sweep over the fleet mesh axis
    (cached per (mesh, axis, iters, residual_history) — mesh objects
    hash)."""
    key = (mesh, axis, iters, residual_history)
    fn = _FLEET_SHARDED_CACHE.get(key)
    if fn is not None:
        return fn

    def body(pk, damping):
        return jax.lax.map(
            lambda q: solve_vmapped(q, iters=iters, damping=damping,
                                    residual_history=residual_history),
            pk)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(axis), P()), out_specs=P(axis),
        check_vma=False))
    _FLEET_SHARDED_CACHE[key] = fn
    return fn


_FLEET_SHARDED_CACHE: dict = {}


def solve_fleet(stacked: PackedDD, iters: int = 60, damping: float = 1.0,
                residual_history: bool = False, mesh=None,
                axis: str = "fleet", x0=None):
    """Advance every problem of a stacked cohort one solve in one dispatch.

    The per-problem sweep is ``lax.map`` over the leading problem axis —
    each problem executes the *identical op graph* as a standalone
    :func:`solve_vmapped` call, so the fleet results are **bitwise
    identical** to per-problem solves (an extra ``vmap`` axis would
    reassociate the matvec/triangular-solve reductions; ``lax.map`` does
    not).  With ``mesh=`` the problem axis is additionally sharded over
    the ``axis`` mesh axis via ``shard_map`` — one slice of the cohort
    per device, still ``lax.map`` inside, still bitwise — which is where
    the fleet throughput comes from on real multi-core/multi-device
    hardware (the cohort size must divide evenly; the serving layer pads
    cohorts with dummy slots to the mesh multiple).

    Returns the (S, n) stacked estimates, or ``(x, hist)`` with ``hist``
    of shape (S, iters) under ``residual_history=True``.

    ``x0`` (single-device path only) is an optional (S, n) stack of
    global warm starts, one per problem — see :func:`solve_vmapped`.
    """
    if mesh is None:
        if x0 is not None:
            return _solve_fleet_map_warm(
                stacked, jnp.asarray(x0, stacked.A_loc.dtype),
                iters=iters, damping=damping,
                residual_history=residual_history)
        return _solve_fleet_map(stacked, iters=iters, damping=damping,
                                residual_history=residual_history)
    if x0 is not None:
        raise NotImplementedError(
            "solve_fleet warm start is single-device only (the sharded "
            "fleet path has no x0 plumbing)")
    k = int(mesh.shape[axis])
    S = int(stacked.A_loc.shape[0])
    if S % k:
        raise ValueError(
            f"cohort size {S} does not divide over the {k}-device "
            f"'{axis}' mesh axis — pad the cohort to a multiple of {k}")
    fn = _fleet_sharded_fn(mesh, axis, iters, residual_history)
    return fn(stacked, damping)


# ---------------------------------------------------------------------------
# Production path: subdomains sharded over a mesh axis.
# ---------------------------------------------------------------------------

def solve_shardmap(packed: PackedDD, mesh, axis="sub",
                   iters: int = 60, damping: float = 1.0,
                   comm: str = "allreduce",
                   halo: "dd_mod.HaloExchange | None" = None,
                   mvec: str = "auto",
                   residual_history: bool = False,
                   return_per_device: bool = False):
    """Same iteration with one device per subdomain, on a 1D or 2D mesh.

    ``axis`` is one mesh axis name or a tuple of names — pass
    ``("row", "col")`` to run subdomain ``r * pc + c`` on device (r, c)
    of a ``pr x pc`` mesh (the paper's processor topology: grid axes map
    onto the mesh axes, so neighbour-halo traffic stays on-axis).

    Per iteration the communication is the all-reduce of the (m,)
    observation-space product — ``mvec="psum"`` as a plain psum, or
    ``mvec="scatter"`` as the bandwidth-optimal reduce-scatter +
    all-gather pair along the innermost axis; ``"auto"`` picks scatter
    in the dense-network regime (m >= ``MVEC_SCATTER_RATIO`` * n, read
    off the packed shapes) — plus the overlap-consistency exchange of
    the state estimate, with two paths:

      * ``comm="allreduce"`` — assemble the full (n,) global estimate
        with psum_scatter + all_gather along the innermost mesh axis and
        gather back.  O(n) bytes per device per iteration regardless of
        the overlap width.
      * ``comm="neighbour"`` — the paper's T^p_oh communication pattern:
        ``jax.lax.ppermute`` rounds over the precomputed edge schedule
        (``halo`` = the decomposition's cached ``halo_exchange``; one
        permute per graph-colouring class), exchanging *only the halo
        slots*.  O(s) bytes per device per iteration — proportional to
        the overlap width, not the problem size.  Multiplicity-1 columns
        never leave their device; the single full-vector assembly happens
        once, after the final iteration, to emit the global estimate.

    Both paths iterate the identical additive-Schwarz update and agree to
    reduction-order ULPs (collective associativity only).

    Observability hooks: ``residual_history=True`` switches the inner
    loop to ``lax.scan`` and returns ``(x, hist)`` with ``hist[k]`` the
    psum'd global update norm per iteration (identical on every device);
    ``return_per_device=True`` returns the full sharded (p, n) assembly
    instead of row 0, so the caller can observe per-device shard-ready
    times (``x.addressable_shards``) before collapsing to the global
    estimate — what feeds the straggler monitor's per-device rows.
    """
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    sizes = [mesh.shape[a] for a in axes]
    if int(np.prod(sizes)) != packed.p:
        raise ValueError(
            f"mesh axes {axes} have {int(np.prod(sizes))} devices but the "
            f"packing has p={packed.p} subdomains")
    if comm not in ("allreduce", "neighbour"):
        raise ValueError(f"comm must be 'allreduce' or 'neighbour' "
                         f"(got {comm!r})")
    if comm == "neighbour":
        if halo is None:
            raise ValueError(
                "comm='neighbour' needs the halo-exchange schedule: pass "
                "halo=dec.halo_exchange (cached on the Decomposition)")
        if halo.p != packed.p or halo.w != packed.w:
            raise ValueError(
                f"halo schedule shape (p={halo.p}, w={halo.w}) does not "
                f"match the packing (p={packed.p}, w={packed.w})")
    if mvec == "auto":
        mvec = ("scatter" if packed.m >= MVEC_SCATTER_RATIO * packed.n
                else "psum")
    if mvec not in ("psum", "scatter"):
        raise ValueError(f"mvec must be 'auto', 'psum' or 'scatter' "
                         f"(got {mvec!r})")
    perms = halo.perms if comm == "neighbour" else ()
    # Neighbour-path schedule arrays (sharded like the packing).  The
    # perms and round count are static Python; only the per-device
    # pack/unpack payload maps travel as operands — int32 end to end
    # (the schedule indexes w + 1 <= 2^31 slots; int64 operands would
    # silently downcast under default x32 and double the index payload).
    empty = np.zeros((packed.p, 0, 0), np.int32)
    pack_idx = jnp.asarray(halo.pack_idx if comm == "neighbour" else empty,
                           jnp.int32)
    unpack_idx = jnp.asarray(halo.unpack_idx if comm == "neighbour"
                             else empty, jnp.int32)
    fn = _shardmap_fn(mesh, axes, iters, comm, mvec, perms,
                      residual_history, return_per_device)
    out, hist = fn(packed, pack_idx, unpack_idx,
                   jnp.asarray(damping, packed.A_loc.dtype))
    if residual_history:
        return out, hist
    return out


def _shardmap_fn(mesh, axes: tuple, iters: int, comm: str, mvec: str,
                 perms: tuple, residual_history: bool,
                 return_per_device: bool):
    """Jitted shard_map of the one-subdomain-per-device sweep (cached per
    (mesh, axes, iters, comm, mvec, halo perms, residual_history,
    return_per_device) — the packing's shapes and its static meta fields
    (solve kernel, block) key jit's own trace cache, so a stream of
    same-shape cycles compiles once)."""
    key = (mesh, axes, iters, comm, mvec, perms, residual_history,
           return_per_device)
    fn = _SHARDMAP_CACHE.get(key)
    if fn is not None:
        return fn
    ppermute_axis = axes if len(axes) > 1 else axes[0]
    rounds = len(perms)
    # Innermost axis carries the scatters; pad the reduced vectors so
    # their length splits evenly (the n-vector keeps one extra slot as
    # the -1-column dump).
    ks = int(mesh.shape[axes[-1]])

    def axis_allreduce(part):
        """All-reduce a ks-divisible vector over every mesh axis: plain
        psum on the outer axes, reduce-scatter + all-gather (the
        bandwidth-optimal all-reduce on a torus) on the innermost."""
        if len(axes) > 1:
            part = jax.lax.psum(part, axes[:-1])
        chunk = jax.lax.psum_scatter(part, axes[-1], scatter_dimension=0,
                                     tiled=True)
        return jax.lax.all_gather(chunk, axes[-1], tiled=True)

    def run(packed, pack_idx, unpack_idx, damping):
        n, m, w, kern = packed.n, packed.m, packed.w, packed.solve_kernel
        n_pad = -(-(n + 1) // ks) * ks
        m_pad = -(-m // ks) * ks

        def mvec_allreduce(part):
            if mvec == "psum":
                return jax.lax.psum(part, axes)
            if m_pad > m:
                part = jnp.concatenate(
                    [part, jnp.zeros((m_pad - m,), part.dtype)])
            return axis_allreduce(part)[:m]

        def per_device(A_i, L_i, mask_i, muov_i, wdiv_i, scat_i, gath_i,
                       mloc_i, pack_i, unpack_i, r, b, mult, damping):
            # Leading axis of size 1 (= this device's subdomain).
            (A_i, L_i, mask_i, muov_i, wdiv_i, scat_i, gath_i, mloc_i,
             pack_i, unpack_i) = (A_i[0], L_i[0], mask_i[0], muov_i[0],
                                  wdiv_i[0], scat_i[0], gath_i[0],
                                  mloc_i[0], pack_i[0], unpack_i[0])

            def scatter_part(x_i):
                # scat_i parks padding on slot n (< n_pad): same dump
                # trick.
                return jnp.zeros((n_pad,), x_i.dtype).at[scat_i].add(
                    x_i * mask_i)

            def exchange_allreduce(x_i2):
                # Overlap consistency (eq. 28): multiplicity-weighted
                # average of the duplicated columns via the global
                # assembly, then gather back.
                x_glob = axis_allreduce(scatter_part(x_i2))[:n] / mult
                return x_glob[gath_i] * mask_i

            def exchange_neighbour(x_i2):
                # Same average, neighbour-only: own contribution plus the
                # halo slots received over the directed coloured rounds,
                # divided by the local multiplicity.  Each round is ONE
                # ppermute of one packed h-lane buffer — this device
                # gathers its outgoing payload at pack_idx (send partner)
                # and scatter-adds the received buffer at unpack_idx
                # (recv partner, not necessarily the same device) —
                # exactly halo.rounds permutes per iteration however many
                # edges meet here.  Slot w is the dump: it gathers zero
                # (payload padding) and absorbs scatter padding.
                xm = x_i2 * mask_i
                acc = jnp.concatenate([xm, jnp.zeros((1,), xm.dtype)])
                xm_pad = acc
                for rnd in range(rounds):
                    buf = xm_pad[pack_i[rnd]]
                    got = jax.lax.ppermute(buf, ppermute_axis,
                                           perm=perms[rnd])
                    acc = acc.at[unpack_i[rnd]].add(got)
                return acc[:w] / mloc_i

            exchange = (exchange_neighbour if comm == "neighbour"
                        else exchange_allreduce)

            def step(x_i):
                if kern == "jnp":
                    Ax = mvec_allreduce(A_i @ (x_i * wdiv_i))
                    new = _local_update(A_i, L_i, mask_i, muov_i, x_i, Ax,
                                        r, b)
                else:
                    mode = _KERNEL_OPS_MODE[kern]
                    y, u = ops_mod.schwarz_fwd(A_i[None], x_i[None],
                                               wdiv_i[None], mode=mode,
                                               block_m=packed.solve_block)
                    Ax = mvec_allreduce(y[0])
                    rhs = ops_mod.schwarz_bwd(A_i[None], r, b, Ax, u,
                                              x_i[None], muov_i[None],
                                              mask_i[None], mode=mode,
                                              block_m=packed.solve_block)[0]
                    new = _chol_solve(L_i, rhs) * mask_i
                return exchange((1.0 - damping) * x_i + damping * new)

            x_i = jnp.zeros((w,), dtype=A_i.dtype)
            if residual_history:
                # Per-iteration global update norm: local squared delta,
                # psum'd over the whole mesh — every device carries the
                # identical history (overlap slots count with
                # multiplicity, matching solve_vmapped's (p, w) Frobenius
                # norm).
                def sbody(x_prev, _):
                    nxt = step(x_prev)
                    d2 = jax.lax.psum(jnp.sum((nxt - x_prev) ** 2), axes)
                    return nxt, jnp.sqrt(d2)

                x_i, hist = jax.lax.scan(sbody, x_i, None, length=iters)
            else:
                x_i = jax.lax.fori_loop(0, iters, lambda _, x: step(x), x_i)
                hist = jnp.zeros((0,), dtype=A_i.dtype)
            # One full assembly at the end (both paths): emit the global
            # estimate.  On the neighbour path this is the only O(n)
            # collective of the whole solve.
            return ((axis_allreduce(scatter_part(x_i))[:n] / mult)[None],
                    hist[None])

        specs = P(axes if len(axes) > 1 else axes[0])
        out, hist = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(specs,) * 10 + (P(),) * 4,
            out_specs=(specs, specs), check_vma=False)(
                packed.A_loc, packed.L_loc, packed.mask, packed.muov,
                packed.wdiv, packed.scatter_cols, packed.gather_cols,
                packed.mult_loc, pack_idx, unpack_idx, packed.r, packed.b,
                packed.mult, damping)
        return (out if return_per_device else out[0]), hist[0]

    fn = jax.jit(run)
    _SHARDMAP_CACHE[key] = fn
    return fn


_SHARDMAP_CACHE: dict = {}


# ---------------------------------------------------------------------------
# Parallel-in-time path: independent *windows* x subdomains on a
# ("time", "sub") mesh.
# ---------------------------------------------------------------------------

def _window_sharded_fn(mesh, time_axis: str, sub_axis: str, iters: int,
                       n: int):
    """Jitted shard_map of the window-stacked Schwarz sweep (cached per
    (mesh, axes, iters, n) — mesh objects hash; shapes recompile under
    jit as usual)."""
    key = (mesh, time_axis, sub_axis, iters, n)
    fn = _WINDOW_SHARDED_CACHE.get(key)
    if fn is not None:
        return fn
    ks = int(mesh.shape[sub_axis])
    # The (n,)-assembly keeps one extra slot as the -1-column dump and
    # must split evenly over the sub axis for the reduce-scatter pair.
    n_pad = -(-(n + 1) // ks) * ks

    def per_device(A, L, mask, muov, wdiv, scat, gath, mult, r, b, x0,
                   damping):
        # A: (Kl, pl, m, w) — this device's window slice x subdomain
        # slice; mult: (Kl, n); r, b, x0: (Kl, ·).  Windows are
        # independent problems: every collective reduces over ``sub``
        # only.
        def scatter_part(xm):
            def one(sc, x_k):
                return jnp.zeros((n_pad,), x_k.dtype).at[
                    sc.reshape(-1)].add(x_k.reshape(-1))
            return jax.vmap(one)(scat, xm)          # (Kl, n_pad)

        def assemble_glob(x):
            part = scatter_part(x * mask)
            chunk = jax.lax.psum_scatter(part, sub_axis,
                                         scatter_dimension=1, tiled=True)
            glob = jax.lax.all_gather(chunk, sub_axis, axis=1,
                                      tiled=True)   # (Kl, n_pad)
            return glob[:, :n] / mult               # (Kl, n)

        def step(x):
            # One additive-Schwarz iteration per window, batched over
            # this device's (Kl, pl) slice — the jnp composition of
            # solve_vmapped (fused-kernel packings ride this path too;
            # the two steps agree to reduction-order ULPs).
            Ax = jax.lax.psum(
                jnp.einsum("kpmw,kpw->km", A, x * wdiv), sub_axis)
            resid = (b[:, None, :] - Ax[:, None, :]
                     + jnp.einsum("kpmw,kpw->kpm", A, x))
            rhs = (jnp.einsum("kpmw,kpm->kpw", A, r[:, None, :] * resid)
                   + muov * x) * mask
            new = jax.vmap(jax.vmap(_chol_solve))(L, rhs) * mask
            x2 = (1.0 - damping) * x + damping * new
            x_glob = assemble_glob(x2)
            return jax.vmap(lambda xg, g: xg[g])(x_glob, gath) * mask

        # Warm start: gather the (Kl, n) global x0 into the local slots
        # (an all-zero x0 gathers to exactly the historic zero start).
        x_init = jax.vmap(lambda xg, g: xg[g])(x0, gath) * mask
        x = jax.lax.fori_loop(0, iters, lambda _, v: step(v), x_init)
        # (Kl, 1, n): the sub axis carries one replicated copy out.
        return assemble_glob(x)[:, None, :]

    ws = P(time_axis, sub_axis)
    wt = P(time_axis)
    fn = jax.jit(jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(ws, ws, ws, ws, ws, ws, ws, wt, wt, wt, wt, P()),
        out_specs=ws, check_vma=False))
    _WINDOW_SHARDED_CACHE[key] = fn
    return fn


_WINDOW_SHARDED_CACHE: dict = {}


def solve_window_stack(stacked: PackedDD, mesh, time_axis: str = "time",
                       sub_axis: str = "sub", iters: int = 60,
                       damping: float = 1.0, x0=None) -> jax.Array:
    """Solve a window-stacked packing on a 2D ``("time", "sub")`` mesh.

    ``stacked`` is a :func:`stack_packed` result whose leading axis is K
    independent *windows* (one cycle's rhs-injected packing per active
    window of the Parareal fine sweep).  The window axis shards over
    ``time_axis`` and the subdomain axis over ``sub_axis`` — K * p
    problems-by-subdomains on kt * ks devices, multiplying the usable
    device count beyond the p-subdomain cap of :func:`solve_shardmap`.
    Every collective (the (m,) product psum and the overlap-consistency
    assembly's reduce-scatter + all-gather pair) runs over ``sub`` only:
    windows never communicate, which is what makes the time axis free
    parallelism.

    The iteration is the jnp additive-Schwarz composition of
    :func:`solve_vmapped` with allreduce state exchange — per-window
    results agree with standalone ``solve_vmapped`` calls to
    reduction-order ULPs (a tolerance contract; the Parareal driver's
    bitwise degeneration path never reaches this function).

    ``x0`` is an optional (K, n) stack of global warm starts, one per
    window — see :func:`solve_vmapped`.  None starts from zeros (the
    historic behaviour, bitwise).

    Returns the (K, n) per-window global estimates.
    """
    K = int(stacked.A_loc.shape[0])
    kt = int(mesh.shape[time_axis])
    ks = int(mesh.shape[sub_axis])
    if K % kt:
        raise ValueError(
            f"window count {K} does not divide over the {kt}-device "
            f"'{time_axis}' mesh axis — pad the stack to a multiple")
    if stacked.p % ks:
        raise ValueError(
            f"p={stacked.p} subdomains do not divide over the "
            f"{ks}-device '{sub_axis}' mesh axis")
    fn = _window_sharded_fn(mesh, time_axis, sub_axis, iters, stacked.n)
    dt = stacked.A_loc.dtype
    x0 = (jnp.zeros((K, stacked.n), dt) if x0 is None
          else jnp.asarray(x0, dt))
    out = fn(stacked.A_loc, stacked.L_loc, stacked.mask, stacked.muov,
             stacked.wdiv, stacked.scatter_cols, stacked.gather_cols,
             stacked.mult, stacked.r, stacked.b, x0,
             jnp.asarray(damping, dt))
    return out[:, 0]


# ---------------------------------------------------------------------------
# Convenience driver: DyDD + DD-KF end to end on a 1D domain.
# ---------------------------------------------------------------------------

def ddkf_with_dydd(prob: cls_mod.CLSProblem, obs_locations: np.ndarray,
                   p: int, overlap: int = 0, iters: int = 60,
                   mu: float = 1.0):
    """Balance observations with DyDD, decompose, and solve with DD-KF.

    Returns (x_ddkf, dydd_result, decomposition).
    """
    from repro.core import dydd as dydd_mod

    res = dydd_mod.dydd_1d(obs_locations, p)
    dec = dd_mod.decompose_1d(prob.n, res.boundaries, overlap=overlap)
    packed = pack(prob, dec, mu=mu)
    x = solve_vmapped(packed, iters=iters)
    return x, res, dec
