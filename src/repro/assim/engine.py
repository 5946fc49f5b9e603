"""Streaming multi-cycle DD-KF assimilation engine with online DyDD.

The engine consumes an observation stream cycle by cycle and, per cycle:

  1. counts the incoming observations against the *current* subdomain
     boundaries of its :class:`~repro.core.domain.Domain` and decides —
     threshold + hysteresis, see :class:`EngineConfig` — whether to fire a
     DyDD repartition (DD-step for empty subdomains, Hu–Blake–Emerson
     diffusion scheduling on the domain's processor graph, geometric
     boundary migration — ``dydd_1d`` on an :class:`Interval1D`,
     ``dydd_2d``'s per-axis passes on a :class:`ShelfTiling2D`);
  2. decomposes the state index set on the (possibly moved) boundaries and
     packs the local operator blocks — the device-side gather of the
     padded blocks from ``A = [H0; H1]`` plus the batched normal-matrix
     inverse build (``ddkf.pack_operator``, ``kernels.ops.gram``);
  3. injects the cycle's right-hand side (background carried forward from
     the previous analysis + fresh observation data) and runs the sharded
     DD-KF solve (``ddkf.solve_vmapped`` / ``solve_shardmap``);
  4. journals loads, imbalance, migration volume and timings
     (:mod:`repro.assim.metrics`).

Pipelining: with ``double_buffer=True`` step 1+2 for cycle t+1 run on a
host worker thread while the device solves cycle t.  This is sound
because the rebalance decision and the operator packing depend only on
the observation stream and the boundary state — never on a solve result;
only the rhs (step 3) consumes the carried analysis, and it is injected
on the main thread via a cheap ``dataclasses.replace``.
"""
from __future__ import annotations

import copy
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Iterable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import cls as cls_mod
from repro.core import dd as dd_mod
from repro.core import ddkf as ddkf_mod
from repro.core import domain as domain_mod
from repro.core import dydd as dydd_mod
from repro.core import kdtree as kdtree_mod
from repro.checkpoint import manager as ckpt_mod
from repro.kernels import ops as ops_mod
from repro.obs import meters as meters_mod
from repro.obs import trace as trace_mod
from repro.runtime import chaos as chaos_mod
from repro.runtime.straggler import StragglerConfig, StragglerMonitor
from repro.assim import streams as streams_mod
from repro.assim.metrics import CycleMetrics, Journal, imbalance_ratio


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Streaming DD-KF engine configuration.

    Domain selection: ``ndim=1`` (default) runs on an
    :class:`~repro.core.domain.Interval1D` with ``p`` subdomains over an
    ``n``-point mesh; ``ndim=2`` runs on a
    :class:`~repro.core.domain.ShelfTiling2D` of ``pr x pc`` cells over an
    ``nx x ny`` raster mesh (``nx``/``ny`` default to the most-square
    factoring of ``n``).  An explicit ``domain=`` handed to the engine
    overrides all of these.

    Solver selection: ``solver="vmapped"`` (default) batches subdomains on
    a leading axis of one device; ``solver="shardmap"`` runs one device
    per subdomain on a mesh shaped like the domain's processor graph —
    a (p,) chain in 1D, a (pr, pc) grid in 2D.  The engine builds the
    mesh itself from the first p visible devices when at least p are
    visible (e.g. under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``), or accepts
    an explicit ``mesh=``; too few devices, or a mesh of another size,
    is rejected up front.  On the mesh each device packs its own
    subdomain's block (``ddkf.pack_operator(mesh=)``).
    ``overlap`` (>= 0, validated here for every domain) is the Schwarz
    halo width in mesh columns/rows absorbed from each grid-graph
    neighbour, with ``mu`` the overlap regularization of eq. 25-26.

    Rebalance trigger policy: a repartition fires at the start of a cycle
    when EITHER (a) some subdomain would receive zero observations (the
    DD-step must split a neighbour — never deferred), or (b) the max/mean
    load ratio against the incoming boundaries has exceeded
    ``imbalance_threshold`` for ``hysteresis`` consecutive cycles.  The
    hysteresis keeps a near-balanced network from thrashing boundaries
    (and recompiling nothing, but re-inverting p local normal matrices)
    every cycle on noise.
    """

    n: int = 256                      # state dimension
    p: int = 4                        # subdomains (= processors), 1D and
                                      # kdtree (leaf count)
    ndim: int = 1                     # 1 = Interval1D, 2 = ShelfTiling2D
    domain_kind: Optional[str] = None  # "interval" | "shelf" | "kdtree";
                                      # None derives from ndim (1 ->
                                      # interval, 2 -> shelf).  "kdtree"
                                      # is a 2D adaptive k-d tree of p
                                      # leaves over the nx x ny mesh
                                      # (anisotropic networks)
    pr: int = 2                       # 2D: strip count
    pc: int = 2                       # 2D: cells per strip
    nx: Optional[int] = None          # 2D: mesh width (default: factor n)
    ny: Optional[int] = None          # 2D: mesh height
    overlap: int = 0                  # shared columns between neighbours
    mu: float = 1.0                   # overlap regularization
    iters: int = 120                  # DD-KF Schwarz iterations per cycle
    damping: float = 1.0              # additive-Schwarz under-relaxation
    rebalance: bool = True            # online DyDD on/off (off = static DD)
    imbalance_threshold: float = 1.5  # max/mean ratio that arms the trigger
    hysteresis: int = 1               # consecutive over-threshold cycles
    double_buffer: bool = True        # overlap t+1 packing with t's solve
    track_reference: bool = False     # per-cycle ||x - one_shot|| (O(n^3))
    seed: int = 0                     # truth trajectory + data noise
    smooth: float = 0.25              # H0 second-difference weight
    obs_noise: float = 1e-3           # observation data noise
    truth_drift: float = 0.05         # per-cycle truth random-walk scale
    solver: str = "vmapped"           # "vmapped" | "shardmap"
    comm: str = "allreduce"           # sharded overlap exchange:
                                      # "allreduce" (full n-vector) |
                                      # "neighbour" (halo-only ppermute)
    halo_weight: float = 0.0          # overlap-aware DyDD: work units per
                                      # halo column added to the loads the
                                      # diffusion schedule balances (0 =
                                      # unweighted, the historic policy)
    record_residuals: bool = False    # journal the per-iteration Schwarz
                                      # update-norm history (switches the
                                      # inner loop to lax.scan; identical
                                      # numerics, one extra (iters,)
                                      # output per solve)
    solver_kernel: str = "auto"       # what runs the one Schwarz sweep's
                                      # two passes: "jnp" (the jnp
                                      # reference) | "fused" (Pallas on
                                      # TPU, the reference elsewhere) |
                                      # "fused_interpret" (Pallas in
                                      # interpret mode) | "auto" ("fused"
                                      # on TPU, "jnp" elsewhere)
    solve_retries: int = 2            # bounded retry on a TransientFault
                                      # from prepare/solve (exponential
                                      # backoff); exceeding it is fatal.
                                      # Retries are bitwise-safe: faults
                                      # fire before any state mutation
    time_windows: int = 1             # parallel-in-time (Parareal) window
                                      # count for repro.assim.timepar;
                                      # 1 = the sequential cycle loop
                                      # (bitwise-identical degeneration)
    pint_tol: float = 1e-8            # Parareal convergence tolerance on
                                      # the max window-boundary
                                      # correction (max-abs norm)
    pint_max_iters: int = 8           # Parareal iteration cap; 0 forces
                                      # the sequential engine (bitwise
                                      # degeneration, like time_windows=1)
    pint_coarse_iters: int = 0        # Schwarz iterations of the coarse
                                      # propagator; 0 = max(1, iters//10)
    pint_fine_iters: int = 0          # Schwarz iterations of the fine
                                      # sweeps; 0 = iters (cold-start
                                      # equivalent).  When set, fine
                                      # solves warm-start from the coarse
                                      # trajectory, so the combined
                                      # coarse+fine iteration count is
                                      # what buys the accuracy — the
                                      # work-optimal Parareal variant


def _resolve_mesh_shape(cfg: EngineConfig) -> tuple:
    """(nx, ny) of the 2D raster mesh from the config (factor n if only
    one or neither axis is given)."""
    nx, ny = cfg.nx, cfg.ny
    if nx is None and ny is None:
        return domain_mod.factor_mesh(cfg.n)
    if nx is None or ny is None:
        # One axis given: the other must complete cfg.n exactly.
        given = nx if nx is not None else ny
        if given < 1 or cfg.n % given:
            raise ValueError(
                f"mesh axis {given} does not divide n={cfg.n}; give "
                f"both nx and ny or a divisor of n")
        return (given, cfg.n // given) if nx is not None \
            else (cfg.n // given, given)
    return nx, ny


def _domain_from_config(cfg: EngineConfig) -> domain_mod.Domain:
    if cfg.ndim not in (1, 2):
        raise ValueError(f"ndim must be 1 or 2 (got {cfg.ndim})")
    kind = cfg.domain_kind
    if kind is None:
        kind = "interval" if cfg.ndim == 1 else "shelf"
    if kind == "interval":
        return domain_mod.Interval1D(n=cfg.n, p=cfg.p)
    if kind == "shelf":
        nx, ny = _resolve_mesh_shape(cfg)
        return domain_mod.ShelfTiling2D(nx=nx, ny=ny, pr=cfg.pr, pc=cfg.pc)
    if kind == "kdtree":
        nx, ny = _resolve_mesh_shape(cfg)
        return kdtree_mod.KDTreeDomain(nx=nx, ny=ny, p=cfg.p)
    raise ValueError(f"domain_kind must be 'interval', 'shelf' or "
                     f"'kdtree' (got {cfg.domain_kind!r})")


def _placed_bytes(x: jax.Array) -> int:
    """Bytes of ``x`` held on its devices, summed over every copy."""
    return sum(int(s.data.nbytes) for s in x.addressable_shards)


# Checkpoint-tree key prefix for the domain's boundary-state arrays.
_DOMAIN_PREFIX = "domain/"


@dataclasses.dataclass
class _Prepared:
    """Host-side work for one cycle, computable before cycle t-1 finishes."""

    cycle: int
    obs: np.ndarray
    packed_op: "ddkf_mod.PackedDD"
    H0: np.ndarray
    H1: np.ndarray
    y1: np.ndarray                # observation data (truth-driven)
    loads: np.ndarray             # post-repartition per-subdomain counts
    loads_before: np.ndarray      # counts against the incoming boundaries
    loads_weighted: np.ndarray    # loads + halo-cost offsets (the
                                  # overlap-aware schedule's view)
    imbalance_before: float
    repartitioned: bool
    migrated: int
    rounds: int
    pack_time: float
    halo: "dd_mod.HaloExchange | None"  # neighbour-exchange schedule of
                                        # the cycle's decomposition
    comm_bytes_per_cycle: float
    halo_fraction: float
    rebalance_suppressed: bool = False  # trigger armed but suppressed
                                        # (previous rebalance already
                                        # left these exact loads)
    phases: trace_mod.Phases = dataclasses.field(
        default_factory=trace_mod.Phases)
                                        # host-phase durations (count/
                                        # dydd/halo/pack/pack.*/data) and
                                        # the compiles each caused;
                                        # _solve adds solve.*,
                                        # complete_cycle solve
    comm_edge_bytes_per_cycle: dict = dataclasses.field(
        default_factory=dict)           # "i-j" -> per-cycle endpoint
                                        # bytes (neighbour-path pricing
                                        # of the halo geometry)
    comm_mvec_bytes_per_cycle: float = 0.0
    comm_mvec_axis_bytes_per_cycle: dict = dataclasses.field(
        default_factory=dict)           # mesh-axis name -> per-cycle
                                        # m-vector all-reduce bytes (torus
                                        # pricing: outer axes full-vector)
    window: int = -1                    # time-window id (parallel-in-time
                                        # runs); -1 on sequential cycles
    placed_bytes: int = 0               # bytes of H1 (and, on the first
                                        # prepare, H0) copied host ->
                                        # devices, over every device


@dataclasses.dataclass
class CycleStep:
    """One cycle of the engine's per-cycle state machine.

    ``run`` (and external drivers: the fleet runner, the Parareal
    window engine) advance a step through the three stages —
    :meth:`AssimilationEngine.prepare` fills ``prep``,
    :meth:`AssimilationEngine.solve_step` fills the solve outputs,
    :meth:`AssimilationEngine.finish_step` journals it — making the
    cycle lifecycle a first-class record instead of loop-local state.
    ``window`` tags which time window the cycle belongs to (-1 =
    sequential run) and rides through to the journal.
    """

    cycle: int
    obs: np.ndarray
    window: int = -1
    prep: Optional[_Prepared] = None
    analysis: Optional[jax.Array] = None
    background: Optional[np.ndarray] = None
    solve_time: float = 0.0
    hist: object = None
    device_times: list = dataclasses.field(default_factory=list)


class AssimilationEngine:
    """Multi-cycle DD-KF with online DyDD rebalancing on a Domain.

    Usage::

        cfg = EngineConfig(n=128, p=4, rebalance=True)
        eng = AssimilationEngine(cfg)
        journal = eng.run(streams.make_stream("drifting_swarm", 400, 6))

        cfg2d = EngineConfig(ndim=2, nx=16, ny=8, pr=2, pc=2)
        journal = AssimilationEngine(cfg2d).run_scenario(
            "rotating_swarm", m=400, cycles=6)

    The analysis of cycle t is carried as the background of cycle t+1
    (persistence forecast by default; pass ``forecast`` to override).
    ``eng.analysis`` holds the latest analysis state.
    """

    def __init__(self, config: EngineConfig,
                 forecast: Optional[Callable] = None,
                 mesh=None, mesh_axis=None,
                 domain: Optional[domain_mod.Domain] = None,
                 straggler_config: Optional[StragglerConfig] = None,
                 chaos: "chaos_mod.ChaosInjector | None" = None):
        self.cfg = config
        self.forecast = forecast or (lambda x: x)
        if config.solver not in ("vmapped", "shardmap"):
            raise ValueError(f"unknown solver {config.solver!r}")
        if config.comm not in ("allreduce", "neighbour"):
            raise ValueError(f"comm must be 'allreduce' or 'neighbour' "
                             f"(got {config.comm!r})")
        if config.solver_kernel not in ddkf_mod.SOLVER_KERNELS:
            raise ValueError(
                f"solver_kernel must be one of {ddkf_mod.SOLVER_KERNELS} "
                f"(got {config.solver_kernel!r})")
        if config.halo_weight < 0:
            raise ValueError(f"halo_weight is a per-halo-column work cost "
                             f"and must be >= 0 (got {config.halo_weight})")
        if config.overlap < 0:
            raise ValueError(
                f"overlap is a halo width and must be >= 0 "
                f"(got {config.overlap})")
        if config.hysteresis < 1:
            raise ValueError(
                f"hysteresis must be >= 1 (got {config.hysteresis}); "
                f"1 means fire as soon as the threshold is crossed")
        if config.imbalance_threshold < 1.0:
            raise ValueError(
                f"imbalance_threshold is a max/mean ratio and must be "
                f">= 1.0 (got {config.imbalance_threshold})")
        if config.time_windows < 1:
            raise ValueError(
                f"time_windows must be >= 1 (got {config.time_windows})")
        if (config.pint_max_iters < 0 or config.pint_coarse_iters < 0
                or config.pint_fine_iters < 0):
            raise ValueError(
                f"pint_max_iters/pint_coarse_iters/pint_fine_iters must "
                f"be >= 0 (got {config.pint_max_iters}/"
                f"{config.pint_coarse_iters}/{config.pint_fine_iters})")
        if config.pint_tol <= 0:
            raise ValueError(
                f"pint_tol must be > 0 (got {config.pint_tol})")

        self.domain = domain if domain is not None \
            else _domain_from_config(config)
        self.n = self.domain.n
        self.p = self.domain.p
        self.mesh, self.mesh_axis = self._resolve_mesh(mesh, mesh_axis)
        self.journal = Journal(meta=self.domain.describe())
        self.analysis: Optional[jax.Array] = None
        self._H0 = cls_mod.state_operator(self.n, smooth=config.smooth)
        self._H0_dev: Optional[jax.Array] = None  # see _H0_device
        self._rng = np.random.default_rng(config.seed)
        self._truth = self._rng.normal(size=self.n)
        self._streak = 0  # consecutive over-threshold cycles
        self._last_rebalance_loads: Optional[np.ndarray] = None
        self._suppressed = False  # this cycle's trigger was suppressed
        self._dec_cache: Optional[dd_mod.Decomposition] = None
        self._t_last = time.perf_counter()
        # One EWMA-deadline straggler monitor per subdomain device; the
        # shardmap path feeds each its shard-ready time, the vmapped path
        # feeds monitor 0 the whole-solve time (one logical device).
        self._stragglers = [StragglerMonitor(straggler_config)
                            for _ in range(self.p)]
        self._straggler_config = straggler_config
        self._chaos = chaos
        # The stream being consumed, when it exposes a serializable
        # cursor (streams.ResumableStream) — what snapshot() records so
        # resume can fast-forward the seeded generator.
        self._stream = None
        self._restored_cursor: Optional[dict] = None
        # Optional per-cycle analysis hook: called as
        # ``on_analysis(cycle, x)`` from complete_cycle right after the
        # analysis is published — how parity tests and the Parareal
        # gate capture the sequential analysis chain without journalling
        # (n,) vectors.
        self.on_analysis: Optional[Callable] = None

    # -- mesh resolution for the sharded solver ----------------------------

    def _resolve_mesh(self, mesh, mesh_axis):
        """Validate or build the device mesh for ``solver='shardmap'``.

        The solver needs one device per subdomain, laid out as the
        domain's processor graph (``domain.mesh_axes()``: a (p,) chain in
        1D, a (pr, pc) grid in 2D), built from the first p visible
        devices.  Fewer devices than p, or a given mesh of another size,
        is rejected here, up front, with the fix spelled out — downstream
        it would only surface as an opaque shard_map shape error.
        """
        if self.cfg.solver != "shardmap":
            return mesh, mesh_axis
        names, shape = self.domain.mesh_axes()
        if mesh is None:
            devices = jax.devices()
            n_dev = len(devices)
            if n_dev < self.p:
                raise ValueError(
                    f"solver='shardmap' requires a mesh with one device "
                    f"per subdomain: p={self.p} but {n_dev} JAX device(s) "
                    f"are visible.  Pass mesh= explicitly, or set "
                    f"XLA_FLAGS=--xla_force_host_platform_device_count="
                    f"{self.p} to fan a host platform out, or match the "
                    f"config's p/pr*pc to the hardware")
            mesh = jax.make_mesh(
                tuple(shape), tuple(names),
                axis_types=(jax.sharding.AxisType.Auto,) * len(shape),
                devices=devices[:self.p])
            return mesh, (names if len(names) > 1 else names[0])
        n_mesh = int(np.prod(list(mesh.shape.values())))
        if n_mesh != self.p:
            raise ValueError(
                f"solver='shardmap' requires a mesh with one device per "
                f"subdomain: p={self.p} but the given mesh has {n_mesh} "
                f"device(s) (shape {dict(mesh.shape)}).  Rebuild the mesh "
                f"to match, or change p/pr/pc")
        if mesh_axis is None:
            axes = tuple(mesh.shape.keys())
            mesh_axis = axes if len(axes) > 1 else axes[0]
        return mesh, mesh_axis

    @property
    def boundaries(self):
        """1D compatibility view of the domain's interval edges."""
        return getattr(self.domain, "boundaries", None)

    # -- rebalance trigger policy ------------------------------------------

    def _should_rebalance(self, loads: np.ndarray) -> bool:
        self._suppressed = False
        if not self.cfg.rebalance:
            self._streak = 0
            return False
        fire = False
        if (loads == 0).any():
            # Empty subdomain: the DD step cannot wait out the hysteresis.
            self._streak = 0
            fire = True
        else:
            if imbalance_ratio(loads) > self.cfg.imbalance_threshold:
                self._streak += 1
            else:
                self._streak = 0
            if self._streak >= self.cfg.hysteresis:
                self._streak = 0
                fire = True
        if fire and self._last_rebalance_loads is not None \
                and np.array_equal(loads, self._last_rebalance_loads):
            # The last rebalance already left exactly these loads:
            # re-firing would schedule the same targets again, so a
            # genuinely unpopulatable subdomain (e.g. fewer observations
            # than subdomains) would otherwise re-trigger the empty-DD
            # step every cycle — suppress, and journal the suppression.
            # On Interval1D this is exact (migration realizes targets
            # from loads alone); on position-dependent domains (kdtree
            # median cuts) a stream whose positions moved while the
            # count vector stayed identical keeps the previous cuts one
            # extra cycle — the deliberate trade against trigger thrash
            # (any count change lifts the suppression).
            self._suppressed = True
            return False
        return fire

    # -- host-side cycle preparation (runs on the worker thread) -----------

    def _current_dec(self) -> dd_mod.Decomposition:
        """The decomposition of the *current* boundaries, cached across
        cycles and invalidated only by a rebalance (the engine is the
        sole mutator of its domain's boundary state).  Reusing one
        Decomposition object is what lets its ``cached_property`` halo
        schedule actually hit — the O(n·mult²) edge discovery and the
        colouring/slot-map build would otherwise re-run every cycle and
        be charged to ``pack_time``."""
        if self._dec_cache is None:
            self._dec_cache = self.domain.decomposition(
                overlap=self.cfg.overlap)
        return self._dec_cache

    def _halo_offsets(self) -> np.ndarray | None:
        """Per-subdomain halo-cost offsets for the overlap-aware DyDD
        weighting, from the *current* boundaries (the decomposition the
        rebalance decision is looking at) — None when the weighting is
        off or there is no overlap to weigh."""
        if self.cfg.halo_weight <= 0 or self.cfg.overlap <= 0:
            return None
        return self.cfg.halo_weight * self._current_dec().halo_sizes

    @property
    def _pack_mesh(self):
        """The mesh the packing is laid out on: the sharded solver's,
        None for the single-device solver."""
        return self.mesh if self.cfg.solver == "shardmap" else None

    def _to_devices(self, x: np.ndarray) -> jax.Array:
        """A host array copied to the device(s) the packing runs on, in
        the device's dtype: whole on every device of the mesh, or to
        the default device."""
        mesh = self._pack_mesh
        if mesh is None:
            return jnp.asarray(x)
        return jax.device_put(x, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec()))

    def _H0_device(self) -> jax.Array:
        """H0 on the device(s), in the device's dtype: copied once, on the
        first prepare, since H0 never changes."""
        if self._H0_dev is None:
            self._H0_dev = self._to_devices(self._H0)
        return self._H0_dev

    def prepare(self, cycle: int, obs: np.ndarray,
                window: int = -1) -> _Prepared:
        """Host-side work for one cycle: DyDD decision, repartition,
        operator packing, observation data.  Depends only on the stream
        and boundary state — never on a solve result — so it may run on
        a worker thread while the device solves an earlier cycle (or,
        for the parallel-in-time engine, for *every* cycle of the stream
        up front: the mutation chain is identical to the sequential
        sweep's, whatever backgrounds later flow into the solves).  The
        engine mutates its domain/truth/rng state here, so at most one
        ``prepare`` per engine may be in flight at a time (the serving
        layer's packing pool enforces this per stream).  ``window`` tags
        the resulting cycle record with a time-window id."""
        with trace_mod.span("prepare", cycle=cycle):
            return self._prepare(cycle, obs, window)

    def _prepare(self, cycle: int, obs: np.ndarray,
                 window: int) -> _Prepared:
        # Fault injection sits BEFORE any state mutation: a retried
        # prepare after a TransientFault starts from identical rng/
        # domain/truth state, so the retry is bitwise-equivalent to an
        # uninjected run.
        if self._chaos is not None:
            self._chaos.check("pack", cycle)
        t0 = time.perf_counter()
        cfg = self.cfg
        obs = np.asarray(obs, dtype=np.float64)
        phases = trace_mod.Phases()

        with trace_mod.phase(phases, "count", cycle=cycle):
            loads_in = self.domain.counts(obs)
            imb_before = imbalance_ratio(loads_in)
            fire = self._should_rebalance(loads_in)
        repartitioned, migrated, rounds = False, 0, 0
        if fire:
            with trace_mod.phase(phases, "dydd", cycle=cycle):
                info = self.domain.rebalance(
                    obs, cost_offsets=self._halo_offsets())
            repartitioned = True
            migrated = info.migrated
            rounds = info.rounds
            self._dec_cache = None   # boundaries moved
        suppressed = self._suppressed
        loads = self.domain.counts(obs)
        if repartitioned:
            self._last_rebalance_loads = np.asarray(loads).copy()

        with trace_mod.phase(phases, "halo", cycle=cycle):
            dec = self._current_dec()
            # Weighted loads: what the overlap-aware schedule balances
            # (the plain counts when halo_weight is 0).
            loads_weighted = loads + np.rint(
                cfg.halo_weight * dec.halo_sizes).astype(np.int64)
            # Neighbour-exchange schedule (cached on the Decomposition;
            # empty edge set when there is no overlap) — the comm model
            # prices the neighbour path even when the solve runs
            # allreduce/vmapped.
            halo = dec.halo_exchange
        with trace_mod.phase(phases, "pack", cycle=cycle, p=self.p):
            with trace_mod.phase(phases, "pack.h1"):
                H1 = cls_mod.observation_operator(
                    self.n, self.domain.obs_positions(obs),
                    block=self.domain.row_size)
            # H1, the only part of A that changes from cycle to cycle,
            # crosses to the device one way, in the device's dtype
            # (float32 unless x64 is on): on a mesh to every device, so
            # that each packs its own block.  H0 went with the first.
            step = "pack.roundtrip" if self._pack_mesh is None \
                else "pack.place"
            with trace_mod.phase(phases, step) as ph:
                placed = 0 if self._H0_dev is not None else _placed_bytes(
                    self._H0_device())
                H1_dev = ph.fence(self._to_devices(H1))
                placed += _placed_bytes(H1_dev)
            with trace_mod.phase(phases, "pack.concat") as ph:
                A = ph.fence(jnp.concatenate([self._H0_device(), H1_dev]))
                r = np.ones((A.shape[0],))
            # pack_operator gathers the local blocks from the device A
            # and times pack.fill, pack.h2d and pack.factor, which blocks
            # on the batched factor build (still on the worker thread
            # under double buffering) so pack_time is honest.
            packed_op = ddkf_mod.pack_operator(
                A, r, dec, mu=cfg.mu, solver_kernel=cfg.solver_kernel,
                phases=phases, mesh=self._pack_mesh, axis=self.mesh_axis)

        with trace_mod.phase(phases, "data", cycle=cycle):
            # Truth-driven observation data: the truth random-walks each
            # cycle (deterministic under cfg.seed, independent of any
            # solve result — which is what makes this whole method
            # pipelineable).
            self._truth = ((1.0 - cfg.truth_drift) * self._truth
                           + cfg.truth_drift * self._rng.normal(
                               size=self.n))
            y1 = H1 @ self._truth + cfg.obs_noise * self._rng.normal(
                size=H1.shape[0])

        # Modelled per-cycle communication volume for the configured
        # state-exchange path (with no overlap the neighbour path moves
        # no state bytes at all — only the m-vector all-reduce remains).
        axis_names, axis_shape = self.domain.mesh_axes()
        stats = packed_op.comm_stats(halo=halo, comm=cfg.comm,
                                     mesh_shape=axis_shape)
        comm_bytes = stats["bytes_per_iter_total"] * cfg.iters
        # Per-edge bytes are always the neighbour-path pricing (the
        # allreduce path has no per-edge structure to report) — like
        # comm_bytes on a vmapped run, a model of what the halo geometry
        # would move, journalled for every comm config.
        edge_bytes = {k: float(v) * cfg.iters
                      for k, v in packed_op.edge_send_bytes(halo).items()}
        mvec_bytes = (stats["mvec_bytes_per_device"] * self.p * cfg.iters)
        # Per-torus-axis m-vector all-reduce split (outer axes pay plain
        # full-vector psum hops; only the innermost rides the
        # reduce-scatter pricing) — journalled so roofline --solve can
        # attribute the collective term by mesh axis.
        mvec_axis_bytes = {
            name: float(v) * self.p * cfg.iters
            for name, v in zip(axis_names,
                               stats["mvec_bytes_per_device_per_axis"])}

        return _Prepared(cycle=cycle, obs=obs, packed_op=packed_op,
                         H0=self._H0, H1=H1, y1=y1, loads=loads,
                         loads_before=loads_in,
                         loads_weighted=loads_weighted,
                         imbalance_before=imb_before,
                         repartitioned=repartitioned, migrated=migrated,
                         rounds=rounds,
                         pack_time=time.perf_counter() - t0,
                         halo=halo,
                         comm_bytes_per_cycle=float(comm_bytes),
                         halo_fraction=dec.halo_fraction,
                         rebalance_suppressed=suppressed,
                         phases=phases,
                         comm_edge_bytes_per_cycle=edge_bytes,
                         comm_mvec_bytes_per_cycle=float(mvec_bytes),
                         comm_mvec_axis_bytes_per_cycle=mvec_axis_bytes,
                         window=window, placed_bytes=placed)

    # -- device-side solve (main thread) -----------------------------------

    def solve_input(self, prep: _Prepared):
        """(rhs-injected packing, background) for a prepared cycle.

        This is the only step that consumes the carried analysis, so it
        must run *after* the previous cycle's :meth:`complete_cycle` (the
        fleet runner calls it on the main thread just before batching the
        cohort; ``run`` reaches it through :meth:`_solve`)."""
        background = (np.zeros(self.n) if self.analysis is None
                      else np.asarray(self.forecast(self.analysis)))
        y0 = prep.H0 @ background
        packed = ddkf_mod.with_rhs(prep.packed_op,
                                   np.concatenate([y0, prep.y1]))
        return packed, background

    def _solve(self, prep: _Prepared):
        """Returns (analysis, background, residual_hist, device_times).

        ``residual_hist`` is the per-iteration Schwarz update-norm array
        (None unless ``record_residuals``); ``device_times`` is the
        per-device time-to-shard-ready since dispatch on the shardmap
        path (empty for vmapped — the caller substitutes the whole-solve
        time for the single logical device).  Shard-ready times are
        observed by blocking the addressable shards in subdomain order,
        so device i's figure is an upper bound that includes any wait on
        devices 0..i-1 the host blocked on first — ordering-biased, but
        a genuine per-device completion signal on a forced-multi-device
        host platform, and exactly what the straggler monitor needs
        (a straggler's shard-ready time is late under any ordering).
        """
        cfg = self.cfg
        # The solve mutates no engine state until complete_cycle, so a
        # fault raised here leaves the cycle cleanly retryable.
        if self._chaos is not None:
            self._chaos.check("solve", prep.cycle)
        with trace_mod.phase(prep.phases, "solve.input", cycle=prep.cycle):
            packed, background = self.solve_input(prep)
        hist = None
        device_times: list = []
        with trace_mod.phase(prep.phases, "solve.device", cycle=prep.cycle,
                             solver=cfg.solver) as ph:
            t0 = time.perf_counter()
            if cfg.solver == "shardmap":
                out = ddkf_mod.solve_shardmap(
                    packed, self.mesh, axis=self.mesh_axis,
                    iters=cfg.iters, damping=cfg.damping,
                    comm=cfg.comm, halo=prep.halo,
                    residual_history=cfg.record_residuals,
                    return_per_device=True)
                x_pd = out[0] if cfg.record_residuals else out
                if cfg.record_residuals:
                    hist = out[1]
                shards = sorted(x_pd.addressable_shards,
                                key=lambda s: s.index[0].start or 0)
                for sh in shards:
                    sh.data.block_until_ready()
                    dt = time.perf_counter() - t0
                    device_times.append(dt)
                    trace_mod.emit(
                        "solve", t0, dt,
                        track=f"device {sh.index[0].start or 0}",
                        cycle=prep.cycle)
                # Row 0 as device 0 holds it: indexing the sharded x_pd
                # would send the index to every device.
                x = shards[0].data[0]
            else:
                out = ddkf_mod.solve_vmapped(
                    packed, iters=cfg.iters, damping=cfg.damping,
                    residual_history=cfg.record_residuals)
                x = out[0] if cfg.record_residuals else out
                if cfg.record_residuals:
                    hist = out[1]
            ph.fence(x)
        return x, background, hist, device_times

    def _reference_error(self, prep: _Prepared, background: np.ndarray,
                         x: jax.Array) -> float:
        """||x_engine - x_one_shot|| for the cycle's CLS problem."""
        dtype = prep.packed_op.A_loc.dtype
        prob = cls_mod.CLSProblem(
            H0=jnp.asarray(prep.H0, dtype),
            y0=jnp.asarray(prep.H0 @ background, dtype),
            H1=jnp.asarray(prep.H1, dtype),
            y1=jnp.asarray(prep.y1, dtype),
            R0=jnp.ones((prep.H0.shape[0],), dtype),
            R1=jnp.ones((prep.H1.shape[0],), dtype))
        return float(jnp.linalg.norm(x - cls_mod.solve(prob)))

    # -- driver -------------------------------------------------------------

    def run(self, stream: Iterable[np.ndarray], *,
            checkpoint_dir: str | None = None,
            snapshot_every: int = 0) -> Journal:
        """Consume the stream to exhaustion; returns the journal.

        Resume-aware: cycle numbering continues from the journal (a
        restored engine picks up at ``len(journal)``), and when the
        stream exposes a ``cursor`` (:class:`streams.ResumableStream`)
        it is recorded for :meth:`snapshot`.  With ``checkpoint_dir``
        and ``snapshot_every=k``, an atomic engine checkpoint is saved
        every k completed cycles — on those cycles the next cycle's
        prepare (which mutates rng/domain/truth state) is *deferred*
        until the snapshot is taken, so the saved state is exactly the
        cycle boundary and resume is bitwise journal-continuing.
        """
        cfg = self.cfg
        self._stream = stream if hasattr(stream, "cursor") else None
        it = iter(stream)
        base = len(self.journal.records)
        self._t_last = time.perf_counter()

        def snap_due(cycle: int) -> bool:
            return (checkpoint_dir is not None and snapshot_every > 0
                    and (cycle + 1) % snapshot_every == 0)

        def finish(step: "CycleStep") -> None:
            self.finish_step(self.solve_step(step))
            if snap_due(step.cycle):
                self.save_checkpoint(checkpoint_dir, step=step.cycle + 1)
            if self._chaos is not None:
                # After the snapshot: a kill at cycle c resumes from a
                # checkpoint no newer than c+1, never a torn mid-cycle.
                self._chaos.maybe_kill("cycle_end", step.cycle)

        if not cfg.double_buffer:
            for i, obs in enumerate(it):
                step = CycleStep(cycle=base + i, obs=obs)
                step.prep = chaos_mod.retry_transient(
                    lambda: self.prepare(step.cycle, step.obs),
                    retries=max(cfg.solve_retries, 0),
                    site="pack", cycle=step.cycle)
                finish(step)
            return self.journal

        # Double-buffered: prepare cycle t+1 on the worker while the main
        # thread solves cycle t.  _prepare mutates boundary/truth state, so
        # exactly one prepare is in flight at a time (single worker, next
        # submit only after the previous result is claimed).
        # thread_name_prefix names the worker's trace track: packing
        # spans land on a "pack_0" row next to the main solve thread.
        with ThreadPoolExecutor(max_workers=1,
                                thread_name_prefix="pack") as pool:
            try:
                first = next(it)
            except StopIteration:
                return self.journal
            step = CycleStep(cycle=base, obs=first)
            fut = pool.submit(self.prepare, step.cycle, step.obs)
            cycle = base
            while fut is not None:
                step.prep = self._claim_prepare(fut, pool, step.cycle,
                                                step.obs)
                cur = step
                cycle += 1
                fut = None

                def submit_next():
                    nonlocal fut, step
                    nxt = next(it, None)
                    if nxt is not None:
                        step = CycleStep(cycle=cycle, obs=nxt)
                        fut = pool.submit(self.prepare, step.cycle,
                                          step.obs)

                if snap_due(cur.cycle):
                    # Snapshot cycle: do NOT pipeline — the next prepare
                    # would mutate rng/domain/truth before the save, and
                    # the checkpoint would no longer be a cycle boundary.
                    finish(cur)
                    submit_next()
                else:
                    submit_next()
                    finish(cur)
        return self.journal

    def _claim_prepare(self, fut, pool, cycle: int, obs):
        """Claim an in-flight prepare, retrying TransientFaults with
        exponential backoff by resubmitting the same (cycle, obs) — safe
        because injected pack faults fire before any state mutation."""
        retries = max(self.cfg.solve_retries, 0)
        for attempt in range(retries + 1):
            try:
                return fut.result()
            except chaos_mod.TransientFault:
                if attempt >= retries:
                    raise
                m = meters_mod.get_meters()
                m.event("chaos.retry", site="pack", cycle=int(cycle),
                        attempt=attempt + 1)
                m.inc("chaos.retries")
                time.sleep(0.05 * (2.0 ** attempt))
                fut = pool.submit(self.prepare, cycle, obs)

    def run_scenario(self, name: str, m: int, cycles: int,
                     seed: int = 0, **kw) -> Journal:
        """Convenience: run a registered stream scenario end to end."""
        spec = streams_mod.get(name)
        if spec.ndim != self.domain.ndim:
            raise ValueError(
                f"scenario {name!r} is {spec.ndim}D but the engine domain "
                f"is {self.domain.ndim}D")
        return self.run(streams_mod.make_stream(name, m, cycles,
                                                seed=seed, **kw))

    def solve_step(self, step: CycleStep) -> CycleStep:
        """Stage 2 of the cycle state machine: drive a prepared step
        through the device solve (bounded TransientFault retries; wall
        time measured to analysis-ready).  Its journal phases
        ``solve.input`` and ``solve.device`` are written into the
        prepared cycle's ``phases``."""
        with trace_mod.span("solve", cycle=step.prep.cycle):
            t0 = time.perf_counter()
            x, background, hist, device_times = chaos_mod.retry_transient(
                lambda: self._solve(step.prep),
                retries=max(self.cfg.solve_retries, 0),
                site="solve", cycle=step.prep.cycle)
            step.analysis = jax.block_until_ready(x)
            step.solve_time = time.perf_counter() - t0
        step.background = background
        step.hist = hist
        step.device_times = device_times
        return step

    def finish_step(self, step: CycleStep) -> CycleStep:
        """Stage 3: journal the solved step and publish its analysis."""
        self.complete_cycle(step.prep, step.analysis, step.background,
                            solve_time=step.solve_time, hist=step.hist,
                            device_times=step.device_times)
        return step

    def _run_cycle(self, prep: _Prepared) -> None:
        step = CycleStep(cycle=prep.cycle, obs=prep.obs,
                         window=prep.window, prep=prep)
        self.finish_step(self.solve_step(step))

    def reset_clock(self) -> None:
        """Restart the per-cycle wall-clock reference (``cycle_time`` of
        the next completed cycle is measured from now) — what ``run``
        does at stream start, exposed for external drivers admitting an
        engine mid-flight."""
        self._t_last = time.perf_counter()

    def complete_cycle(self, prep: _Prepared, x, background,
                       solve_time: float, hist=None,
                       device_times=None) -> None:
        """Journal a solved cycle and carry its analysis forward.

        The reentrant tail of the cycle: callers that dispatch the solve
        themselves (the fleet runner batches many engines' cycles into
        one device program) hand the analysis back here with the solve
        wall time they measured; ``run`` reaches it through
        :meth:`_run_cycle`.  Must be called in cycle order per engine —
        it consumes ``prep`` and publishes ``self.analysis`` for the next
        cycle's :meth:`solve_input`."""
        device_times = list(device_times) if device_times else []
        x = jax.block_until_ready(x)
        now = time.perf_counter()
        # Measured wall time since the previous cycle completed — with
        # double buffering this is what the pipelining actually buys
        # (~max(pack, solve), not their sum).
        cycle_time = now - self._t_last
        t_cycle0 = self._t_last
        self._t_last = now
        self.analysis = x
        if self.on_analysis is not None:
            self.on_analysis(prep.cycle, x)

        # The cycle span covers the measured wall-clock by construction
        # (emitted after the fact from the same timestamps cycle_time is
        # computed from) — the acceptance coverage metric reads these.
        trace_mod.emit("cycle", t_cycle0, cycle_time, cycle=prep.cycle)

        # Straggler detection: per-device shard-ready times on the
        # shardmap path; the vmapped solve is one logical device.
        if not device_times:
            device_times = [solve_time]
        if self._chaos is not None:
            # Forced straggler: inflate the scheduled device's *reported*
            # time — the solve already happened, analyses stay bitwise.
            device_times = self._chaos.straggle(prep.cycle, device_times)
        flags = [i for i, dt in enumerate(device_times)
                 if self._stragglers[i].record(dt)]

        residual_history = ([] if hist is None
                            else [float(v) for v in np.asarray(hist)])
        phases = dict(prep.phases)
        phases["solve"] = solve_time

        m = meters_mod.get_meters()
        m.inc("engine.cycles")
        if prep.repartitioned:
            m.inc("engine.rebalance.fired")
        if prep.rebalance_suppressed:
            m.inc("engine.rebalance.suppressed")
        if prep.migrated:
            m.inc("engine.migrated", prep.migrated)
        m.inc("solve.comm_bytes_per_cycle", prep.comm_bytes_per_cycle)
        if flags:
            m.inc("engine.straggler.flags", len(flags))
            m.event("engine.straggler", cycle=prep.cycle, devices=flags,
                    device_times=[float(t) for t in device_times])

        err = (self._reference_error(prep, background, x)
               if self.cfg.track_reference else float("nan"))
        self.journal.append(CycleMetrics(
            cycle=prep.cycle,
            loads=[int(v) for v in prep.loads],
            loads_before=[int(v) for v in prep.loads_before],
            imbalance=imbalance_ratio(prep.loads),
            imbalance_before=prep.imbalance_before,
            efficiency=dydd_mod.balance_ratio(prep.loads),
            repartitioned=prep.repartitioned,
            migrated=prep.migrated,
            rounds=prep.rounds,
            pack_time=prep.pack_time,
            solve_time=solve_time,
            cycle_time=cycle_time,
            error_vs_direct=err,
            comm_bytes_per_cycle=prep.comm_bytes_per_cycle,
            halo_fraction=prep.halo_fraction,
            loads_weighted=[int(v) for v in prep.loads_weighted],
            rebalance_suppressed=prep.rebalance_suppressed,
            phases=phases,
            compiles={k: list(v) for k, v in prep.phases.compiles.items()},
            residual_history=residual_history,
            comm_edge_bytes_per_cycle=prep.comm_edge_bytes_per_cycle,
            comm_mvec_bytes_per_cycle=prep.comm_mvec_bytes_per_cycle,
            comm_mvec_axis_bytes_per_cycle=(
                prep.comm_mvec_axis_bytes_per_cycle),
            device_solve_times=[float(t) for t in device_times],
            straggler_flags=flags,
            window=prep.window,
            placed_bytes=prep.placed_bytes))

    # -- checkpoint / resume ------------------------------------------------

    # v2 adds nothing mandatory over v1 — it marks snapshots that may
    # carry the optional "pint" metadata entry (window id + window count
    # of a parallel-in-time window-boundary save) and may be assembled
    # from a stashed host_state().  restore() accepts both versions.
    SNAPSHOT_VERSION = 2
    _SNAPSHOT_VERSIONS = (1, 2)

    def host_state(self) -> dict:
        """Deep copy of the host-side mutable state ``prepare`` advances
        (truth, rng, domain boundary state, trigger state, stream
        cursor) at the current point of the prepare sweep.

        The parallel-in-time engine prepares *every* cycle up front, so
        a window boundary's host state is long gone by the time the
        window's analyses exist — it stashes this at each boundary
        during the sweep and hands it back to :meth:`snapshot` when the
        completion phase reaches the boundary."""
        cursor = self._stream.cursor if self._stream is not None else None
        return {
            "truth": np.asarray(self._truth, np.float64).copy(),
            "rng_state": copy.deepcopy(self._rng.bit_generator.state),
            "domain": {k: np.asarray(v).copy()
                       for k, v in self.domain.state_dict().items()},
            "streak": int(self._streak),
            "last_rebalance_loads": (
                None if self._last_rebalance_loads is None
                else np.asarray(self._last_rebalance_loads).copy()),
            "cursor": copy.deepcopy(cursor),
        }

    def snapshot(self, host_state: dict | None = None,
                 extra_meta: dict | None = None) -> tuple:
        """(tree, metadata) capturing everything resume needs.

        Must be taken at a cycle boundary with no prepare in flight
        (``run`` defers the pipelined next-prepare around snapshot
        cycles).  The tree holds the array state (truth, carried
        analysis, domain boundary state); the metadata holds the
        JSON-side state: config, rng bit-generator state (exact — resume
        re-draws the same truth walk and data noise), journal, stream
        cursor, straggler EWMAs and the gram/schwarz autotune caches.

        ``host_state`` substitutes a stashed :meth:`host_state` capture
        for the live truth/rng/domain/trigger/cursor state — the
        parallel-in-time engine's window-boundary snapshots, where the
        prepare sweep has already advanced past the boundary while the
        analysis/journal side (taken live) is exactly at it.
        ``extra_meta`` merges extra JSON entries into the metadata
        (e.g. the ``"pint"`` window descriptor).
        """
        hs = host_state
        truth = (self._truth if hs is None else hs["truth"])
        domain_sd = (self.domain.state_dict() if hs is None
                     else hs["domain"])
        last_loads = (self._last_rebalance_loads if hs is None
                      else hs["last_rebalance_loads"])
        tree: dict = {"truth": np.asarray(truth, np.float64)}
        if self.analysis is not None:
            tree["analysis"] = np.asarray(jax.device_get(self.analysis))
        if last_loads is not None:
            tree["last_rebalance_loads"] = np.asarray(last_loads)
        for k, v in domain_sd.items():
            tree[_DOMAIN_PREFIX + k] = np.asarray(v)
        cursor = (self._stream.cursor
                  if self._stream is not None else None) \
            if hs is None else hs["cursor"]
        metadata = {
            "snapshot_version": self.SNAPSHOT_VERSION,
            "config": dataclasses.asdict(self.cfg),
            "domain": self.domain.describe(),
            "rng_state": (self._rng.bit_generator.state if hs is None
                          else hs["rng_state"]),
            "streak": int(self._streak if hs is None else hs["streak"]),
            "journal": self.journal.to_dict(),
            "cursor": cursor,
            "stragglers": [s.state_dict() for s in self._stragglers],
            "autotune": ops_mod.export_tune_caches(),
        }
        if extra_meta:
            metadata.update(extra_meta)
        return tree, metadata

    def save_checkpoint(self, directory: str, step: int,
                        host_state: dict | None = None,
                        extra_meta: dict | None = None) -> str:
        """Atomic engine checkpoint via the hash-verified manager
        primitives; ``step`` is the completed-cycle count.  Returns the
        final checkpoint path."""
        tree, metadata = self.snapshot(host_state=host_state,
                                       extra_meta=extra_meta)
        t0 = time.perf_counter()
        path = ckpt_mod.save_pytree(tree, directory, step, metadata)
        m = meters_mod.get_meters()
        m.inc("engine.snapshots")
        m.observe("engine.snapshot_time", time.perf_counter() - t0)
        return path

    @classmethod
    def restore(cls, checkpoint: str, *,
                config: "EngineConfig | None" = None,
                domain: Optional[domain_mod.Domain] = None,
                mesh=None, mesh_axis=None,
                forecast: Optional[Callable] = None,
                straggler_config: Optional[StragglerConfig] = None,
                chaos: "chaos_mod.ChaosInjector | None" = None
                ) -> "AssimilationEngine":
        """Rebuild an engine from a checkpoint directory (latest verified
        step) or a specific ``step_XXXX`` path.

        Same-shape resume (``config``/``domain`` omitted) restores the
        exact saved state and is bitwise journal-continuing.  Passing a
        ``config`` and ``domain`` overrides them for an *elastic* resume
        under a different p — the saved domain state is then not loaded
        (the caller, :func:`repro.runtime.elastic.remesh_assim_domain`,
        derives the new tiling) while truth/rng/analysis/journal carry
        over, so the stream still continues without replaying cycles.
        """
        flat, manifest = ckpt_mod.restore_pytree(checkpoint)
        meta = manifest["metadata"]
        ver = meta.get("snapshot_version")
        if ver not in cls._SNAPSHOT_VERSIONS:
            raise ValueError(f"unsupported engine snapshot version {ver}")
        cfg = config if config is not None \
            else EngineConfig(**meta["config"])
        eng = cls(cfg, forecast=forecast, mesh=mesh, mesh_axis=mesh_axis,
                  domain=domain, straggler_config=straggler_config,
                  chaos=chaos)
        eng._load_snapshot(flat, meta, remeshed=domain is not None)
        return eng

    def _load_snapshot(self, flat: dict, meta: dict,
                       remeshed: bool = False) -> None:
        self._truth = np.asarray(flat["truth"], np.float64)
        if "analysis" in flat:
            self.analysis = jnp.asarray(flat["analysis"])
        # Exact generator state, not a reseed: the resumed run draws the
        # same truth steps and data noise the uninterrupted run would.
        self._rng.bit_generator.state = meta["rng_state"]
        journal = Journal.from_dict(meta["journal"])
        resume_log = list(journal.meta.get("resume", []))
        resume_log.append({"at_cycle": len(journal.records),
                           "p": int(self.p), "remeshed": bool(remeshed)})
        if remeshed:
            # New tiling: domain state stays as the caller derived it,
            # trigger/straggler state is stale for the new p — start
            # those fresh.  The journal meta switches to the new
            # descriptor so downstream load_table reshapes correctly.
            journal.meta = self.domain.describe()
        else:
            self.domain.load_state(
                {k.split(_DOMAIN_PREFIX, 1)[1]: v
                 for k, v in flat.items()
                 if k.startswith(_DOMAIN_PREFIX)})
            self._streak = int(meta.get("streak", 0))
            if "last_rebalance_loads" in flat:
                self._last_rebalance_loads = np.asarray(
                    flat["last_rebalance_loads"])
            for mon, st in zip(self._stragglers,
                               meta.get("stragglers", [])):
                mon.load_state(st)
        journal.meta["resume"] = resume_log
        self.journal = journal
        self._dec_cache = None
        self._restored_cursor = meta.get("cursor")
        ops_mod.import_tune_caches(meta.get("autotune"))

    def resume_stream(self) -> "streams_mod.ResumableStream | None":
        """The stream continuation from the restored cursor (None when
        the snapshot was taken without a cursor-bearing stream)."""
        cursor = self._restored_cursor
        if cursor is None:
            return None
        return streams_mod.ResumableStream.from_cursor(cursor)
