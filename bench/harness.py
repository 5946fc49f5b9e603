"""Drives one cell: set-up, the measured window, the check, the metrics.

``run_cell`` builds the engine from the cell's configuration file (every
key that names an ``EngineConfig`` field), hands it the traffic mix's
warm-up cycles through ``AssimilationEngine.run`` (set-up), turns the
persistent compile cache off, and hands it the window's cycles through
``run`` again until ``seconds`` have passed.  ``check`` then compares
every analysis of the run with the plain reference that the
configuration's ``reference`` key names, and each of the cell's metrics
is read by its reader, ``bench/metrics/<metric>.py``.

Everything the readers read is on the returned :class:`Run`.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from bench import stream, tracing  # noqa: E402

# EngineConfig fields the harness sets itself, whatever a configuration
# file says.
HARNESS_KEYS = ("seed", "track_reference")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Run:
    """What one run measured; the metric readers take their numbers
    from here."""

    cell: dict
    config: dict
    devices: list
    peaks: dict
    setup_s: float = 0.0
    t0: float = 0.0                  # window start (host perf_counter)
    handed: list = dataclasses.field(default_factory=list)
    ready: list = dataclasses.field(default_factory=list)
    cycles: list = dataclasses.field(default_factory=list)
    compiles: list = dataclasses.field(default_factory=list)
    trace: "tracing.Reduced | None" = None

    @property
    def done(self) -> int:
        """Window cycles whose analysis was ready."""
        return len(self.ready)

    @property
    def window_s(self) -> float:
        """Window start to the last analysis."""
        return (self.ready[-1] - self.t0) if self.ready else 0.0


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT):
    """(cell, config, traffic mix, metric entries of the cell) from
    ``BENCHMARK.json`` and the files it names."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    config = load_json(root / cfg["file"])
    mix = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")

    def mine(metrics):
        return [m for m in metrics
                if name in m.get("workloads", [name])]

    return (cell, config, mix, mine(spec["end_to_end"]),
            mine(spec["per_layer"]))


def load_limits(cell_name: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell_name}.json")


def peaks_for(kind: str) -> dict:
    """The published peaks of one device kind; a kind missing from the
    table is an error, not a default."""
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise SystemExit(f"no peaks for device kind {kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[kind]


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The ``read(run)`` function of ``bench/metrics/<metric>.py``."""
    return _module(BENCH / "metrics" / f"{metric}.py",
                   f"bench_metric_{metric}").read


def reference_module(config: dict):
    """The plain reference the configuration names (a path under the
    checkout's root, e.g. ``bench/reference.py``)."""
    path = ROOT / config["reference"]
    return _module(path, f"bench_reference_{path.stem}")


def engine_config(config: dict, seed: int):
    """Every key of the configuration that names an ``EngineConfig``
    field, with the run's seed and no per-cycle reference solve."""
    from repro.assim import EngineConfig
    fields = {f.name for f in dataclasses.fields(EngineConfig)}
    return EngineConfig(seed=seed, track_reference=False,
                        **{k: v for k, v in config.items()
                           if k in fields and k not in HARNESS_KEYS})


def _annotated(fn, name):
    def wrapped(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)
    return wrapped


def _window(source, run: Run, seconds: float, log: list):
    """Hand the engine cycles until ``seconds`` have passed since the
    window opened; the engine finishes every cycle it was handed."""
    for obs in source:
        now = time.perf_counter()
        if now - run.t0 >= seconds:
            return
        run.handed.append(now)
        log.append(obs)
        yield obs


def run_cell(cell: dict, config: dict, mix: dict, *, seed: int,
             seconds: float, trace: bool, t_start: float, devices: list,
             peaks: dict) -> tuple:
    """Set up and measure.  Returns (Run, positions, analyses) of every
    cycle of the run, set-up cycles first."""
    from repro.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_enable_compilation_cache", True)
    # Cache every program set-up compiles, however quick, so that a
    # second run's set-up compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run = Run(cell=cell, config=config, devices=devices, peaks=peaks)
    compiles: list = []

    def on_compile(ev, d, **kw):
        if ev == COMPILE_EVENT:
            compiles.append((time.perf_counter(), d))

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        return _measure(run, compiles, config, mix, seed, seconds, trace,
                        t_start)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)


def _measure(run, compiles, config, mix, seed, seconds, trace, t_start):
    from jax.experimental.compilation_cache import compilation_cache
    from repro.assim import AssimilationEngine

    eng = AssimilationEngine(engine_config(config, seed))
    packs: list = []
    analyses: list = []
    prepare = eng.prepare

    def observed_prepare(*a, **kw):
        prep = prepare(*a, **kw)
        pk = prep.packed_op
        packs.append({"cycle": prep.cycle, "w": pk.w, "p": pk.p, "m": pk.m,
                      "dtype": str(pk.A_loc.dtype),
                      "solve_kernel": pk.solve_kernel,
                      "solve_block": pk.solve_block,
                      "repartitioned": prep.repartitioned})
        return prep

    eng.prepare = observed_prepare
    if trace:
        eng.prepare = _annotated(eng.prepare, "bench.prepare")
        eng.solve_step = _annotated(eng.solve_step, "bench.solve")
        eng.complete_cycle = _annotated(eng.complete_cycle,
                                        "bench.complete")
    eng.on_analysis = lambda c, x: analyses.append(x)

    m = int(config["m_obs"])
    log = stream.setup_cycles(mix, m)
    eng.run(list(log))
    n_setup = len(log)
    jax.block_until_ready(analyses)
    # The window's compiles are the same work in every run of a cell,
    # whatever an earlier run left in the cache: they neither read nor
    # write it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    run.setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)

    def ready(c, x):
        run.ready.append(time.perf_counter())
        analyses.append(x)

    eng.on_analysis = ready
    run.t0 = time.perf_counter()
    win = _window(stream.window_cycles(mix, m, seed), run, seconds, log)
    if trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.run(win)
    else:
        eng.run(win)
    t_end = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    run.compiles = [(t, d) for t, d in compiles if run.t0 <= t <= t_end]
    records = {r.cycle: r for r in eng.journal.records}
    run.cycles = [dict(pk, phases=dict(records[pk["cycle"]].phases),
                       solve_time=records[pk["cycle"]].solve_time,
                       pack_time=records[pk["cycle"]].pack_time)
                  for pk in packs[n_setup:] if pk["cycle"] in records]
    if trace:
        try:
            run.trace = tracing.reduce(
                tracing.load(trace_dir), n_devices=len(run.devices),
                compiles=[(t - run.t0 - d, t - run.t0)
                          for t, d in run.compiles])
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    xs = [np.asarray(x, np.float64) for x in analyses]
    del eng
    return run, log, xs


def memory_peak(devices: list) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _worst(g: np.ndarray) -> float:
    """The largest gap; inf where one is not a number or there is none."""
    return float(g.max()) if g.size and np.isfinite(g).all() \
        else float("inf")


def gaps(config: dict, seed: int, positions: list, xs: list) -> dict:
    """Per answered cycle, the widest relative gap of ``xs`` (one
    analysis per cycle, from the first) to the reference given the
    cycle's observations, made by the reference from ``seed``:

    * ``analysis_gap``: from the background ``xs`` itself carried (its
      previous analysis), so each cycle is judged alone;
    * ``chain_gap``: to the reference's own chain of analyses over the
      whole stream, so a drift carried from cycle to cycle shows.

    An analysis of the wrong shape reads inf."""
    ref = reference_module(config)
    n = int(config["n"])
    xs = np.stack([x if np.shape(x) == (n,) else np.full(n, np.nan)
                   for x in xs])
    c = len(xs)
    ys = ref.observations(config, seed, positions)[:c]
    xb = np.concatenate([np.zeros((1, n)), xs[:-1]])
    with np.errstate(invalid="ignore"):
        step = ref.relative_gap(
            xs, ref.solve(config, np.stack(positions[:c]), np.stack(ys), xb))
        chained = ref.relative_gap(xs, ref.chain(config, positions[:c], ys))
    return {"analysis_gap": step, "chain_gap": chained}


def check(config: dict, seed: int, positions: list, xs: list,
          limits: dict, n_setup: int) -> dict:
    """The numbers compared for ``correct``, each {"value", "limit"}:
    the widest gaps over every analysis of the run, the window's cycles
    over a limit, and the cycles handed to the engine that it never
    answered."""
    g = (gaps(config, seed, positions, xs) if xs else
         {k: np.zeros(0) for k in ("analysis_gap", "chain_gap")})
    out = {k: {"value": _worst(v), "limit": limits[k]["limit"]}
           for k, v in g.items()}
    over = np.zeros(max(len(xs) - n_setup, 0), bool)
    for k, v in g.items():
        over |= ~(v[n_setup:] <= limits[k]["limit"])
    out["cycles_over"] = {"value": int(over.sum()), "limit": 0}
    out["cycles_unanswered"] = {"value": len(positions) - len(xs),
                                "limit": 0}
    return out


def correct(checks: dict) -> bool:
    """Every number compared is within its limit (nan is not)."""
    return all(c["value"] <= c["limit"] for c in checks.values())
