"""Observability: span tracing + metrics for the assimilation stack.

``repro.obs`` is a leaf subsystem (it imports nothing from the rest of
``repro``) so every layer — engine, solver, halo exchange, DyDD,
kernels — can report into it without import cycles.

  * :mod:`repro.obs.trace` — nested span tracer with thread attribution,
    device-sync fences and Chrome/Perfetto ``trace_events`` export;
    disabled by default at zero overhead (``trace.span`` is a shared
    no-op until a :class:`~repro.obs.trace.Tracer` is installed or a
    ``jax.profiler`` trace runs, whose timeline gets every span as
    ``repro.<name>``); the journal-plus-span ``phase`` helper; the
    process-wide backend-compile counter.
  * :mod:`repro.obs.meters` — process-wide counters/gauges/series/events
    registry, always on.

See ``src/repro/assim/README.md`` §Observability for the span taxonomy
and meter names.
"""
from repro.obs.trace import (  # noqa: F401
    NULL_TRACER, NullTracer, Phases, Tracer, get_tracer, jax_profile,
    phase, set_tracer, span, tracing)
from repro.obs.meters import (  # noqa: F401
    Meters, comm_matrix, get_meters, set_meters)
