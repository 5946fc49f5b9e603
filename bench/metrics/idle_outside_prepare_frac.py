"""Share of the traced window in which chip 0 was idle while no prepare
was running on any host thread: the idle time that faster packing
cannot remove.  The prepare intervals are the harness's bench.prepare
spans, which wrap the same call as the program's repro.prepare (the
trace reduction keeps the bench.* host spans)."""
from bench.tracing import _union

PREPARE = "bench.prepare"


def read(run):
    tr = run.trace
    if tr is None or tr.window_s <= 0:
        return None
    prepare = _union([[s, e] for name, s, e in tr.host if name == PREPARE])
    idle = 0.0
    for gs, ge in tr.devices[0].gaps:
        idle += (ge - gs) - sum(max(0.0, min(ge, e) - max(gs, s))
                                for s, e in prepare)
    return idle / (tr.window_ns[1] - tr.window_ns[0])
