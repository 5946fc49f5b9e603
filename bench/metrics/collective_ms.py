"""Cross-chip exchange: device time of the collectives inside the timed
sharded solve (its module matched by ``bench.kernels.SOLVE_MODULES``),
per window cycle and averaged over the chips, in ms.

The solve's collectives are the all-reduces of the (m,) observation
product each sweep and of the final (n,) assembly, and the collective
permutes of the neighbour halo rounds.  Their names as a TPU v5e trace
of four chips gives them (``bench/tests/data/v5e_4chip_trace_slice.json``):
``all-reduce.18``, ``collective-permute-start.1``,
``collective-permute-done.1``.  Reduce-scatter, all-gather and
all-to-all, and the ``-start``/``-done`` halves of any of them, count
too.  Nothing where the trace holds no such op in that module (one chip,
or a solve module of another name)."""
from bench import kernels

COLLECTIVE_OPS = (r"^(all-reduce|reduce-scatter|all-gather|"
                  r"collective-permute|all-to-all)(-start|-done)?"
                  r"(\.\d+)?(\s|$)")


def read(run):
    tr = run.trace
    if tr is None or not run.done:
        return None
    dev = tr.op_seconds(COLLECTIVE_OPS, kernels.SOLVE_MODULES)
    if dev <= 0:
        return None
    return 1e3 * dev / len(tr.devices) / run.done
