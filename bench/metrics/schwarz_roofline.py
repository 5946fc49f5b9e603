"""Share of its roofline the fused Schwarz kernels reach in the window:
the least time of every window cycle's sweeps (two passes over the
operator blocks at their own shapes, without lane padding: memory
bound) over the device time of the schwarz_fwd / schwarz_bwd kernels
inside the solve, summed over chips, in %."""
from bench import counts, kernels


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.op_seconds(kernels.SCHWARZ_OPS, kernels.SOLVE_MODULES)
    if dev <= 0:
        return None
    least = 0.0
    for c in run.cycles:
        flops, nbytes = counts.schwarz_sweep(c["p"], c["m"], c["w"])
        t, _ = counts.least_seconds(flops, nbytes, run.peaks)
        least += t * int(run.config["iters"])
    return 100.0 * least / dev
