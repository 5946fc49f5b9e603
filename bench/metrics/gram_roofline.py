"""Share of its roofline the Pallas gram kernel reaches in the window:
the least time of every window cycle's gram (2 p m w^2 flops at the
bf16 peak, or its bytes, whichever bounds) over the device time of the
gram kernel inside the factor build, summed over chips, in %."""
from bench import counts, kernels


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.op_seconds(kernels.GRAM_OPS, kernels.FACTOR_MODULES)
    if dev <= 0:
        return None
    least = 0.0
    for c in run.cycles:
        t, _ = counts.least_seconds(*counts.gram(c["p"], c["m"], c["w"]),
                                    run.peaks)
        least += t
    return 100.0 * least / dev
