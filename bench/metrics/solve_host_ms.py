"""The host's part of the solve on the main thread, before dispatch: the
forecast pulled to the host, H0 @ background and the rhs copy (the
journal's solve.input), mean per window cycle, in ms."""
from bench import phases


def read(run):
    return phases.mean_ms(run, "solve.input")
