"""Operator packing, the block lookups and the batched gram + Cholesky
factor build up to its end: the journal's pack.factor, mean per window
cycle, in ms."""
from bench import phases


def read(run):
    return phases.mean_ms(run, "pack.factor")
