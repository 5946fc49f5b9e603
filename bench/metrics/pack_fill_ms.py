"""Operator packing, filling the padded (p, m, w) A_loc, its column maps
and masks on the host: the journal's pack.fill, mean per window cycle,
in ms."""
from bench import phases


def read(run):
    return phases.mean_ms(run, "pack.fill")
