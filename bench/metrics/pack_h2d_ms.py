"""Operator packing, copying A_loc and its maps to the device up to the
copy's end: the journal's pack.h2d, mean per window cycle, in ms."""
from bench import phases


def read(run):
    return phases.mean_ms(run, "pack.h2d")
