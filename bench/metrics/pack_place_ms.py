"""Operator packing, placing the cycle's H1 whole on every chip of the
sharded solver's mesh up to the copy's end: the journal's pack.place,
mean per window cycle, in ms.  Nothing where the program does not time
that step."""
from bench import phases


def read(run):
    return phases.mean_ms(run, "pack.place")
