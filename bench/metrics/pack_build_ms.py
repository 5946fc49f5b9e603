"""Operator packing, building A: the journal's pack.h1 (the observation
operator), pack.concat (A = [H0; H1] and r) and pack.roundtrip (A to the
device and back), mean per window cycle, in ms."""
from bench import phases


def read(run):
    return phases.mean_ms(run, "pack.h1", "pack.concat", "pack.roundtrip")
