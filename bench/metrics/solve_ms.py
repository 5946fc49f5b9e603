"""Schwarz solve: the journal's solve_time, mean per window cycle, in
ms."""
import numpy as np


def read(run):
    if not run.cycles:
        return None
    return 1e3 * float(np.mean([c["solve_time"] for c in run.cycles]))
