"""The journal's phase seconds of the window's cycles, for the readers
of program spans (``bench/metrics/pack_*_ms.py``, ``solve_host_ms.py``)."""
import numpy as np


def mean_ms(run, *names):
    """Mean over the window's cycles of the summed journal phases
    ``names``, in ms; None where a cycle lacks one of them (a program
    that does not time those steps)."""
    if not run.cycles or any(n not in c["phases"]
                             for c in run.cycles for n in names):
        return None
    return 1e3 * float(np.mean([sum(c["phases"][n] for n in names)
                                for c in run.cycles]))
