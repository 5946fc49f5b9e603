"""DyDD and decomposition: the journal's dydd and halo phases, mean per
window cycle, in ms."""
import numpy as np


def read(run):
    if not run.cycles:
        return None
    return 1e3 * float(np.mean([c["phases"].get("dydd", 0.0)
                                + c["phases"].get("halo", 0.0)
                                for c in run.cycles]))
