"""Operator packing: the journal's pack phase (H1 build, A_loc fill,
copy to the device, gram + Cholesky factors, autotune), mean per window
cycle, in ms."""
import numpy as np


def read(run):
    if not run.cycles:
        return None
    return 1e3 * float(np.mean([c["phases"]["pack"] for c in run.cycles]))
