"""1 - busy / window over the traced window, busy being the union of the
intervals in which an op ran, averaged over the chips used."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 1.0 - run.trace.busy_s / run.trace.window_s
