"""Analyses completed over the window's time: from the window's start
to the last analysis, so whole cycles are never cut by its end."""


def read(run):
    return run.done / run.window_s if run.done else None
