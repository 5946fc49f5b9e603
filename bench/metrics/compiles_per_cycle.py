"""Backend compiles inside the window, per window cycle."""


def read(run):
    return len(run.compiles) / run.done if run.done else None
