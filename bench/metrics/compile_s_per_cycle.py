"""Seconds of backend compiles (jax.monitoring's
backend_compile_duration) inside the window, per window cycle."""


def read(run):
    return sum(d for _, d in run.compiles) / run.done if run.done else None
