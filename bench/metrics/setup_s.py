"""Process start to the window: JAX start-up, the engine, the warm-up
cycles with their compiles and block autotuning."""


def read(run):
    return run.setup_s
