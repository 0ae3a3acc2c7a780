"""Seconds from the process's start to the window's open: JAX start-up,
weights, engine or generator, warm-up and any compilation."""


def read(run):
    return run.setup_s
