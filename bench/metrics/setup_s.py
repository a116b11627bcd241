"""Seconds from process start to the first request due: JAX start-up,
the request pool, and the warm-up of every shape from the compile cache."""


def read(run):
    return run.setup_s
