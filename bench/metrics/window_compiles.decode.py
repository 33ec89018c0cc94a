"""Decode-heavy cells: XLA compilations (``jax.monitoring`` backend-compile
events) inside the measured window; each is a program the set-up did not
warm."""


def read(run):
    return run.compiles
