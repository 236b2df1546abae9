"""Seconds jax spent in backend compiles (persistent-cache reads included)
during set-up, from ``jax.monitoring``.  Layer: entry.  Moves ``setup_s``."""


def read(ctx):
    return ctx["compile_s"]
