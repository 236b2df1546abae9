"""Host milliseconds a step inside ``prefetch_to_mesh``'s ``put``: the
``jax.device_put`` of one host batch onto the mesh (phase ``h2d_put``).
Median over the window's epochs.  Layer: input."""

from benchmarks.lib import phases


def read(ctx):
    return phases.ms_per_step("h2d_put")
