"""Host milliseconds a step inside ``prefetch_to_mesh``'s ``next(it)``: parquet
decode, the shuffle pool, stacking (phase ``loader_next``).  Median over the
window's epochs.  Layer: input.  With ``h2d_put_ms`` it is the same work
``loader_examples_per_s`` times from outside with no step running."""

from benchmarks.lib import phases


def read(ctx):
    return phases.ms_per_step("loader_next")
