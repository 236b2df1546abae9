"""The longest single ``next(batches)`` of an epoch (phase ``next_batch``,
``max_seconds``): a burst — a row group read, a pool refill — longer than the
two batches ``prefetch_to_mesh`` keeps in flight starves the device, and a
mean cannot show it.  The first batch of an epoch is under ``epoch_open`` and
not here.  Median over the window's epochs.  Layer: input."""

from benchmarks.lib import phases


def read(ctx):
    return phases.median_over_epochs(
        lambda r: 1e3 * phases.phase(r, "next_batch")[2])
