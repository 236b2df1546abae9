"""Share of the epoch loop the host spends in ``next(batches)``: the program's
``next_batch`` phase (loader, shuffle pool and ``device_put`` of the batch two
ahead, all on the dispatch thread when ``num_workers = 0``) over ``loop_s``.
Median over the window's epochs.  Layer: input.  High with ``loss_sync_ms``
near zero = the job is input-bound."""

from benchmarks.lib import phases


def read(ctx):
    return phases.median_over_epochs(
        lambda r: 100.0 * phases.seconds(r, "next_batch") / r["loop_s"])
