"""Share of the epoch loop spent at its two ends: ``epoch_open`` (entry to the
first batch in hand: stream opened, pool filled, first puts, guard snapshot)
plus ``epoch_close`` (after the last step: the final loss sync, which drains
the device, cache flush, ``train_auc``) over ``loop_s``.  Median over the
window's epochs.  Layer: host loop."""

from benchmarks.lib import phases


def read(ctx):
    return phases.median_over_epochs(
        lambda r: 100.0 * (phases.seconds(r, "epoch_open")
                           + phases.seconds(r, "epoch_close")) / r["loop_s"])
