"""Host milliseconds a step blocked on the device for values: ``flush_checks``
(queued losses fetched, the guard's snapshot copies dispatched) and the
log-cadence fetches (phase ``loss_sync``, children included).  Median over the
window's epochs.  Layer: host loop."""

from benchmarks.lib import phases


def read(ctx):
    return phases.ms_per_step("loss_sync")
