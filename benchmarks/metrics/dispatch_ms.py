"""Host milliseconds a step inside the ``train_step`` call (phase ``dispatch``:
argument handling and enqueue; where the runtime makes the host wait for the
device, the wait shows here).  Median over the window's epochs.  Layer: host
loop."""

from benchmarks.lib import phases


def read(ctx):
    return phases.ms_per_step("dispatch")
