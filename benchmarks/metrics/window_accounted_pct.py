"""What ties the program's spans to the end-to-end clock: the ``loop_s`` of the
window's epochs, summed, over the host seconds the harness clocked for the
same steps (``steps x batch / rate``).  One value, no median.  99-100 when the
spans see the whole window; under 98 means time they do not see.  Layer: host
loop."""

from benchmarks.lib import phases


def read(ctx):
    records = phases.window_epochs()
    if records is None:
        return None
    host_s = sum(r["steps"] for r in records) * ctx["batch"] / ctx["rate"]
    return 100.0 * sum(r["loop_s"] for r in records) / host_s
