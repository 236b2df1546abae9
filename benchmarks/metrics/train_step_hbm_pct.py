"""Share of the chip's HBM bandwidth the step's NECESSARY traffic would need
during the step's device time: bytes the algorithm must move per step
(``lib/work.py``: looked-up rows, distinct rows' parameters and optimizer
state read and written, dense parameters and moments, the batch) over the
peak bytes/s, over ``step_device_ms``.  Distinct rows are counted on the host
from the ids of the recorded steps.  Layer: whole step."""

from benchmarks.lib import peaks, work


def read(ctx):
    s = ctx["summary"]
    if s is None or not s.steps:
        return None
    need = work.step_bytes(
        lookups=ctx["batch"] * ctx["n_columns"],
        unique_rows=ctx["unique_rows_per_step"], dim=ctx["dim"],
        kind=ctx["kind"], dense_param_count=ctx["dense_count"],
        batch_bytes=ctx["batch_bytes"])
    floor_s = need / (peaks.chip_peaks(ctx["device_kind"]).hbm_bytes_per_s
                      * ctx["n_chips"])
    return 100.0 * floor_s / (s.busy_s / s.steps)
