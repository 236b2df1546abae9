"""Roofline share of the row-sparse table update (bandwidth-bound: it has no
matmul): the update's necessary bytes (distinct rows x (row + optimizer state),
read and written) over the peak bytes/s, over the per-step device time of the
events the CONFIGURATION's ``trace_patterns.table_update`` names (the
``tpu_custom_call`` events where the fused fat-line kernel runs, the table
scatters where XLA does the update).  A pattern that matches no event is an
error of the traced run, never a 0."""

from benchmarks.lib import peaks, trace, work


def read(ctx):
    s = ctx["summary"]
    if s is None or not s.steps:
        return None
    pattern = ctx["config"].get("trace_patterns", {}).get("table_update")
    if not pattern:
        raise RuntimeError(
            f"configuration {ctx['config']['name']!r} names no "
            "trace_patterns.table_update: every training configuration says "
            "which device events are its table update")
    events = trace.matching(s.ops, pattern)
    if not events:
        raise RuntimeError(
            f"table_update_roofline: pattern {pattern!r} matches no device "
            f"event; the longest are {trace.top_ops(s.ops, 15)}")
    need = work.update_bytes(ctx["unique_rows_per_step"], ctx["dim"], ctx["kind"])
    floor_s = need / (peaks.chip_peaks(ctx["device_kind"]).hbm_bytes_per_s
                      * ctx["n_chips"])
    return 100.0 * floor_s / (trace.union_ns(events) / 1e9 / s.steps)
