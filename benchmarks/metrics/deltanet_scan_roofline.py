"""Roofline share of the chunked gated delta rule: the FLOPs its chunked
form needs for one step (``lib/work_lm.py`` ``delta_rule_flops``: every
linear-attention layer, forward and backward, recomputation not counted)
over the chip's bf16 peak, over the device time under ``deltanet_scan``.
Compute-bound by its count (its bytes, ``delta_rule_bytes``, need less time
than its FLOPs at the peaks); what holds it far under the peak is the
sequential scan over chunks.  Layer: kernels.  ``train_step_mfu_pct`` bounds
it.  Nothing where the trace or the program has no such scope."""

from benchmarks.lib import peaks, work_lm


def read(ctx):
    ms = (ctx.get("scope_ms") or {}).get("deltanet_scan")
    shape = ctx.get("lm_shape")
    if not ms or not shape:
        return None
    flops = ctx["batch"] * shape["linear_layers"] * work_lm.delta_rule_flops(
        shape["tokens"], shape["linear_heads"], shape["dk"], shape["dv"],
        shape["chunk"])
    peak = peaks.chip_peaks(ctx["device_kind"]).flops_per_s * ctx["n_chips"]
    return 100.0 * flops / peak / (ms / 1e3)
