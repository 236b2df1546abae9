"""Roofline share of the held routed experts: the least time the chip could
take for the pairs the window REALISED (``moe_pairs`` a step from the
program's epoch records; ``lib/work_nemotron.py``: ``expert_flops`` over the
bf16 peak or ``expert_bytes`` over the HBM peak, whichever is longer, a
layer) over the device time under ``moe_experts``.  A form that computes
every held expert over every token reads a few percent here, which is the
point.  Layer: kernels.  ``train_step_mfu_pct`` bounds it.  Nothing where
the trace or the program has no such scope or counter."""

from benchmarks.lib import peaks, work_nemotron


def read(ctx):
    ms = (ctx.get("scope_ms") or {}).get("moe_experts")
    shape, pairs = ctx.get("nemotron_shape"), ctx.get("moe_pairs_per_step")
    if not ms or not shape or not pairs:
        return None
    layers = shape["expert_layers"]
    peak = peaks.chip_peaks(ctx["device_kind"])
    floor_s = layers * max(
        work_nemotron.expert_flops(pairs / layers, shape["latent"],
                                   shape["expert_width"]) / peak.flops_per_s,
        work_nemotron.expert_bytes(pairs / layers, shape["experts"],
                                   shape["latent"], shape["expert_width"])
        / peak.hbm_bytes_per_s) / ctx["n_chips"]
    return 100.0 * floor_s / (ms / 1e3)
