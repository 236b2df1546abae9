"""Roofline share of latent attention's core: the least time the chip could
take for what the MASK admits (``lib/work_deepseek.py``: the (query, key)
pairs within the written documents at ``j <= i``, counted on the host and
held equal to the program's ``attn_pairs`` counter by the comparison;
``mla_attn_flops`` over the bf16 peak, or ``mla_attn_bytes`` over the HBM
peak, whichever is longer, a layer) over the device time under
``mla_attn``.  The pairs a blockwise path forms above the diagonal or across
documents are not counted, so a kernel that skips them raises the share.
Layer: kernels.  ``train_step_mfu_pct`` bounds it.  Nothing where the trace
or the program has no such scope."""

from benchmarks.lib import peaks, work_deepseek


def read(ctx):
    ms = (ctx.get("scope_ms") or {}).get("mla_attn")
    shape = ctx.get("deepseek_shape")
    if not ms or not shape:
        return None
    peak = peaks.chip_peaks(ctx["device_kind"])
    floor_s = shape["layers"] * max(
        work_deepseek.mla_attn_flops(
            shape["pairs_per_step"], shape["heads"], shape["qk_head_dim"],
            shape["v_head_dim"]) / peak.flops_per_s,
        work_deepseek.mla_attn_bytes(
            shape["positions"], shape["heads"], shape["qk_head_dim"],
            shape["v_head_dim"]) / peak.hbm_bytes_per_s) / ctx["n_chips"]
    return 100.0 * floor_s / (ms / 1e3)
