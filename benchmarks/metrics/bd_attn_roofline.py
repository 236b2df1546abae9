"""Roofline share of block diffusion's attention: the least time the chip
could take for what the MASK admits (``lib/work_sdar.py``: the (query, key)
pairs of the written documents, counted on the host, ``bd_attn_flops`` over
the bf16 peak, or ``bd_attn_bytes`` over the HBM peak, whichever is longer,
a layer) over the device time under ``bd_attn``.  The tiles a kernel forms
are not counted, so a kernel that skips dead tiles raises the share.
Layer: kernels.  ``train_step_mfu_pct`` bounds it.  Nothing where the trace
or the program has no such scope."""

from benchmarks.lib import peaks, work_sdar


def read(ctx):
    ms = (ctx.get("scope_ms") or {}).get("bd_attn")
    shape = ctx.get("sdar_shape")
    if not ms or not shape:
        return None
    peak = peaks.chip_peaks(ctx["device_kind"])
    floor_s = shape["layers"] * max(
        work_sdar.bd_attn_flops(shape["pairs_per_step"], shape["heads"],
                                shape["head_dim"]) / peak.flops_per_s,
        work_sdar.bd_attn_bytes(shape["positions"], shape["heads"],
                                shape["key_value_heads"], shape["head_dim"])
        / peak.hbm_bytes_per_s) / ctx["n_chips"]
    return 100.0 * floor_s / (ms / 1e3)
