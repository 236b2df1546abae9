"""Roofline share of the chunked Mamba-2 scan: the least time the chip could
take for what its chunked form needs for one step (``lib/work_nemotron.py``:
``ssd_flops`` over the bf16 peak or ``ssd_bytes`` over the HBM peak,
whichever is longer; every ``M`` layer, forward and backward, recomputation
not counted) over the device time under ``ssd_scan``.  At the cell's shapes
the bytes bound it (0.28 against 0.20 ms a layer).  Layer: kernels.
``train_step_mfu_pct`` bounds it.  Nothing where the trace or the program
has no such scope."""

from benchmarks.lib import peaks, work_nemotron


def read(ctx):
    ms = (ctx.get("scope_ms") or {}).get("ssd_scan")
    shape = ctx.get("nemotron_shape")
    if not ms or not shape:
        return None
    sizes = (shape["tokens"], shape["mamba_heads"], shape["mamba_groups"],
             shape["mamba_head_dim"], shape["state"])
    peak = peaks.chip_peaks(ctx["device_kind"])
    floor_s = ctx["batch"] * shape["mamba_layers"] * max(
        work_nemotron.ssd_flops(*sizes, shape["chunk"]) / peak.flops_per_s,
        work_nemotron.ssd_bytes(*sizes) / peak.hbm_bytes_per_s) / ctx["n_chips"]
    return 100.0 * floor_s / (ms / 1e3)
