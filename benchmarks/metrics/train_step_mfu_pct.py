"""Model FLOP/s utilisation of the WHOLE step, end to end: the FLOPs the
forward and backward passes need per example (``lib/work.py``: dense kernels,
and the interaction as the configuration's ``work`` table states it;
recomputation never counts) times the window's examples per second, over
chips times the chip's bf16 peak.  Layer: whole step.  It
bounds every kernel's roofline share: a later PR that takes a kernel off the
path can claim a gain only while this still moves."""

from benchmarks.lib import peaks, work


def read(ctx):
    flops = work.step_flops(
        ctx["kernel_shapes"],
        ctx["config"]["work"]["interaction_flops_per_example"], 1)
    peak = peaks.chip_peaks(ctx["device_kind"]).flops_per_s * ctx["n_chips"]
    return 100.0 * flops * ctx["rate"] / peak
