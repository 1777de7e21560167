"""Whole-step share of the chip's peak: the FLOPs the algorithm
requires for the work done in the traced window (counted by the
driver through ``models/<family>.py``) over the window's length and the
peak of ``device_kind``. Bounds every kernel's roofline share."""


def read(ctx):
    flops, window_s = ctx.log.get("required_flops"), ctx.log.get("window_s")
    if not flops or not window_s or ctx.peaks is None:
        return None
    peak = ctx.peaks["flops_per_s_bf16"] * len(ctx.devices)
    return 100.0 * flops / window_s / peak
