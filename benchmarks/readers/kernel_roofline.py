"""A kernel's share of its roofline: the least time the chip could take
for the bytes (or operations) the algorithm requires, over the summed
device time of the kernel's events in the traced window. ``kernel`` is
matched inside the device operation's name (the kernel's stable name);
``bytes_key`` / ``flops_key`` name what the driver counted in its log.
A run whose trace holds no such event returns nothing - never 0."""


def read(ctx, kernel, bytes_key=None, flops_key=None):
    r = ctx.reduced
    if not r or ctx.peaks is None:
        return None
    seconds = sum(t for name, t in r["ops"].items() if kernel in name)
    if seconds <= 0:
        return None
    least = 0.0
    if bytes_key and ctx.log.get(bytes_key):
        least = max(least, ctx.log[bytes_key] / ctx.peaks["hbm_bytes_per_s"])
    if flops_key and ctx.log.get(flops_key):
        least = max(least, ctx.log[flops_key] / ctx.peaks["flops_per_s_bf16"])
    if least <= 0:
        return None
    return 100.0 * least / seconds
