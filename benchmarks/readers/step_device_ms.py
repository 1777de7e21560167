"""Device busy time per optimizer step in the traced window."""


def read(ctx):
    r, steps = ctx.reduced, ctx.log.get("steps")
    if not r or not steps:
        return None
    return 1000.0 * r["busy_s"] / steps
