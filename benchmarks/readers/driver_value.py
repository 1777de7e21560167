"""A number the driver took itself, on the host's clock, over the traced
window (``key`` names it among the driver's end-to-end readings): a tail
that is too unsteady to carry a bound stands here, beside the layer that
makes it. A run in which the driver had nothing to take it from returns
nothing."""


def read(ctx, key):
    return ctx.end_to_end.get(key)
