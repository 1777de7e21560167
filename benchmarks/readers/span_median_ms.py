"""Median duration of one of the program's own telemetry spans in the
traced window (``span`` names it). The driver switches the program's
span tracer on for the traced window only and hands over what it
recorded; a run that recorded none returns nothing."""
import statistics


def read(ctx, span):
    durations = ctx.spans.get(span)
    if not durations:
        return None
    return 1000.0 * statistics.median(durations)
