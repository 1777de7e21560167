"""A quantile of one of the program's own always-on histograms, read in
the benchmark's process from ``bigdl_tpu.telemetry.registry()`` after
the run (``name`` names the instrument, ``q`` the percentile, nearest
rank). It is for a driver that does not switch the span tracer on. The
histogram holds every observation the process made, warm-up windows
too, which is why a median is asked for and not a mean. A program
without the instrument, or one that observed nothing, returns nothing."""
from benchmarks.drivers.serve_closed import percentile


def read(ctx, name, q=50):
    from bigdl_tpu import telemetry

    instrument = telemetry.registry().get(name)
    values = getattr(instrument, "samples", list)()
    return percentile(values, q) if values else None
