"""Median, over the program calls of the traced window, of a count the
program wrote into its span ring (``record`` names the records, ``field``
the count in their ``args``; ``kind`` keeps the calls of one kind,
``per`` divides by another field of the same record, ``pct_of_config``
turns the result into a share, in percent, of a count the configuration
states). The driver clears the ring as the traced window opens and
switches the tracer off as it closes, so the records are that window's:
an always-on histogram would also hold the warm-up and the drain after
the close, when slots stand empty. A program that wrote no such record
returns nothing."""
import statistics


def read(ctx, record, field, kind=None, per=None, pct_of_config=None):
    from bigdl_tpu import telemetry

    values = []
    for s in telemetry.tracer().spans():
        a = s.args
        if s.name != record or not a or field not in a:
            continue
        if kind is not None and a.get("kind") != kind:
            continue
        values.append(a[field] / a[per] if per else a[field])
    if not values:
        return None
    value = statistics.median(values)
    if pct_of_config:
        whole = ctx.config.get(pct_of_config)
        if not whole:
            return None
        value = 100.0 * value / float(whole)
    return value
