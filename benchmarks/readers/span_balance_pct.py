"""A balance of the program's own telemetry spans as a share of a whole:
100 x (the summed durations of the spans named in ``plus`` less those
named in ``minus``) / the summed durations of the spans named in ``of``,
in percent. For an ``of`` that tiles one thread's loop it is the share
of that thread's time spent in one phase of it; ``minus`` takes out the
part of the phase spent waiting (spans of ``minus`` lie inside spans of
``plus``).

The whole is not the measured window's length, for the reason
``span_share_pct`` gives: the driver hands over only the spans that lie
wholly inside the window, and both sides are taken under that rule. A
run that recorded none of some name in ``plus`` (a program that has no
such span) or none of ``of`` returns nothing - never 0."""


def read(ctx, plus, of, minus=()):
    total = lambda names: sum(sum(ctx.spans.get(n) or ()) for n in names)
    whole = total(of)
    if not whole or not all(ctx.spans.get(n) for n in plus):
        return None
    return 100.0 * (total(plus) - total(minus)) / whole
