"""Share of a whole that the program's own telemetry spans named in
``spans`` cover: their summed durations over the summed durations of the
spans named in ``of``, in percent - for an ``of`` that tiles one thread's
loop, the share of that thread's time. With ``complement`` the rest: 100
less that share, which for spans that bound a thread's waiting is the
time that thread worked.

The whole is not the measured window's length, because the driver hands
over only the spans that lie wholly inside the window: a long span cut
by an edge of the window is missing, and over the window's length the
part of it inside would read as complement (one 93 ms wait at each edge
of a 6 s window: 1.5 points). Both sides of this ratio are taken under
that one rule. The spans of each list have to be disjoint, as spans of
one thread that do not nest are. A run that recorded none of ``spans``,
or none of ``of``, returns nothing - never 0 or 100."""


def read(ctx, spans, of, complement=False):
    total = lambda names: sum(sum(ctx.spans.get(n) or ()) for n in names)
    part, whole = total(spans), total(of)
    if not part or not whole:
        return None
    share = 100.0 * part / whole
    return 100.0 - share if complement else share
