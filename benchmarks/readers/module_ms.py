"""Device time of one run of a compiled program: the summed duration of
its events on the device's ``XLA Modules`` line inside the traced
window, over their count. ``module`` is matched inside the program's
name (``jit_<function>(<hash>)``: the function's name is stable, the
hash changes with every edit). A run cut by an edge of the window counts
as a run with the part that lies inside, so with n runs the value reads
low by at most 2/n of itself. A trace without such a program (a CPU
rehearsal has no ``XLA Modules`` line; an older program gave the
function another name) returns nothing - never 0."""


def read(ctx, module):
    r = ctx.reduced
    if not r:
        return None
    runs, seconds = 0, 0.0
    for name, (count, total) in r["modules"].items():
        if module in name:
            runs += count
            seconds += total
    if not runs:
        return None
    return 1000.0 * seconds / runs
