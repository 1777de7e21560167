"""A state-space decode kernel's share of its roofline: the least time
the chip could take to read and write the recurrent states the traced
window's decode calls had to update, over the summed device time of the
kernel's events (``kernel`` is matched inside the device operation's
name).

What they had to update comes from the family and the program, never
from the kernel: ``models/<family>.py`` ``state_bytes(cfg)`` is one
layer's state for one slot, and every decode call writes, while the
driver has the tracer on, a ``serving/ssm/step`` ring record whose
``slot_layers`` is its live slots times the layers that keep a state
(``generation/engine.py``). Bytes: the sum of ``slot_layers`` over the
window's decode calls x ``state_bytes`` x 2 (read once, written once).
The share reads the same work whatever implements the step. A program
without such records (no state-space layer, or one that predates them),
a family without ``state_bytes``, or a trace without the kernel returns
nothing - never 0."""


def read(ctx, kernel, record="serving/ssm/step"):
    r = ctx.reduced
    if not r or ctx.peaks is None or not hasattr(ctx.family, "state_bytes"):
        return None
    seconds = sum(t for name, t in r["ops"].items() if kernel in name)
    if seconds <= 0:
        return None
    from bigdl_tpu import telemetry

    calls = [s.args for s in telemetry.tracer().spans()
             if s.name == record and s.args
             and s.args.get("kind") == "decode"]
    slot_layers = sum(c["slot_layers"] for c in calls)
    if not slot_layers:
        return None
    moved = 2.0 * slot_layers * ctx.family.state_bytes(ctx.config)
    ctx.log["ssm_window"] = {"calls": len(calls),
                             "slot_layers": slot_layers,
                             "state_bytes_moved": moved,
                             "kernel_s": seconds}
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / seconds
