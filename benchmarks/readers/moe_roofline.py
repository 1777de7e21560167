"""The routed experts' share of their roofline: the least time the chip
could take for what the expert layers had to do in the traced window,
over the summed device time of the grouped-product kernel's events
(``kernel`` is matched inside the device operation's name).

What they had to do comes from the program: every prefill and decode
call returns, beside its logits, how many held experts its
token-expert pairs fell on and how many pairs were local, and the
engine writes each call's totals into the span ring as a
``serving/moe/step`` record while the driver has the tracer on - the
traced window and nothing else. Bytes: experts touched x one expert's
weights (each must be read once a call); operations: local pairs x one
expert's FLOPs. The larger of the two bounds it. A program without
such records (one that has no expert layer, or predates them), or a
trace without the kernel, returns nothing - never 0."""
import numpy as np


def read(ctx, kernel, record="serving/moe/step"):
    r = ctx.reduced
    if not r or ctx.peaks is None:
        return None
    seconds = sum(t for name, t in r["ops"].items() if kernel in name)
    if seconds <= 0:
        return None
    from bigdl_tpu import telemetry

    calls = [s.args for s in telemetry.tracer().spans()
             if s.name == record and s.args]
    fam = ctx.family
    if not calls or not hasattr(fam, "expert_bytes"):
        return None
    itemsize = np.dtype(ctx.traffic.get("weights_dtype",
                                        "float32")).itemsize
    touched = sum(c["experts_touched"] for c in calls)
    pairs = sum(c["local_pairs"] for c in calls)
    least = max(
        touched * fam.expert_bytes(ctx.config, itemsize)
        / ctx.peaks["hbm_bytes_per_s"],
        pairs * fam.pair_flops(ctx.config) / ctx.peaks["flops_per_s_bf16"])
    ctx.log["moe_window"] = {"calls": len(calls),
                             "experts_touched": touched,
                             "local_pairs": pairs,
                             "kernel_s": seconds}
    return 100.0 * least / seconds if least > 0 else None
