"""Model FLOPs of one step (``bench/flops.py``) over the train step's
device time, as a share of the chip's bf16 peak (``bench/peaks.json``),
in %."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.step_count:
        return None
    if ctx.peak is None:
        raise ValueError("the device is not in bench/peaks.json")
    step_s = ctx.trace.step_ns / ctx.trace.step_count / 1e9
    return ctx.flops_per_step / step_s / ctx.peak["bf16_flop_per_s"] * 100
