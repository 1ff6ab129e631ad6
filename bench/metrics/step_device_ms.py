"""Device time of one execution of the jitted train step: the train-step
module's time in the traced window over its executions there, in ms."""


def read(ctx):
    if ctx.trace is None or not ctx.trace.step_count:
        return None
    return ctx.trace.step_ns / ctx.trace.step_count / 1e6
