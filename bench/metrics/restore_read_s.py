"""Mean time of the program's ``ckpt.restore.read`` span: each
restore's layer reads and the copies of their payloads into the leaves,
in s."""

from bench import layers


def read(ctx):
    if ctx.telemetry is None:
        return None
    return layers.host_numbers(ctx.telemetry.spans,
                               ctx.telemetry.counters).get("restore_read_s")
