"""Mean time of the program's ``ckpt.save.write`` span: each partner
save's shard and partner writes through the layer, in s."""

from bench import layers


def read(ctx):
    if ctx.telemetry is None:
        return None
    return layers.host_numbers(ctx.telemetry.spans,
                               ctx.telemetry.counters).get("save_write_s")
