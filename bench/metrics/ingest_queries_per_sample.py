"""Query RPCs that ingest made per sample it fed in the window: the
program's counters ``ingest.queries`` over ``ingest.samples``."""

from bench import layers


def read(ctx):
    if ctx.telemetry is None:
        return None
    return layers.host_numbers(
        ctx.telemetry.spans,
        ctx.telemetry.counters).get("ingest_queries_per_sample")
