"""Host time the job spent fetching each batch through ``TokenPipeline``
(the store's reads over the consistency layer, the stack and the copy to
the device), over the window's steps, in ms."""


def read(ctx):
    spans = ctx.window.spans.get("ingest")
    if not spans or not ctx.window.steps:
        return None
    return sum(spans) / ctx.window.steps * 1e3
