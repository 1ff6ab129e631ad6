"""Device time of attention in one train step: the operations under the
program's ``attention`` scope (forward, backward and remat recompute),
by the compiled step's HLO, over the window's steps, in ms."""


def read(ctx):
    return (ctx.scopes or {}).get("attention") or None
