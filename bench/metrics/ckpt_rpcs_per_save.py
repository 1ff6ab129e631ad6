"""RPC events the program's ledger recorded during each partner save,
over the window's saves."""


def read(ctx):
    saves = len(ctx.window.save_s)
    return ctx.window.save_rpcs / saves if saves else None
