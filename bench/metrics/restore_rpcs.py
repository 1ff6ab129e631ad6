"""RPC events the program's ledger recorded during each restore from the
partner copy, over the window's restores."""


def read(ctx):
    restores = len(ctx.window.resume_s)
    return ctx.window.restore_rpcs / restores if restores else None
