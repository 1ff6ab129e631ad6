"""Attention's share of its roofline (``bench/roofline.py``): the least
time its scores and weighted sums need per step, by the FLOPs and the
HBM bytes that the configuration's reference counts (else
``bench/flops.py``), over ``attn_ms``, in %.  Remat recompute is in the
time, not in the FLOPs."""

from bench import flops, roofline


def read(ctx):
    ms = (ctx.scopes or {}).get("attention")
    if not ms:
        return None
    args = (ctx.model, ctx.job["batch"], ctx.job["seq"])
    count = getattr(ctx.ref, "attention_flops_per_step",
                    flops.attention_flops_per_step)
    nbytes = getattr(ctx.ref, "attention_bytes_per_step",
                     flops.attention_bytes_per_step)
    return roofline.share(ms / 1e3, count(*args), nbytes(*args), ctx.peak)
