"""Mixture-of-Experts FFN: top-k router + capacity-bounded sorted dispatch.

Two dispatch implementations share the same routing math:

* ``sort_scatter`` (paper-era baseline): tokens are argsorted by expert and
  scattered into an (E*C, D) slab with GLOBAL indices.  Under GSPMD the
  data-dependent scatter across mismatched shardings forces the partitioner
  to all-gather the full token stream per MoE layer — measured as the
  dominant collective term in the baseline roofline (EXPERIMENTS.md §Perf
  cell B).

* ``a2a`` (production expert parallelism, §Perf iter B1): a ``shard_map``
  over the mesh keeps tokens data-sharded; each shard routes and packs its
  own (E, C_local, D) slab, an ``all_to_all`` over the expert axis delivers
  per-expert slabs to their owners (GShard/DeepSpeed-MoE pattern), local
  experts run their FFN, and a reverse ``all_to_all`` returns outputs for
  the local combine.  Collectives: exactly 2 A2As of k*S_local*D bytes per
  layer instead of full-stream all-gathers.

Experts shard over the "model" axis (expert parallelism).  Capacity-
overflow tokens are dropped (standard dropping MoE), capacity factor 1.25.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.models.layers import _dense
from repro.models.sharding import current_context

Params = Dict[str, Any]


def moe_init(key, cfg: ModelConfig) -> Params:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.moe_experts
    ks = jax.random.split(key, 4)
    p: Params = {
        "router": _dense(ks[0], D, (D, E), jnp.float32),
        "wo": _dense(ks[3], F, (E, F, D), cfg.dtype),
    }
    if cfg.ffn in ("swiglu", "geglu"):
        p["wi"] = _dense(ks[1], D, (E, D, F), cfg.dtype)
        p["wg"] = _dense(ks[2], D, (E, D, F), cfg.dtype)
    else:
        p["wi"] = _dense(ks[1], D, (E, D, F), cfg.dtype)
    return p


def moe_spec(cfg: ModelConfig) -> Params:
    p: Params = {
        "router": P(None, None),
        "wo": P("model", None, "fsdp"),
        "wi": P("model", "fsdp", None),
    }
    if cfg.ffn in ("swiglu", "geglu"):
        p["wg"] = P("model", "fsdp", None)
    return p


def capacity(cfg: ModelConfig, n_tokens: int) -> int:
    c = int(cfg.moe_capacity * cfg.moe_topk * n_tokens / cfg.moe_experts)
    return max(8, -(-c // 8) * 8)  # round up to a lane-friendly multiple


# ---------------------------------------------------------------------------
# Shared routing / dispatch / combine math (operates on a flat token array).
# ---------------------------------------------------------------------------
def _route(xf: jax.Array, router: jax.Array, E: int, k: int, C: int):
    """Top-k routing with capacity positions via stable sort."""
    S = xf.shape[0]
    logits = jnp.einsum("sd,de->se", xf.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)                    # (S,E)
    topv, topi = jax.lax.top_k(logits, k)                      # (S,k)
    weights = jax.nn.softmax(topv, axis=-1)                    # renormalized

    fe = topi.reshape(-1)                                      # (S*k,)
    order = jnp.argsort(fe, stable=True)
    fe_sorted = fe[order]
    counts = jnp.zeros((E,), jnp.int32).at[fe].add(1)
    starts = jnp.cumsum(counts) - counts                       # (E,)
    pos = jnp.arange(S * k, dtype=jnp.int32) - starts[fe_sorted]
    keep = pos < C
    dest = jnp.where(keep, fe_sorted * C + pos, E * C)         # E*C = dropped
    tok = order // k                                           # source token
    wslot = (weights.reshape(-1)[order] * keep)                # (S*k,)
    return dest, tok, wslot, keep, counts, probs


def _expert_ffn(slab: jax.Array, p: Params, cfg: ModelConfig) -> jax.Array:
    """(E?, C?, D) slab -> (E?, C?, D) through each expert's FFN."""
    h = jnp.einsum("ecd,edf->ecf", slab, p["wi"])
    if cfg.ffn in ("swiglu", "geglu"):
        g = jnp.einsum("ecd,edf->ecf", slab, p["wg"])
        act = jax.nn.silu if cfg.ffn == "swiglu" else jax.nn.gelu
        h = act(g.astype(jnp.float32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(jnp.float32)).astype(h.dtype)
    return jnp.einsum("ecf,efd->ecd", h, p["wo"])


def _aux_loss(counts: jax.Array, probs: jax.Array, E: int) -> jax.Array:
    """Switch-style load-balance loss from local routing statistics."""
    S_k = jnp.maximum(counts.sum(), 1)
    me = probs.mean(axis=0)
    ce = counts.astype(jnp.float32) / S_k.astype(jnp.float32)
    return E * jnp.sum(me * ce)


def _moe_local(xf: jax.Array, p: Params, cfg: ModelConfig, C: int
               ) -> Tuple[jax.Array, jax.Array]:
    """The sort-scatter data path on one (logical) shard of tokens."""
    S, D = xf.shape
    E, k = cfg.moe_experts, cfg.moe_topk
    dest, tok, wslot, keep, counts, probs = _route(xf, p["router"], E, k, C)
    slab = jnp.zeros((E * C, D), xf.dtype).at[dest].set(xf[tok], mode="drop")
    ye = _expert_ffn(slab.reshape(E, C, D), p, cfg).reshape(E * C, D)
    gathered = ye[jnp.where(keep, dest, 0)] * wslot.astype(xf.dtype)[:, None]
    y = jnp.zeros((S, D), xf.dtype).at[tok].add(gathered)
    return y, _aux_loss(counts, probs, E)


# ---------------------------------------------------------------------------
# Entry point: pick the dispatch implementation.
# ---------------------------------------------------------------------------
def moe_forward(p: Params, x: jax.Array, cfg: ModelConfig
                ) -> Tuple[jax.Array, jax.Array]:
    """x: (B,T,D) -> (y, aux_loss)."""
    ctx = current_context()
    if cfg.moe_impl == "a2a" and ctx is not None:
        rules, mesh = ctx
        out = _moe_forward_a2a(p, x, cfg, rules, mesh)
        if out is not None:
            return out
    B, T, D = x.shape
    xf = x.reshape(B * T, D)
    y, aux = _moe_local(xf, p, cfg, capacity(cfg, B * T))
    return y.reshape(B, T, D), aux


def _rule_axes(rules, key) -> Tuple[str, ...]:
    v = rules.get(key)
    if v is None:
        return ()
    return v if isinstance(v, tuple) else (v,)


def _moe_forward_a2a(p: Params, x: jax.Array, cfg: ModelConfig, rules, mesh
                     ) -> Optional[Tuple[jax.Array, jax.Array]]:
    """GShard-style expert parallelism over the mesh's expert axis.

    Returns None (caller falls back to sort_scatter) when the expert count
    does not divide the expert axis or no expert axis is mapped.
    """
    B, T, D = x.shape
    E = cfg.moe_experts
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ex = [a for a in _rule_axes(rules, "expert")
          if a in sizes and E % sizes[a] == 0 and sizes[a] > 1]
    if not ex:
        return None
    ex_ax = ex[0]
    G = sizes[ex_ax]

    # Token sharding inside the MoE region: batch over the data axes AND —
    # crucially — over the expert axis itself (else every device in a
    # model-axis row routes the SAME tokens and expert compute + A2A
    # duplicate G-fold; §Perf iter B2).  Batch first; if B does not divide,
    # shard the sequence dim over the expert axis instead.
    dp = []
    cur = 1
    for a in (*_rule_axes(rules, "batch"), ex_ax):
        if a in dp or a not in sizes:
            continue
        if B % (cur * sizes[a]) == 0:
            dp.append(a)
            cur *= sizes[a]
    seq_ax = None
    if ex_ax not in dp and T % G == 0:
        seq_ax = ex_ax
    B_loc = B // cur
    T_loc = T // (G if seq_ax else 1)
    S_loc = B_loc * T_loc
    C = capacity(cfg, S_loc)
    x_spec = P(tuple(dp) if dp else None, seq_ax, None)
    w_spec = P(ex_ax, None, None)
    has_wg = "wg" in p

    def local(xl, router, wi, wg, wo):
        pl = {"router": router, "wi": wi, "wo": wo}
        if has_wg:
            pl["wg"] = wg
        Bl, Tl, Dl = xl.shape
        xf = xl.reshape(Bl * Tl, Dl)
        dest, tok, wslot, keep, counts, probs = _route(
            xf, router, E, cfg.moe_topk, C)
        slab = jnp.zeros((E * C, Dl), xf.dtype).at[dest].set(
            xf[tok], mode="drop").reshape(E, C, Dl)
        # -> expert owners: (E, C, D) -> (E/G, G*C, D)
        slab = jax.lax.all_to_all(slab, ex_ax, 0, 1, tiled=True)
        ye = _expert_ffn(slab, pl, cfg)
        # back to token owners: (E/G, G*C, D) -> (E, C, D)
        ye = jax.lax.all_to_all(ye, ex_ax, 1, 0, tiled=True)
        ye = ye.reshape(E * C, Dl)
        gathered = ye[jnp.where(keep, dest, 0)] * wslot.astype(
            xf.dtype)[:, None]
        y = jnp.zeros((Bl * Tl, Dl), xf.dtype).at[tok].add(gathered)
        aux = _aux_loss(counts, probs, E)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return y.reshape(Bl, Tl, Dl), aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()), check_vma=False)
    wg = p["wg"] if has_wg else p["wi"]
    return fn(x, p["router"], p["wi"], wg, p["wo"])
