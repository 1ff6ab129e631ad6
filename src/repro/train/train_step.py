"""The train step: microbatched gradient accumulation + AdamW.

``make_train_step(cfg, opt, num_microbatches)`` returns a pure function
``train_step(state, batch) -> (state, metrics)`` ready for ``jax.jit``
with shardings from :func:`repro.launch.mesh.state_shardings`.

* Gradient accumulation is a ``lax.scan`` over microbatches — activation
  memory is one microbatch deep; gradients accumulate in fp32-or-policy
  dtype buffers that shard like the parameters.
* The model forward already checkpoints each super-block (``cfg.remat``),
  so peak activation = one super-block of one microbatch + saved block
  inputs along the layer scan.
* MoE aux (load-balance) loss folds in with weight ``aux_weight``.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.models.sharding import shard_tree
from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update

TrainState = Dict[str, Any]  # {"params": ..., "opt": ..., "step": int32}


def train_state_init(key, cfg: ModelConfig, opt: AdamWConfig) -> TrainState:
    params = T.init_params(key, cfg)
    return {"params": params, "opt": adamw_init(params, opt),
            "step": jnp.zeros((), jnp.int32)}


def loss_fn(params, batch: Dict[str, jax.Array], cfg: ModelConfig,
            aux_weight: float = 0.01) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Causal-LM cross entropy.  batch: tokens, labels (+frames/patches)."""
    with jax.named_scope("forward"):
        extras = {k: batch[k] for k in ("frames", "patches") if k in batch}
        logits, aux = T.forward(params, batch["tokens"], cfg, **extras)
        labels = batch["labels"]
        Tl = labels.shape[1]
        logits = logits[:, -Tl:].astype(jnp.float32)  # vision prefix cut off
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        ntok = jnp.maximum(mask.sum(), 1.0)
        ce = jnp.sum((logz - gold) * mask) / ntok
        loss = ce + aux_weight * aux
        return loss, {"ce": ce, "moe_aux": aux}


def make_train_step(cfg: ModelConfig, opt: AdamWConfig,
                    num_microbatches: int = 1, aux_weight: float = 0.01):
    """Build the jit-able train step for this arch."""

    grad_fn = jax.value_and_grad(
        functools.partial(loss_fn, cfg=cfg, aux_weight=aux_weight),
        has_aux=True,
    )

    def train_step(state: TrainState, batch: Dict[str, jax.Array]
                   ) -> Tuple[TrainState, Dict[str, jax.Array]]:
        params = state["params"]
        M = num_microbatches

        pspecs = T.param_specs(cfg)
        if M == 1:
            (loss, aux), grads = grad_fn(params, batch)
            grads = shard_tree(grads, pspecs)
        else:
            def split(x):
                return x.reshape((M, x.shape[0] // M) + x.shape[1:])
            mbatch = jax.tree.map(split, batch)
            gzero = shard_tree(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params), pspecs)

            def mb_step(carry, mb):
                gacc, lacc, aacc = carry
                (lval, a), g = grad_fn(params, mb)
                # Pin each microbatch's contribution to the parameter
                # sharding: the cross-data reduction becomes a
                # reduce-scatter into the fsdp shard, not a full-gradient
                # all-reduce (§Perf iter C1).
                gacc = shard_tree(jax.tree.map(
                    lambda acc, gi: acc + gi.astype(jnp.float32) / M,
                    gacc, g), pspecs)
                return (gacc, lacc + lval / M, aacc + a["ce"] / M), None

            # Checkpoint the microbatch body: the scan VJP otherwise saves
            # every microbatch's full layer-input stack (M x depth x B_mb x
            # T x D) — 8x the activation budget at 405B (§Perf iter C2).
            (grads, loss, ce), _ = jax.lax.scan(
                jax.checkpoint(mb_step),
                (gzero, jnp.zeros(()), jnp.zeros(())), mbatch)
            aux = {"ce": ce, "moe_aux": jnp.zeros(())}

        with jax.named_scope("optimizer"):
            newp, newopt, om = adamw_update(grads, state["opt"], params, opt)
        metrics = {"loss": loss, **aux, **om, "step": state["step"] + 1}
        return (
            {"params": newp, "opt": newopt, "step": state["step"] + 1},
            metrics,
        )

    return train_step
