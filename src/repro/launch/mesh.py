"""Production meshes + sharding binding for every (arch × shape) cell.

``make_production_mesh`` is a FUNCTION (never a module constant) so that
importing this module touches no jax device state; the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import and only then calls it.

Mesh shapes: single pod (16, 16) = 256 chips ("data", "model");
multi-pod (2, 16, 16) = 512 chips ("pod", "data", "model") — the pod
axis composes with data parallelism (cross-pod gradient all-reduce,
DCN-like in real deployments).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig, ShapeCell
from repro.models.frontends import extra_inputs
from repro.models.sharding import Rules, resolve_tree, rules_for
from repro.models.sharding import resolve_spec  # noqa: F401  (re-export)
from repro.train.optimizer import AdamWConfig, opt_state_specs


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code places activations with
    # with_sharding_constraint, which only refers to Auto axes.
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def arch_rules(cfg: ModelConfig, multi_pod: bool) -> Rules:
    return rules_for(cfg.policy, multi_pod, fsdp=cfg.fsdp)


def opt_for(cfg: ModelConfig) -> AdamWConfig:
    return AdamWConfig(state_dtype=cfg.opt_state_dtype)


# ---------------------------------------------------------------------------
# Abstract state/batch + bound shardings
# ---------------------------------------------------------------------------
def abstract_params(cfg: ModelConfig):
    return jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg))


def abstract_state(cfg: ModelConfig):
    from repro.train.train_step import train_state_init
    return jax.eval_shape(
        lambda: train_state_init(jax.random.PRNGKey(0), cfg, opt_for(cfg)))


def state_spec_tree(cfg: ModelConfig):
    pspec = T.param_specs(cfg)
    return {"params": pspec, "opt": opt_state_specs(pspec), "step": P()}


def params_shardings(cfg: ModelConfig, mesh, rules: Rules):
    return resolve_tree(T.param_specs(cfg), abstract_params(cfg), rules, mesh)


def state_shardings(cfg: ModelConfig, mesh, rules: Rules):
    return resolve_tree(state_spec_tree(cfg), abstract_state(cfg),
                        rules, mesh)


def batch_abstract(cfg: ModelConfig, cell: ShapeCell) -> Dict[str, Any]:
    B, S = cell.global_batch, cell.seq_len
    out = {
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
    }
    out.update(extra_inputs(cfg, B))        # abstract frames / patches
    return out


def batch_shardings(cfg: ModelConfig, cell: ShapeCell, mesh, rules: Rules
                    ) -> Dict[str, Any]:
    ab = batch_abstract(cfg, cell)
    spec = {k: P(*(["batch"] + [None] * (v.ndim - 1)))
            for k, v in ab.items()}
    return resolve_tree(spec, ab, rules, mesh)


def cache_abstract(cfg: ModelConfig, cell: ShapeCell):
    return jax.eval_shape(
        lambda: T.init_cache(cfg, cell.global_batch, cell.seq_len))


def cache_shardings(cfg: ModelConfig, cell: ShapeCell, mesh, rules: Rules):
    return resolve_tree(T.cache_specs(cfg), cache_abstract(cfg, cell),
                        rules, mesh)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())
