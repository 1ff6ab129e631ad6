"""The Pallas kernels compile for a TPU v5e at real model widths.

No chip is needed: the TPU compiler describes a v5e and compiles for it
(``interpret=False``), which refuses what the chip would refuse: slices
not aligned to the tiling, unsupported primitives, too much VMEM.  Each
compiled program must hold the kernel as a ``tpu_custom_call``.

The topology is described inside a fixture, never while the module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_config
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.quantize import quantize_pallas
from repro.kernels.rglru_scan import rglru_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _flash(spec):
    cfg = get_config("whisper-small")
    qkv = spec((1, 1024, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    return (lambda q, k, v: flash_attention_pallas(q, k, v, interpret=False),
            (qkv, qkv, qkv))


def _attention_loss(causal):
    def loss(q, k, v):
        with jax.named_scope("attention"):
            o = flash_attention_pallas(q, k, v, causal=causal,
                                       interpret=False)
        return jnp.sum(o.astype(jnp.float32))
    return loss


def _flash_grad(spec):
    """Forward and both backward kernels: whisper-small's encoder
    (1,500 frames, bidirectional, bf16)."""
    cfg = get_config("whisper-small")
    qkv = spec((1, 1500, cfg.n_heads, cfg.head_dim), jnp.bfloat16)
    return (jax.grad(_attention_loss(False), argnums=(0, 1, 2)),
            (qkv, qkv, qkv))


def _flash_grad_causal(spec):
    """GPT-2 small's causal self-attention, f32 with bf16 dot operands."""
    qkv = spec((2, 1024, 12, 64), jnp.float32)
    return (jax.grad(_attention_loss(True), argnums=(0, 1, 2)),
            (qkv, qkv, qkv))


def _flash_grad_wide(head_dim):
    """The largest blocks: 2,048 keys in one kv block, with head_dim 128
    or the kernel's limit of 256, causal, bf16."""
    def build(spec):
        qkv = spec((1, 2048, 4, head_dim), jnp.bfloat16)
        return (jax.grad(_attention_loss(True), argnums=(0, 1, 2)),
                (qkv, qkv, qkv))
    return build


def _ssm(spec):
    cfg = get_config("falcon-mamba-7b")
    T, I, N = 256, cfg.inner, cfg.ssm_state
    seq = spec((1, T, I), jnp.bfloat16)
    return (lambda x, dt, A, B, C, D: ssm_scan_pallas(x, dt, A, B, C, D,
                                                      interpret=False),
            (seq, seq, spec((I, N), jnp.float32), spec((1, T, N), jnp.bfloat16),
             spec((1, T, N), jnp.bfloat16), spec((I,), jnp.float32)))


def _rglru(spec):
    L = get_config("recurrentgemma-9b").lru
    seq = spec((1, 256, L), jnp.bfloat16)
    return (lambda x, a, i, lam: rglru_pallas(x, a, i, lam, interpret=False),
            (seq, seq, seq, spec((L,), jnp.float32)))


def _quantize(spec):
    return (lambda x: quantize_pallas(x, interpret=False),
            (spec((4096, 4096), jnp.float32),))


@pytest.mark.parametrize("build", [_flash, _flash_grad, _flash_grad_causal,
                                   _flash_grad_wide(128),
                                   _flash_grad_wide(256),
                                   _ssm, _rglru, _quantize],
                         ids=["flash_attention", "flash_attention_grad",
                              "flash_attention_grad_causal",
                              "flash_attention_grad_2048_d128",
                              "flash_attention_grad_2048_d256", "ssm_scan",
                              "rglru", "quantize"])
def test_kernel_compiles_for_v5e(one_chip, build):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("build", [_flash_grad, _flash_grad_causal],
                         ids=["bidirectional", "causal"])
def test_attention_kernels_compile_under_the_attention_scope(one_chip,
                                                             build):
    """The forward and both backward kernels are Mosaic custom calls whose
    name stacks carry ``attention``, as the step's attention time reads
    them; the backward's under ``transpose(``."""
    from bench import layers

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(spec)
    text = jax.jit(fn).lower(*args).compile().as_text()
    scopes = layers.hlo_scopes(text)
    kernels = {}
    for line in text.splitlines():
        if "tpu_custom_call" in line and "custom-call(" in line:
            name = line.split("=")[0].replace("ROOT", "").strip().lstrip("%")
            kernels[name.split(".")[0]] = scopes[name]
    assert sorted(kernels) == ["flash_attention_dkv", "flash_attention_dq",
                               "flash_attention_fwd"]
    for name, scope in kernels.items():
        assert "attention" in layers._scope_words(scope), scope
    assert layers.step_part(kernels["flash_attention_dq"]) == "backward"
    assert layers.step_part(kernels["flash_attention_dkv"]) == "backward"
