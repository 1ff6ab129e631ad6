"""The four Pallas kernels compile for a TPU v5e at real model widths.

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


@pytest.mark.parametrize("build", [_flash, _ssm, _rglru, _quantize],
                         ids=["flash_attention", "ssm_scan", "rglru",
                              "quantize"])
def test_kernel_compiles_for_v5e(one_chip, build):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = build(spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
