"""Every Pallas kernel compiles for one described TPU v5e chip at real widths.

Interpret mode (what every other kernel test runs) checks neither Mosaic's
tiling rules nor its lowering, so a kernel can pass all of them and still be
refused by the chip's compiler.  These tests lower each kernel with
``interpret=False`` against a ``v5e:2x2`` topology that is described, not
attached, and compile it with the TPU compiler installed alongside JAX: no
chip is needed and nothing runs.  A compile that passes is not a chip run.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and the test workers all import
this file.  The persistent compilation cache is off around these compiles,
since an entry written for a described chip cannot be read back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_arch
from repro.kernels import compress, flash_attention, fused_adam, \
    paged_attention, rg_lru, ssd_scan, wavg

MAMBA = get_arch("mamba2-370m")
# one mamba2-370m client-stage leaf: the (tied) embedding, per client
LEAF = MAMBA.vocab_size * MAMBA.d_model
CLIENTS = 4


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _flash(hq, hkv, hd):
    def case(spec):
        q = spec((1, hq, 2048, hd), jnp.bfloat16)
        kv = spec((1, hkv, 2048, hd), jnp.bfloat16)
        return (lambda q, k, v: flash_attention.flash_attention_bhsd(q, k, v),
                (q, kv, kv))
    return case


def _paged(hq, hkv, hd, bs=16, pool=256, nb=32, b=8):
    def case(spec):
        kv = spec((pool, bs, hkv, hd), jnp.bfloat16)
        return (paged_attention.paged_decode_attention,
                (spec((b, hq, hd), jnp.bfloat16), kv, kv,
                 spec((pool, bs), jnp.int32), spec((b, nb), jnp.int32),
                 spec((b,), jnp.int32)))
    return case


def _ssd(spec):
    h, p, n = MAMBA.ssm_heads, MAMBA.ssm_head_dim, MAMBA.ssm_state
    b, s = 2, 1024
    return (lambda x, dt, a, b_, c_: ssd_scan.ssd_scan(
                x, dt, a, b_, c_, chunk=MAMBA.ssm_chunk, block_h=8),
            (spec((b, s, h, p), jnp.bfloat16), spec((b, s, h)), spec((h,)),
             spec((b, s, n), jnp.bfloat16), spec((b, s, n), jnp.bfloat16)))


def _rg_lru(spec):
    x = spec((2, 1024, 2560))
    return rg_lru.rg_lru_scan, (x, x)


def _wavg(spec):
    return wavg.weighted_average_2d, (spec((CLIENTS, LEAF)),
                                      spec((CLIENTS,)))


def _fused_adam(spec):
    p = spec((CLIENTS, LEAF))
    return fused_adam.fused_adamw_2d, (p, p, p, p, spec((CLIENTS,)),
                                       spec((9,)))


def _quantize(spec):
    x = spec((CLIENTS, LEAF))
    return (lambda x, u, inv: compress.quantize_stochastic_2d(
                x, u, inv, jnp.float32(127.0)),
            (x, x, spec((CLIENTS,))))


def _dequantize(spec):
    return compress.dequantize_2d, (spec((CLIENTS, LEAF), jnp.int8),
                                    spec((CLIENTS,)))


def _topk(spec):
    return compress.topk_mask_2d, (spec((CLIENTS, LEAF)), spec((CLIENTS,)))


CASES = {
    "flash-24x64": _flash(24, 24, 64),
    "flash-gqa-32/8x128": _flash(32, 8, 128),
    "paged-8x128-bs16": _paged(32, 8, 128),
    "paged-24x64-bs16": _paged(24, 24, 64),
    "ssd-mamba2-370m": _ssd,
    "rg-lru-2560": _rg_lru,
    "wavg-mamba2-leaf": _wavg,
    "fused-adam-mamba2-leaf": _fused_adam,
    "quantize-mamba2-leaf": _quantize,
    "dequantize-mamba2-leaf": _dequantize,
    "topk-mask-mamba2-leaf": _topk,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    def spec(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    fn, args = CASES[name](spec)
    compiled = jax.jit(fn).lower(*args).compile()
    # the kernel is really in the program, as a Mosaic custom call
    assert "tpu_custom_call" in compiled.as_text()
