"""Ahead-of-time TPU v5e compiles of every Pallas kernel at published
widths.  Interpret mode (tests/test_kernels.py) checks the math; only the
TPU compiler checks block tiling and VMEM use, and it runs here for a
described, unattached chip.  Each compile must contain the kernel
(``tpu_custom_call``): a silent fallback to XLA would pass otherwise."""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_model_config
from repro.kernels.flash_attention import (cache_update, cache_update_paged,
                                           flash_attention, flash_decode,
                                           flash_decode_paged)
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.ssd import ssd

MINITRON = get_model_config("minitron-4b")   # 24 q heads, 8 kv, head_dim 128
ZAMBA2 = get_model_config("zamba2-1.2b")     # SSD heads 64 x 64, state 64
OLMOE = get_model_config("olmoe-1b-7b")      # 64 experts, d 2048, d_ff 1024
BATCH, BLOCK = 8, 16


@pytest.fixture(scope="module")
def chip():
    """One device of a described v5e:2x2 host (skips where libtpu cannot
    describe it).  The persistent compile cache is off meanwhile: these
    compiles could be written to it but not read back without a chip."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no libtpu, or it cannot load here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_compiles(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles(chip):
    c = MINITRON
    q = _sds((1, 4096, c.n_heads, c.head_dim), jnp.bfloat16, chip)
    kv = _sds((1, 4096, c.n_kv_heads, c.head_dim), jnp.bfloat16, chip)
    _kernel_compiles(lambda q, k, v: flash_attention(q, k, v), q, kv, kv)


@pytest.mark.parametrize("sq", [1, 256])
def test_flash_decode_compiles(chip, sq):
    c = MINITRON
    q = _sds((BATCH, sq, c.n_heads, c.head_dim), jnp.bfloat16, chip)
    cache = _sds((BATCH, 4096, c.n_kv_heads, c.head_dim), jnp.bfloat16,
                 chip)
    lens = _sds((BATCH,), jnp.int32, chip)
    _kernel_compiles(lambda q, k, v, n: flash_decode(q, k, v, n),
                     q, cache, cache, lens)


@pytest.mark.parametrize("sq", [1, 256])
def test_flash_decode_paged_compiles(chip, sq):
    """The engine's path: a layer-stacked pool read at a traced layer."""
    c = MINITRON
    max_blocks = 4096 // BLOCK
    q = _sds((BATCH, sq, c.n_heads, c.head_dim), jnp.bfloat16, chip)
    pool = _sds((c.n_layers, BATCH * max_blocks + 1, BLOCK, c.n_kv_heads,
                 c.head_dim), jnp.bfloat16, chip)
    lens = _sds((BATCH,), jnp.int32, chip)
    tables = _sds((BATCH, max_blocks), jnp.int32, chip)
    layer = _sds((), jnp.int32, chip)
    _kernel_compiles(
        lambda q, k, v, n, t, ly: flash_decode_paged(q, k, v, n, t, ly),
        q, pool, pool, lens, tables, layer)


@pytest.mark.parametrize("max_seq", [4096, 32768])
def test_cache_update_compiles(chip, max_seq):
    c = MINITRON
    cache = _sds((BATCH, max_seq, c.n_kv_heads, c.head_dim), jnp.bfloat16,
                 chip)
    new = _sds((BATCH, 1, c.n_kv_heads, c.head_dim), jnp.bfloat16, chip)
    idx = _sds((BATCH,), jnp.int32, chip)
    _kernel_compiles(lambda kc, vc, kn, vn, i: cache_update(kc, vc, kn, vn, i),
                     cache, cache, new, new, idx)


@pytest.mark.parametrize("sn", [1, 256])
def test_cache_update_paged_compiles(chip, sn):
    c = MINITRON
    max_blocks = 4096 // BLOCK
    pool = _sds((c.n_layers, BATCH * max_blocks + 1, BLOCK, c.n_kv_heads,
                 c.head_dim), jnp.bfloat16, chip)
    new = _sds((BATCH, sn, c.n_kv_heads, c.head_dim), jnp.bfloat16, chip)
    idx = _sds((BATCH,), jnp.int32, chip)
    tables = _sds((BATCH, max_blocks), jnp.int32, chip)
    layer = _sds((), jnp.int32, chip)
    _kernel_compiles(
        lambda kp, vp, kn, vn, i, t, ly: cache_update_paged(kp, vp, kn, vn,
                                                            i, t, ly),
        pool, pool, new, new, idx, tables, layer)


def test_ssd_compiles(chip):
    c = ZAMBA2
    S = 4096
    x = _sds((1, S, c.ssm_heads, c.ssm_headdim), jnp.bfloat16, chip)
    dt = _sds((1, S, c.ssm_heads), jnp.bfloat16, chip)
    a = _sds((c.ssm_heads,), jnp.float32, chip)
    bc = _sds((1, S, 1, c.ssm_state), jnp.bfloat16, chip)
    _kernel_compiles(lambda x, dt, a, b, cc: ssd(x, dt, a, b, cc,
                                                 chunk=c.ssm_chunk),
                     x, dt, a, bc, bc)


def test_grouped_matmul_compiles(chip):
    c = OLMOE
    lhs = _sds((c.n_experts, 128, c.d_model), jnp.bfloat16, chip)
    rhs = _sds((c.n_experts, c.d_model, c.d_ff), jnp.bfloat16, chip)
    _kernel_compiles(grouped_matmul, lhs, rhs)
