"""Compile-only guards: the serving path's Pallas kernels at olmo-1b widths,
compiled for a described (not attached) TPU v5e.

Interpret mode never checks TPU tiling or VMEM limits, so a kernel can pass
every interpret-mode test and still be refused by the chip's compiler.  These
tests run that compiler on a ``v5e:2x2`` topology description: nothing
executes, only the lowering and the Mosaic compile.  The topology is
described inside a fixture (never at import), so every pytest worker
collects the same tests and only the worker that runs this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import (
    paged_decode_attention_bt_kernel_call, paged_decode_attention_kernel_call)
from repro.kernels.flash_attention import flash_attention

# olmo-1b serving shapes (configs/olmo_1b.py): 16 heads, 16 KV heads,
# head_dim 128; 8 decode slots over a 2048-row cache of 128-row blocks
B, H, KH, D, S, BS = 8, 16, 16, 128, 2048, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                      # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # compiles for a described chip land in the persistent cache but can
    # never be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _decode_shapes(sh, dtype):
    q = jax.ShapeDtypeStruct((B, H, D), jnp.bfloat16, sharding=sh)
    lens = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=sh)
    kv = jax.ShapeDtypeStruct((B, S, KH, D), dtype, sharding=sh)
    scale = jax.ShapeDtypeStruct((B, S, KH), jnp.float32, sharding=sh)
    return q, lens, kv, scale


def _pool_shapes(sh, dtype):
    nb = S // BS
    pool = jax.ShapeDtypeStruct((B * nb + 8, BS, KH, D), dtype, sharding=sh)
    scale = jax.ShapeDtypeStruct((B * nb + 8, BS, KH), jnp.float32,
                                 sharding=sh)
    tables = jax.ShapeDtypeStruct((B, nb), jnp.int32, sharding=sh)
    return pool, scale, tables


@pytest.mark.parametrize("kw", [dict(), dict(window=512, softcap=30.0)],
                         ids=["plain", "window_softcap"])
def test_paged_decode_compiles(one_chip, kw):
    q, lens, kv, _ = _decode_shapes(one_chip, jnp.bfloat16)
    hlo = _compile(lambda q, k, v, n: paged_decode_attention_kernel_call(
        q, k, v, n, interpret=False, **kw), q, kv, kv, lens)
    assert "tpu_custom_call" in hlo


def test_paged_decode_int8_compiles(one_chip):
    q, lens, kv, scale = _decode_shapes(one_chip, jnp.int8)
    hlo = _compile(lambda q, k, v, n, ks, vs:
                   paged_decode_attention_kernel_call(
                       q, k, v, n, k_scale=ks, v_scale=vs, interpret=False),
                   q, kv, kv, lens, scale, scale)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_paged_decode_block_table_compiles(one_chip, dtype):
    q, lens, _, _ = _decode_shapes(one_chip, dtype)
    pool, scale, tables = _pool_shapes(one_chip, dtype)
    if dtype == jnp.int8:
        def fn(q, k, v, n, t, ks, vs):
            return paged_decode_attention_bt_kernel_call(
                q, k, v, n, t, k_scale=ks, v_scale=vs, interpret=False)
        hlo = _compile(fn, q, pool, pool, lens, tables, scale, scale)
    else:
        hlo = _compile(lambda q, k, v, n, t:
                       paged_decode_attention_bt_kernel_call(
                           q, k, v, n, t, interpret=False),
                       q, pool, pool, lens, tables)
    assert "tpu_custom_call" in hlo


def test_paged_decode_block_table_compiles_at_lfm2_widths(one_chip):
    """lfm2-8b-a1b's serving shapes: GQA 32/8 heads of 64 over the pool of
    its 6 attention layers (192 blocks of 128 rows each), 12 slots of 2048
    rows."""
    b, nb = 12, 16
    q = jax.ShapeDtypeStruct((b, 32, 64), jnp.bfloat16, sharding=one_chip)
    lens = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one_chip)
    pool = jax.ShapeDtypeStruct((6 * 192, 128, 8, 64), jnp.bfloat16,
                                sharding=one_chip)
    tables = jax.ShapeDtypeStruct((b, nb), jnp.int32, sharding=one_chip)
    hlo = _compile(lambda q, k, v, n, t: paged_decode_attention_bt_kernel_call(
        q, k, v, n, t, interpret=False), q, pool, pool, lens, tables)
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, H, S, D), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, KH, S, D), jnp.bfloat16, sharding=one_chip)
    hlo = _compile(lambda q, k, v: flash_attention(q, k, v, interpret=False),
                   q, kv, kv)
    assert "tpu_custom_call" in hlo
