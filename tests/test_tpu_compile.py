"""Compile-only checks for a described TPU v5e: the kernels of the two hot
paths, at real widths, through the TPU compiler with no chip attached.

Nothing runs, so these say nothing about results or speed — they catch
what interpret mode cannot: a kernel the chip's compiler refuses (an
int16 MXU accumulator, int32 MXU operands, misaligned tiles, too much
VMEM).  The topology is described inside a fixture, never at import: only
one process may load the TPU compiler library, and every test worker
imports this file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models.attention import init_attention, paged_attention


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


# im2col (M, K, N) of CNV-w2a2 / RN8-w3a3 convs at batch 256: the first
# conv (3 input channels, padded 27 -> 128) and the widest one
IM2COL = {"conv1": (65536, 27, 16), "conv_wide": (4096, 576, 64)}


@pytest.mark.parametrize("layer", list(IM2COL))
@pytest.mark.parametrize("dequant", [False, True])
@pytest.mark.parametrize("acc_bits", [12, 32])
def test_int_matmul_compiles(one_chip, layer, dequant, acc_bits):
    M, K, N = IM2COL[layer]
    out_dtype = jnp.float32 if dequant else None

    def fn(x, w, s, b):
        return ops.int_matmul(x, w, s if dequant else None,
                              b if dequant else None, acc_bits=acc_bits,
                              out_dtype=out_dtype, use_pallas=True,
                              interpret=False)
    c = _compile(fn, ((M, K), jnp.int8), ((K, N), jnp.int8),
                 ((N,), jnp.float32), ((N,), jnp.float32),
                 sharding=one_chip)
    _assert_kernel(c)
    want = (jnp.float32 if dequant
            else jnp.int16 if acc_bits <= 15 else jnp.int32)
    assert c.out_info.dtype == want


@pytest.mark.parametrize("n_thr", [3, 7, 15, 255])
@pytest.mark.parametrize("cmp_dtype", [jnp.int32, jnp.float32])
def test_multithreshold_compiles(one_chip, cmp_dtype, n_thr):
    M, C = 16384, 32
    fn = functools.partial(ops.multithreshold, out_bias=0,
                           out_dtype=jnp.int32, use_pallas=True,
                           interpret=False)
    _assert_kernel(_compile(fn, ((M, C), cmp_dtype), ((n_thr, C), cmp_dtype),
                            sharding=one_chip))


@pytest.mark.parametrize("out_dtype", [jnp.int32, jnp.int8])
def test_quantize_compiles(one_chip, out_dtype):
    M, C = 12288, 16
    fn = functools.partial(ops.quantize, qmin=-2, qmax=1,
                           out_dtype=out_dtype, use_pallas=True,
                           interpret=False)
    _assert_kernel(_compile(fn, ((M, C), jnp.float32), ((1,), jnp.float32),
                            ((1,), jnp.float32), sharding=one_chip))


@pytest.mark.parametrize("pool_dtype", [jnp.int8, jnp.bfloat16])
def test_paged_attention_layer_compiles(one_chip, pool_dtype):
    """One full-width qwen2-1.5b attention layer of a decode step: 8 slots
    x 2048 max_seq in 8-token pages."""
    cfg = get_config("qwen2-1.5b")
    B, T, page, max_seq = 8, 1, 8, 2048
    n_pages = max_seq // page
    P = B * n_pages + 1
    KV, hd = cfg.n_kv_heads, cfg.hd
    pshapes = jax.eval_shape(
        lambda k: init_attention(k, cfg.d_model, cfg.n_heads, KV, hd,
                                 cfg.qkv_bias, cfg.dtype),
        jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        pshapes)
    scale = np.full((KV,), 0.05, np.float32)
    int8 = pool_dtype == jnp.int8

    def step(p, x, kp, vp, table, lens):
        return paged_attention(
            p, x, kp, vp, table, lens, n_heads=cfg.n_heads, n_kv=KV, hd=hd,
            theta=cfg.rope_theta, page_size=page,
            k_scale=scale if int8 else None, v_scale=scale if int8 else None)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    c = jax.jit(step).lower(
        params, sds((B, T, cfg.d_model), cfg.dtype),
        sds((P, page, KV, hd), pool_dtype), sds((P, page, KV, hd), pool_dtype),
        sds((B, n_pages), jnp.int32), sds((B,), jnp.int32)).compile()
    y, kp, _ = c.out_info
    assert y.shape == (B, T, cfg.d_model) and kp.dtype == pool_dtype


def test_copy_page_is_in_place_at_qwen2_width(one_chip):
    """The prefix cache's page copy over every pool of full-width
    qwen2-1.5b (16 slots x 4096 in 8-token pages, one layer int8): the
    donated pools alias the outputs and the copy needs no buffer of its
    own, so it moves one page, not 1.88 GB of pools."""
    from repro.serve.kv_cache import copy_page
    cfg = get_config("qwen2-1.5b")
    P = 16 * 4096 // 8 + 1

    def pool(dtype):
        return jax.ShapeDtypeStruct((P, 8, cfg.n_kv_heads, cfg.hd), dtype,
                                    sharding=one_chip)
    pages = [{"k": pool(d), "v": pool(d)}
             for d in [jnp.int8] + [cfg.dtype] * (cfg.n_layers - 1)]
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    m = copy_page.lower(pages, i, i).compile().memory_analysis()
    page_bytes = 8 * cfg.n_kv_heads * cfg.hd * 2
    assert m.alias_size_in_bytes > 1e9
    assert m.output_size_in_bytes - m.alias_size_in_bytes < page_bytes
    assert m.temp_size_in_bytes < page_bytes
