"""The paged Pallas kernels compiled for a TPU v5e chip that is described,
not attached: Mosaic refuses here what interpret mode never checks (block
tiling, VMEM limits), at the published head widths of the served configs.

Only the fixture below describes the topology, so the TPU library is loaded
by whichever test process runs these tests and by no other.
"""
import jax
import jax.numpy as jnp
import pytest

import dataclasses

from repro.configs import get_arch
from repro.kernels.decode_attention import (
    paged_chunk_attention,
    paged_decode_attention,
)
from repro.models.model import init_params, prefill_packed

ARCHS = ["qwen2.5-3b", "smollm-135m", "phi3-medium-14b"]
POOL_DTYPES = ["bfloat16", "int8", "float32"]
N_BLOCKS, BLOCK, BATCH, MAX_BLOCKS = 64, 16, 4, 8
PACKED = [32, 128]


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _operands(one_chip, arch, pool_dtype, n_q):
    """Shapes for one layer: the K/V pool in ``pool_dtype`` (and, for int8,
    its (n_blocks, KVH) scales) and q (n_q, H, hd) in the compute dtype:
    float32 beside a float32 pool, bfloat16 otherwise."""
    cfg = get_arch(arch)
    H, KVH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    pool = s((N_BLOCKS, BLOCK, KVH, hd), jnp.dtype(pool_dtype))
    scales = {}
    if pool_dtype == "int8":
        scales = dict(k_scale=s((N_BLOCKS, KVH), jnp.float32),
                      v_scale=s((N_BLOCKS, KVH), jnp.float32))
    q_dtype = jnp.float32 if pool_dtype == "float32" else jnp.bfloat16
    return s((n_q, H, hd), q_dtype), pool, s, scales


def _assert_kernel_compiled(fn, name, *args, **kw):
    """The program compiles to exactly one Mosaic custom call, the kernel,
    carrying its own name (``%<name>.N = ...
    custom_call_target="tpu_custom_call"``)."""
    text = jax.jit(fn).lower(*args, **kw).compile().as_text()
    calls = [line.lstrip() for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 1, calls
    assert calls[0].startswith(f"%{name}"), calls[0]


@pytest.mark.parametrize("pool_dtype", POOL_DTYPES)
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_decode_compiles(one_chip, arch, pool_dtype):
    q, pool, s, scales = _operands(one_chip, arch, pool_dtype, BATCH)

    def decode(q, k, v, tables, lengths, k_scale=None, v_scale=None):
        return paged_decode_attention(q, k, v, tables, lengths,
                                      k_scale=k_scale, v_scale=v_scale,
                                      interpret=False)

    _assert_kernel_compiled(decode, "paged_decode_attention", q, pool, pool,
                            s((BATCH, MAX_BLOCKS), jnp.int32),
                            s((BATCH,), jnp.int32), **scales)


@pytest.mark.parametrize("packed", PACKED)
@pytest.mark.parametrize("pool_dtype", POOL_DTYPES)
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_chunk_compiles(one_chip, arch, pool_dtype, packed):
    q, pool, s, scales = _operands(one_chip, arch, pool_dtype, packed)
    per_token = s((packed,), jnp.int32)

    def chunk(q, k, v, tables, row_of, slots, p_end, s_start, k_scale=None,
              v_scale=None):
        return paged_chunk_attention(q, k, v, tables, row_of, slots, p_end,
                                     s_start, k_scale=k_scale,
                                     v_scale=v_scale, interpret=False)

    _assert_kernel_compiled(chunk, "paged_chunk_attention", q, pool, pool,
                            s((BATCH, MAX_BLOCKS), jnp.int32),
                            per_token, per_token, per_token, per_token,
                            **scales)


@pytest.mark.parametrize("pool_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch", ARCHS)
def test_ragged_step_has_one_chunk_kernel(one_chip, arch, pool_dtype):
    """The whole ragged step (embedding, the layer scan, the head) at the
    published attention widths, cut to two layers and a small vocabulary:
    the chunk kernel's tiling adds XLA gathers around it and no second
    custom call, so a trace finds the kernel as the step's only one."""
    cfg = dataclasses.replace(get_arch(arch), num_layers=2, vocab_size=512)
    KVH, hd, T = cfg.num_kv_heads, cfg.head_dim, PACKED[-1]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree_util.tree_map(
        lambda x: s(x.shape, x.dtype),
        jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0))))
    pool = s((cfg.num_layers, N_BLOCKS, BLOCK, KVH, hd),
             jnp.int8 if pool_dtype == "int8" else cfg.dtype)
    scales = s((cfg.num_layers, N_BLOCKS, KVH), jnp.float32)
    quantized = pool_dtype == "int8"
    per_token = s((T,), jnp.int32)

    def step(params, k, v, k_sc, v_sc, tables, tokens, row_of, slots,
             positions, p_end, s_start):
        return prefill_packed(cfg, params, k, v, tables, tokens, row_of,
                              slots, positions, p_end, s_start,
                              block_size=BLOCK, null_block=N_BLOCKS - 1,
                              impl="pallas", interpret=False,
                              k_scales=k_sc, v_scales=v_sc)

    _assert_kernel_compiled(step, "paged_chunk_attention", params, pool, pool,
                            scales if quantized else None,
                            scales if quantized else None,
                            s((BATCH, MAX_BLOCKS), jnp.int32),
                            *[per_token] * 6)
