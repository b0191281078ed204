"""The paged Pallas kernels compiled for a TPU v5e chip that is described,
not attached: Mosaic refuses here what interpret mode never checks (block
tiling, VMEM limits), at the published head widths of the served configs.

Only the fixture below describes the topology, so the TPU library is loaded
by whichever test process runs these tests and by no other.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_arch
from repro.kernels.decode_attention import (
    paged_chunk_attention,
    paged_decode_attention,
)

ARCHS = ["qwen2.5-3b", "smollm-135m"]
POOL_DTYPES = ["bfloat16", "int8", "float32"]
N_BLOCKS, BLOCK, BATCH, MAX_BLOCKS, PACKED = 64, 16, 4, 8, 32


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
    """The kernel compiles to a Mosaic custom call that carries its own
    name (``%<name>.N = ... custom_call_target="tpu_custom_call"``)."""
    text = jax.jit(fn).lower(*args, **kw).compile().as_text()
    assert "tpu_custom_call" in text
    assert any(line.lstrip().startswith(f"%{name}")
               and 'custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines()), name


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


@pytest.mark.parametrize("pool_dtype", POOL_DTYPES)
@pytest.mark.parametrize("arch", ARCHS)
def test_paged_chunk_compiles(one_chip, arch, pool_dtype):
    q, pool, s, scales = _operands(one_chip, arch, pool_dtype, PACKED)
    per_token = s((PACKED,), jnp.int32)

    def chunk(q, k, v, tables, row_of, slots, p_end, s_start, k_scale=None,
              v_scale=None):
        return paged_chunk_attention(q, k, v, tables, row_of, slots, p_end,
                                     s_start, k_scale=k_scale,
                                     v_scale=v_scale, interpret=False)

    _assert_kernel_compiled(chunk, "paged_chunk_attention", q, pool, pool,
                            s((BATCH, MAX_BLOCKS), jnp.int32),
                            per_token, per_token, per_token, per_token,
                            **scales)
