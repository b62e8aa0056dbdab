"""Kernels of the serve path compiled at their real widths by the chip's
own compiler, for a DESCRIBED v5e and no chip time: what the Pallas
interpreter cannot refuse (a slice off the tiling, too much fast memory, a
copy of the pool in front of the call) is refused here. Compiled, not run:
nothing in this file is a time or a result.

The topology is described inside a fixture, never at import (one process
at a time may load the TPU's library; a worker that cannot skips these
tests, it does not lose the others), and all such tests live in this one
file."""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# the `internlm2` serve cells' decode shapes: 16 slots, 16 heads over 8 kv
# heads x 128, a 24-layer pool of 256 + 1 pages of 64, rows of 20 pages
@pytest.mark.parametrize("window,dtype", [(1, jnp.bfloat16),
                                          (3, jnp.bfloat16),
                                          (1, jnp.float32)])
def test_paged_attention_kernel_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, window, dtype):
    from tpudist.ops.pallas import paged_attention as pa
    slots, h, kv, hd, layers, n_pool, pt, maxp = 16, 16, 8, 128, 24, 257, 64, 20
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pool = sds((layers, kv, n_pool, pt, hd), dtype)
    assert pa.supports((slots, window, h, hd), pool.shape, dtype, pt)

    def read(q, pool_k, pool_v, layer, table, pos):
        return pa.paged_attention(q, pool_k, pool_v, layer,
                                  pa.walk(table, pos, pt, n_pool))

    # an ambient "highest" (a test file of the same worker sets it; a user
    # may) must not reach the bfloat16 matmuls, which Mosaic would refuse
    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(read).lower(
            sds((slots, window, h, hd), dtype), pool, pool,
            sds((), jnp.int32),
            sds((slots, maxp), jnp.int32), sds((slots, window), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_attn_decode" in text
    # the pool goes to the kernel where it lies: no staged layer, no copy
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# the `cmdaplus` cell's full layer: 32 slots, 128 query heads over 8 kv
# heads x 128 (a group of 16), ONE layer's pool of 3072 + 1 pages of 64,
# rows of 144 pages (max_seq 9216)
def test_paged_attention_kernel_compiles_at_the_full_layers_shapes(
        one_chip, no_compile_cache):
    from tpudist.ops.pallas import paged_attention as pa
    slots, h, kv, hd, n_pool, pt, maxp = 32, 128, 8, 128, 3073, 64, 144
    dtype = jnp.bfloat16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pool = sds((1, kv, n_pool, pt, hd), dtype)
    assert pa.supports((slots, 1, h, hd), pool.shape, dtype, pt)
    assert pa.slot_groups(slots, h, hd, hd, dtype) == slots
    assert pa.pages_per_block(pool.shape, dtype, maxp) == 8

    def read(q, pool_k, pool_v, table, pos):
        return pa.paged_attention(q, pool_k, pool_v, 0,
                                  pa.walk(table, pos, pt, n_pool))

    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(read).lower(
            sds((slots, 1, h, hd), dtype), pool, pool,
            sds((slots, maxp), jnp.int32), sds((slots, 1), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_attn_decode" in text
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# the `longcat` cell's decode read: 192 slots, 64 query heads as one group
# over ONE pool of 8 sublayers x 4096 + 1 pages of 64 rows of 640 lanes
# (576 values and 64 dead lanes), values the rows' first 512 lanes, the
# scale the published 192 ** -0.5; 32 slots a grid step
def test_paged_attention_kernel_compiles_at_the_latent_cells_shapes(
        one_chip, no_compile_cache):
    from tpudist.ops.pallas import paged_attention as pa
    slots, h, subs, n_pool, pt, maxp, vw = 192, 64, 8, 4097, 64, 32, 512
    width = 640
    dtype = jnp.bfloat16
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    pool = sds((subs, 1, n_pool, pt, width), dtype)
    assert pa.supports((slots, 1, h, width), pool.shape, dtype, pt, vw)
    assert pa.slot_groups(slots, h, width, vw, dtype) == 32

    def read(q, pool, table, pos):
        return pa.paged_attention(q, pool, None, 5,
                                  pa.walk(table, pos, pt, n_pool),
                                  scale=192 ** -0.5, v_width=vw)

    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(read).lower(
            sds((slots, 1, h, width), dtype), pool,
            sds((slots, maxp), jnp.int32), sds((slots, 1), jnp.int32))
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "paged_attn_decode" in text
    # one pool, handed over where it lies: no staged sublayer, no copy
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# the `sdar` cell's expert layer: 128 experts of 2048 x 768 as one stack a
# leaf, 512 tokens a dispatch in blocks of 64, 1024 a prefill in blocks of
# 128, top-8
@pytest.mark.parametrize("n,block,dtype", [(512, 64, jnp.bfloat16),
                                           (1024, 128, jnp.bfloat16),
                                           (512, 64, jnp.float32)],
                         ids=["dispatch", "prefill", "dispatch-float32"])
def test_grouped_experts_compile_at_the_cells_shapes(
        one_chip, no_compile_cache, monkeypatch, n, block, dtype):
    """The whole grouped path of ``dropless.routed`` (one gather, the
    kernel, the combine) for the described chip: one Mosaic call, the
    stacks handed to it where they lie, and no loop left."""
    from tpudist.models import dropless
    from tpudist.ops.pallas import grouped_experts as ge
    E, d, dff, k = 128, 2048, 768, 8
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    ex = (sds((E, d, dff), dtype), sds((E, d, dff), dtype),
          sds((E, dff, d), dtype))
    assert dropless.block_rows(n, k, E) == block
    assert ge.supports(ex, block, dtype)
    # the routing asks the backend, which here is the CPU
    monkeypatch.setattr(dropless, "_use_grouped_kernel", ge.supports)

    def mix(y, top_e, top_w, real, *ex):
        return dropless.routed(y, top_e, top_w, ex, first=0, held=E,
                               n_routed=E, real=real)

    with jax.default_matmul_precision("highest"):
        lowered = jax.jit(mix).lower(
            sds((n, d), dtype), sds((n, k), jnp.int32),
            sds((n, k), jnp.float32), sds((n,), jnp.bool_), *ex)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "grouped_experts" in text
    assert " while(" not in text and "conditional(" not in text
    # the sorted rows, the kernel's rows and the pairs' rows gathered
    # back, no copy of a stack: far under one stack's 402 MB
    tiles = ge.tiles_max(n * k, E, block)
    size = jnp.dtype(dtype).itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 4 * tiles * block * d * size + 8 * n * k * d
