"""Ahead-of-time compiles of the wire's Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these run on a CPU-only host.  They catch what
interpret mode cannot: Mosaic refusing an op (a float iota, a DMA narrower
than the 128-lane tiling), a kernel over its VMEM or SMEM budget.  Shapes
are qwen2-0.5b's (configs/qwen2_0_5b.py): the stacked MLP leaf of
24 x 896 x 4864 f32 elements (block-top-k also at the embedding and the
final norm, and at blocks 1024 and 4096), and for rand-k (whose kernel compares f32
positions below 2**24) the stacked k/v projection of 24 x 896 x 128.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import pack

MLP_LEAF = 24 * 896 * 4864
KV_LEAF = 24 * 896 * 128
EMBED_BLOCKS = 151936 * 896 // 256  # 531,776: a multiple of 64, not of 128
NORM_BLOCKS = 4                     # the final norm's 896 values, padded
EMBED_4096_BLOCKS = 151936 * 896 // 4096  # 33,236: ragged at 128 rows
SMALL_4096_BLOCKS = 120             # at most 128 blocks: one whole-leaf tile


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host, with the persistent compile
    cache off: an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


@pytest.mark.parametrize("stream", [False, True], ids=["plain", "stream"])
@pytest.mark.parametrize("nb, block, kb", [
    (MLP_LEAF // 256, 256, 16), (EMBED_BLOCKS, 256, 16),
    (NORM_BLOCKS, 256, 16), (MLP_LEAF // 1024, 1024, 64),
    (EMBED_4096_BLOCKS, 4096, 64), (SMALL_4096_BLOCKS, 4096, 64)],
    ids=["mlp", "embed", "norm", "mlp-b1024", "embed-b4096", "small-b4096"])
def test_block_topk_pack_compiles(one_chip, nb, block, kb, stream):
    """At each of qwen2's leaf sizes the tile pack_tile picks compiles
    within the VMEM limit: a full tile, a ragged last grid step (the
    embedding's block count is no multiple of 128) and a whole-leaf tile,
    at block 256 (the benchmark's) and at blocks 1024 and 4096, where even
    128 rows pass the default scoped limit and the kernel asks for more.
    The streaming kernel takes rows padded to its tile, as ops pads them."""
    if stream:
        nb = -(-nb // pack.STREAM_TILE_NB) * pack.STREAM_TILE_NB
    slab = jax.ShapeDtypeStruct((nb, block), jnp.float32, sharding=one_chip)
    fn = functools.partial(pack.pack_update_pallas, lam=0.9, kb=kb,
                           stream=stream)
    assert "tpu_custom_call" in _compiled_text(fn, slab, slab)


def test_qsgd_pack_compiles(one_chip):
    nr = MLP_LEAF // 1024
    slab = jax.ShapeDtypeStruct((nr, 1024), jnp.float32, sharding=one_chip)
    norm = jax.ShapeDtypeStruct((1, 1), jnp.float32, sharding=one_chip)
    fn = functools.partial(pack.qsgd_pack_update_pallas, s=16, lam=0.9)
    assert "tpu_custom_call" in _compiled_text(fn, slab, slab, slab, norm)


def test_randk_update_compiles(one_chip):
    nr = KV_LEAF // 1024
    k = KV_LEAF // 100
    slab = jax.ShapeDtypeStruct((nr, 1024), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((k,), jnp.int32, sharding=one_chip)
    fn = functools.partial(pack.randk_update_pallas, scale=KV_LEAF / k,
                           lam=0.9)
    assert "tpu_custom_call" in _compiled_text(fn, slab, slab, idx)
