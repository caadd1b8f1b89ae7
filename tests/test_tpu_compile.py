"""Ahead-of-time compiles of the wire's Pallas kernels for a TPU v5e chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described, not attached, so these run on a CPU-only host.  They catch what
interpret mode cannot: Mosaic refusing an op (a float iota, a DMA narrower
than the 128-lane tiling), a kernel over its VMEM or SMEM budget.  Shapes
are qwen2-0.5b's (configs/qwen2_0_5b.py): the stacked MLP leaf of
24 x 896 x 4864 f32 elements (block-top-k's pack and decode-sum also at
the embedding, the pack at the final norm, and both at block 4096, the pack
at block 1024 too), and for rand-k (whose kernel compares f32 positions
below 2**24) the stacked k/v projection of 24 x 896 x 128.  The MoE
layer's grouped products (kernels/gmm.py) compile at granite-moe-3b-a800m's
widths (d 1536, experts of 512, 8 held) on one 4,096-token row's 32,768
assignments, the decode-sum at its stacked expert leaf of
8 x 8 x 1536 x 512, and the grouped products at dbrx-132b's (d 6144, 16
experts of 10,752, top-4) on the described host's four chips as a 4-way
model axis, where the experts are expert-parallel.  The splash attention
kernels compile at granite's attention shapes (24 query heads and 8 kv
heads of 64 over 4,096 tokens).
"""

from __future__ import annotations

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.configs import get_config
from repro.kernels import gmm, ops, pack

MLP_LEAF = 24 * 896 * 4864
KV_LEAF = 24 * 896 * 128
EMBED_BLOCKS = 151936 * 896 // 256  # 531,776: a multiple of 64, not of 128
NORM_BLOCKS = 4                     # the final norm's 896 values, padded
EMBED_4096_BLOCKS = 151936 * 896 // 4096  # 33,236: ragged at 128 rows
SMALL_4096_BLOCKS = 120             # at most 128 blocks: one whole-leaf tile


@pytest.fixture(scope="module")
def v5e_host():
    """The four chips of a described v5e:2x2 host, with the persistent
    compile cache off: an entry compiled for a described chip cannot be read
    back without one."""
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
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(v5e_host):
    return SingleDeviceSharding(v5e_host[0])


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


EXPERT_LEAF = 8 * 8 * 1536 * 512  # granite's 8 layers of 8 held experts


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("nb, block, kb", [
    (MLP_LEAF // 256, 256, 16), (EMBED_BLOCKS, 256, 16),
    (EMBED_4096_BLOCKS, 4096, 64), (EXPERT_LEAF // 256, 256, 16)],
    ids=["mlp", "embed", "embed-b4096", "expert"])
def test_block_unpack_sum_compiles(one_chip, nb, block, kb, n):
    """The decode-sum kernel at qwen2's MLP leaf, its embedding (ragged at
    the tile) at blocks 256 and 4096, and granite's expert leaf, on one
    message and on four stacked: within the VMEM limit its tile asks for,
    and one kernel call each."""
    vals = jax.ShapeDtypeStruct((n, nb, kb), jnp.float32, sharding=one_chip)
    idx = jax.ShapeDtypeStruct((n, nb, kb), jnp.int32, sharding=one_chip)
    fn = functools.partial(ops.block_decode_sum, block=block,
                           interpret=False)
    text = _compiled_text(fn, vals, idx)
    assert text.count('custom_call_target="tpu_custom_call"') == 1


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


@pytest.mark.parametrize("k, n", [(1536, 512), (512, 1536)],
                         ids=["gate-up", "down"])
def test_grouped_products_compile(one_chip, k, n):
    """The forward gmm and the backward's transposed gmm and tgmm, each a
    Pallas kernel within the VMEM limit at the tiles ``gmm.tiling`` picks."""
    rows = jax.ShapeDtypeStruct((4096 * 8, k), jnp.bfloat16, sharding=one_chip)
    experts = jax.ShapeDtypeStruct((8, k, n), jnp.bfloat16, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((9,), jnp.int32, sharding=one_chip)

    def fwd_bwd(lhs, rhs, group_sizes):
        return jax.value_and_grad(lambda a, b: jnp.sum(
            gmm.gmm(a, b, group_sizes, impl="kernel").astype(jnp.float32)),
            (0, 1))(lhs, rhs)

    text = _compiled_text(fwd_bwd, rows, experts, sizes)
    assert text.count('custom_call_target="tpu_custom_call"') == 3


def test_grouped_products_expert_parallel_compile(v5e_host):
    """dbrx-132b's expert SwiGLU, forward and backward, inside the trainers'
    shard_map (manual over 'data', 'model' GSPMD-auto) on a 1 x 4 mesh with
    the 16 experts stored over 'model' as ``moe_init`` places them: each
    chip runs its own 4 experts (9 kernels) and no expert matrix is
    gathered."""
    cfg = get_config("dbrx-132b")
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    rows = 512 * cfg.experts_per_tok               # a 512-token row's choices
    mesh = jax.sharding.Mesh(
        np.asarray(v5e_host).reshape(1, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def ffn(xs, wg, wu, wd, sizes):
        h = jax.nn.silu(gmm.gmm(xs, wg, sizes, impl="kernel")) \
            * gmm.gmm(xs, wu, sizes, impl="kernel")
        return gmm.gmm(h, wd, sizes, impl="kernel")

    def worker(xs, wg, wu, wd, sizes):
        loss = lambda *a: jnp.sum(
            jnp.square(ffn(*a, sizes[0]).astype(jnp.float32)))
        return jax.grad(loss, (0, 1, 2, 3))(xs, wg, wu, wd)

    step = compat.shard_map(
        worker, mesh=mesh, in_specs=(P("data"), P(), P(), P(), P("data")),
        out_specs=(P("data"), P(), P(), P()), manual_axes=("data",))
    experts = P("model")
    text = jax.jit(step).lower(
        shaped((rows, d), jnp.bfloat16, P("data")),
        shaped((E, d, ff), jnp.bfloat16, experts),
        shaped((E, d, ff), jnp.bfloat16, experts),
        shaped((E, ff, d), jnp.bfloat16, experts),
        shaped((1, E + 1), jnp.int32, P("data"))).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 9
    local = [f"[{E // 4},{d},{ff}]", f"[{E // 4},{ff},{d}]"]
    whole = [f"[{E},{d},{ff}]", f"[{E},{ff},{d}]"]
    gathered = [line for line in text.splitlines()
                if re.search(r"\sall-gather(-start)?\(", line)
                and any(w in line.split("=")[1] for w in whole)]
    assert not gathered, gathered
    assert any(w in text for w in local)


def test_splash_attention_compiles_at_granite_shapes(one_chip):
    """Causal attention at granite-moe-3b-a800m's shapes (1 x 4,096 tokens,
    24 query heads and 8 kv heads of 64, scores times 1/64) under
    ``jax.grad``: the splash forward, dq and dkv kernels, and temporaries
    below one f32 score tensor (24 x 4,096^2 x 4 B = 1.61e9 B), which the
    materialised path writes."""
    from repro.kernels import attention

    q = jax.ShapeDtypeStruct((1, 4096, 24, 64), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 8, 64), jnp.bfloat16,
                              sharding=one_chip)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(attention.causal_attention(
            *a, scale=1 / 64, impl="kernel").astype(jnp.float32)),
            (0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, kv, kv).compile()
    text = compiled.as_text()
    for phase in ("fwd", "dq", "dkv"):
        assert re.search(rf"%splash_mha_{phase}_\w+(\.\d+)? = ", text), phase
    assert compiled.memory_analysis().temp_size_in_bytes < 24 * 4096**2 * 4


@pytest.mark.parametrize("downlink", ["", "block_topk:256,16"],
                         ids=["uplink", "downlink"])
def test_sparse_decode_compiles_in_four_chip_step(v5e_host, monkeypatch,
                                                  downlink):
    """The EF-BV step of qwen2's small preset on the described host's four
    chips (mesh 4x1, four workers), with the Pallas kernels: the decode-sum
    runs outside the trainers' shard_map, where GSPMD cannot partition a
    Mosaic kernel, once a leaf on the uplink and again on a block-top-k
    downlink."""
    from repro.launch import train
    from repro.models import build_model

    monkeypatch.setenv("REPRO_WIRE_KERNEL", "pallas")
    args = train.parse_args([
        "--arch", "qwen2-0.5b", "--smoke", "--mesh", "4x1",
        "--global-batch", "4", "--seq", "64", "--steps", "2",
        "--compressor", "block_topk:256,16", "--agg", "sparse_allgather",
        "--downlink", downlink])
    spec = train.load_spec(args)
    run = train.build(spec)
    mesh = jax.sharding.Mesh(
        np.asarray(v5e_host).reshape(4, 1), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2)
    model = build_model(train.get_smoke_config(spec.problem))
    opt = train.make_optimizer(args, spec)
    params = jax.eval_shape(model.init, jax.random.key(0))
    shapes = jax.eval_shape(lambda p: run.init_state(p, opt, mesh), params)
    state = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, run.state_shardings(mesh, model.param_specs(), shapes))
    rows = jax.ShapeDtypeStruct((4, 64), jnp.int32,
                                sharding=NamedSharding(mesh, P("data")))
    step = run.train_step(model.loss, opt, mesh)
    text = step.lower(state, {"tokens": rows, "labels": rows},
                      jax.eval_shape(lambda: jax.random.key(0))
                      ).compile().as_text()
    leaves = len(jax.tree.leaves(params))
    decodes = re.findall(r"%block_unpack_sum(?:\.\d+)? = ", text)
    assert len(decodes) == leaves * (2 if downlink else 1)
