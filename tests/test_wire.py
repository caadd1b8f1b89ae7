"""Differential tests for the fused wire-codec pipeline.

Pins, bit-for-bit: jnp oracle == fused Pallas kernels (interpret; compiled
on TPU) for block-top-k, rand-k and QSGD over whole trajectories, payload
bytes == wire.bits_per_round(), sparse_allgather == dense_psum for
representatives of every codec family, and the bidirectional trainer's
Identity-server invariant.  (Per-codec roundtrip/accounting property tests
live in tests/test_wire_codecs.py.)
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import (assert_bit_identical, available_pack_impls, codec_impls,
                     run_codec_trajectory, run_wire_trajectory)
from repro.core import (BlockTopK, EFBV, Identity, MixKK, Natural, QSGD,
                        RandK, SignNorm, TopK, theory)
from repro.distributed import wire
from repro.distributed.aggregate import efbv_aggregate_reference

KEY = jax.random.key(0)

# >= 3 compressor configs, incl. a padded leaf (size % block != 0) and a
# kb == block identity block
CONFIGS = [
    # (d, block, kb)
    (1024, 128, 8),
    (1000, 256, 16),   # padding path
    (640, 128, 128),   # kb == block
]


# ---------------------------------------------------------------------------
# payload producers are bit-identical
# ---------------------------------------------------------------------------

def _case(d, block, kb, inputs="normal"):
    suffix = "" if inputs == "normal" else f"-{inputs}"
    return pytest.param(d, block, kb, inputs, id=f"{d}-{block}-{kb}{suffix}")


# the pack kernel's tiles on top of CONFIGS (whose leaves each fit one tile):
# block counts that are not a multiple of the tile (a ragged last grid
# step), a one-block leaf, and inputs whose magnitudes are all tied
PACK_CONFIGS = [_case(*c) for c in CONFIGS] + [
    _case(290 * 128 - 50, 128, 8),    # 290 blocks: tile 256, 34 left
    _case(129 * 128, 128, 4),         # 129 blocks: tile 128, 1 left
    _case(100, 128, 4),               # one padded block
    _case(6 * 256, 256, 16, "tied"),  # |delta| 0.5 or +-0.0 throughout
    _case(300 * 128, 128, 8, "tied"),  # the same over a ragged grid
]


def _pack_inputs(d, block, inputs):
    if inputs == "normal":
        return (jax.random.normal(KEY, (d,)),
                jax.random.normal(jax.random.key(1), (d,)))
    # random signs; every other block all zeros, so those blocks tie at
    # magnitude 0 with deltas of both signs (-0.0 - 0.0 is -0.0)
    sign = jnp.where(jax.random.bernoulli(KEY, 0.5, (d,)), 1.0, -1.0)
    size = jnp.where((jnp.arange(d) // block) % 2 == 0, 0.5, 0.0)
    return sign * size, jnp.zeros((d,))


def _float_bits(tree):
    """Float leaves as their bit patterns: assert_array_equal takes -0.0
    for +0.0, the bits do not."""
    return [np.asarray(x).view(np.uint32) if x.dtype == jnp.float32
            else np.asarray(x) for x in jax.tree.leaves(tree)]


@pytest.mark.parametrize("d,block,kb,inputs", PACK_CONFIGS)
def test_fused_pack_matches_oracle(d, block, kb, inputs):
    lw = wire.LeafWire(shape=(d,), size=d, block=block, kb=kb)
    g, h = _pack_inputs(d, block, inputs)
    ref = wire.fused_pack(lw, g, h, 0.37, kernel="oracle")
    for impl in available_pack_impls():
        for stream in (False, True):
            got = wire.fused_pack(lw, g, h, 0.37, kernel=impl, stream=stream)
            ctx = f"impl={impl} stream={stream} cfg={(d, block, kb, inputs)}"
            assert_bit_identical(got, ref, ctx)
            assert_bit_identical(_float_bits(got), _float_bits(ref), ctx)


def test_fused_pack_matches_oracle_on_ties():
    """Quantized input forces magnitude ties; selection order must still
    match jax.lax.top_k exactly."""
    lw = wire.LeafWire(shape=(512,), size=512, block=128, kb=8)
    g = jnp.round(jax.random.normal(KEY, (512,)) * 2) / 2
    ref = wire.fused_pack(lw, g, jnp.zeros_like(g), 0.5, kernel="oracle")
    for impl in available_pack_impls():
        got = wire.fused_pack(lw, g, jnp.zeros_like(g), 0.5, kernel=impl)
        assert_bit_identical(got, ref, f"impl={impl} (ties)")


def test_fused_pack_mixed_dtypes_bit_identical():
    """bf16 grads against f32 control variates: the kernel must subtract in
    f32 without pre-rounding h, or backends diverge."""
    lw = wire.LeafWire(shape=(512,), size=512, block=128, kb=8)
    g = jax.random.normal(KEY, (512,)).astype(jnp.bfloat16)
    h = jax.random.normal(jax.random.key(1), (512,))  # f32
    ref = wire.fused_pack(lw, g, h, 0.37, kernel="oracle")
    for impl in available_pack_impls():
        got = wire.fused_pack(lw, g, h, 0.37, kernel=impl)
        assert_bit_identical(got, ref, f"impl={impl} (mixed dtypes)")


def test_fused_pack_unaligned_block_falls_back_to_oracle():
    """block % 128 != 0 has no Pallas tiling; auto dispatch must fall back
    to the (bit-identical) oracle, explicit kernel requests must error."""
    lw = wire.LeafWire(shape=(300,), size=300, block=100, kb=4)
    g = jax.random.normal(KEY, (300,))
    h = jnp.zeros((300,))
    ref = wire.fused_pack(lw, g, h, 0.5, kernel="oracle")
    got = wire.fused_pack(lw, g, h, 0.5)  # auto
    assert_bit_identical(got, ref, "auto fallback, block=100")
    with pytest.raises(ValueError, match="block % 128"):
        wire.fused_pack(lw, g, h, 0.5, kernel="interpret")


def test_fused_pack_wide_block_falls_back_to_oracle():
    """A block so wide that 128 rows of it pass the pack kernel's VMEM has
    no kernel: auto dispatch takes the oracle, explicit requests error."""
    block = 32768
    lw = wire.LeafWire(shape=(block,), size=block, block=block, kb=16)
    g = jax.random.normal(KEY, (block,))
    h = jnp.zeros((block,))
    ref = wire.fused_pack(lw, g, h, 0.5, kernel="oracle")
    got = wire.fused_pack(lw, g, h, 0.5)  # auto
    assert_bit_identical(got, ref, "auto fallback, block=32768")
    with pytest.raises(ValueError, match="MiB of VMEM"):
        wire.fused_pack(lw, g, h, 0.5, kernel="interpret")


def test_pack_oracle_matches_compressor_encode():
    """wire.pack_oracle IS BlockTopK.encode (the layout has one spec)."""
    d, block, kb = 1000, 256, 16
    lw = wire.LeafWire(shape=(d,), size=d, block=block, kb=kb)
    x = jax.random.normal(KEY, (d,))
    vals, idx = wire.pack_oracle(lw, x)
    ov, oi = BlockTopK(block, kb).encode(None, x)
    np.testing.assert_array_equal(np.asarray(vals), np.asarray(ov))
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(oi))
    # and unpack reproduces the dense compressor output
    np.testing.assert_array_equal(
        np.asarray(wire.unpack(lw, vals, idx)),
        np.asarray(BlockTopK(block, kb)(None, x)))


# ---------------------------------------------------------------------------
# the decode-sum kernel is bit-identical to the scatter
# ---------------------------------------------------------------------------

def _stacked_payload(lw, n, inputs="normal"):
    """n workers' real block-top-k messages of one leaf, stacked (n, nb,
    kb) in the wire's value dtype."""
    msgs = []
    for w in range(n):
        key = jax.random.key(100 + w)
        if inputs == "normal":
            x = jax.random.normal(key, (lw.size,))
        else:  # the tied inputs: kept -0.0 and +0.0 in every other block
            sign = jnp.where(jax.random.bernoulli(key, 0.5, (lw.size,)),
                             1.0, -1.0)
            x = sign * jnp.where((jnp.arange(lw.size) // lw.block) % 2, 0.0,
                                 0.5)
        msgs.append(lw.encode(None, x))
    return tuple(jnp.stack(a) for a in zip(*msgs))


def _unpack_case(block, kb, n, via="scatter", inputs="normal",
                 val_dtype="float32"):
    # 130 blocks: a ragged last grid step past a 128-block tile, and a
    # padded tail of 37 values
    return pytest.param(130 * block - 37, block, kb, n, via, inputs,
                        val_dtype,
                        id=f"b{block}-kb{kb}-n{n}-{via}-{inputs}-{val_dtype}")


UNPACK_CASES = [_unpack_case(b, kb, n) for b in (256, 1024, 4096)
                for kb in (16, 64) for n in (1, 4)] + [
    _unpack_case(256, 16, 4, inputs="tied"),
    _unpack_case(256, 16, 4, via="chunks2"),
    _unpack_case(256, 16, 4, via="decode", val_dtype="bfloat16"),
    _unpack_case(256, 16, 1, via="decode", val_dtype="bfloat16"),
]


@pytest.mark.parametrize("d,block,kb,n,via,inputs,val_dtype", UNPACK_CASES)
def test_block_unpack_sum_matches_scatter(monkeypatch, d, block, kb, n, via,
                                          inputs, val_dtype):
    """The sort-free decode-sum kernel (interpret mode) against the jnp
    scatter, bit for bit: one message (n = 1) and four stacked, blocks of
    256 to 4096, through ``scatter_add`` itself, the pipelined exchange's
    two-chunk decode and a bf16 wire's decode, whose values the codec
    widens to f32 before the sum."""
    lw = wire.LeafWire(shape=(d,), size=d, block=block, kb=kb,
                       val_dtype=val_dtype)
    vals, idx = _stacked_payload(lw, n, inputs)
    if n == 1:
        vals, idx = vals[0], idx[0]
    if via == "scatter":
        ref = wire.scatter_add(lw, vals, idx, kernel="oracle")
        got = wire.scatter_add(lw, vals, idx, kernel="interpret")
    else:
        def decode():
            if via == "chunks2":
                return wire.chunked_decode_sum(lw, (vals, idx), 2)
            return lw.decode_sum((vals, idx))

        monkeypatch.setenv("REPRO_WIRE_KERNEL", "oracle")
        ref = decode()
        monkeypatch.setenv("REPRO_WIRE_KERNEL", "interpret")
        got = decode()
    assert_bit_identical(got, ref, f"{(d, block, kb, n, via)}")
    assert_bit_identical(_float_bits(got), _float_bits(ref), via)


@pytest.mark.parametrize("block, dtype, gap", [
    (100, jnp.float32, "block 100 is not a multiple of 128"),
    (256, jnp.bfloat16, "bfloat16 values"),
    (2 ** 17, jnp.float32, "MiB of VMEM"),
], ids=["block", "bf16", "vmem"])
def test_block_unpack_sum_gaps_fall_back_to_scatter(block, dtype, gap):
    """A payload the decode kernel refuses takes the jnp scatter under
    'auto' (and on a TPU); an explicit kernel request names the gap."""
    lw = wire.LeafWire(shape=(3 * block,), size=3 * block, block=block,
                       kb=4)
    vals, idx = _stacked_payload(lw, 2)
    vals = vals.astype(dtype)
    ref = wire.scatter_add(lw, vals, idx, kernel="oracle")
    assert_bit_identical(wire.scatter_add(lw, vals, idx), ref, gap)
    with pytest.raises(ValueError, match=gap):
        wire.scatter_add(lw, vals, idx, kernel="interpret")


# ---------------------------------------------------------------------------
# whole-trajectory bit-identity (the acceptance criterion)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,block,kb", CONFIGS)
def test_trajectory_bit_identical_across_backends(d, block, kb):
    """(x, h) trajectories of Algorithm 1 over the sparse wire are
    bit-identical between the jnp oracle and the fused Pallas kernel."""
    kw = dict(steps=6, n=4, d=d, block=block, kb=kb,
              lam=0.9, nu=1.0, gamma=0.1)
    ref = run_wire_trajectory("oracle", **kw)
    for impl in available_pack_impls():
        got = run_wire_trajectory(impl, **kw)
        assert_bit_identical((got["x"], got["h"], got["payload"]),
                             (ref["x"], ref["h"], ref["payload"]),
                             f"impl={impl} cfg={(d, block, kb)}")
    # sanity: the trajectory actually moves
    assert float(jnp.linalg.norm(ref["x"][-1])) > 0


# ---------------------------------------------------------------------------
# whole-trajectory bit-identity for the other fused-kernel codecs
# ---------------------------------------------------------------------------

CODEC_TRAJ = [RandK(8), QSGD(16), QSGD(400)]


@pytest.mark.parametrize("comp", CODEC_TRAJ, ids=lambda c: repr(c))
def test_codec_trajectory_bit_identical_across_backends(comp):
    """(x, h, payload) trajectories of Algorithm 1 over each fused-kernel
    codec are bit-identical between the jnp oracle and the Pallas kernel
    (interpret on CPU, compiled on TPU) -- the rand-k/QSGD analogue of the
    block-top-k test above."""
    d, n = 600, 3
    lam = theory.lambda_star(comp.eta(d), comp.omega(d))
    nu = theory.nu_star(comp.eta(d), comp.omega(d) / n)
    kw = dict(compressor=comp, steps=5, n=n, d=d, lam=lam, nu=nu, gamma=0.05)
    ref = run_codec_trajectory("oracle", **kw)
    impls = codec_impls(ref["codec"])
    assert impls != ["oracle"], "fused-kernel codec expected"
    for impl in impls[1:]:
        got = run_codec_trajectory(impl, **kw)
        assert_bit_identical((got["x"], got["h"], got["payload"]),
                             (ref["x"], ref["h"], ref["payload"]),
                             f"impl={impl} comp={comp!r}")
    assert float(jnp.linalg.norm(ref["x"][-1])) > 0


def test_oracle_only_codecs_run_trajectories():
    """Codecs without a fused kernel (sign, natural, top-k, ...) still run
    whole trajectories through the same harness, and an explicit kernel
    request on them errors instead of silently diverging."""
    for comp in [SignNorm(), Natural(), TopK(6), MixKK(2, 6)]:
        res = run_codec_trajectory("oracle", compressor=comp, steps=3, n=2,
                                   d=96, lam=0.5, nu=0.5, gamma=0.05)
        assert codec_impls(res["codec"]) == ["oracle"]
        assert np.all(np.isfinite(np.asarray(res["x"])))
        with pytest.raises(ValueError):
            wire.encode_update(res["codec"], KEY, jnp.zeros(96),
                               jnp.zeros(96), 0.5, kernel="interpret")


def test_codec_kernel_hlo_one_pass():
    """AOT TPU HLO proof for the new fused kernels: rand-k's custom call
    emits ONLY h_out; QSGD's emits only the quantized stream + h_out."""
    bench = pytest.importorskip("benchmarks.compressor_bench")
    try:
        rk = bench.randk_update_hlo_report(nr=16, cols=256, k=32)
        qs = bench.qsgd_pack_hlo_report(nr=32, cols=256, s=16)
    except Exception as e:  # pragma: no cover - jax.export surface drift
        pytest.skip(f"TPU AOT export unavailable: {type(e).__name__}")
    assert rk["h_out_only"], rk
    assert qs["one_dense_f32"] and qs["quantized_stream"], qs


# ---------------------------------------------------------------------------
# exact bit accounting
# ---------------------------------------------------------------------------

def test_payload_bytes_equal_bits_per_round():
    """Measured payload bytes == wire.bits_per_round() EXACTLY."""
    comp = BlockTopK(256, 16)
    tree = {"w": jax.random.normal(KEY, (37, 29)),
            "b": jax.random.normal(jax.random.key(1), (65,))}
    fmt = wire.format_for(comp, tree)
    payload = []
    for lw, leaf in zip(fmt.leaves, jax.tree.leaves(tree)):
        (vals, idx), _ = wire.fused_pack(lw, leaf, jnp.zeros_like(leaf), 1.0)
        payload.append((vals, idx))
    assert 8 * wire.payload_bytes(payload) == fmt.bits_per_round()
    # consistent with the compressor's own Wire(words=...) accounting
    words = sum(comp.wire(l.size).words for l in fmt.leaves)
    assert fmt.bits_per_round() == 32 * words
    # and per-round totals scale linearly in n (paper: bits ~ t*k per node)
    assert fmt.bits_per_round(n_workers=8) == 8 * fmt.bits_per_round()


def test_trajectory_payload_accounting():
    res = run_wire_trajectory("oracle", steps=2, n=3, d=1000, block=128,
                              kb=4, lam=1.0, nu=1.0, gamma=0.1)
    vals, idx = res["payload"]
    per_worker = vals[0].nbytes + idx[0].nbytes
    fmt = wire.WireFormat((res["lw"],))
    assert 8 * per_worker == fmt.bits_per_round()


def test_fused_kernel_never_materializes_dense_d():
    """The one-HBM-pass claim, proven from the TPU-lowered HLO (Mosaic
    lowering is AOT, so this runs on CPU hosts): the fused pack kernel's
    custom call emits only (values, indices, h_out); the unfused dense
    kernel's result IS the dense d."""
    bench = pytest.importorskip("benchmarks.compressor_bench")
    try:
        rep = bench.fused_pack_hlo_report(nb=16, block=256, kb=8)
    except Exception as e:  # pragma: no cover - jax.export surface drift
        pytest.skip(f"TPU AOT export unavailable: {type(e).__name__}")
    assert rep["fused_one_hbm_pass"], rep
    assert rep["unfused_dense_output"], rep


# ---------------------------------------------------------------------------
# wire modes and the sharded trainer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp", [
    BlockTopK(64, 8), TopK(20), RandK(12), QSGD(16), SignNorm(), Natural(),
    MixKK(4, 8), Identity(),
], ids=lambda c: repr(c))
def test_sparse_allgather_equals_dense_psum(comp):
    """Same compressor draws -> the wire format must not change Algorithm 1
    (the payload path is exercised through compress_local/combine_global)
    -- for a representative of every codec family."""
    n, shape = 4, (32, 16)
    algo = EFBV(comp, lam=0.8, nu=0.9)
    grads = {"w": jax.random.normal(KEY, (n,) + shape)}
    h = {"w": jnp.zeros((n,) + shape)}
    h_avg = {"w": jnp.zeros(shape)}
    keys = jax.random.split(KEY, n)  # repro: noqa(prng-reuse) -- deterministic fixture, draws need not be independent
    dense = efbv_aggregate_reference(algo, keys, grads, h, h_avg,
                                     mode="dense_psum")
    sparse = efbv_aggregate_reference(algo, keys, grads, h, h_avg,
                                      mode="sparse_allgather")
    for a, b in zip(jax.tree.leaves(dense), jax.tree.leaves(sparse)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_bidirectional_identity_downlink_matches_unidirectional():
    """With an Identity downlink the bidirectional trainer reproduces the
    unidirectional trajectory BIT-FOR-BIT: the lossless f32 broadcast
    assigns w = x verbatim (no x_hat + (x - x_hat) re-rounding), so the
    workers' gradients see bit-identical params every round."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import Downlink
    from repro.launch.mesh import make_mesh
    from repro.optim import constant, sgd
    from repro.train import (init_train_state, make_train_step,
                             train_state_shardings)

    mesh = make_mesh((1, 1))
    D = 16
    params = {"w": jax.random.normal(KEY, (D,)) * 0.1}
    specs = {"w": P(None)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2), {}

    algo = EFBV(BlockTopK(8, 2), lam=0.9, nu=0.9)
    opt = sgd(constant(0.05))

    def run(downlink):
        # fresh copies: the jitted step donates its state buffers
        st = init_train_state(jax.tree.map(jnp.array, params), opt, mesh,
                              bidirectional=downlink is not None)
        sh = train_state_shardings(mesh, specs, st)
        st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
        step = make_train_step(loss_fn, opt, algo, mesh,
                               agg_mode="sparse_allgather",
                               downlink=downlink)
        for i in range(5):
            kb_ = jax.random.fold_in(jax.random.key(42), i)
            x = jax.random.normal(kb_, (4, D))
            batch = {"x": x, "y": x @ jnp.ones((D,)) * 0.3}
            st, m = step(st, batch, jax.random.fold_in(KEY, i))
        return st, m

    st_uni, _ = run(None)
    st_bi, m_bi = run(Downlink(Identity()))
    np.testing.assert_array_equal(np.asarray(st_uni.params["w"]),
                                  np.asarray(st_bi.params["w"]))
    np.testing.assert_array_equal(np.asarray(st_uni.h["w"]),
                                  np.asarray(st_bi.h["w"]))
    np.testing.assert_array_equal(np.asarray(st_bi.params["w"]),
                                  np.asarray(st_bi.w["w"]))
    assert float(m_bi["w_err"]) == 0.0


@pytest.mark.slow
def test_wire_trajectory_1_vs_8_devices():
    """Harness leg: the 8-fake-device shard_map trainer matches the
    single-device vmap reference running the same Algorithm 1 over the same
    sparse wire.  Per-worker packing is deterministic and bit-identical; the
    cross-device d_bar mean is an all-reduce whose f32 summation order
    differs from the single-device reduction, so the trajectories agree to
    reduction-order tolerance (bit-identity holds within a fixed device
    count -- the backend tests above)."""
    from conftest import run_with_devices
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EFBV, BlockTopK
        from repro.optim import sgd, constant
        from repro.train import (make_train_step, init_train_state,
                                 train_state_shardings)
        from repro.launch.mesh import make_mesh
        from repro.distributed.aggregate import efbv_aggregate_reference
        from repro.optim.optimizers import apply_updates

        D, n, key = 16, 8, jax.random.key(0)
        params = {"w": jax.random.normal(key, (D,)) * 0.1}
        algo = EFBV(BlockTopK(8, 2), lam=0.8, nu=0.9)
        opt = sgd(constant(0.05))

        def loss_fn(p, batch):
            return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2), {}

        def batches(i):
            kb = jax.random.fold_in(jax.random.key(42), i)
            x = jax.random.normal(kb, (16, D))
            return x, x @ jnp.ones((D,)) * 0.3

        mesh = make_mesh((8, 1))
        st = init_train_state(jax.tree.map(jnp.array, params), opt, mesh)
        sh = train_state_shardings(mesh, {"w": P(None)}, st)
        st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
        step = make_train_step(loss_fn, opt, algo, mesh,
                               agg_mode="sparse_allgather")
        for i in range(6):
            x, y = batches(i)
            batch = {"x": jax.device_put(x, NamedSharding(mesh, P("data"))),
                     "y": jax.device_put(y, NamedSharding(mesh, P("data")))}
            st, _ = step(st, batch, jax.random.fold_in(key, i))

        w = jax.tree.map(jnp.array, params)["w"]
        h, h_avg = jnp.zeros((n, D)), jnp.zeros((D,))
        opt_state = opt.init({"w": w})
        for i in range(6):
            x, y = batches(i)
            xw, yw = x.reshape(n, 2, D), y.reshape(n, 2)
            grads = jax.vmap(lambda xb, yb: jax.grad(
                lambda p: jnp.mean((xb @ p - yb) ** 2))(w))(xw, yw)
            keys = jax.vmap(lambda j: jax.random.fold_in(
                jax.random.fold_in(key, i), j))(jnp.arange(n))
            g_hat, hh, hav = efbv_aggregate_reference(
                algo, keys, {"w": grads}, {"w": h}, {"w": h_avg},
                mode="sparse_allgather")
            h, h_avg = hh["w"], hav["w"]
            updates, opt_state = opt.update(g_hat, opt_state, {"w": w})
            w = apply_updates({"w": w}, updates)["w"]

        np.testing.assert_allclose(np.asarray(st.params["w"]),
                                   np.asarray(w), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(st.h["w"]), np.asarray(h),
                                   rtol=1e-6, atol=1e-6)
        print("WIRE_1V8_OK")
    """, n_devices=8)
    assert "WIRE_1V8_OK" in out


@pytest.mark.parametrize("schedule", ["shard_map", "pipelined"])
def test_bidirectional_compressed_downlink_tracks_model(schedule):
    """With a contractive downlink C_s, w tracks the model: the
    reconstruction error stays bounded and training still reduces the loss
    -- under the sequential AND the pipelined (depth:1) schedule."""
    from jax.sharding import PartitionSpec as P
    from repro.core import Downlink
    from repro.core.efbv import Pipeline
    from repro.launch.mesh import make_mesh
    from repro.optim import constant, sgd
    from repro.train import (init_train_state, make_train_step,
                             train_state_shardings)

    mesh = make_mesh((1, 1))
    D = 32
    params = {"w": jnp.zeros((D,))}
    specs = {"w": P(None)}

    def loss_fn(p, batch):
        return jnp.mean((batch["x"] @ p["w"] - batch["y"]) ** 2), {}

    algo = EFBV(BlockTopK(8, 4), lam=1.0, nu=1.0)
    opt = sgd(constant(0.1))
    pipeline = Pipeline.parse("depth:1" if schedule == "pipelined" else "off")
    st = init_train_state(params, opt, mesh, bidirectional=True, algo=algo,
                          agg_mode="sparse_allgather", pipeline=pipeline)
    sh = train_state_shardings(mesh, specs, st)
    st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
    step = make_train_step(loss_fn, opt, algo, mesh,
                           agg_mode="sparse_allgather",
                           downlink=Downlink(BlockTopK(8, 4)),
                           pipeline=pipeline)
    losses = []
    for i in range(30):
        kb_ = jax.random.fold_in(jax.random.key(7), i)
        x = jax.random.normal(kb_, (8, D))
        batch = {"x": x, "y": x @ (jnp.arange(D) / D)}
        st, m = step(st, batch, jax.random.fold_in(KEY, i))
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0], losses
    assert float(m["w_err"]) < 1.0


@pytest.mark.parametrize("comp, wire_dtype, leaf, reason", [
    (BlockTopK(256, 16), "float32", (4, 256), None),
    (BlockTopK(100, 4), "float32", (4, 256),
     "block 100 is not a multiple of 128"),
    (BlockTopK(256, 16), "bfloat16", (4, 256), "bfloat16 wire values"),
    (RandK(1000), "float32", (2 ** 24,), "size 16777216 >= 2**24"),
    (BlockTopK(32768, 16), "float32", (32768,),
     "needs 192 MiB of VMEM for block 32768, kb 16 at 128 rows, over its "
     "96 MiB"),
], ids=["fits", "block", "bf16", "randk-size", "vmem"])
def test_kernel_gaps_name_each_oracle_leaf(comp, wire_dtype, leaf, reason):
    """Leaves whose codec has a Pallas kernel they cannot use are named with
    the reason: under kernel 'auto' they take the jnp oracle on a TPU too."""
    tree = {"a": jax.ShapeDtypeStruct(leaf, jnp.float32),
            "b": {"c": jax.ShapeDtypeStruct(leaf, jnp.float32)}}
    fmt = wire.tree_format_for(comp, tree, wire_dtype=wire_dtype)
    gaps = wire.kernel_gaps(fmt, tree)
    assert gaps == (() if reason is None else
                    (("a", reason), ("b/c", reason)))
