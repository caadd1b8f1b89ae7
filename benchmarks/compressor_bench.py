"""Compressor micro-benchmarks (us/call on this host) incl. the Pallas
block-top-k kernel (interpret mode on CPU) vs its XLA oracle, the
packed-vs-dense wire pipeline comparison (one HBM pass, proven from the
TPU-lowered HLO), and measured payload bytes vs theoretical bits_per_round
for EVERY registered wire codec -- all compressors have one."""

from __future__ import annotations

import functools
import re

import jax
import jax.numpy as jnp

from benchmarks.common import KEY, timeit
from repro.core import (BlockTopK, CompKK, Identity, MixKK, Natural, QSGD,
                        RandK, SignNorm, TopK)
from repro.distributed import wire
from repro.kernels import ops, ref


def run(fast: bool = True):
    d = 1 << 16
    x = jax.random.normal(KEY, (d,))
    rows = []
    cases = [
        ("topk_1pc", jax.jit(lambda k, v: TopK(d // 100)(k, v))),
        ("randk_1pc", jax.jit(lambda k, v: RandK(d // 100)(k, v))),
        ("comp_k_kp", jax.jit(lambda k, v: CompKK(d // 100, d // 2)(k, v))),
        ("block_topk_core", jax.jit(lambda k, v: BlockTopK(1024, 16)(k, v))),
        ("natural", jax.jit(lambda k, v: Natural()(k, v))),
        ("qsgd_s16", jax.jit(lambda k, v: QSGD(16)(k, v))),
        ("block_topk_ref", jax.jit(lambda k, v: ref.block_topk_ref(v, 1024, 16))),
    ]
    iters = 5 if fast else 30
    for name, fn in cases:
        us = timeit(fn, KEY, x, iters=iters)
        rows.append({"name": f"compressor/{name}", "us_per_call": f"{us:.1f}",
                     "derived": f"d={d}"})
    # pallas kernel (interpret on CPU -- not a speed claim, a parity check)
    us = timeit(lambda v: ops.block_topk(v, block=1024, kb=16), x, iters=3)
    rows.append({"name": "compressor/block_topk_pallas_interpret",
                 "us_per_call": f"{us:.1f}", "derived": "interpret=True"})
    rows.extend(packed_vs_dense(fast=fast))
    rows.extend(codec_payload_rows())
    return rows


# ---------------------------------------------------------------------------
# measured payload bytes vs theoretical bits for every registered codec
# ---------------------------------------------------------------------------

def codec_payload_rows(d: int = 1 << 16):
    """Every compressor has a wire codec; measure the bytes its payload
    actually occupies and pin them against the exact bits_per_round
    accounting and the fp32 dense baseline.  QSGD and natural compression
    must land at <= 1/3 of dense fp32 (acceptance criterion)."""
    x = jax.random.normal(KEY, (d,))
    dense_bytes = 4 * d
    cases = [
        ("identity", Identity()),
        ("topk_1pc", TopK(d // 100)),
        ("randk_1pc", RandK(d // 100)),
        ("comp_k_kp", CompKK(d // 100, d // 10)),
        ("mix_k_kp", MixKK(d // 200, d // 200)),
        ("block_topk", BlockTopK(1024, 16)),
        ("sign", SignNorm()),
        ("natural", Natural()),
        ("qsgd_s16", QSGD(16)),
    ]
    rows = []
    for name, comp in cases:
        codec = wire.codec_of(comp, (d,), d)
        payload = codec.encode(KEY, x)
        measured = wire.payload_bytes(payload)
        assert 8 * measured == codec.payload_bits, (name, measured)
        ratio = measured / dense_bytes
        if name in ("qsgd_s16", "natural"):
            assert ratio <= 1 / 3, (name, ratio)
        rows.append({
            "name": f"wire/codec_{name}",
            "us_per_call": "",
            "derived": f"kind={codec.kind} payload_bytes={measured} "
                       f"bits_per_round={codec.payload_bits} "
                       f"vs_dense_fp32={ratio:.4f}x",
        })
    return rows


# ---------------------------------------------------------------------------
# packed vs dense wire pipeline
# ---------------------------------------------------------------------------

def _custom_call_result_types(mlir_text: str):
    """Result tensor types of the (single) tpu_custom_call in an exported
    module, e.g. ['tensor<64x16xf32>', 'tensor<64x16xi32>', ...]."""
    line = next(l for l in mlir_text.splitlines() if "tpu_custom_call" in l)
    tail = re.compile(r"->\s*\(([^()]*)\)(?:\s*loc\([^)]*\))?\s*$")
    single = re.compile(r"->\s*(tensor<[^\s,]+>)(?:\s*loc\([^)]*\))?\s*$")
    m = tail.search(line) or single.search(line)
    if m is None:
        return []
    return [t.strip() for t in m.group(1).split(",") if t.strip()]


def fused_pack_hlo_report(nb: int = 64, block: int = 256, kb: int = 16):
    """Prove the one-HBM-pass claim from the LOWERED HLO: the fused pack
    kernel's TPU custom call must emit only (values, indices, h_out) -- the
    dense d never reaches HBM -- while the unfused dense kernel's whole
    RESULT is the dense d, which pack/update then re-read.

    Mosaic lowering is AOT (jax.export with platforms=['tpu']), so this runs
    on CPU-only hosts too.
    """
    from jax import export as jexport
    from repro.kernels.block_topk import block_topk_pallas
    from repro.kernels.pack import pack_update_pallas

    sds = jax.ShapeDtypeStruct((nb, block), jnp.float32)
    fused = jax.jit(functools.partial(pack_update_pallas, lam=0.9, kb=kb,
                                      interpret=False))
    fused_res = _custom_call_result_types(
        jexport.export(fused, platforms=["tpu"])(sds, sds).mlir_module())
    unfused = jax.jit(lambda g: block_topk_pallas(g, kb, interpret=False))
    unfused_res = _custom_call_result_types(
        jexport.export(unfused, platforms=["tpu"])(sds).mlir_module())

    dense_ty = f"tensor<{nb}x{block}xf32>"
    # the kernel writes its payload lane-dense, (kb, nb); the (nb, kb)
    # layout of the wire is a transpose outside the custom call
    payload_tys = {f"tensor<{kb}x{nb}xf32>", f"tensor<{kb}x{nb}xi32>"}
    report = {
        # exactly one dense output (h_out) and the packed payload: d is
        # never materialized in HBM
        "fused_one_hbm_pass": (fused_res.count(dense_ty) == 1
                               and payload_tys.issubset(set(fused_res))),
        "fused_outputs": fused_res,
        # the unfused kernel's output IS the dense d
        "unfused_dense_output": unfused_res.count(dense_ty) == 1,
    }
    return report


def randk_update_hlo_report(nr: int = 16, cols: int = 256, k: int = 32):
    """The rand-k fused kernel's TPU custom call must emit ONLY h_out (one
    dense f32 tensor): the dense rand-k output d lives in VMEM, and the
    O(k) payload gather never touches the kernel.  AOT-lowered like
    ``fused_pack_hlo_report``, so this runs on CPU-only hosts."""
    from jax import export as jexport
    from repro.kernels.pack import randk_update_pallas

    g = jax.ShapeDtypeStruct((nr, cols), jnp.float32)
    idx = jax.ShapeDtypeStruct((k,), jnp.int32)
    fn = jax.jit(functools.partial(randk_update_pallas, scale=75.0, lam=0.9,
                                   interpret=False))
    res = _custom_call_result_types(
        jexport.export(fn, platforms=["tpu"])(g, g, idx).mlir_module())
    dense_ty = f"tensor<{nr}x{cols}xf32>"
    return {"h_out_only": res == [dense_ty], "outputs": res}


def qsgd_pack_hlo_report(nr: int = 32, cols: int = 256, s: int = 16):
    """The QSGD fused kernel's TPU custom call must emit only the int8
    level stream and h_out: one dense f32 tensor, no dequantized d."""
    from jax import export as jexport
    from repro.kernels.pack import qsgd_pack_update_pallas

    g = jax.ShapeDtypeStruct((nr, cols), jnp.float32)
    norm = jax.ShapeDtypeStruct((1, 1), jnp.float32)
    fn = jax.jit(functools.partial(qsgd_pack_update_pallas, s=s, lam=0.9,
                                   interpret=False))
    res = _custom_call_result_types(
        jexport.export(fn, platforms=["tpu"])(g, g, g, norm).mlir_module())
    f32_ty = f"tensor<{nr}x{cols}xf32>"
    lvl_ty = f"tensor<{nr}x{cols}xi{8 if s <= 127 else 16}>"
    return {
        "one_dense_f32": res.count(f32_ty) == 1,
        "quantized_stream": lvl_ty in res,
        "outputs": res,
    }


def packed_vs_dense(fast: bool = True):
    """us/call of the fused compress-and-pack pipeline vs the unfused
    (dense-compress, then pack, then h-update) one, plus exact wire bytes."""
    d, block, kb = 1 << 16, 1024, 16
    lw = wire.LeafWire(shape=(d,), size=d, block=block, kb=kb)
    g = jax.random.normal(KEY, (d,))
    h = jax.random.normal(jax.random.key(1), (d,))
    lam = 0.9
    comp = BlockTopK(block, kb)

    @jax.jit
    def unfused(g, h):
        delta = g - h                                   # HBM pass 1
        dns = comp(None, delta).reshape(-1)             # dense d: pass 2
        vals, idx = comp.encode(None, delta)            # re-read: pass 3
        return (vals, idx), h + lam * dns               # h update: pass 4

    fused = jax.jit(lambda g, h: wire.fused_pack(lw, g, h, lam))

    iters = 5 if fast else 30
    rows = []
    us_u = timeit(unfused, g, h, iters=iters)
    us_f = timeit(fused, g, h, iters=iters)
    fmt = wire.WireFormat((lw,))
    rows.append({"name": "wire/unfused_compress_pack", "us_per_call": f"{us_u:.1f}",
                 "derived": f"d={d} dense_d_materialized=True"})
    rows.append({"name": "wire/fused_pack", "us_per_call": f"{us_f:.1f}",
                 "derived": f"d={d} payload_bits={fmt.bits_per_round()}"})

    try:
        rep = fused_pack_hlo_report()
        rows.append({"name": "wire/fused_pack_hlo",
                     "us_per_call": "",
                     "derived": f"one_hbm_pass={rep['fused_one_hbm_pass']} "
                                f"unfused_dense_output={rep['unfused_dense_output']}"})
        rk = randk_update_hlo_report()
        rows.append({"name": "wire/randk_update_hlo", "us_per_call": "",
                     "derived": f"h_out_only={rk['h_out_only']}"})
        qs = qsgd_pack_hlo_report()
        rows.append({"name": "wire/qsgd_pack_hlo", "us_per_call": "",
                     "derived": f"one_dense_f32={qs['one_dense_f32']} "
                                f"quantized_stream={qs['quantized_stream']}"})
    except Exception as e:  # jax.export unavailable on some versions
        rows.append({"name": "wire/fused_pack_hlo", "us_per_call": "",
                     "derived": f"skipped ({type(e).__name__})"})
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run(fast=True))
