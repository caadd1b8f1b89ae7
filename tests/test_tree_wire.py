"""The pytree-native wire layer and its per-leaf differential harness.

Four contracts, each pinned exactly:

1. SINGLE-LEAF PARITY -- :func:`harness.run_tree_trajectory` over a
   single-leaf pytree is BIT-identical to the flat-vector
   :func:`harness.run_trajectory` for every codec in the zoo, in every
   execution mode (full / federated / bidirectional / pipelined), on every
   pack backend the codec has.
2. NESTED DIFFERENTIAL -- on genuinely nested trees with mixed per-leaf
   codecs (block-top-k / QSGD / dense), oracle == interpret (== compiled on
   TPU), including the real qwen2-0.5b smoke parameter tree.
3. COMPOSED ACCOUNTING -- the TreeWire's composed ``bits_per_round`` is
   EXACTLY the sum of its per-leaf bits, independent of leaf order, and
   ``payload_bytes`` of a real message equals bits / 8.
4. DEGENERATE LEAVES -- 0-d, size-1 and size < k leaves encode, decode,
   zero-message and mask-message without clamping crashes (the per-leaf
   compressor is clamped to the leaf's size), including the pipelined
   schedule's priming payload.

Plus the negative paths: every inconsistent-combo SpecError added since the
spec PR asserted VERBATIM, and the new leaf_codecs rejections with them.
"""

import json
import os
import random
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
from _prop import given, settings, st
from conftest import REPO, run_with_devices
from repro.core import ExperimentSpec, SpecError
from repro.core.compressors import make_compressor
from repro.core.spec import REFERENCE_PROBLEMS
from repro.distributed import wire

# every codec in the zoo, as compressor specs (d = 64 in the parity legs)
ZOO = ["identity", "topk:8", "randk:8", "scaled_randk:8", "comp:4,16",
       "mix:4,4", "block_topk:32,4", "sign", "natural", "qsgd:16",
       "frac_topk:125"]


def _spec(comp, **kw):
    base = dict(compressor=comp, problem="quadratic", backend="reference",
                n=4, d=64, steps=3, gamma=0.05)
    base.update(kw)
    return ExperimentSpec(**base)


def _assert_same_trajectory(a, b, context):
    harness.assert_bit_identical(a["x"], b["x"], context + " x")
    harness.assert_bit_identical(a["h"], b["h"], context + " h")
    assert a["round_bits"] == b["round_bits"], context


# ---------------------------------------------------------------------------
# 1. single-leaf pytree == flat vector, bit-for-bit, whole zoo
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comp", ZOO)
def test_single_leaf_parity_whole_zoo(comp):
    spec = _spec(comp)
    codec = wire.codec_of(make_compressor(comp), (64,), 64, "float32")
    for kernel in harness.codec_impls(codec):
        a = harness.run_trajectory(spec, kernel)
        b = harness.run_tree_trajectory(spec, kernel)
        _assert_same_trajectory(a, b, f"{comp}/{kernel}")
        harness.assert_bit_identical(a["payload"], b["payload"][0],
                                     f"{comp}/{kernel} payload")
        assert b["bits_by_leaf"] == (codec.payload_bits,)


def test_single_leaf_parity_federated():
    spec = _spec("qsgd:16", participation="bernoulli:0.7")
    a = harness.run_trajectory(spec)
    b = harness.run_tree_trajectory(spec)
    _assert_same_trajectory(a, b, "federated")
    harness.assert_bit_identical(a["masks"], b["masks"], "federated masks")


def test_single_leaf_parity_bidirectional():
    spec = _spec("block_topk:32,4", downlink="qsgd:16")
    a = harness.run_trajectory(spec)
    b = harness.run_tree_trajectory(spec)
    _assert_same_trajectory(a, b, "bidirectional")
    harness.assert_bit_identical(a["w"], b["w"], "bidirectional w")


def test_single_leaf_parity_pipelined():
    spec = _spec("randk:8", backend="shard_map", mesh="4x1",
                 pipeline="depth:1")
    a = harness.run_trajectory(spec)
    b = harness.run_tree_trajectory(spec)
    _assert_same_trajectory(a, b, "pipelined")
    harness.assert_bit_identical(a["pending"], b["pending"][0],
                                 "pipelined in-flight buffer")


# ---------------------------------------------------------------------------
# 2. nested trees, mixed codecs: oracle == interpret (== pallas on TPU)
# ---------------------------------------------------------------------------

NESTED_TREE = {"embed": jnp.zeros((16, 8)),
               "mlp": {"w": jnp.zeros((64,)), "bias": jnp.zeros((1,))},
               "scale": jnp.zeros(())}
MIXED_RULES = "embed*=qsgd:16;*bias=identity"


def test_nested_mixed_codecs_differential():
    spec = _spec("block_topk:32,4", leaf_codecs=MIXED_RULES)
    ref = harness.run_tree_trajectory(spec, "oracle", tree=NESTED_TREE)
    kinds = [c.kind for c in ref["fmt"].leaves]
    assert kinds == ["qsgd_quant", "dense_pack", "block_sparse",
                     "block_sparse"]
    for kernel in harness.available_pack_impls()[1:]:
        out = harness.run_tree_trajectory(spec, kernel, tree=NESTED_TREE)
        _assert_same_trajectory(ref, out, f"nested oracle vs {kernel}")


def test_nested_pipelined_differential():
    spec = _spec("block_topk:32,4", backend="shard_map", mesh="4x1",
                 pipeline="depth:1", leaf_codecs=MIXED_RULES)
    ref = harness.run_tree_trajectory(spec, "oracle", tree=NESTED_TREE)
    for kernel in harness.available_pack_impls()[1:]:
        out = harness.run_tree_trajectory(spec, kernel, tree=NESTED_TREE)
        _assert_same_trajectory(ref, out, f"pipelined nested vs {kernel}")
        harness.assert_bit_identical(ref["pending"], out["pending"],
                                     "pipelined nested in-flight")
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(ref["x"]))


def test_qwen2_param_tree_mixed_codecs():
    """The ISSUE's proof obligation (b): the REAL qwen2-0.5b (smoke)
    parameter tree with mixed block-top-k / QSGD / dense leaves runs the
    identical trajectory through every available pack backend, and the
    composed accounting is exactly the per-leaf sum."""
    from repro.configs import get_smoke_config
    from repro.models import build_model

    params = build_model(get_smoke_config("qwen2-0.5b")).init(
        jax.random.key(0))
    spec = ExperimentSpec(
        compressor="block_topk:256,16", problem="quadratic",
        backend="reference", n=2, d=131072, steps=2, gamma=0.01,
        leaf_codecs="*embed*=qsgd:16;*norm*=identity")
    ref = harness.run_tree_trajectory(spec, "oracle", tree=params)
    kinds = {c.kind for c in ref["fmt"].leaves}
    assert kinds == {"block_sparse", "qsgd_quant", "dense_pack"}
    assert ref["round_bits"]["up"] == spec.n * sum(ref["bits_by_leaf"])
    for kernel in harness.available_pack_impls()[1:]:
        out = harness.run_tree_trajectory(spec, kernel, tree=params)
        _assert_same_trajectory(ref, out, f"qwen2 oracle vs {kernel}")


# ---------------------------------------------------------------------------
# 3. composed accounting
# ---------------------------------------------------------------------------

def test_composed_bits_is_sum_of_leaf_bits():
    fmt = wire.TreeWire.for_tree(
        make_compressor("block_topk:32,4"), NESTED_TREE,
        rules=wire.parse_leaf_rules(MIXED_RULES))
    per_worker = fmt.bits_per_round()
    assert per_worker == sum(fmt.bits_by_leaf())
    assert fmt.bits_per_round(n_workers=4) == 4 * per_worker
    assert fmt.dense_bits() == 32 * (16 * 8 + 64 + 1 + 1)


def test_composed_bits_leaf_order_independent():
    """Permuting WHERE each (path, leaf) pair sits in the tree structure
    cannot move the composed accounting: rules follow the path, so the
    per-leaf bit multiset -- and its sum -- is structure-order free."""
    comp = make_compressor("topk:8")
    rules = wire.parse_leaf_rules("*embed*=qsgd:16")
    named = [("embed", jnp.zeros((16, 8))), ("w", jnp.zeros((64,))),
             ("tiny", jnp.zeros((5,)))]
    layouts = [dict(named),
               {"outer": dict(named[::-1])},
               (dict(named[:1]), dict(named[1:]))]
    fmts = [wire.TreeWire.for_tree(comp, t, rules=rules) for t in layouts]
    assert len({f.bits_per_round() for f in fmts}) == 1
    assert len({tuple(sorted(f.bits_by_leaf())) for f in fmts}) == 1


# ---------------------------------------------------------------------------
# 4. property tests: random nested pytrees (seed-driven, so the _prop shim
#    and real hypothesis both drive them)
# ---------------------------------------------------------------------------

_LEAF_SHAPES = [(), (1,), (7,), (64,), (3, 5), (16, 8), (2, 2, 3)]
_LEAF_DTYPES = [jnp.float32, jnp.bfloat16]
_PROP_COMPS = ["topk:8", "randk:8", "block_topk:32,4", "qsgd:16", "sign",
               "identity", "mix:4,4", "comp:4,16"]


def _random_tree(rng: random.Random):
    """A random nested pytree of dict/tuple/list nodes with 1..6 mixed
    f32/bf16 leaves, always including at least one degenerate (0-d or
    size-1) leaf candidate in the shape pool."""
    n_leaves = rng.randint(1, 6)
    leaves = [jnp.zeros(rng.choice(_LEAF_SHAPES),
                        rng.choice(_LEAF_DTYPES)) for _ in range(n_leaves)]

    def nest(ls):
        if len(ls) == 1 and rng.random() < 0.5:
            return ls[0]
        kind = rng.choice(["dict", "tuple", "list"])
        if kind == "dict":
            return {f"k{i}": l for i, l in enumerate(ls)}
        if len(ls) >= 2 and rng.random() < 0.4:
            split = rng.randint(1, len(ls) - 1)
            inner = nest(ls[split:])
            ls = ls[:split] + [inner]
        return tuple(ls) if kind == "tuple" else list(ls)

    return nest(leaves)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_prop_decode_encode_equals_dense_per_leaf(seed):
    """decode(encode(delta)) == the (clamped) dense compressor output,
    bit-for-bit, on EVERY leaf of a random nested tree."""
    rng = random.Random(seed)
    tree = _random_tree(rng)
    base = make_compressor(rng.choice(_PROP_COMPS))
    rules = ()
    if rng.random() < 0.6:
        rules = wire.parse_leaf_rules(
            f"*k0*={rng.choice(_PROP_COMPS)};*k1*={rng.choice(_PROP_COMPS)}")
    fmt = wire.TreeWire.for_tree(base, tree, rules=rules)
    key = jax.random.key(seed)
    ks = fmt.leaf_keys(key)
    flat = jax.tree_util.tree_leaves(tree)
    for j, (codec, comp, leaf) in enumerate(
            zip(fmt.leaves, fmt.compressors, flat)):
        kj = ks[j]
        delta = jax.random.normal(jax.random.fold_in(jax.random.key(7), j),
                                  jnp.shape(leaf), jnp.float32)
        payload = codec.encode(kj, delta.reshape(-1))
        dec = codec.decode(payload).reshape(jnp.shape(leaf))
        dense = comp(kj, delta)
        np.testing.assert_array_equal(np.asarray(dec), np.asarray(dense),
                                      err_msg=f"leaf {fmt.paths[j]} "
                                              f"codec {codec.kind}")


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_prop_payload_bytes_equals_bits(seed):
    """What actually crosses the wire -- payload_bytes of a real encoded
    message -- is EXACTLY bits_per_round / 8, per leaf and composed."""
    rng = random.Random(seed)
    tree = _random_tree(rng)
    fmt = wire.TreeWire.for_tree(make_compressor(rng.choice(_PROP_COMPS)),
                                 tree)
    ks = fmt.leaf_keys(jax.random.key(seed))
    total = 0
    for j, codec in enumerate(fmt.leaves):
        payload = codec.encode(ks[j], jnp.arange(codec.size,
                                                 dtype=jnp.float32))
        assert wire.payload_bytes(payload) == codec.payload_bits // 8
        assert codec.payload_bits % 8 == 0
        total += codec.payload_bits
    assert fmt.bits_per_round() == total


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_prop_zero_messages_decode_to_zero(seed):
    """The pipelined priming payload decodes to EXACTLY zero on every leaf
    of a random tree -- including degenerate 0-d / size-1 leaves."""
    rng = random.Random(seed)
    tree = _random_tree(rng)
    fmt = wire.TreeWire.for_tree(make_compressor(rng.choice(_PROP_COMPS)),
                                 tree)
    zmsgs = fmt.zero_messages(jax.random.key(seed))
    dense = fmt.decode(zmsgs)
    for leaf in jax.tree_util.tree_leaves(dense):
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.zeros(leaf.shape, np.float32))


# ---------------------------------------------------------------------------
# 5. degenerate leaves: size-1, 0-d and size < k (satellite: the
#    zero_message / mask_message priming regression)
# ---------------------------------------------------------------------------

DEGENERATE_TREE = {"scalar": jnp.zeros(()), "one": jnp.zeros((1,)),
                   "tiny": jnp.zeros((3,)), "wide": jnp.zeros((64,))}


@pytest.mark.parametrize("comp", ["topk:8", "randk:8", "scaled_randk:8",
                                  "block_topk:32,4", "mix:4,4", "comp:4,16",
                                  "qsgd:16", "sign", "natural"])
def test_degenerate_leaves_encode_decode_zero_mask(comp):
    """k > leaf size clamps per leaf: encode, decode, zero_message and
    mask_message all work on 0-d / size-1 / size-3 leaves, and the masked
    zero message still decodes to exactly zero."""
    fmt = wire.TreeWire.for_tree(make_compressor(comp), DEGENERATE_TREE)
    key = jax.random.key(3)
    ks = fmt.leaf_keys(key)
    for j, codec in enumerate(fmt.leaves):
        delta = jax.random.normal(jax.random.fold_in(key, 100 + j),
                                  (codec.size,), jnp.float32)
        payload = codec.encode(ks[j], delta)
        dec = codec.decode(payload)
        assert dec.shape == (codec.size,)
        zero = wire.zero_message(codec, ks[j])
        np.testing.assert_array_equal(np.asarray(codec.decode(zero)),
                                      np.zeros((codec.size,), np.float32))
        masked = codec.mask_message(payload, jnp.float32(0.0))
        np.testing.assert_array_equal(np.asarray(codec.decode(masked)),
                                      np.zeros((codec.size,), np.float32))


def test_degenerate_tree_pipelined_priming_trajectory():
    """The regression the clamp exists for: a pipelined trajectory over a
    tree with size-1 / size<k leaves primes, streams and decodes without
    crashing, and stays finite."""
    spec = _spec("block_topk:32,4", backend="shard_map", mesh="4x1",
                 pipeline="depth:1", steps=3)
    out = harness.run_tree_trajectory(spec, tree=DEGENERATE_TREE)
    assert all(bool(jnp.all(jnp.isfinite(l)))
               for l in jax.tree.leaves(out["x"]))
    # the priming buffer itself: one stacked zero message per leaf
    assert len(out["pending"]) == len(out["fmt"].leaves)


def test_clamp_for_leaf_identity_when_no_clamp_needed():
    """clamp_for_leaf returns the SAME object when k fits -- hashing (and
    so jit caches and spec fingerprints) cannot be perturbed."""
    for comp in ["topk:8", "randk:8", "block_topk:32,4", "mix:4,4",
                 "comp:4,16", "qsgd:16", "sign", "identity"]:
        c = make_compressor(comp)
        assert wire.clamp_for_leaf(c, 64) is c
    small = wire.clamp_for_leaf(make_compressor("topk:8"), 3)
    assert small.k == 3
    mix = wire.clamp_for_leaf(make_compressor("mix:4,4"), 5)
    assert (mix.k, mix.kp) == (4, 1)  # k + kp <= d, no double-counting


# ---------------------------------------------------------------------------
# 6. negative paths: inconsistent spec combos, messages VERBATIM
# ---------------------------------------------------------------------------

def _verbatim(msg):
    return "^" + re.escape(msg) + "$"


def test_rejects_pipelined_reference_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "the pipelined schedule double-buffers the trainer's wire "
            "payload; the reference backend runs the exact sequential "
            "recursion (set pipeline='off', or backend='shard_map')")):
        ExperimentSpec(n=2, d=8, pipeline="depth:1")


@pytest.mark.parametrize("source", ["fields", "spec_file"])
def test_rejects_fsdp_backend_verbatim(source):
    # 'fsdp' names no trainer: a spec (or spec file) asking for it fails
    # validation instead of falling back to the shard_map trainer
    msg = "spec.backend = 'fsdp' not in ('reference', 'shard_map')"
    with pytest.raises(SpecError, match=_verbatim(msg)):
        if source == "fields":
            ExperimentSpec(compressor="qsgd:16", backend="fsdp",
                           problem="qwen2-0.5b", smoke=True, mesh="1x1",
                           n=1, d=8)
        else:
            path = os.path.join(REPO, "examples", "specs", "zoo_qwen2.json")
            raw = json.loads(open(path).read())
            ExperimentSpec.from_json(json.dumps({**raw, "backend": "fsdp"}))


def test_rejects_smoke_reference_problem_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "spec.smoke selects a model arch's reduced config; the "
            f"built-in problems {REFERENCE_PROBLEMS} are sized by "
            "spec.d/n")):
        ExperimentSpec(n=2, d=8, smoke=True)


def test_rejects_reference_mesh_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "spec.mesh is a trainer-backend field; the reference backend "
            "takes n directly (set mesh='')")):
        ExperimentSpec(n=2, d=8, mesh="2x1")


def test_rejects_resample_quadratic_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "the quadratic problem has exact gradients only; "
            "resample=True needs problem='logreg' or a trainer backend")):
        ExperimentSpec(n=2, d=8, resample=True)


def test_rejects_mesh_worker_mismatch_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "spec.n = 4 but mesh '2x2' has 2 workers (product of the "
            "non-'model' axes)")):
        ExperimentSpec(compressor="qsgd:16", backend="shard_map",
                       problem="quadratic", mesh="2x2", n=4, d=8)


def test_rejects_leaf_codecs_with_fleet_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "spec.leaf_codecs assigns compressors per LEAF of one uplink "
            "compressor; a heterogeneous fleet assigns them per WORKER -- "
            "use one or the other (got compressor='topk:8;qsgd:16')")):
        ExperimentSpec(compressor="topk:8;qsgd:16", n=2, d=8,
                       leaf_codecs="*=sign")


def test_rejects_leaf_codecs_mode_none_verbatim():
    with pytest.raises(SpecError, match=_verbatim(
            "spec.leaf_codecs configures the compression layer's wire; "
            "mode='none' has no compression layer")):
        ExperimentSpec(mode="none", n=2, d=8, leaf_codecs="*=sign")


def test_rejects_malformed_leaf_rule_verbatim():
    with pytest.raises(ValueError, match=_verbatim(
            "leaf-codec rule '=qsgd:16' needs both a leaf-path pattern "
            "and a compressor spec around the '='")):
        ExperimentSpec(n=2, d=8, leaf_codecs="=qsgd:16")


def test_rejects_unknown_compressor_in_leaf_rule():
    with pytest.raises(ValueError, match="unknown compressor 'mnice'"):
        ExperimentSpec(n=2, d=8, leaf_codecs="embed*=mnice:4,2")


def test_rejects_joint_leaf_rule_verbatim():
    """The string grammar cannot name a joint compressor, so the guard is
    exercised on the programmatic EFBV.make path (same message as
    wire.parse_leaf_rules' own)."""
    from repro.core.compressors import MNice, TopK
    from repro.core.efbv import EFBV
    with pytest.raises(ValueError, match=_verbatim(
            "jointly-defined compressors (m-nice) cannot be leaf-codec "
            "rules: their draws couple all workers")):
        EFBV.make(TopK(4), d=16, n=4, leaf_rules=(("*", MNice(4, 2)),))


def test_rejects_fleet_plus_leaf_rules_in_efbv_make():
    from repro.core.compressors import QSGD, TopK
    from repro.core.efbv import EFBV
    with pytest.raises(ValueError, match=_verbatim(
            "per-leaf codec rules cannot be combined with a heterogeneous "
            "worker fleet")):
        EFBV.make([TopK(4), QSGD(16)], d=16, n=2,
                  leaf_rules=(("*", QSGD(16)),))


# ---------------------------------------------------------------------------
# 7. tuning composition over leaves
# ---------------------------------------------------------------------------

def test_tree_constants_single_leaf_noop():
    """ONE leaf: tree composition is exactly the leaf's own constants --
    the tuning (and so every existing fingerprinted run) cannot move."""
    from repro.core import theory
    c = make_compressor("topk:8")
    eta, omega = c.eta(64), c.omega(64)
    for agg in ("worst", "mean"):
        e, o, oav = theory.tree_constants([eta], [omega], [64], n=4,
                                          aggregate=agg)
        assert (e, o) == (eta, omega)
        assert oav == omega / 4
    flat = theory.tune_for(c, d=64, n=4)
    tree = theory.tune_tree([eta], [omega], [64], n=4)
    assert (flat.lam, flat.nu, flat.r, flat.r_av, flat.theta) == \
        (tree.lam, tree.nu, tree.r, tree.r_av, tree.theta)


def test_tune_tree_worst_case_dominated_by_worst_leaf():
    from repro.core import theory
    etas, omegas = [0.3, 0.9], [4.0, 0.5]
    e, o, _ = theory.tree_constants(etas, omegas, n=4, aggregate="worst")
    assert (e, o) == (0.9, 4.0)
    t = theory.tune_tree(etas, omegas, n=4, aggregate="worst")
    worst = theory.tune(eta=0.9, omega=4.0, omega_av=1.0, n=4)
    assert (t.lam, t.nu) == (worst.lam, worst.nu)


def test_spec_fingerprint_unchanged_without_leaf_codecs():
    a = ExperimentSpec(n=2, d=8)
    assert "leaf_codecs" not in a.to_dict()
    b = ExperimentSpec(n=2, d=8, leaf_codecs="*=sign")
    assert b.to_dict()["leaf_codecs"] == "*=sign"
    assert a.fingerprint() != b.fingerprint()
    rt = ExperimentSpec.from_json(b.to_json())
    assert rt == b and rt.fingerprint() == b.fingerprint()


# ---------------------------------------------------------------------------
# 8. the trainers consume the tree path (multi-device, subprocess)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tree_wire_trainer_4dev():
    """4-device shard_map run of a leaf_codecs spec: the trainer's wire is
    a TreeWire (mixed leaf kinds), training stays finite, and dropping the
    rules changes the trajectory (the per-leaf wire is real, not cosmetic).
    """
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core import ExperimentSpec, build
        from repro.distributed import wire
        from repro.launch.train import main

        spec = ExperimentSpec(
            compressor="block_topk:256,16", mode="efbv",
            agg="sparse_allgather", backend="shard_map",
            problem="qwen2-0.5b", smoke=True, mesh="2x2", n=2, d=131072,
            steps=2, leaf_codecs="*embed*=qsgd:16;*norm*=identity")
        run = build(spec)
        assert run.leaf_rules is not None and len(run.leaf_rules) == 2
        print("SPEC_OK", spec.fingerprint())

        import json, tempfile, os
        path = os.path.join(tempfile.mkdtemp(), "tree.json")
        with open(path, "w") as f:
            f.write(spec.to_json())
        main(["--spec", path, "--smoke", "--global-batch", "8",
              "--seq", "32", "--steps", "2", "--log-every", "1"])
        print("TRAIN_OK")
    """, n_devices=4)
    assert "TRAIN_OK" in out
