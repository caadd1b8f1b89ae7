"""The pipelined (one-round-stale) execution schedule, end to end.

Covers the whole vertical slice the `pipeline` spec field switches on --
spec surface (parsing, fingerprint stability, backend gating), theory
composition (staleness as a compressor perturbation), the reference oracle
(depth-0 bitwise no-op, round-0 priming), the differential harness legs
(depth-1 oracle == interpret over randomized bidirectional + federated
trajectories), the trainer and the mid-pipeline checkpoint round-trip --
plus the satellite regressions that rode along: `WireFormat.bits_per_round`
int/float typing, `make_mesh` axis-name validation, the streaming pack
kernel's bit-identity, and the fixed-order chunked decode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _prop import given, settings, st
from conftest import run_with_devices
from harness import (assert_bit_identical, quadratic_grads, run_trajectory)

from repro.core import ExperimentSpec, build, make_compressor, theory
from repro.core.efbv import (EFBV, PIPELINE_FOLD, Pipeline, run_reference)
from repro.core.spec import SpecError
from repro.distributed import wire
from repro.distributed.aggregate import ring_allgather
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# 1. spec surface
# ---------------------------------------------------------------------------

def test_pipeline_parse():
    assert Pipeline.parse("off") == Pipeline(depth=0)
    assert Pipeline.parse("") == Pipeline(depth=0)
    assert Pipeline.parse("depth:0") == Pipeline(depth=0)
    assert Pipeline.parse("depth:1") == Pipeline(depth=1)
    assert Pipeline.parse("off").is_off
    assert not Pipeline.parse("depth:1").is_off
    with pytest.raises(ValueError, match="depth"):
        Pipeline.parse("depth:2")  # one in-flight buffer only
    with pytest.raises(ValueError, match="pipeline spec"):
        Pipeline.parse("depth:")
    with pytest.raises(ValueError, match="pipeline spec"):
        Pipeline.parse("async")
    with pytest.raises(ValueError, match="depth"):
        Pipeline(depth=-1)


def test_spec_pipeline_fingerprint_stable():
    """pipeline='off' serializes to NOTHING: every pre-pipeline spec (and
    its fingerprint, the BENCH/checkpoint row key) is unchanged."""
    base = ExperimentSpec(compressor="qsgd:16", n=4, d=32, steps=3)
    off = dataclasses.replace(base, pipeline="off")
    assert "pipeline" not in base.to_dict()
    assert base.to_dict() == off.to_dict()
    assert base.fingerprint() == off.fingerprint()
    assert ExperimentSpec.from_json(off.to_json()) == off

    deep = ExperimentSpec(compressor="qsgd:16", backend="shard_map",
                          problem="quadratic", mesh="4x1", n=4, d=32,
                          steps=3, pipeline="depth:1")
    assert deep.to_dict()["pipeline"] == "depth:1"
    assert deep.fingerprint() != base.fingerprint()
    assert ExperimentSpec.from_json(deep.to_json()) == deep


def test_reference_backend_rejects_pipeline():
    with pytest.raises(SpecError, match="sequential"):
        ExperimentSpec(n=2, d=8, pipeline="depth:1")
    # depth:0 IS the sequential schedule: allowed everywhere
    ExperimentSpec(n=2, d=8, pipeline="depth:0")


def test_build_carries_pipeline():
    spec = ExperimentSpec(compressor="block_topk:16,4",
                          agg="sparse_allgather", backend="shard_map",
                          problem="quadratic", mesh="4x1", n=4, d=64,
                          steps=2, pipeline="depth:1")
    run = build(spec)
    assert run.pipeline == Pipeline(depth=1)
    t = run.tuned
    seq = theory.tune_for(run.compressor, spec.d, spec.n)
    assert t.r < 1.0
    assert t.r > seq.r  # staleness can only slow the certified rate


# ---------------------------------------------------------------------------
# 2. theory: staleness composition
# ---------------------------------------------------------------------------

def test_theory_depth0_exact_noop():
    for eta, omega in [(0.2, 3.0), (0.9, 0.1), (1.0 - 1e-6, 0.0)]:
        assert theory.pipeline_eta(0, eta) == eta
        assert theory.pipeline_omega(0, eta, omega) == omega
    comp = make_compressor("block_topk:16,4")
    seq = theory.tune_for(comp, 64, 4)
    assert theory.tune_for(comp, 64, 4, pipeline=None) == seq
    assert theory.tune_for(comp, 64, 4, pipeline=0) == seq


def test_theory_depth1_composition():
    for eta in [0.1, 0.5, 0.9]:
        eta_d = theory.pipeline_eta(1, eta)
        assert eta < eta_d < 1.0
        om_d = theory.pipeline_omega(1, eta, 2.0)
        assert om_d >= 2.0
    # composes AFTER participation and still certifies a rate < 1
    comp = make_compressor("block_topk:16,4")
    t = theory.tune_for(comp, 64, 4, participation=0.5, pipeline=1)
    assert 0.0 < t.r < 1.0
    assert t != theory.tune_for(comp, 64, 4, participation=0.5)


def test_theory_drift_guard():
    # rho = depth * drift * (1 - eta) must stay below 1/2
    with pytest.raises(ValueError, match="rho"):
        theory.pipeline_eta(1, 0.0, drift=0.5)
    with pytest.raises(ValueError, match="drift"):
        theory.pipeline_eta(1, 0.5, drift=-0.1)
    with pytest.raises(ValueError, match="depth"):
        theory.pipeline_eta(-1, 0.5)
    # the default drift is safe for the whole eta range
    theory.pipeline_eta(1, 0.0)


# ---------------------------------------------------------------------------
# 3. reference driver
# ---------------------------------------------------------------------------

def _ref(pipeline, steps=5, n=4, d=32, seed=0):
    grad_fn = quadratic_grads(n, d, seed)
    algo = EFBV.make(make_compressor("block_topk:16,4"), d=d, n=n,
                     pipeline=(pipeline.depth or None) if pipeline else None)
    return run_reference(algo=algo, grad_fn=lambda _k, x: grad_fn(x),
                         x0=jnp.zeros((d,)), gamma=0.05, steps=steps,
                         key=jax.random.key(seed), n=n, pipeline=pipeline)


def test_reference_depth0_bit_identical_to_off():
    a = _ref(None)
    b = _ref(Pipeline(depth=0))
    assert_bit_identical((a.x, a.state.h, a.state.h_avg),
                         (b.x, b.state.h, b.state.h_avg), "depth-0 == off")
    assert a.pending is None and b.pending is None


def test_reference_depth1_round0_is_noop_on_x():
    """Round 0 applies the zero priming buffer: g = h_avg0 + nu*0 = 0, so x
    is untouched while the workers' control variates advance on time."""
    seq = _ref(None, steps=1)
    pipe = _ref(Pipeline(depth=1), steps=1)
    np.testing.assert_array_equal(np.asarray(pipe.x), np.zeros(32))
    assert float(jnp.max(jnp.abs(seq.x))) > 0.0
    # round 0 compresses the same grads at the same x with the same key:
    # h advances identically on both schedules
    assert_bit_identical(pipe.state.h, seq.state.h, "round-0 h")
    assert pipe.pending is not None
    assert float(jnp.max(jnp.abs(pipe.pending))) > 0.0


def test_reference_depth1_matches_manual_double_buffer():
    """The scan's depth-1 carry == an eager double-buffer simulation built
    from the same compress_round / master_update primitives."""
    n, d, steps, gamma = 4, 32, 5, 0.05
    grad_fn = quadratic_grads(n, d, 0)
    algo = EFBV.make(make_compressor("block_topk:16,4"), d=d, n=n, pipeline=1)
    res = _ref(Pipeline(depth=1), steps=steps, n=n, d=d)

    x = jnp.zeros((d,))
    st = algo.init(x, n)
    pending = jnp.zeros((d,))
    keys = jax.random.split(jax.random.key(0), steps)
    for k in keys:
        d_new, h_new = algo.compress_round(k, grad_fn(x), st)
        g, h_avg = algo.master_update(st.h_avg, pending)
        st = type(st)(h=h_new, h_avg=h_avg, step=st.step + 1)
        x = x - gamma * g
        pending = d_new
    np.testing.assert_allclose(np.asarray(res.x), np.asarray(x),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.asarray(res.pending), np.asarray(pending),
                               rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# 4. differential harness legs
# ---------------------------------------------------------------------------

def _pipe_spec(seed=0, **kw):
    base = dict(compressor="block_topk:128,8", agg="sparse_allgather",
                backend="shard_map", problem="quadratic", mesh="4x1",
                n=4, d=256, steps=4, gamma=0.05, seed=seed,
                pipeline="depth:1")
    base.update(kw)
    return ExperimentSpec(**base)


def test_harness_depth0_bit_identical_to_off():
    """'depth:0' through the spec-driven harness is the SAME trajectory as
    'off' -- the historical pins cannot move."""
    off = ExperimentSpec(compressor="qsgd:16", agg="sparse_allgather",
                         downlink="sign", participation="bernoulli:0.5",
                         n=4, d=96, steps=4, gamma=0.05, seed=3)
    zero = dataclasses.replace(off, pipeline="depth:0")
    a = run_trajectory(off, "oracle")
    b = run_trajectory(zero, "oracle")
    assert_bit_identical((a["x"], a["w"], a["h"], a["masks"], a["payload"]),
                         (b["x"], b["w"], b["h"], b["masks"], b["payload"]),
                         "harness depth-0 == off")
    assert "pending" not in a and "pending" not in b


def test_harness_depth1_round0_noop_then_diverges():
    spec = _pipe_spec()
    pipe = run_trajectory(spec, "oracle")
    seq = run_trajectory(
        dataclasses.replace(spec, pipeline="off"), "oracle")
    # round 0 applies the zero-decoding priming payload
    np.testing.assert_array_equal(np.asarray(pipe["x"][0]),
                                  np.zeros(spec.d, np.float32))
    # ... then the one-round-stale schedule is a genuinely different run
    assert not np.array_equal(np.asarray(pipe["x"][-1]),
                              np.asarray(seq["x"][-1]))
    # the last round's payload is exactly what is left in flight
    assert_bit_identical(pipe["pending"], pipe["payload"], "in-flight")


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=6, deadline=None)
def test_harness_depth1_oracle_matches_interpret_bidirectional(seed):
    """The acceptance pin: depth-1 oracle == interpret, bit for bit, over
    randomized bidirectional + federated trajectories."""
    spec = _pipe_spec(seed=seed, downlink="qsgd:16",
                      participation="bernoulli:0.75")
    a = run_trajectory(spec, "oracle")
    b = run_trajectory(spec, "interpret")
    assert_bit_identical(
        (a["x"], a["w"], a["h"], a["masks"], a["payload"], a["pending"],
         a["down_payload"]),
        (b["x"], b["w"], b["h"], b["masks"], b["payload"], b["pending"],
         b["down_payload"]), f"depth-1 oracle==interpret seed={seed}")


@given(seed=st.integers(0, 2**31 - 1))
@settings(max_examples=4, deadline=None)
def test_harness_depth1_oracle_matches_interpret_full(seed):
    spec = _pipe_spec(seed=seed)
    a = run_trajectory(spec, "oracle")
    b = run_trajectory(spec, "interpret")
    assert_bit_identical((a["x"], a["h"], a["payload"], a["pending"]),
                         (b["x"], b["h"], b["payload"], b["pending"]),
                         f"depth-1 full seed={seed}")


# ---------------------------------------------------------------------------
# 5. wire primitives of the pipelined exchange
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec_str", ["block_topk:16,4", "qsgd:16", "sign",
                                      "topk:7", "identity"])
def test_zero_message_decodes_to_zero(spec_str):
    codec = wire.codec_of(make_compressor(spec_str), (96,), 96)
    key = jax.random.fold_in(jax.random.key(0), PIPELINE_FOLD)
    msg = wire.zero_message(codec, key)
    np.testing.assert_array_equal(np.asarray(codec.decode(msg)),
                                  np.zeros(96, np.float32))
    stacked = jax.tree.map(lambda a: jnp.tile(a[None], (4,) + (1,) * a.ndim),
                           tuple(msg))
    np.testing.assert_array_equal(np.asarray(codec.decode_sum(stacked)),
                                  np.zeros(96, np.float32))


def test_pipeline_chunks():
    assert wire.pipeline_chunks(1) == 1
    # n < 4: a chunk would be one worker's slice -- resharding eats the win
    assert wire.pipeline_chunks(2) == 1
    assert wire.pipeline_chunks(3) == 1
    assert wire.pipeline_chunks(4) == 4
    assert wire.pipeline_chunks(6) == 2
    assert wire.pipeline_chunks(8) == 4


def test_chunked_decode_sum():
    codec = wire.codec_of(make_compressor("block_topk:16,4"), (64,), 64)
    key = jax.random.key(7)
    msgs = [codec.encode(jax.random.fold_in(key, i),
                         jax.random.normal(jax.random.fold_in(key, 100 + i),
                                           (64,)))
            for i in range(8)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *msgs)
    whole = codec.decode_sum(stacked)
    # chunks=1 is LITERALLY decode_sum
    assert_bit_identical(wire.chunked_decode_sum(codec, stacked, 1), whole,
                         "chunks=1")
    for chunks in (2, 4, 8):
        got = wire.chunked_decode_sum(codec, stacked, chunks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                                   rtol=1e-6, atol=1e-6)
    # the fixed ascending order is replica-deterministic: same split, same sum
    assert_bit_identical(wire.chunked_decode_sum(codec, stacked, 4),
                         wire.chunked_decode_sum(codec, stacked, 4),
                         "deterministic")
    with pytest.raises(ValueError, match="split"):
        wire.chunked_decode_sum(codec, stacked, 3)


def test_ring_allgather_matches_stacked_order():
    n = 4
    msg = (jax.random.normal(jax.random.key(0), (n, 6)),
           jax.random.normal(jax.random.key(1), (n, 2, 3)))
    out = jax.vmap(lambda m: ring_allgather(m, "w", n), axis_name="w")(msg)
    for leaf, full in zip(jax.tree.leaves(out), jax.tree.leaves(msg)):
        assert leaf.shape == (n,) + full.shape
        for i in range(n):  # every worker reconstructs the canonical stack
            np.testing.assert_array_equal(np.asarray(leaf[i]),
                                          np.asarray(full))


def test_streaming_pack_bit_identical():
    lw = wire.LeafWire(shape=(640,), size=640, block=128, kb=8)
    g = jax.random.normal(jax.random.key(2), (640,))
    h = jax.random.normal(jax.random.key(3), (640,)) * 0.1
    base_p, base_h = wire.fused_pack(lw, g, h, 0.9, kernel="interpret")
    for kernel in ("interpret", "oracle"):  # oracle ignores stream
        p, hn = wire.fused_pack(lw, g, h, 0.9, kernel=kernel, stream=True)
        assert_bit_identical((p, hn), (base_p, base_h), f"stream {kernel}")


# ---------------------------------------------------------------------------
# 6. satellite regressions: wire bits typing, mesh axis validation
# ---------------------------------------------------------------------------

def test_bits_per_round_integer_counts_are_int():
    fmt = wire.format_for(make_compressor("qsgd:16"), jnp.zeros((96,)))
    per_worker = sum(l.payload_bits for l in fmt.leaves)
    bitmap = 32 * wire.bitmap_words(8)

    full = fmt.bits_per_round(n_workers=8)
    assert type(full) is int and full == 8 * per_worker

    got = fmt.bits_per_round(n_workers=8, participants=3)
    assert type(got) is int and got == bitmap + 3 * per_worker
    # an integral float |S_t| (e.g. float(mask.sum())) is still exact int
    got = fmt.bits_per_round(n_workers=8, participants=3.0)
    assert type(got) is int and got == bitmap + 3 * per_worker
    # a fractional expected count stays an (explicitly documented) float
    exp = fmt.bits_per_round(n_workers=8, participants=2.5)
    assert type(exp) is float and exp == bitmap + 2.5 * per_worker


def test_bits_per_round_exact_past_float53():
    """The historical int(float) round-trip silently rounded above 2**53."""
    fmt = wire.format_for(make_compressor("qsgd:16"), jnp.zeros((96,)))
    per_worker = sum(l.payload_bits for l in fmt.leaves)
    s = 2**53 + 1  # not representable as a float
    n = s + 7
    got = fmt.bits_per_round(n_workers=n, participants=s)
    assert type(got) is int
    assert got == 32 * wire.bitmap_words(n) + s * per_worker


def test_make_mesh_rejects_4d_shape_without_axes():
    with pytest.raises(ValueError, match="pass axes= explicitly"):
        make_mesh((2, 1, 1, 2))


# ---------------------------------------------------------------------------
# 7. checkpoints: the depth-1 fingerprint gates restore
# ---------------------------------------------------------------------------

def test_checkpoint_pipeline_fingerprint_gates_restore(tmp_path):
    from repro.checkpoint import restore_checkpoint, save_checkpoint

    deep = ExperimentSpec(compressor="block_topk:16,4",
                          agg="sparse_allgather", backend="shard_map",
                          problem="quadratic", mesh="4x1", n=4, d=64,
                          steps=2, pipeline="depth:1")
    off = dataclasses.replace(deep, pipeline="off")
    # an in-flight payload buffer checkpoints like any other state leaf
    tree = {"params": jnp.ones((4,)),
            "inflight": [(jnp.ones((4, 2, 3)), jnp.zeros((4, 2, 3),
                                                         jnp.int32))]}
    save_checkpoint(str(tmp_path), 3, tree, spec=deep)
    out = restore_checkpoint(str(tmp_path), 3, tree, spec=deep)
    assert_bit_identical(out, tree, "mid-pipeline round-trip")
    with pytest.raises(ValueError, match="refusing resume"):
        restore_checkpoint(str(tmp_path), 3, tree, spec=off)


# ---------------------------------------------------------------------------
# 8. the trainer (multi-device, subprocess)
# ---------------------------------------------------------------------------

_TRAINER_PRELUDE = """
        import jax, jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.core import EFBV, BlockTopK
        from repro.core.efbv import Pipeline
        from repro.optim import sgd, constant
        from repro.train import (make_train_step, init_train_state,
                                 train_state_shardings)
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((4, 1))
        D, H = 8, 16
        key = jax.random.key(0)
        params0 = {"w1": jax.random.normal(key, (D, H)) * 0.1,
                   "w2": jax.random.normal(key, (H, D)) * 0.1}
        specs = {"w1": P(None, "model"), "w2": P("model", None)}

        def loss_fn(p, batch):
            pred = jnp.tanh(batch["x"] @ p["w1"]) @ p["w2"]
            return jnp.mean((pred - batch["y"]) ** 2), {}

        algo = EFBV.make(BlockTopK(16, 4), d=D * H, n=4)
        opt = sgd(constant(0.05))

        def batch_at(i):
            kb = jax.random.fold_in(jax.random.key(42), i)
            x = jax.random.normal(kb, (8, D)); y = x * 0.3
            return {"x": jax.device_put(x, NamedSharding(mesh, P("data"))),
                    "y": jax.device_put(y, NamedSharding(mesh, P("data")))}

        def fresh_params():
            return jax.tree.map(lambda a: jnp.array(a, copy=True), params0)

        def run(agg, pipe, steps):
            st = init_train_state(fresh_params(), opt, mesh, algo=algo,
                                  agg_mode=agg, pipeline=pipe)
            sh = train_state_shardings(mesh, specs, st)
            st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
            step = make_train_step(loss_fn, opt, algo, mesh, agg_mode=agg,
                                   pipeline=pipe)
            for i in range(steps):
                st, m = step(st, batch_at(i), jax.random.fold_in(key, i))
            return st
"""


@pytest.mark.slow
def test_trainer_depth0_bit_identical_4dev():
    """pipeline=depth:0 and pipeline=off are the SAME program in both wire
    modes -- the sequential trajectories cannot move."""
    out = run_with_devices(_TRAINER_PRELUDE + """
        for agg in ["dense_psum", "sparse_allgather"]:
            a = run(agg, None, 4)
            b = run(agg, Pipeline(depth=0), 4)
            for la, lb in zip(jax.tree.leaves((a.params, a.h, a.h_avg)),
                              jax.tree.leaves((b.params, b.h, b.h_avg))):
                np.testing.assert_array_equal(np.asarray(la),
                                              np.asarray(lb))
            assert a.inflight is None and b.inflight is None
            print("IDENT", agg)
        print("DEPTH0_OK")
    """, n_devices=4)
    assert "DEPTH0_OK" in out


@pytest.mark.slow
def test_trainer_depth1_semantics_4dev():
    """Depth-1: round 0 leaves params untouched (zero priming payload) while
    h advances exactly as the sequential schedule's round 0; the in-flight
    buffer is carried; later rounds genuinely diverge from sequential."""
    out = run_with_devices(_TRAINER_PRELUDE + """
        for agg in ["dense_psum", "sparse_allgather"]:
            pipe1 = run(agg, Pipeline(depth=1), 1)
            seq1 = run(agg, None, 1)
            for lp, l0 in zip(jax.tree.leaves(pipe1.params),
                              jax.tree.leaves(params0)):
                np.testing.assert_array_equal(np.asarray(lp),
                                              np.asarray(l0))
            for la, lb in zip(jax.tree.leaves(pipe1.h),
                              jax.tree.leaves(seq1.h)):
                np.testing.assert_array_equal(np.asarray(la),
                                              np.asarray(lb))
            assert pipe1.inflight is not None
            pipe3 = run(agg, Pipeline(depth=1), 3)
            seq3 = run(agg, None, 3)
            diff = max(float(jnp.max(jnp.abs(a - b))) for a, b in zip(
                jax.tree.leaves(pipe3.params), jax.tree.leaves(seq3.params)))
            assert diff > 0.0, agg
            assert all(bool(jnp.all(jnp.isfinite(l)))
                       for l in jax.tree.leaves(pipe3.params))
            print("SEMANTICS", agg)
        print("DEPTH1_OK")
    """, n_devices=4)
    assert "DEPTH1_OK" in out


@pytest.mark.slow
def test_checkpoint_midpipeline_resume_bit_identical_4dev():
    """Save a depth-1 TrainState MID-pipeline (in-flight payload included),
    restore, continue: bit-identical to the uninterrupted run."""
    out = run_with_devices(_TRAINER_PRELUDE + """
        import tempfile
        from repro.checkpoint import restore_checkpoint, save_checkpoint

        agg = "sparse_allgather"
        pipe = Pipeline(depth=1)
        step = make_train_step(loss_fn, opt, algo, mesh, agg_mode=agg,
                               pipeline=pipe)

        def init():
            st = init_train_state(fresh_params(), opt, mesh, algo=algo,
                                  agg_mode=agg, pipeline=pipe)
            sh = train_state_shardings(mesh, specs, st)
            return jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh), sh

        st, sh = init()
        for i in range(5):
            st, m = step(st, batch_at(i), jax.random.fold_in(key, i))
        gold = st

        st, sh = init()
        for i in range(2):
            st, m = step(st, batch_at(i), jax.random.fold_in(key, i))
        with tempfile.TemporaryDirectory() as ckpt:
            save_checkpoint(ckpt, 2, st)
            template, _ = init()
            st = restore_checkpoint(ckpt, 2, template)
        st = jax.tree.map(lambda x, s: jax.device_put(x, s), st, sh)
        for i in range(2, 5):
            st, m = step(st, batch_at(i), jax.random.fold_in(key, i))

        for la, lb in zip(jax.tree.leaves(gold), jax.tree.leaves(st)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        print("RESUME_OK")
    """, n_devices=4)
    assert "RESUME_OK" in out
