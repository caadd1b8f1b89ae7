"""The static-analysis layer (repro/analysis/).

Per rule: a bad fixture produces exactly the expected finding, a good
fixture stays clean, a ``# repro: noqa(rule)`` suppression is honored, and
a stale suppression is itself flagged.  Plus: the committed golden counts
match a fresh run over the tree, the dense-free proof holds for every
registered pack kernel (and catches a deliberately dense function), and
the REFERENCE_FOLD extraction of PR 10 is pinned to its pre-existing
literal so reference trajectories are unchanged.
"""

import json
import textwrap
from pathlib import Path

import pytest

from repro.analysis import framework
from repro.analysis import hlo
from repro.analysis import rules as _rules  # noqa: F401  (registers rules)
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.docs import discover_doctests
from repro.core import efbv

REPO = Path(__file__).resolve().parents[1]


def run_rules(tmp_path, code, rule_names, relpath="mod.py"):
    """-> (findings, suppressed) of the named rules over a fixture file."""
    p = tmp_path / relpath
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(code))
    subset = {n: framework.RULES[n] for n in rule_names}
    kept, suppressed, _errors = framework.analyze_file(p, subset)
    return kept, suppressed


# ---------------------------------------------------------------------------
# R1 prng-reuse
# ---------------------------------------------------------------------------


def test_r1_flags_double_consumption(tmp_path):
    bad = """
    import jax

    def f(key):
        a = jax.random.normal(key, (3,))
        b = jax.random.uniform(key, (3,))
        return a + b
    """
    kept, _ = run_rules(tmp_path, bad, ["prng-reuse"])
    assert [f.rule for f in kept] == ["prng-reuse"]
    assert "already consumed" in kept[0].message
    assert kept[0].line == 6  # the second consumption is the defect site


def test_r1_split_interleaving_is_clean(tmp_path):
    good = """
    import jax

    def f(key):
        k1, k2 = jax.random.split(key)
        a = jax.random.normal(k1, (3,))
        b = jax.random.uniform(k2, (3,))
        return a + b
    """
    kept, _ = run_rules(tmp_path, good, ["prng-reuse"])
    assert kept == []


def test_r1_early_return_branches_are_independent(tmp_path):
    # the Participation.sample_mask shape: mutually-exclusive `if: return`
    # arms each consume the key once -- no reuse on any real path
    good = """
    import jax

    def sample(kind, key, n):
        if kind == "bernoulli":
            return jax.random.bernoulli(key, 0.5, (n,))
        if kind == "fixed":
            return jax.random.permutation(key, n)
        return None
    """
    kept, _ = run_rules(tmp_path, good, ["prng-reuse"])
    assert kept == []


def test_r1_flags_loop_carried_reuse_and_accepts_fold_in(tmp_path):
    bad = """
    import jax

    def f(key):
        out = []
        for i in range(4):
            out.append(jax.random.normal(key, (2,)))
        return out
    """
    kept, _ = run_rules(tmp_path, bad, ["prng-reuse"])
    assert [f.rule for f in kept] == ["prng-reuse"]
    assert "loop iterations" in kept[0].message

    good = """
    import jax

    def f(key):
        out = []
        for i in range(4):
            out.append(jax.random.normal(jax.random.fold_in(key, i), (2,)))
        return out
    """
    kept, _ = run_rules(tmp_path, good, ["prng-reuse"])
    assert kept == []


def test_r1_flags_literal_fold_constants(tmp_path):
    bad = """
    import jax

    def f(key):
        return jax.random.fold_in(key, 0xDEADBEEF)
    """
    kept, _ = run_rules(tmp_path, bad, ["prng-reuse"])
    assert [f.rule for f in kept] == ["prng-reuse"]
    assert "*_FOLD" in kept[0].message

    good = """
    import jax
    from repro.core.efbv import DOWNLINK_FOLD

    def f(key, j):
        a = jax.random.fold_in(key, DOWNLINK_FOLD)   # registry name: fine
        b = jax.random.fold_in(key, 3)               # small index: fine
        return a, b, jax.random.fold_in(key, j)
    """
    kept, _ = run_rules(tmp_path, good, ["prng-reuse"])
    assert kept == []


# ---------------------------------------------------------------------------
# R2 low-precision-accumulation
# ---------------------------------------------------------------------------


def test_r2_flags_bf16_contractions_and_reductions(tmp_path):
    bad = """
    import jax.numpy as jnp

    def f(a, b):
        x = a.astype(jnp.bfloat16)
        d = jnp.dot(x, b)
        m = x @ b
        s = x.sum()
        return d, m, s
    """
    kept, _ = run_rules(tmp_path, bad, ["low-precision-accumulation"])
    assert [f.rule for f in kept] == ["low-precision-accumulation"] * 3
    assert {f.line for f in kept} == {6, 7, 8}


def test_r2_preferred_element_type_or_upcast_is_clean(tmp_path):
    good = """
    import jax.numpy as jnp

    def f(a, b):
        x = a.astype(jnp.bfloat16)
        d = jnp.dot(x, b, preferred_element_type=jnp.float32)
        s = x.sum(dtype=jnp.float32)
        y = x.astype(jnp.float32)
        m = y @ b
        dyn = a.astype(b.dtype) @ b      # dynamic dtype: not statically low
        return d, s, m, dyn
    """
    kept, _ = run_rules(tmp_path, good, ["low-precision-accumulation"])
    assert kept == []


# ---------------------------------------------------------------------------
# R3 hot-path-ravel
# ---------------------------------------------------------------------------


def test_r3_flags_ravel_only_in_hot_paths(tmp_path):
    code = """
    def f(x, tree):
        from jax.flatten_util import ravel_pytree
        flat, unravel = ravel_pytree(tree)
        return x.ravel(), flat
    """
    kept, _ = run_rules(tmp_path, code, ["hot-path-ravel"],
                        relpath="kernels/k.py")
    assert [f.rule for f in kept] == ["hot-path-ravel"] * 2

    kept, _ = run_rules(tmp_path, code, ["hot-path-ravel"],
                        relpath="models/m.py")
    assert kept == []


# ---------------------------------------------------------------------------
# R4 spec-fingerprint-stability
# ---------------------------------------------------------------------------


def test_r4_flags_post_v1_field_without_delete_guard(tmp_path):
    bad = """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class ExperimentSpec:
        compressor: str = "topk:8"
        pipeline: str = "off"

        def to_dict(self):
            return {"compressor": self.compressor, "pipeline": self.pipeline}
    """
    kept, _ = run_rules(tmp_path, bad, ["spec-fingerprint-stability"])
    assert [f.rule for f in kept] == ["spec-fingerprint-stability"]
    assert "pipeline" in kept[0].message
    assert "fingerprint" in kept[0].message


def test_r4_flags_unfrozen_class_and_bad_defaults(tmp_path):
    bad = """
    import dataclasses

    @dataclasses.dataclass
    class ServeSpec:
        replicas: int = 2
        slots: list = dataclasses.field(default_factory=list)
        prompt: int
    """
    kept, _ = run_rules(tmp_path, bad, ["spec-fingerprint-stability"])
    msgs = "\n".join(f.message for f in kept)
    assert "frozen=True" in msgs
    assert "slots" in msgs and "immutable JSON scalar" in msgs
    assert "prompt" in msgs and "no default" in msgs


def test_r4_flags_guard_default_mismatch(tmp_path):
    bad = """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class ExperimentSpec:
        compressor: str = "topk:8"
        serve: str = ""

        def to_dict(self):
            d = {"compressor": self.compressor, "serve": self.serve}
            if self.serve == "none":
                del d["serve"]
            return d
    """
    kept, _ = run_rules(tmp_path, bad, ["spec-fingerprint-stability"])
    assert len(kept) == 1
    assert "default-constructed spec would" in kept[0].message


def test_r4_clean_on_guarded_spec_and_on_the_real_one(tmp_path):
    good = """
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class ExperimentSpec:
        compressor: str = "topk:8"
        pipeline: str = "off"

        def to_dict(self):
            d = {"compressor": self.compressor, "pipeline": self.pipeline}
            if self.pipeline == "off":
                del d["pipeline"]
            return d
    """
    kept, _ = run_rules(tmp_path, good, ["spec-fingerprint-stability"])
    assert kept == []

    # the shipped spec module is the rule's real target: it must hold
    subset = {"spec-fingerprint-stability":
              framework.RULES["spec-fingerprint-stability"]}
    kept, _, _ = framework.analyze_file(
        REPO / "src" / "repro" / "core" / "spec.py", subset)
    assert kept == []


# ---------------------------------------------------------------------------
# R5 pallas-kernel-hygiene
# ---------------------------------------------------------------------------


def test_r5_flags_closure_missing_specs_and_f64(tmp_path):
    bad = """
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def wrapper(x, lam):
        scale = lam * 2.0

        def _scale_kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * scale
            tmp = jnp.full((4, 4), 0.5)
            big = x_ref[...].astype(jnp.float64)

        return pl.pallas_call(_scale_kernel,
                              out_shape=jax.ShapeDtypeStruct(x.shape,
                                                             x.dtype))(x)
    """
    kept, _ = run_rules(tmp_path, bad, ["pallas-kernel-hygiene"],
                        relpath="kernels/k.py")
    msgs = "\n".join(f.message for f in kept)
    assert "closes over 'scale'" in msgs
    assert "without in_specs" in msgs and "without out_specs" in msgs
    assert "f64 inside a kernel" in msgs
    assert "explicit dtype" in msgs


def test_r5_clean_kernel_and_outside_kernels_dir(tmp_path):
    good = """
    import functools
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def _scale_kernel(x_ref, o_ref, *, scale: float):
        o_ref[...] = x_ref[...] * scale
        tmp = jnp.full((4, 4), 0.5, jnp.float32)

    def wrapper(x, lam):
        return pl.pallas_call(
            functools.partial(_scale_kernel, scale=float(lam)),
            in_specs=[pl.BlockSpec(x.shape, lambda: (0, 0))],
            out_specs=pl.BlockSpec(x.shape, lambda: (0, 0)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)
    """
    kept, _ = run_rules(tmp_path, good, ["pallas-kernel-hygiene"],
                        relpath="kernels/k.py")
    assert kept == []

    # same bad code outside kernels/ is out of the rule's scope
    bad = "def _k_kernel(x_ref):\n    y = x_ref[...].astype('float64')\n"
    p = tmp_path / "models" / "m.py"
    p.parent.mkdir(exist_ok=True)
    p.write_text(bad)
    subset = {"pallas-kernel-hygiene":
              framework.RULES["pallas-kernel-hygiene"]}
    kept, _, _ = framework.analyze_file(p, subset)
    assert kept == []


# ---------------------------------------------------------------------------
# R6 shard-map-spec-consistency
# ---------------------------------------------------------------------------


def test_r6_flags_bad_axis_arity_and_collective(tmp_path):
    bad = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import compat

    def phase(a, b):
        return jax.lax.psum(a + b, "model")

    def run(mesh, x, y):
        return compat.shard_map(phase, mesh=mesh,
                                in_specs=(P("rows"),),
                                out_specs=P("data"))(x, y)
    """
    kept, _ = run_rules(tmp_path, bad, ["shard-map-spec-consistency"])
    msgs = "\n".join(f.message for f in kept)
    assert "'rows' is not a mesh axis" in msgs
    assert "in_specs has 1 entries but callee 'phase' takes 2" in msgs
    assert "psum over axis 'model'" in msgs  # specs only name rows/data


def test_r6_clean_on_consistent_call(tmp_path):
    good = """
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import compat

    def phase(a, b):
        return jax.lax.psum(a + b, "data")

    def run(mesh, x, y):
        return compat.shard_map(phase, mesh=mesh,
                                in_specs=(P("data"), P("data")),
                                out_specs=P("data"))(x, y)
    """
    kept, _ = run_rules(tmp_path, good, ["shard-map-spec-consistency"])
    assert kept == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------


def test_suppression_is_honored(tmp_path):
    code = """
    def f(x):
        return x.ravel()  # repro: noqa(hot-path-ravel) -- test fixture
    """
    kept, suppressed = run_rules(tmp_path, code, ["hot-path-ravel"],
                                 relpath="kernels/k.py")
    assert kept == []
    assert [f.rule for f in suppressed] == ["hot-path-ravel"]


def test_unused_suppression_is_flagged(tmp_path):
    code = """
    def f(x):
        return x + 1  # repro: noqa(hot-path-ravel)
    """
    kept, _ = run_rules(tmp_path, code, ["hot-path-ravel"],
                        relpath="kernels/k.py")
    assert [f.rule for f in kept] == [framework.UNUSED_SUPPRESSION]
    assert "stale" in kept[0].message


def test_unknown_rule_in_noqa_is_flagged(tmp_path):
    code = "x = 1  # repro: noqa(not-a-rule)\n"
    p = tmp_path / "m.py"
    p.write_text(code)
    kept, _, _ = framework.analyze_file(p)
    assert [f.rule for f in kept] == [framework.UNUSED_SUPPRESSION]
    assert "unknown rule" in kept[0].message


def test_noqa_inside_string_literal_is_not_a_suppression(tmp_path):
    code = '''
    DOC = """example: x.ravel()  # repro: noqa(hot-path-ravel)"""

    def f(x):
        return x.ravel()
    '''
    kept, suppressed = run_rules(tmp_path, code, ["hot-path-ravel"],
                                 relpath="kernels/k.py")
    # the real ravel still fires; the string-embedded noqa neither
    # suppresses anything nor counts as stale
    assert [f.rule for f in kept] == ["hot-path-ravel"]
    assert suppressed == []


# ---------------------------------------------------------------------------
# runner + golden
# ---------------------------------------------------------------------------


def test_cli_exit_codes_and_golden_roundtrip(tmp_path):
    bad_dir = tmp_path / "kernels"
    bad_dir.mkdir()
    (bad_dir / "k.py").write_text("def f(x):\n    return x.ravel()\n")
    assert analysis_main([str(bad_dir)]) == 1
    (bad_dir / "k.py").write_text("def f(x):\n    return x\n")
    assert analysis_main([str(bad_dir)]) == 0

    golden = tmp_path / "g.json"
    assert analysis_main([str(bad_dir), "--write-golden", str(golden)]) == 0
    data = json.loads(golden.read_text())
    assert data["files"] == 1 and data["findings"] == {}
    assert analysis_main([str(bad_dir), "--golden", str(golden)]) == 0
    (bad_dir / "k2.py").write_text("y = 2\n")
    assert analysis_main([str(bad_dir), "--golden", str(golden)]) == 1


def test_committed_golden_matches_fresh_run():
    result = framework.analyze_paths([str(REPO / "src"), str(REPO / "tests")])
    assert result.findings == [] and result.errors == []
    diffs = framework.compare_golden(result, str(REPO / "ANALYSIS_GOLDEN.json"))
    assert diffs == [], diffs


def test_parse_error_is_reported(tmp_path):
    p = tmp_path / "broken.py"
    p.write_text("def f(:\n")
    kept, _, _ = framework.analyze_file(p)
    assert [f.rule for f in kept] == ["parse-error"]


# ---------------------------------------------------------------------------
# docs analysis
# ---------------------------------------------------------------------------


def test_docs_doctest_census_counts_examples(tmp_path):
    md = tmp_path / "x.md"
    md.write_text("# t\n\n```\n>>> 1 + 1\n2\n>>> 2 + 2\n4\n```\n")
    n, errors = discover_doctests(md)
    assert n == 2 and errors == []


# ---------------------------------------------------------------------------
# dense-free proofs
# ---------------------------------------------------------------------------


def test_all_registered_pack_kernels_prove_dense_free():
    assert set(hlo.PACK_KERNELS) == {"block_topk_pack", "randk_update",
                                     "qsgd_pack"}
    for name in sorted(hlo.PACK_KERNELS):
        r = hlo.dense_free(name)
        assert r.ok, (name, r.violations)
        assert r.n_pallas_calls >= 1
        assert 0 < r.tile < r.d          # a strict fraction of d per step
        assert r.max_inner <= r.tile     # nothing denser than the tile


def test_hlo_gate_cli_proves_every_registered_kernel(capsys):
    """``python -m repro.analysis --hlo-gate`` in-process: exit 0 and one
    PROVEN line per registered pack kernel."""
    assert analysis_main(["--hlo-gate"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert all(line.endswith("-> PROVEN") for line in lines), lines
    assert [line.split(":")[0] for line in lines] == [
        f"dense-free {name}" for name in sorted(hlo.PACK_KERNELS)]


def test_dense_free_catches_a_dense_implementation(monkeypatch):
    def _dense_case():
        import jax.numpy as jnp

        d = 1024
        g = jnp.zeros((d,), jnp.float32)
        h = jnp.zeros((d,), jnp.float32)

        def fn(g, h):
            delta = g - h                       # dense d-sized intermediate
            return jnp.where(delta > 0, delta, 0.0)

        return fn, (g, h), d

    monkeypatch.setitem(hlo.PACK_KERNELS, "dense_strawman", _dense_case)
    r = hlo.dense_free("dense_strawman")
    assert not r.ok
    assert any("no pallas_call" in v for v in r.violations)
    assert any("materializes" in v for v in r.violations)


# ---------------------------------------------------------------------------
# the R1 fix of this PR: the reference driver's named fold constant
# ---------------------------------------------------------------------------


def test_reference_fold_pins_pre_existing_trajectories():
    # Run.reference() used the literal 0x5EED before the constant was named;
    # the name must keep the exact value or every recorded reference
    # trajectory (and the bit-identity pins in test_spec.py) shifts
    assert efbv.REFERENCE_FOLD == 0x5EED
