"""The reduction by named layer (chipbench/scopes.py): on a hand-made
program and events, and on a small trace recorded here on the CPU."""

from __future__ import annotations

import contextlib
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import scopes  # noqa: E402
from chipbench import trace as tr  # noqa: E402

HLO = """\
HloModule jit_train_step, is_scheduled=true

%body (p.1: f32[4]) -> f32[4] {
  %p.1 = f32[4]{0} parameter(0)
  %dot.1 = f32[4]{0} dot(%p.1, %p.1), metadata={op_name="jit(train_step)/efbv.fwd_bwd/while/body/dot_general"}
  ROOT %copy.2 = f32[4]{0} copy(%dot.1)
}

%cond (p.2: f32[4]) -> pred[] {
  %p.2 = f32[4]{0} parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0), metadata={op_name="x"}
  %while.3 = f32[4]{0} while(%x), condition=%cond, body=%body, metadata={op_name="jit(train_step)/efbv.fwd_bwd/jvp()/while"}
  %copy.4 = f32[4]{0} copy(%while.3)
  %fusion.5 = f32[4]{0} fusion(%copy.4), kind=kLoop, calls=%fused, metadata={op_name="jit(train_step)/efbv.decode/add"}
  %add.6 = f32[4]{0} add(%fusion.5, %fusion.5), metadata={op_name="jit(train_step)/add"}
  ROOT %copy.7 = f32[4]{0} copy(%add.6)
}
"""


def ev(name, start, end):
    return tr.Event(name, name, start, end)


def test_scope_map_attribution():
    m = scopes.scope_map(HLO)
    # own op_name; the while's body takes the while's scope
    assert m["while.3"] == m["dot.1"] == m["copy.2"] == "efbv.fwd_bwd"
    assert m["lt.1"] == "efbv.fwd_bwd"
    # a compiler-made copy takes its user's scope
    assert m["copy.4"] == "efbv.decode" and m["fusion.5"] == "efbv.decode"
    # an op_name outside every scope, and a compiler-made op whose users have
    # none, stay unnamed (other)
    assert "add.6" not in m and "copy.7" not in m and "x" not in m


def test_layer_seconds_union_and_other():
    spans = [ev("bench.window", 0.0, 10.0)]
    ops = {"/device:TPU:0": [
        ev("%while.3", 0.0, 4.0),          # encloses its body's ops
        ev("%dot.1", 0.5, 1.5),
        ev("%copy.2", 2.0, 3.0),
        ev("%copy.4", 4.0, 5.0),
        ev("%fusion.5", 5.0, 6.0),
        ev("%add.6", 6.0, 6.5),            # op_name with no scope
        ev("%copy.7", 6.5, 7.0),           # no metadata, no scoped user
        ev("%fusion.9", 7.0, 7.5),         # another program's op
        ev("%fusion.5", 9.5, 11.0),        # clipped to the window
    ]}
    got = scopes.layer_seconds(ops, spans, scopes.scope_map(HLO))
    assert got["efbv.fwd_bwd"] == pytest.approx(4.0)   # once, not 6
    assert got["efbv.decode"] == pytest.approx(2.5)
    assert got[scopes.UNSCOPED] == pytest.approx(1.5)
    red = tr.reduce(ops, spans, {})
    assert sum(got.values()) == pytest.approx(red.busy_s)


def test_layer_seconds_mean_over_devices():
    spans = [ev("bench.window", 0.0, 10.0)]
    ops = {"/device:TPU:0": [ev("%fusion.5", 0.0, 2.0)],
           "/device:TPU:1": [ev("%fusion.5", 0.0, 1.0), ev("%add.6", 1.0, 3.0)]}
    got = scopes.layer_seconds(ops, spans, scopes.scope_map(HLO))
    assert got == {"efbv.decode": pytest.approx(1.5),
                   scopes.UNSCOPED: pytest.approx(1.0)}
    with pytest.raises(ValueError):
        scopes.layer_seconds(ops, [], {})


def _toy(named):
    import jax
    import jax.numpy as jnp

    def scope(name):
        return jax.named_scope(name) if named else contextlib.nullcontext()

    def step(x):
        with scope("efbv.fwd_bwd"):
            y = jnp.tanh(x @ x)
        with scope("efbv.optimizer"):
            z = (y * 0.5) @ x
        return z + 1.0

    return jax.jit(step)


def test_program_text_ignores_scopes_only():
    import jax.numpy as jnp

    x = jnp.ones((64, 64), jnp.float32)
    scoped = _toy(True).lower(x).compile().as_text()
    plain = _toy(False).lower(x).compile().as_text()
    assert "efbv.fwd_bwd" in scoped and "efbv.fwd_bwd" not in plain
    assert scopes.program_text(scoped) == scopes.program_text(plain)
    assert "metadata=" not in scopes.program_text(scoped)
    other = _toy(True).lower(jnp.ones((32, 32), jnp.float32)).compile()
    assert scopes.program_digest(other.as_text()) != \
        scopes.program_digest(scoped)


def _kernel_line(fn, loc):
    """One tpu_custom_call instruction whose body is a serialized module
    holding one operation ``k.<fn>`` at source location ``loc``."""
    import base64
    import io

    from jax._src.lib.mlir import ir

    with ir.Context() as ctx:
        ctx.allow_unregistered_dialects = True
        module = ir.Module.parse(
            f'module {{ "k.{fn}"() : () -> () loc({loc}) }}')
        buf = io.BytesIO()
        module.operation.write_bytecode(file=buf)
    body = base64.b64encode(buf.getvalue()).decode()
    return ('  %k.1 = f32[8]{0} custom-call(), custom_call_target='
            '"tpu_custom_call", backend_config={"custom_call_config":'
            f'{{"body":"{body}"}}}}, metadata={{op_name="efbv.compress"}}\n')


def test_program_text_ignores_kernel_source_locations():
    here = _kernel_line("kernel", '"/a/src/pack.py":10:3')
    there = _kernel_line("kernel", '"/b/src/pack.py":97:5')
    other = _kernel_line("other_kernel", '"/a/src/pack.py":10:3')
    assert here != there
    assert scopes.program_text(here) == scopes.program_text(there)
    assert scopes.program_text(here) != scopes.program_text(other)


def test_layers_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    toy = _toy(True)
    x = jnp.ones((256, 256), jnp.float32)
    compiled = toy.lower(x).compile()
    compiled(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            compiled(x).block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = tr.read(tr.find_xplane(str(tmp_path)))
    got = scopes.layer_seconds(ops, spans, scopes.scope_map(compiled.as_text()))
    assert got["efbv.fwd_bwd"] > 0 and got["efbv.optimizer"] > 0
    assert set(got) <= {"efbv.fwd_bwd", "efbv.optimizer", scopes.UNSCOPED}
    # the ops run one after another: the layers add up to the busy time
    assert sum(got.values()) == pytest.approx(tr.reduce(ops, spans).busy_s,
                                              rel=1e-9)
