"""The trace reduction (chipbench/trace.py): on hand-made events, and on a
small trace recorded here on the CPU."""

from __future__ import annotations

import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import trace as tr  # noqa: E402


def ev(name, start, end, text=None):
    return tr.Event(name, text or name, start, end)


def test_reduce_hand_made_events():
    spans = [ev("bench.window", 0.0, 10.0), ev("bench.make_batch", 1.0, 3.0),
             ev("bench.wait", 6.0, 9.5)]
    ops = {
        "/device:TPU:0": [ev("fusion.1", -1.0, 1.5),        # clipped to 1.5
                          ev("pack", 3.0, 5.0, "custom-call pack_update"),
                          ev("fusion.2", 4.0, 6.0),         # overlaps pack
                          ev("copy.3", 9.0, 9.5)],
        "/device:TPU:1": [ev("fusion.1", 0.0, 4.0),
                          ev("copy.1", 4.0, 5.0)],
    }
    red = tr.reduce(ops, spans, {"pack": r"pack_update"})
    assert red.devices == 2
    assert red.window_s == pytest.approx(10.0)
    # device 0: [0, 1.5] + [3, 6] + [9, 9.5] = 5.0; device 1: [0, 5] = 5.0
    assert red.busy_s == pytest.approx(5.0)
    assert red.kernel_s["pack"] == pytest.approx(2.0 / 2)
    assert red.device_ops[0] == ["fusion.1", pytest.approx(1.5 + 4.0)]
    # device 0's gaps: [1.5, 3] in make_batch, [6, 9] in wait, [9.5, 10]
    assert red.idle_gaps[0] == ["bench.wait", pytest.approx(3.0)]
    assert red.idle_gaps[1] == ["bench.make_batch", pytest.approx(1.5)]
    assert red.idle_gaps[2] == ["host: outside any span", pytest.approx(0.5)]


def test_reduce_needs_one_window():
    with pytest.raises(ValueError):
        tr.reduce({"d": [ev("x", 0, 1)]}, [], {})


def test_reduction_of_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def toy_step(x):
        return jnp.tanh(x @ x) * 0.5

    x = jnp.ones((256, 256), jnp.float32)
    toy_step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.make_batch"):
                time.sleep(0.03)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                y = toy_step(x)
            with jax.profiler.TraceAnnotation("bench.wait"):
                y.block_until_ready()
    jax.profiler.stop_trace()
    ops, spans = tr.read(tr.find_xplane(str(tmp_path)))
    red = tr.reduce(ops, spans, {"dot": r"dot"})
    window = [s for s in spans if s.name == "bench.window"][0]
    assert red.window_s == pytest.approx(window.end - window.start)
    assert 0 < red.busy_s < red.window_s
    # the union never exceeds the plain sum of the clipped intervals
    first = sorted(ops)[0]
    inside = [(max(e.start, window.start), min(e.end, window.end))
              for e in ops[first] if e.end > window.start and e.start < window.end]
    assert red.busy_s <= sum(b - a for a, b in inside) + 1e-12
    assert red.kernel_s["dot"] > 0
    # three sleeps of 30 ms with the device idle: the longest gaps
    assert [g[0] for g in red.idle_gaps[:3]] == ["bench.make_batch"] * 3
    assert all(g[1] >= 0.025 for g in red.idle_gaps[:3])
