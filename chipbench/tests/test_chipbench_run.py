"""The benchmark harness end to end at a test size on the CPU.

The harness refuses a host without a TPU.  Past that refusal (stubbed
here), a run of a small qwen2 cell through the training entry's own set-up
comes out correct against the plain reference, and comes out not correct
when the timed path underneath is broken: a step that returns its state
unchanged, or one that leaves out half of each batch.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import run as R  # noqa: E402

#: qwen2-0.5b's smoke preset in the program (``--smoke``)
SMALL_QWEN2 = {
    "name": "qwen2-small", "arch": "qwen2-0.5b", "reference": "dense_gqa",
    "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 64, "num_hidden_layers": 2,
    "vocab_size": 1024, "rope_theta": 10000.0, "rms_norm_eps": 1e-05,
    "program": {"name": "qwen2-0.5b-smoke", "d_model": 256, "n_layers": 2,
                "vocab": 1024, "activation_dtype": "bfloat16"},
}
#: above what sound runs of this cell read, below what each fault reads
SMALL_LIMITS = {"loss_gap": 1e-3, "grad_gap": 3e-2, "change_gap": 0.1,
                "state_gap": 0.1}


def small_cell(compressor="block_topk:256,16", algo="efbv",
               agg="sparse_allgather"):
    cell = R.Cell.__new__(R.Cell)
    cell.name = "qwen2-small.test"
    cell.chips = 1
    cell.config = SMALL_QWEN2
    with open(os.path.join(R.BENCH, "traffic", "efbv-btk.1x1.b4.s512.json")) as f:
        cell.traffic = dict(json.load(f), compressor=compressor, algo=algo,
                            agg=agg, seq=32)
    cell.limits = SMALL_LIMITS
    cell.model = R.load_module(os.path.join(R.BENCH, "models", "dense_gqa.py"),
                               "chipbench_model_dense_gqa")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell.end_to_end = [m for m in bench["end_to_end"] if "workloads" not in m]
    cell.per_layer = [m for m in bench["per_layer"] if "workloads" not in m]
    return cell


@pytest.fixture
def off_chip(monkeypatch):
    """Skip the harness's look for a chip, and run the program's small
    preset of the model."""
    import jax

    from chipbench import peaks

    monkeypatch.setattr(R, "require_chips", lambda chips: jax.devices()[:chips])
    v5e = peaks.peaks("TPU v5 lite")
    monkeypatch.setattr(peaks, "peaks", lambda kind: v5e)
    args = R.train_args
    monkeypatch.setattr(R, "train_args", lambda c, s: args(c, s) + ["--smoke"])
    monkeypatch.setattr(R, "use_compile_cache", lambda: None)
    return monkeypatch


class Broken:
    """A compiled step whose calls are replaced by ``call``."""

    def __init__(self, compiled, call):
        self.compiled, self.call = compiled, call

    def __call__(self, state, batch, key):
        return self.call(state, batch, key)

    def memory_analysis(self):
        return self.compiled.memory_analysis()

    def as_text(self):
        return self.compiled.as_text()


def unchanged(job):
    import jax
    import jax.numpy as jnp

    def call(state, batch, key):
        _, m = job.step_fn(jax.tree.map(jnp.copy, state), batch, key)
        return state, m
    return call


def half_batch(job):
    import jax

    def call(state, batch, key):
        half = jax.tree.map(lambda a: a[:a.shape[0] // 2], batch)
        return job.step_fn(state, half, key)
    return call


def run_small(cell, fault=None, monkeypatch=None, seed=2 ** 31 + 11):
    if fault is not None:
        real = R.compile_step
        monkeypatch.setattr(R, "compile_step", lambda job, *a: Broken(
            real(job, *a), fault(job)))
    return R.run(cell, seed, 0.5, False)


@pytest.mark.parametrize("env,why", [
    ({}, "no TPU"),
    ({"REPRO_SANITIZE": "1"}, "REPRO_SANITIZE=1"),
    ({"REPRO_WIRE_KERNEL": "oracle"}, "REPRO_WIRE_KERNEL='oracle'")])
def test_a_host_off_the_device_path_is_refused(env, why):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    res = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "qwen2-0.5b.efbv-btk.1chip", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert res.returncode != 0
    assert "correct" not in res.stdout
    assert why in res.stderr


def test_small_cell_is_correct(off_chip):
    result = run_small(small_cell())
    assert result["correct"], result["check"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "check"
    assert set(result["metrics"]) == {"tokens_per_s", "hbm_gib", "setup_s"}
    assert result["device"]["count"] == 1


@pytest.mark.parametrize("fault", [unchanged, half_batch])
def test_broken_step_is_not_correct(off_chip, fault):
    result = run_small(small_cell(), fault, off_chip)
    assert not result["correct"], result["check"]
