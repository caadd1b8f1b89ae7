"""Scaling sweeps, two kinds (formerly benchmarks/n_scaling.py -- the row
names keep the historical ``n_scaling/`` prefix so the bench trajectory
stays continuous):

* **Worker scaling** (the paper's headline property, Tab. 1 last row):
  EF-BV's convergence improves as the number of workers n grows, while
  EF21's rate is n-independent.  We sweep n and report (a) the theoretical
  stepsize gamma (monotone in n for EF-BV, flat for EF21) and (b) the
  measured suboptimality after a fixed number of rounds on the
  logistic-regression problem.  The participation sweep (federated
  execution mode) holds n fixed and sweeps the per-round sampling fraction
  p: the wire bits of a round scale as |S_t| while the tuned stepsize and
  the measured suboptimality degrade gracefully.

* **Model-zoo scaling** (:func:`zoo_rows`): the committed fine-tune specs
  (examples/specs/finetune_moe.json + zoo_qwen2.json + zoo_mamba2.json --
  smoke-scaled stand-ins for each model family) run through the staged
  harness (repro/train/loop.py) under the compressed wire, recording measured
  steps/sec and exact uplink+downlink bits per round, keyed by each spec's
  committed fingerprint.  These are the model-scale rows of
  BENCH_perf.json / BENCH_bits.json (benchmarks/ci_bench.py)."""

from __future__ import annotations

import json
import os

import jax.numpy as jnp
import numpy as np

from benchmarks.common import KEY, make_problem
from repro.core import (CompKK, Downlink, EFBV, Participation,
                        make_compressor, run_reference, tune_for)
from repro.distributed import wire

SPECS_DIR = os.path.join(os.path.dirname(__file__), os.pardir,
                         "examples", "specs")

# the committed model-zoo fine-tune specs, one per family stand-in (moe,
# dense, ssm); zoo_rows runs each through the staged harness
ZOO_SPEC_FILES = ["finetune_moe.json", "zoo_qwen2.json", "zoo_mamba2.json"]


def run_bench(fast: bool = True):
    steps = 1200 if fast else 6000
    name = "phishing"
    rows = []
    gammas = {"efbv": [], "ef21": []}
    finals = {"efbv": [], "ef21": []}
    ns = [10, 100, 1000] if fast else [10, 50, 100, 500, 1000, 2000]
    for n in ns:
        prob = make_problem(name, n=n)
        _, fstar = prob.solve()
        d = prob.d
        comp = CompKK(1, d // 2)
        for mode in ["efbv", "ef21"]:
            t = tune_for(comp, d, n, mode=mode, L=prob.L(), Ltilde=prob.L_tilde())
            algo = EFBV(comp, lam=t.lam, nu=t.nu)
            res = run_reference(algo=algo,
                                grad_fn=lambda _k, x: prob.grads(x),
                                x0=jnp.zeros(d), gamma=t.gamma, steps=steps,
                                key=KEY, n=n,
                                record=lambda x: prob.f(x) - fstar)
            gammas[mode].append(t.gamma)
            finals[mode].append(float(res.metrics[-1]))
    # theory: EF-BV gamma must increase with n; EF21's is n-independent
    bv_monotone = all(gammas["efbv"][i] <= gammas["efbv"][i + 1] * (1 + 1e-9)
                      for i in range(len(ns) - 1))
    ef21_flat = max(gammas["ef21"]) / max(min(gammas["ef21"]), 1e-30) < 1.3
    rows.append({"name": "n_scaling/gamma_monotone_in_n",
                 "us_per_call": "",
                 "derived": f"efbv_monotone={bv_monotone};ef21_flat={ef21_flat};"
                            f"gamma_efbv={[f'{g:.2e}' for g in gammas['efbv']]};"
                            f"gamma_ef21={[f'{g:.2e}' for g in gammas['ef21']]}"})
    for i, n in enumerate(ns):
        rows.append({"name": f"n_scaling/n{n}/final_gap",
                     "us_per_call": "",
                     "derived": f"efbv={finals['efbv'][i]:.3e};"
                                f"ef21={finals['ef21'][i]:.3e}"})
    rows.extend(participation_rows(fast=fast))
    rows.extend(bidirectional_rows(fast=fast))
    return rows


def bidirectional_rows(fast: bool = True):
    """Up/down bits sweep: fixed uplink (the paper's comp-(k, k')), sweep of
    downlink codecs from dense fp32 to qsgd:16.  Exact total_round_bits
    (uplink x n + ONE broadcast) against the measured suboptimality after a
    fixed round budget -- the bidirectional bits-vs-convergence trade-off."""
    steps = 1500 if fast else 6000
    n = 50
    prob = make_problem("phishing", n=n)
    _, fstar = prob.solve()
    d = prob.d
    comp = CompKK(1, d // 2)
    up_fmt = wire.format_for(comp, jnp.zeros(d))
    t = tune_for(comp, d, n, mode="efbv", L=prob.L(), Ltilde=prob.L_tilde())
    algo = EFBV(comp, lam=t.lam, nu=t.nu)

    downs = ["identity", f"topk:{d // 4}", "qsgd:16"]
    rows, gaps, totals = [], [], []
    for spec in downs:
        down = Downlink(make_compressor(spec))
        # broadcast error feedback tolerates a smaller step for lossy C_s
        gamma = t.gamma if spec == "identity" else t.gamma * 0.5
        res = run_reference(
            algo=algo, downlink=down, grad_fn=lambda k, x: prob.grads(x),
            x0=jnp.zeros(d), gamma=gamma, steps=steps, key=KEY, n=n,
            record=lambda x: prob.f(x) - fstar)
        m = res.metrics
        down_fmt = down.format_for(jnp.zeros(d))
        total = wire.total_round_bits(up_fmt, down_fmt, n_workers=n)
        gaps.append(float(m[-1]))
        totals.append(float(total))
        rows.append({"name": f"n_scaling/bidirectional_{spec.split(':')[0]}",
                     "us_per_call": "",
                     "derived": f"final_gap={gaps[-1]:.3e};"
                                f"up_bits={up_fmt.bits_per_round(n_workers=n):g};"
                                f"down_bits={down_fmt.downlink_bits_per_round():g};"
                                f"total_bits={total:g}"})
    # the downlink shrinks total bits monotonically along the sweep while
    # the gap stays finite (lossy broadcasts still converge)
    assert all(t1 >= t2 for t1, t2 in zip(totals, totals[1:])), totals
    assert all(np.isfinite(g) for g in gaps), gaps
    rows.append({"name": "n_scaling/bidirectional/bits_vs_gap",
                 "us_per_call": "",
                 "derived": f"downs={downs};"
                            f"totals={[f'{t_:g}' for t_ in totals]};"
                            f"gaps={[f'{g:.2e}' for g in gaps]}"})
    return rows


def participation_rows(fast: bool = True):
    """Federated sweep: wire bits/round scale as |S_t|, convergence degrades
    gracefully as the participation fraction p shrinks."""
    steps = 1500 if fast else 6000
    n = 100
    prob = make_problem("phishing", n=n)
    _, fstar = prob.solve()
    d = prob.d
    comp = CompKK(1, d // 2)
    fmt = wire.format_for(comp, jnp.zeros(d))
    rows, gaps, bits = [], [], []
    ps = [1.0, 0.5, 0.25] if fast else [1.0, 0.5, 0.25, 0.1]
    for p in ps:
        part = (Participation() if p >= 1.0
                else Participation(kind="bernoulli", p=p))
        t = tune_for(comp, d, n, mode="efbv", L=prob.L(),
                     Ltilde=prob.L_tilde(),
                     participation=None if p >= 1.0 else p)
        algo = EFBV(comp, lam=t.lam, nu=t.nu)
        res = run_reference(
            algo=algo, grad_fn=lambda k, x: prob.grads(x), x0=jnp.zeros(d),
            gamma=t.gamma, steps=steps, key=KEY, n=n, participation=part,
            record=lambda x: prob.f(x) - fstar)
        m = res.metrics
        # expected federated uplink: mask bitmap + E|S_t| payloads
        b = fmt.bits_per_round(n_workers=n, participants=p * n)
        gaps.append(float(m[-1]))
        bits.append(float(b))
        rows.append({"name": f"n_scaling/participation_p{p:g}/trade_off",
                     "us_per_call": "",
                     "derived": f"final_gap={gaps[-1]:.3e};"
                                f"gamma={t.gamma:.2e};"
                                f"exp_bits_per_round={b:g}"})
    # the wire side of the trade-off is exact: bits scale as |S_t|
    full_payload = n * fmt.bits_per_round()
    assert all(b <= full_payload * p + 32 * wire.bitmap_words(n) + 1e-9
               for p, b in zip(ps, bits)), (ps, bits, full_payload)
    rows.append({"name": "n_scaling/participation/bits_scale_with_s",
                 "us_per_call": "",
                 "derived": f"ps={ps};bits={[f'{b:g}' for b in bits]};"
                            f"monotone={all(b1 >= b2 for b1, b2 in zip(bits, bits[1:]))}"})
    return rows


def load_zoo_specs():
    """The committed zoo fine-tune specs, parsed (fingerprints are the BENCH
    row keys; the files are exact ``spec.to_json()`` bytes, pinned by
    tests/test_finetune.py)."""
    from repro.core import ExperimentSpec

    specs = []
    for fname in ZOO_SPEC_FILES:
        with open(os.path.join(SPECS_DIR, fname)) as f:
            specs.append((fname, ExperimentSpec.from_dict(json.load(f))))
    return specs


def _expert_leaf_bits(fmt, paths):
    """Sum of exact per-leaf payload bits over the MoE expert leaves."""
    from repro.models.moe import EXPERT_LEAVES

    by_leaf = fmt.bits_by_leaf()
    assert fmt.bits_per_round() == sum(by_leaf)
    return sum(b for p, b in zip(paths, by_leaf)
               if p.split("/")[-1] in EXPERT_LEAVES and "moe" in p.split("/"))


def zoo_bits_rows():
    """The exact (machine-independent) half of the zoo sweep: uplink x n +
    ONE broadcast of every committed fine-tune spec's round on its real
    smoke parameter tree, keyed by the committed fingerprints.  MoE rows
    additionally carry the expert-leaf split -- sparse (rescaled topk rules
    on masked gradients) vs the dense block-top-k budget on those same
    leaves -- which the expert-sparsity gate in ci_bench.py pins at
    <= 0.5x."""
    import jax

    from repro.configs import get_smoke_config
    from repro.core import build
    from repro.models import build_model

    rows = {}
    for fname, spec in load_zoo_specs():
        cfg = get_smoke_config(spec.problem)
        params = build_model(cfg).init(jax.random.key(spec.seed))
        run = build(spec)
        rb = run.round_bits(params)
        row = {
            "name": f"zoo_scaling/{fname[:-len('.json')]}",
            "arch": cfg.name,
            "family": cfg.family,
            "spec_file": fname,
            "compressor": spec.compressor,
            "downlink": spec.downlink or "dense_fp32",
            "leaf_codecs": spec.leaf_codecs,
            "params": cfg.param_count(),
            "up_bits": rb["up"],
            "down_bits": rb["down"],
            "total_bits": rb["total"],
            "vs_dense_both_ways": round(rb["total"] / rb["dense_both_ways"],
                                        6),
        }
        if cfg.family == "moe":
            paths = wire.leaf_paths(params)
            sparse_fmt = wire.tree_format_for(
                run.compressor, params, wire_dtype=spec.wire_dtype,
                rules=run.leaf_rules)
            dense_fmt = wire.tree_format_for(
                run.compressor, params, wire_dtype=spec.wire_dtype,
                rules=(("*", run.compressor),))
            sparse_bits = _expert_leaf_bits(sparse_fmt, paths)
            dense_bits = _expert_leaf_bits(dense_fmt, paths)
            row["expert_leaf_bits"] = sparse_bits
            row["dense_expert_leaf_bits"] = dense_bits
            row["expert_sparsity_ratio"] = round(sparse_bits / dense_bits, 6)
        rows[spec.fingerprint()] = row
    return rows


def zoo_perf_rows(measure_steps: int = 3):
    """The measured half of the zoo sweep: steps/sec of every committed
    fine-tune spec through the staged harness (repro/train/loop.py) under
    its compressed wire, keyed by the committed fingerprints.  Compile
    excluded: one warm-up step, then ``measure_steps`` timed."""
    from repro.train.loop import FinetuneLoop, FinetuneSettings

    rows = {}
    for fname, spec in load_zoo_specs():
        loop = FinetuneLoop(
            spec, FinetuneSettings(global_batch=8, seq_len=32, log_every=10),
            verbose=False)
        loop.setup()
        loop.build_data()
        loop.train(steps=1)
        loop.train(steps=measure_steps)
        rows[spec.fingerprint()] = {
            "name": f"zoo_scaling/{fname[:-len('.json')]}",
            "arch": loop.cfg.name,
            "family": loop.cfg.family,
            "params": loop.cfg.param_count(),
            "steps_per_sec": round(loop._steps_per_sec, 4),
            "final_loss": round(loop._final["loss"], 4),
        }
    return rows


if __name__ == "__main__":
    from benchmarks.common import emit
    emit(run_bench(fast=True))
