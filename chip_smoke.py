"""Bring-up check: the EF-BV train step on TPU at qwen2-0.5b's full width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one four-chip host

One chip: the train driver's own set-up (``repro.launch.train.setup``:
``build(spec)`` -> ``run.init_state`` -> ``run.state_shardings`` ->
``run.train_step``) for qwen2-0.5b at its published widths (24 layers,
d_model 896, vocab 151936), EF-BV with ``block_topk:256,16`` over
``sparse_allgather`` on mesh 1x1, global batch 4 x 512, five steps.  It
prints the device, the parameter count, the step's compile seconds, each
step's seconds (to ``block_until_ready``) with its loss, ``g_norm`` and
``h_residual``, the wire line, the Pallas kernel count of the compiled step
and ``peak_bytes_in_use``.

Four chips (mesh 4x1, data=4, two steps each, same init and batches):
(a) EF-BV block_topk:256,16 over sparse_allgather, whose compiled step must
hold its all-gather; (b) EF-BV identity over sparse_allgather (lambda =
nu = 1, so g_t is the plain mean of the workers' gradients); (c) no
compression over dense_psum.  (b)'s parameters must match (c)'s.

The last line of standard output is ``{"ok": true, "device": {...}}``.  The
script exits nonzero, without that line, where JAX finds no TPU, where it
is not run from a checkout of the repository, under ``REPRO_SANITIZE=1``
or a ``REPRO_WIRE_KERNEL`` other than ``auto`` (both would take the kernels
off the device path), or when any check fails.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

ARCH = ["--arch", "qwen2-0.5b"]
ONE_CHIP = dict(mesh="1x1", global_batch=4, seq=512, steps=5)
FOUR_CHIPS = dict(mesh="4x1", global_batch=4, seq=512, steps=2)
JOBS_4 = {"a": ("efbv", "block_topk:256,16", "sparse_allgather"),
          "b": ("efbv", "identity", "sparse_allgather"),
          "c": ("none", "identity", "dense_psum")}


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def refuse_host_fallbacks() -> None:
    """Both switches move the Pallas kernels off the device path."""
    check(os.environ.get("REPRO_SANITIZE", "") != "1",
          "REPRO_SANITIZE=1 forces Pallas interpret mode; unset it")
    mode = os.environ.get("REPRO_WIRE_KERNEL", "auto") or "auto"
    check(mode == "auto", f"REPRO_WIRE_KERNEL={mode!r}: only 'auto' runs the "
                          "compiled kernels; unset it")


def tpu_devices(expect: int):
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise SmokeFailure(f"JAX found no device: {e}") from None
    platform = devices[0].platform
    check(platform == "tpu",
          f"no TPU: JAX sees {len(devices)} {platform} device(s)")
    check(len(devices) == expect,
          f"this phase needs {expect} TPU chip(s), JAX sees {len(devices)}")
    log(f"device kind={devices[0].device_kind} count={len(devices)}")
    return devices


def job_args(train, mesh, global_batch, seq, steps, algo="efbv",
             compressor="block_topk:256,16", agg="sparse_allgather"):
    return train.parse_args(
        ARCH + ["--mesh", mesh, "--algo", algo, "--compressor", compressor,
                "--agg", agg, "--steps", str(steps),
                "--global-batch", str(global_batch), "--seq", str(seq)])


def run_job(args, label: str) -> dict:
    """Set a run up through the train driver, compile its step once, and
    take ``spec.steps`` steps, checking every metric.  Returns the final
    state and what was measured."""
    import jax
    import jax.numpy as jnp
    from repro.launch import train

    job = train.setup(args)
    state = job.state
    n_params = sum(x.size for x in jax.tree.leaves(state.params))
    log(f"{label}: parameters {n_params:,}")

    batch = train.batch_at(job, args, 0)
    t0 = time.perf_counter()
    compiled = job.step_fn.lower(state, batch,
                                 jax.random.fold_in(job.key, 0)).compile()
    compile_s = time.perf_counter() - t0
    hlo = compiled.as_text()
    kernels = hlo.count('custom_call_target="tpu_custom_call"')
    log(f"{label}: step compile {compile_s:.1f} s; tpu_custom_call in the "
        f"compiled step: {kernels}; all-gather ops: {hlo.count('all-gather')}")

    before = jax.device_get(jax.tree.map(jnp.sum, state.params))
    for step in range(job.spec.steps):
        if step:
            batch = train.batch_at(job, args, step)
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch,
                                  jax.random.fold_in(job.key, step))
        jax.block_until_ready((state, metrics))
        step_s = time.perf_counter() - t0
        m = {k: float(v) for k, v in metrics.items()}
        log(f"{label}: step {step} {step_s:.3f} s loss={m['loss']:.6f} "
            f"g_norm={m['g_norm']:.6f} h_residual={m['h_residual']:.6f} "
            f"update_norm={m['update_norm']:.6g}")
        bad = [k for k, v in m.items() if not math.isfinite(v)]
        check(not bad, f"{label}: step {step} metrics not finite: {bad}")
    after = jax.device_get(jax.tree.map(jnp.sum, state.params))
    changed = sum(a != b for a, b in zip(jax.tree.leaves(before),
                                          jax.tree.leaves(after)))
    n_leaves = len(jax.tree.leaves(after))
    log(f"{label}: parameter leaves changed: {changed}/{n_leaves}")
    check(changed > 0, f"{label}: the parameters did not change")
    return {"state": state, "kernels": kernels, "hlo": hlo, "run": job.run}


def peak_bytes(devices) -> None:
    for d in devices:
        stats = d.memory_stats() or {}
        log(f"device {d.id}: peak_bytes_in_use={stats.get('peak_bytes_in_use')}"
            f" bytes_limit={stats.get('bytes_limit')}")


def one_chip(train) -> list:
    devices = tpu_devices(1)
    res = run_job(job_args(train, **ONE_CHIP), "efbv block_topk")
    check(res["kernels"] > 0, "no tpu_custom_call in the compiled step: the "
                              "Pallas pack kernel is not on the path")
    peak_bytes(devices)
    return devices


def four_chips(train) -> list:
    import jax
    import numpy as np

    devices = tpu_devices(4)
    params = {}
    for name, (algo, comp, agg) in JOBS_4.items():
        res = run_job(job_args(train, algo=algo, compressor=comp, agg=agg,
                               **FOUR_CHIPS), f"({name}) {algo} {comp} {agg}")
        if name == "a":
            check(res["kernels"] > 0, "(a): no tpu_custom_call in the step")
            check("all-gather" in res["hlo"],
                  "(a): the compiled step holds no all-gather: the "
                  "compressed payloads do not travel as such")
        if name == "b":
            algo_b = res["run"].algo
            check(algo_b.lam == 1.0 and algo_b.nu == 1.0,
                  f"(b): identity EF-BV has lam={algo_b.lam} nu={algo_b.nu}")
        if name in ("b", "c"):
            params[name] = jax.device_get(res["state"].params)
        del res
    peak_bytes(devices)
    worst = 0.0
    for pb, pc in zip(jax.tree.leaves(params["b"]),
                      jax.tree.leaves(params["c"])):
        worst = max(worst, float(np.max(np.abs(pb - pc))))
        check(np.allclose(pb, pc, rtol=1e-5, atol=1e-6),
              f"(b) and (c) parameters differ after "
              f"{FOUR_CHIPS['steps']} steps (max |diff| so far {worst:g})")
    log(f"(b) == (c) after {FOUR_CHIPS['steps']} steps: max |param diff| = "
        f"{worst:g}")
    return devices


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip exchange phase")
    args = ap.parse_args(argv)
    try:
        refuse_host_fallbacks()
        sys.path.insert(0, os.path.join(REPO, "src"))
        try:
            from repro.launch import runtime, train
        except ImportError as e:
            raise SmokeFailure(f"run from the root of a repository checkout "
                               f"({e})") from None
        log(f"compile cache: {runtime.compile_cache()}")
        devices = four_chips(train) if args.four_chips else one_chip(train)
    except SmokeFailure as e:
        print(f"[chip_smoke] FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
