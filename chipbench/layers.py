"""Device time a step of each named layer of a cell's train step (not part
of a benchmark run).

    python3 chipbench/layers.py --workload <cell> --seed <n> --steps <k> [--program-out PATH]

Builds and compiles the cell's step as a benchmark run does
(``chipbench/run.py`` ``prepare``), drives ``--steps`` steps back to back
under the profiler, one step queued behind the running one, and reduces
the trace by the program's named scopes (``chipbench/scopes.py``).  Prints
one JSON line: the device, the steps, the busy time and the pack kernel's
time per step as the benchmark reduces them (``chipbench/trace.py``), the
milliseconds per step of each scope and of ``other``, and the digest of the
compiled step's text without its debug information (metadata and source
locations), which ``--program-out`` writes out whole.  Needs the chip, as
a benchmark run does.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import run as R  # noqa: E402


def measure(cell: R.Cell, seed: int, steps: int, program_out: str = "") -> dict:
    pre = R.prepare(cell, seed)
    import jax

    from chipbench import scopes
    from chipbench import trace as tr
    from chipbench.kernels import pack

    hlo = pre.step.as_text()
    if program_out:
        with open(program_out, "w") as f:
            f.write(scopes.program_text(hlo))
    state, i = pre.state, 3
    logdir = tempfile.mkdtemp(prefix="chipbench-layers-")
    try:
        jax.profiler.start_trace(logdir)
        pending = None
        with jax.profiler.TraceAnnotation(tr.WINDOW):
            for _ in range(steps):
                state, m = pre.step(state, pre.place(i), pre.key(i))
                i += 1
                if pending is not None:
                    jax.block_until_ready(pending)
                pending = m
            jax.block_until_ready((state, m))
        jax.profiler.stop_trace()
        ops, spans = tr.read(tr.find_xplane(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    red = tr.reduce(ops, spans, {"pack": pack.TRACE_PATTERN})
    layers = scopes.layer_seconds(ops, spans, scopes.scope_map(hlo))
    per_step = {k: 1e3 * v / steps
                for k, v in sorted(layers.items(), key=lambda kv: -kv[1])}
    return {"device": {"kind": pre.kind, "count": len(pre.devices)},
            "steps": steps, "window_s": red.window_s,
            "busy_ms_per_step": 1e3 * red.busy_s / steps,
            "pack_kernel_ms": 1e3 * red.kernel_s["pack"] / steps,
            "layer_ms_per_step": per_step,
            "layer_sum_ms_per_step": sum(per_step.values()),
            "program_digest": scopes.program_digest(hlo)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--program-out", default="")
    args = ap.parse_args(argv)
    try:
        out = measure(R.Cell(args.workload), args.seed, args.steps,
                      args.program_out)
    except R.BenchError as e:
        R.log(f"FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
