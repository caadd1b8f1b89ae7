"""Device time a step of the MoE layer's named scopes inside the EF-BV
step's layers (not part of a benchmark run).

    python3 chipbench/sublayers.py --workload <cell> --seed <n> --steps <k>

Runs ``chipbench/layers.py``'s measurement, keeps the trace and the
compiled step's text it reads, and attributes the instructions once more
with the MoE layer's scopes ``moe.route`` and ``moe.experts`` (inside
``efbv.fwd_bwd``, see ``src/repro/models/moe.py``) as layers of their own:
``scopes.scope_map`` takes the innermost ``efbv.*`` segment of an
``op_name``, so each ``moe.<scope>`` segment is read as ``efbv.moe_<scope>``.
Prints layers.py's JSON line with ``sublayer_ms_per_step`` added: the
milliseconds a step of each MoE scope, the union of its ops' intervals,
which are part of ``efbv.fwd_bwd``'s time in ``layer_ms_per_step``.  Needs
the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import layers, scopes  # noqa: E402
from chipbench import run as R  # noqa: E402

MOE_SCOPES = ("route", "experts")
_MOE = re.compile(rf"(?<![\w.])moe\.({'|'.join(MOE_SCOPES)})\b")


def measure(cell: R.Cell, seed: int, steps: int) -> dict:
    seen = {}
    scope_map, layer_seconds = scopes.scope_map, scopes.layer_seconds

    def keep_map(hlo):
        seen["hlo"] = hlo
        return scope_map(hlo)

    def keep_seconds(ops, spans, smap):
        seen["trace"] = (ops, spans)
        return layer_seconds(ops, spans, smap)

    scopes.scope_map, scopes.layer_seconds = keep_map, keep_seconds
    try:
        out = layers.measure(cell, seed, steps)
    finally:
        scopes.scope_map, scopes.layer_seconds = scope_map, layer_seconds
    sub = layer_seconds(*seen["trace"],
                        scope_map(_MOE.sub(r"efbv.moe_\1", seen["hlo"])))
    out["sublayer_ms_per_step"] = {
        f"moe.{s}": 1e3 * sub.get(f"efbv.moe_{s}", 0.0) / steps
        for s in MOE_SCOPES}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=6)
    args = ap.parse_args(argv)
    try:
        out = measure(R.Cell(args.workload), args.seed, args.steps)
    except R.BenchError as e:
        R.log(f"FAIL: {e}")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
