"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``; its
configuration (``chipbench/configs/<config>.json``), traffic
(``chipbench/traffic/<traffic>.json``) and limits
(``chipbench/limits/<cell>.json``) are found by name, and each metric it
reports by its reader (``chipbench/metrics/<metric>.py``).

A run, in order:

1. builds the job through the training entry's own set-up
   (``repro.launch.train.setup``: ``build(spec)``, the state initialised on
   the device from the seed, the jitted ``train_step``) and compiles the
   step once;
2. drives that compiled step from the seed through its first three steps
   on the benchmark's own batches, and reads what the correctness check
   compares (losses, the first gradient as AdamW holds it, each
   parameter leaf's change);
3. measures: steps go back to back on fresh batches for ``--seconds``,
   the host waiting on step k - 1 once step k is queued, then on the last
   step; with ``--trace 1`` under the profiler;
4. frees the program's state and trains the plain reference
   (``chipbench/reference.py``) through the same three steps, and compares.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (steps in the window), ``failed`` (of those, steps whose loss
or gradient norm is not finite), ``metrics``, ``device``, with ``--trace
1`` ``breakdown``, and last ``check``: each number compared beside its
limit, which also close standard error.  The run exits nonzero with no
such line where JAX finds no TPU or fewer chips than the cell asks for,
under ``REPRO_SANITIZE=1`` or a ``REPRO_WIRE_KERNEL`` other than ``auto``
(both take the kernels off the device path), or where the program is not
beside the benchmark.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, NamedTuple  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: JAX's persistent compilation cache where ``JAX_COMPILATION_CACHE_DIR``
#: names none: a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(Exception):
    pass


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise BenchError(f"cannot read {path}: {e}") from None


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """One entry of ``workloads`` with every file it names."""

    def __init__(self, name: str, root: str = ROOT):
        bench = load_json(root, "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise BenchError(f"no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = self.entry["chips"]
        self.config = load_json(BENCH, "configs", self.entry["config"] + ".json")
        self.traffic = load_json(BENCH, "traffic", self.entry["traffic"] + ".json")
        self.limits = load_json(BENCH, "limits", name + ".json")
        self.model = load_module(
            os.path.join(BENCH, "models", self.config["reference"] + ".py"),
            "chipbench_model_" + self.config["reference"])

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]


def refuse_host_fallbacks() -> None:
    if os.environ.get("REPRO_SANITIZE", "") == "1":
        raise BenchError("REPRO_SANITIZE=1 forces Pallas interpret mode; unset it")
    mode = os.environ.get("REPRO_WIRE_KERNEL", "auto") or "auto"
    if mode != "auto":
        raise BenchError(f"REPRO_WIRE_KERNEL={mode!r}: only 'auto' runs the "
                         "compiled kernels; unset it")


def require_chips(chips: int):
    """The TPU devices of this host; at least ``chips`` of them."""
    import jax

    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise BenchError(f"JAX found no device: {e}") from None
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX sees {len(devices)} "
                         f"{devices[0].platform} device(s)")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def import_program():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch import train
    except ImportError as e:
        raise BenchError(f"the program is not beside the benchmark ({e})") from None
    return train


def train_args(cell: Cell, seed: int) -> list:
    t, opt = cell.traffic, cell.traffic["optimizer"]
    return ["--arch", cell.config["arch"], "--mesh", t["mesh"],
            "--algo", t["algo"], "--compressor", t["compressor"],
            "--agg", t["agg"], "--steps", str(opt["total_steps"]),
            "--lr", repr(opt["lr"]), "--schedule", opt["schedule"],
            "--global-batch", str(t["global_batch"]), "--seq", str(t["seq"]),
            "--heterogeneity", repr(t["heterogeneity"]), "--seed", str(seed)]


def check_program(cell: Cell, job) -> None:
    """The program runs the configuration and the job as they are stated."""
    want = cell.config["program"]
    got = {k: getattr(job.cfg, k) for k in want}
    if got != want:
        bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
        raise BenchError(f"the program's model departs from the configuration "
                         f"(program, stated): {bad}")
    if job.n != cell.traffic["workers"]:
        raise BenchError(f"the program runs {job.n} workers, the traffic "
                         f"states {cell.traffic['workers']}")
    from chipbench.reference import tuning

    lam, nu = tuning(cell.traffic["compressor"], cell.traffic["algo"])
    if (job.run.algo.lam, job.run.algo.nu) != (lam, nu):
        raise BenchError(f"the program runs lam={job.run.algo.lam} "
                         f"nu={job.run.algo.nu}, the paper's tuning gives "
                         f"lam={lam} nu={nu}")


def compile_step(job, state, batch, key):
    """The job's jitted train step, compiled for the cell's shapes: the one
    object that set-up, the correctness readings and the window drive."""
    return job.step_fn.lower(state, batch, key).compile()


def memory_of(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"argument": m.argument_size_in_bytes,
            "output": m.output_size_in_bytes,
            "alias": m.alias_size_in_bytes,
            "temp": m.temp_size_in_bytes}


def use_compile_cache() -> None:
    """Every program of a run in one persistent cache, so that only a
    checkout's first run of a cell compiles: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else :data:`CACHE_DIR`, which the program is handed
    through that variable too."""
    import jax

    path = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class Prepared(NamedTuple):
    devices: list
    kind: str
    peaks: dict
    job: object
    step: object        # the compiled train step
    state: object       # the TrainState after the three set-up steps
    place: Callable     # step index -> that step's batch on the mesh
    key: Callable       # step index -> that step's key
    readings: object    # chipbench.reference.Readings
    memory: dict
    leaf_sizes: list


def prepare(cell: Cell, seed: int) -> Prepared:
    """Build the job, compile its step, and drive it through the three
    steps the check reads."""
    refuse_host_fallbacks()
    use_compile_cache()
    import jax

    devices = require_chips(cell.chips)
    from chipbench import reference
    from chipbench.peaks import peaks
    from chipbench.traffic.synthetic_lm import SyntheticLM

    kind = devices[0].device_kind
    try:
        chip_peaks = peaks(kind)
    except KeyError as e:
        raise BenchError(str(e)) from None
    train = import_program()
    from repro.data import make_batch_shardings

    t = cell.traffic
    with contextlib.redirect_stdout(sys.stderr):
        job = train.setup(train.parse_args(train_args(cell, seed)))
    check_program(cell, job)
    ref_shapes = jax.eval_shape(lambda k: cell.model.init(cell.config, k),
                                jax.random.key(0))
    if (jax.tree.structure(ref_shapes) != jax.tree.structure(job.state.params)
            or [x.shape for x in jax.tree.leaves(ref_shapes)]
            != [x.shape for x in jax.tree.leaves(job.state.params)]):
        raise BenchError("the program's parameter tree is not the "
                         "configuration's")
    data = SyntheticLM(vocab=cell.config["vocab_size"], seq_len=t["seq"],
                       global_batch=t["global_batch"], n_workers=job.n,
                       seed=seed, heterogeneity=t["heterogeneity"])

    def place(step):
        with jax.profiler.TraceAnnotation("bench.make_batch"):
            host = data.batch(step)
        with jax.profiler.TraceAnnotation("bench.place_batch"):
            return make_batch_shardings(job.mesh, host)

    def key(step):
        return jax.random.fold_in(job.key, step)

    state = job.state
    p0 = jax.device_get(state.params)
    batch = place(0)
    step = compile_step(job, state, batch, key(0))
    b1 = t["optimizer"]["b1"]
    losses, g0 = [], None
    for i in range(reference.CHECK_STEPS):
        if i:
            batch = place(i)
        state, m = step(state, batch, key(i))
        losses.append(float(m["loss"]))
        if i == 0:
            g0 = [x / (1.0 - b1)
                  for x in reference.leaf_norms(state.opt_state["m"])]
    change = reference.host_change_norms(p0, jax.device_get(state.params))
    moments = [reference.leaf_norms(state.opt_state["m"]),
               reference.leaf_norms(state.opt_state["v"]),
               reference.leaf_norms(state.h)]
    return Prepared(devices, kind, chip_peaks, job, step, state, place, key,
                    reference.Readings(losses, g0, change, moments),
                    memory_of(step), [int(x.size) for x in jax.tree.leaves(p0)])


def run(cell: Cell, seed: int, seconds: float, trace: bool) -> dict:
    pre = prepare(cell, seed)
    import jax

    from chipbench import reference
    from chipbench.facts import Facts

    t = cell.traffic
    devices, memory, step, state = pre.devices, pre.memory, pre.step, pre.state
    place, key, readings = pre.place, pre.key, pre.readings
    peaks, leaf_sizes = pre.peaks, pre.leaf_sizes
    setup_s = time.perf_counter() - T0
    log(f"set-up {setup_s:.3f} s; memory of the step: {memory}")

    # -- the window -------------------------------------------------------
    logdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(logdir)
    outs, pending, i = [], None, reference.CHECK_STEPS
    with jax.profiler.TraceAnnotation("bench.window"):
        w0 = time.perf_counter()
        while True:
            batch = place(i)
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                state, m = step(state, batch, key(i))
            outs.append(m)
            i += 1
            if pending is not None:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(pending)
            pending = m
            if time.perf_counter() - w0 >= seconds:
                break
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((state, m))
        window_s = time.perf_counter() - w0
    reduction = None
    if trace:
        jax.profiler.stop_trace()
        from chipbench import trace as tr

        kernels = {os.path.basename(p)[:-3]: load_module(
            p, "chipbench_kernel_" + os.path.basename(p)[:-3]).TRACE_PATTERN
            for p in sorted(glob.glob(os.path.join(BENCH, "kernels", "*.py")))
            if not p.endswith("__init__.py")}
        try:
            ops, spans = tr.read(tr.find_xplane(logdir))
        finally:
            shutil.rmtree(logdir, ignore_errors=True)
        reduction = tr.reduce(ops, spans, kernels)
    steps = len(outs)
    vals = jax.device_get([(o["loss"], o["g_norm"]) for o in outs])
    failed = sum(1 for lv, gv in vals
                 if not (math.isfinite(float(lv)) and math.isfinite(float(gv))))
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    footprint = memory["argument"] + memory["output"] - memory["alias"] \
        + memory["temp"]
    log(f"window: {steps} steps in {window_s:.3f} s; peak_bytes_in_use "
        f"{peak}; compiled footprint {footprint}")
    device = {"platform": devices[0].platform, "kind": pre.kind,
              "count": len(devices), "memory_peak_bytes": peak,
              "compiled_footprint_bytes": footprint}
    if reduction is not None:
        device["busy_s"] = reduction.busy_s
        device["window_s"] = reduction.window_s

    # -- the reference, once the program's state is gone ------------------
    del pre, state, step, outs, pending, m, batch
    gc.collect()
    ref = reference.trajectory(cell.model, cell.config, t, seed)
    numbers = reference.compare(readings, ref)
    for name, w in reference.worst_leaves(
            readings, ref, reference.leaf_names(cell.model, cell.config)).items():
        log(f"{name} is set by {w['tree']} of {w['leaf']}: program "
            f"{w['run']!r}, reference {w['reference']!r}, reference median "
            f"{w['median']!r}")
    check = {k: {"value": v, "limit": cell.limits[k]} for k, v in numbers.items()}
    correct = failed == 0 and steps > 0 and all(
        v <= cell.limits[k] for k, v in numbers.items())

    facts = Facts(config=cell.config, traffic=t, chips=cell.chips,
                  peaks=peaks, setup_s=setup_s, steps=steps,
                  tokens=steps * t["global_batch"] * t["seq"],
                  window_s=window_s, memory=memory, peak_bytes=peak,
                  leaf_sizes=leaf_sizes,
                  flops_per_token=cell.model.flops_per_token(cell.config,
                                                             t["seq"]),
                  trace=reduction)
    metrics = {}
    for spec in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(os.path.join(BENCH, "metrics", spec["name"] + ".py"),
                             "chipbench_metric_" + spec["name"].replace(".", "_"))
        value = reader.read(facts)
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    result = {"correct": correct, "attempted": steps, "failed": failed,
              "metrics": metrics, "device": device}
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.device_ops,
                               "idle_gaps": reduction.idle_gaps}
    result["check"] = check
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    try:
        result = run(Cell(args.workload), args.seed, args.seconds,
                     bool(args.trace))
    except BenchError as e:
        log(f"FAIL: {e}")
        return 1
    for name, c in result["check"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
