"""The staged fine-tuning harness: ONE :class:`repro.core.ExperimentSpec`
drives setup -> data pipeline -> compressed train loop -> periodic eval for
every model family in the zoo (CPM-2-style finetune staging).

    from repro.core import ExperimentSpec
    from repro.train.loop import FinetuneLoop, FinetuneSettings

    loop = FinetuneLoop(ExperimentSpec.from_json(open(path).read()),
                        FinetuneSettings(global_batch=8, seq_len=32))
    summary = loop.run()          # all four stages
    # or stage by stage: loop.setup(); loop.build_data(); loop.train();
    #                    loop.evaluate()

What the spec buys here over the raw trainers:

* **Per-leaf compressed wire** -- ``leaf_codecs`` routes every parameter
  leaf through its own uplink codec (``TreeWire`` rules,
  docs/wire_format.md).
* **MoE expert-gradient sparsity** -- for ``family='moe'`` archs the loop
  installs :func:`repro.models.moe.zero_inactive_expert_grads` as the
  trainers' worker-side ``grad_transform``: inactive-expert slabs are pinned
  to exact zero before Algorithm 1 compresses, so a ``topk`` leaf rule on
  the expert leaves (see :func:`expert_sparse_rules`) ships only
  routed-expert entries, with exact ``bits_by_leaf`` accounting.
* **Multi-host-shaped meshes** -- ``FinetuneSettings.num_processes`` builds
  the mesh via :func:`repro.launch.mesh.make_multihost_mesh` (process-major
  device blocks, validated on simulated multi-process CPU).

The runtime-only knobs (batch/seq/lr/eval cadence/checkpoints) live in
:class:`FinetuneSettings` and never enter the spec fingerprint; everything
that changes the experiment's math lives in the spec.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Dict, List, Optional

PyTree = Any

# eval streams draw from a seed decorrelated from the training stream's
# (SyntheticLM folds (seed, step) internally; the xor keeps the two streams
# from ever sharing a fold for any spec.seed)
EVAL_SEED_XOR = 0xE7A1


@dataclasses.dataclass(frozen=True)
class FinetuneSettings:
    """Runtime-only knobs of a fine-tune run.  None of these enter the
    :class:`repro.core.ExperimentSpec` fingerprint -- they change how fast
    or how observably the run executes, never which experiment it is."""

    global_batch: int = 8
    seq_len: int = 32
    lr: float = 1e-4
    schedule: str = "auto"       # auto | cosine | wsd
    eval_every: int = 0          # 0 = final eval only
    eval_batches: int = 2
    log_every: int = 10
    heterogeneity: float = 0.5
    shard_size: int = 64         # for spec.resample fixed-shard minibatches
    num_processes: int = 1       # multi-host-shaped mesh (simulated on CPU)
    ckpt_dir: str = ""
    ckpt_every: int = 0


def expert_sparse_rules(params: PyTree, base, *, n_experts: int,
                        experts_per_tok: int) -> str:
    """The ``leaf_codecs`` rule string that composes MoE expert sparsity
    with the base compressor's budget.

    For every expert leaf (wg/wu/wd under a MoE subtree) the base
    compressor's dense entry budget is rescaled by the routed fraction
    ``experts_per_tok / n_experts`` and spelled as a flat ``topk:K`` rule:
    with inactive-expert gradient slabs pinned to exact zero
    (:func:`repro.models.moe.zero_inactive_expert_grads`), the top-K entries
    of the masked gradient all fall inside routed slabs, so the payload only
    carries routed experts -- at exactly ``a/E`` of the dense-baseline
    expert-leaf bits (both spend 64 bits/entry at float32).

    ``base`` must be a TopK or BlockTopK (the entry-budget compressors);
    other codecs have no per-entry budget to rescale.

    >>> import jax
    >>> from repro.configs import get_smoke_config
    >>> from repro.core.compressors import BlockTopK
    >>> from repro.models import build_model
    >>> cfg = get_smoke_config("granite-moe-3b-a800m")
    >>> params = build_model(cfg).init(jax.random.key(0))
    >>> expert_sparse_rules(params, BlockTopK(256, 16),
    ...                     n_experts=cfg.n_experts,
    ...                     experts_per_tok=cfg.experts_per_tok)
    'layers/moe/wd=topk:8192;layers/moe/wg=topk:8192;layers/moe/wu=topk:8192'
    """
    from repro.core.compressors import BlockTopK, TopK
    from repro.models import moe

    def dense_entries(size: int) -> int:
        if isinstance(base, BlockTopK):
            nb = -(-size // base.block)
            return nb * min(base.kb, base.block)
        if isinstance(base, TopK):
            return min(base.k, size)
        raise ValueError(
            f"expert_sparse_rules rescales an entry budget; base compressor "
            f"{base!r} has none (use topk:k or block_topk:b,kb)")

    leaves: Dict[str, int] = {}

    def walk(node, prefix):
        if not isinstance(node, dict):
            return
        if moe._is_moe_subtree(node):
            for name in moe.EXPERT_LEAVES:
                leaves["/".join(prefix + [name])] = int(node[name].size)
        for k, v in node.items():
            walk(v, prefix + [k])

    walk(params, [])
    if not leaves:
        raise ValueError("expert_sparse_rules: no MoE subtree "
                         "(router + wg/wu/wd) found in the parameter tree")
    rules = []
    for path in sorted(leaves):
        k = max(1, dense_entries(leaves[path]) * experts_per_tok // n_experts)
        rules.append(f"{path}=topk:{k}")
    return ";".join(rules)


def family_batch_extras(cfg, global_batch: int, step: int) -> Dict[str, Any]:
    """The per-family auxiliary batch inputs beyond tokens/labels (the vlm
    vision embeddings, the encdec audio frames); deterministic in ``step``
    so every trainer backend sees identical data."""
    import numpy as np

    if cfg.family == "vlm":
        return {"vision_embeds": np.random.default_rng(step).standard_normal(
            (global_batch, cfg.vision_patches, cfg.d_model),
            dtype=np.float32)}
    if cfg.family == "encdec":
        return {"frames": np.random.default_rng(step).standard_normal(
            (global_batch, cfg.encoder_frames, cfg.d_model),
            dtype=np.float32)}
    return {}


class FinetuneLoop:
    """The four-stage fine-tuning harness of one spec.

    Stages run in order (each checks its prerequisite): :meth:`setup`
    builds mesh/model/optimizer/state, :meth:`build_data` the train + held-
    out eval streams, :meth:`train` the compressed train loop with periodic
    eval, :meth:`evaluate` the held-out loss.  :meth:`run` chains all four
    and returns the summary dict."""

    def __init__(self, spec, settings: Optional[FinetuneSettings] = None, *,
                 config=None, verbose: bool = True):
        from repro.configs import ARCHS, get_config, get_smoke_config
        from repro.core import SpecError, build

        self.spec = spec
        self.settings = settings or FinetuneSettings()
        self.verbose = verbose
        if spec.backend == "reference":
            raise SpecError(
                "the fine-tune harness drives the distributed trainers; a "
                "backend='reference' spec runs via build(spec).reference()")
        if config is None and spec.problem not in ARCHS:
            raise SpecError(
                f"the fine-tune harness trains model archs {sorted(ARCHS)}; "
                f"problem={spec.problem!r} needs an explicit config=")
        self.cfg = config if config is not None else (
            get_smoke_config(spec.problem) if spec.smoke
            else get_config(spec.problem))
        self.run_obj = build(spec)
        self.mesh = None
        self.data = None
        self.eval_data = None
        self.state = None
        self.history: List[Dict[str, float]] = []

    def _log(self, msg: str):
        if self.verbose:
            print(f"[finetune] {msg}")

    # ---- stage 1: setup ----------------------------------------------------

    def setup(self):
        """Mesh (multi-host-shaped), model, optimizer schedule, sharded
        TrainState and the jitted compressed train step."""
        import jax

        from repro.launch.mesh import make_multihost_mesh, num_workers
        from repro.models import build_model, moe
        from repro.optim import adamw, cosine, wsd

        spec, st = self.spec, self.settings
        run = self.run_obj
        self.mesh = make_multihost_mesh(spec.mesh_dims(),
                                        num_processes=st.num_processes)
        self.n = num_workers(self.mesh)
        self.model = build_model(self.cfg)

        kind = st.schedule
        if kind == "auto":
            kind = "wsd" if spec.problem.startswith("minicpm") else "cosine"
        if kind == "wsd":
            sched = wsd(st.lr, warmup_steps=max(spec.steps // 20, 1),
                        stable_steps=int(spec.steps * 0.7),
                        decay_steps=max(int(spec.steps * 0.25), 1))
        else:
            sched = cosine(st.lr, total_steps=spec.steps,
                           warmup_steps=max(spec.steps // 20, 1))
        self.opt = adamw(sched, weight_decay=0.01)

        self.key = jax.random.key(spec.seed)
        params = self.model.init(self.key)
        state = run.init_state(params, self.opt, self.mesh)
        shardings = run.state_shardings(self.mesh, self.model.param_specs(),
                                        state)
        self.state = jax.tree.map(jax.device_put, state, shardings)

        # the worker-side expert-sparsity hook: enforce exact-zero inactive
        # slabs before Algorithm 1 compresses (the identity under dropless
        # routing, and the contract the expert topk leaf rules rely on)
        grad_transform = (moe.zero_inactive_expert_grads
                          if self.cfg.family == "moe" else None)
        loss_fn = self.model.loss
        self.step_fn = run.train_step(loss_fn, self.opt, self.mesh,
                                      grad_transform=grad_transform)
        self._eval_fn = jax.jit(lambda p, b: loss_fn(p, b)[0])

        algo = run.algo
        self._log(f"arch={self.cfg.name} family={self.cfg.family} "
                  f"params~{self.cfg.param_count():,} workers={self.n} "
                  f"backend={spec.backend} mesh={spec.mesh} "
                  f"processes={st.num_processes} algo={spec.mode} "
                  f"lam={algo.lam:.4g} nu={algo.nu:.4g}"
                  + (f" grad_transform=expert_sparsity"
                     if grad_transform else ""))
        self._log(f"spec fingerprint={spec.fingerprint()}")
        rb = self.wire_report()
        if rb:
            self._log(f"wire: up={rb['up']:g} down={rb['down']:g} "
                      f"total={rb['total']:g} bits/round "
                      f"({rb['total'] / max(rb['dense_both_ways'], 1):.4f}x "
                      f"dense both ways)")
        return self

    def wire_report(self) -> Dict[str, float]:
        """Exact up+down bits of one round on this model's parameter tree
        (``{'up','down','total','dense_both_ways'}``; docs/wire_format.md)."""
        if self.state is None:
            raise RuntimeError("wire_report() needs setup() first")
        return self.run_obj.round_bits(self.state.params)

    # ---- stage 2: data pipeline --------------------------------------------

    def build_data(self):
        """Heterogeneous synthetic LM streams: a training stream plus a
        held-out eval stream on a decorrelated seed.  Under a multi-host
        layout each process would feed only its
        :func:`repro.launch.mesh.process_worker_slice` of the global batch;
        the single-process (simulated) harness materializes all of it."""
        from repro.data import SyntheticLM

        spec, st = self.spec, self.settings
        if self.mesh is None:
            self.setup()
        mk = lambda seed: SyntheticLM(  # noqa: E731
            vocab=self.cfg.vocab, seq_len=st.seq_len,
            global_batch=st.global_batch, n_workers=self.n, seed=seed,
            heterogeneity=st.heterogeneity,
            resample_from_shard=spec.resample, shard_size=st.shard_size)
        self.data = mk(spec.seed)
        self.eval_data = mk(spec.seed ^ EVAL_SEED_XOR)
        return self

    def _batch(self, data, step: int):
        import jax

        from repro.data import make_batch_shardings

        batch = make_batch_shardings(self.mesh, data.batch(step))
        for k, v in family_batch_extras(self.cfg, self.settings.global_batch,
                                        step).items():
            batch[k] = jax.device_put(v)
        return batch

    # ---- stage 3: compressed train loop ------------------------------------

    def train(self, steps: Optional[int] = None):
        """The compressed train loop (periodic eval per
        ``settings.eval_every``, checkpoints per ``settings.ckpt_every``)."""
        import jax

        from repro.checkpoint import save_checkpoint

        spec, st = self.spec, self.settings
        if self.data is None:
            self.build_data()
        steps = spec.steps if steps is None else steps
        t0 = time.time()
        metrics = {}
        for step in range(steps):
            batch = self._batch(self.data, step)
            self.state, metrics = self.step_fn(
                self.state, batch, jax.random.fold_in(self.key, step))
            if step % st.log_every == 0 or step == steps - 1:
                m = {k: float(v) for k, v in metrics.items()}
                self._log(f"step {step:5d} loss={m['loss']:.4f} "
                          f"|g|={m['g_norm']:.3f} "
                          f"h_res={m['h_residual']:.3f} "
                          f"({(time.time() - t0) / (step + 1):.2f}s/step)")
            if st.eval_every and (step + 1) % st.eval_every == 0:
                self.evaluate(step=step + 1)
            if st.ckpt_dir and st.ckpt_every and (step + 1) % st.ckpt_every == 0:
                save_checkpoint(st.ckpt_dir, step + 1,
                                {"params": self.state.params}, spec=spec)
                self._log(f"checkpoint @ {step + 1}")
        self._final = {k: float(v) for k, v in metrics.items()}
        self._steps_per_sec = steps / max(time.time() - t0, 1e-9)
        if st.ckpt_dir:
            save_checkpoint(st.ckpt_dir, steps,
                            {"params": self.state.params}, spec=spec)
        return self

    # ---- stage 4: eval -----------------------------------------------------

    def evaluate(self, step: Optional[int] = None) -> float:
        """Mean held-out loss over ``settings.eval_batches`` eval batches,
        at the workers' view of the model (the downlink reconstruction ``w``
        under bidirectional compression, the master params otherwise)."""
        import numpy as np

        if self.eval_data is None:
            self.build_data()
        params = (self.state.w if self.state.w is not None
                  else self.state.params)
        losses = [float(self._eval_fn(params, self._batch(self.eval_data, b)))
                  for b in range(self.settings.eval_batches)]
        loss = float(np.mean(losses))
        self.history.append({"step": float(self.state.step),
                             "eval_loss": loss})
        self._log(f"eval @ {int(self.state.step)}: loss={loss:.4f} "
                  f"({self.settings.eval_batches} held-out batches)")
        return loss

    # ---- all four stages ---------------------------------------------------

    def run(self) -> Dict[str, Any]:
        self.setup()
        self.build_data()
        self.train()
        eval_loss = self.evaluate()
        rb = self.wire_report()
        return {
            "fingerprint": self.spec.fingerprint(),
            "arch": self.cfg.name,
            "family": self.cfg.family,
            "final_loss": self._final["loss"],
            "eval_loss": eval_loss,
            "steps_per_sec": round(self._steps_per_sec, 4),
            "round_bits": rb,
        }


def finetune(spec, settings: Optional[FinetuneSettings] = None, *,
             config=None, verbose: bool = True) -> Dict[str, Any]:
    """Run all four stages of :class:`FinetuneLoop`; returns the summary."""
    return FinetuneLoop(spec, settings, config=config, verbose=verbose).run()
