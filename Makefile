# Tier-1 verify = the fast default test selection (slow subprocess tests
# excluded via the pytest addopts in pyproject.toml).  Everything runs on CPU
# (JAX_PLATFORMS=cpu): the Pallas kernels auto-select interpret mode off-TPU
# and the fused wire pack dispatches to its bit-identical jnp oracle.

PY ?= python
export PYTHONPATH := src:$(PYTHONPATH)

.PHONY: test-tier1 test-all test-slow bench bench-micro smoke smoke-federated \
	smoke-bidirectional smoke-spec smoke-pipelined smoke-tree smoke-serve \
	smoke-finetune docs-test docs-check lint sanitize-smoke

test-tier1:
	JAX_PLATFORMS=cpu $(PY) -m pytest -x -q

test-all:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m ""

test-slow:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q -m slow --durations=25

# the pinned CI bench: writes BENCH_perf.json + BENCH_bits.json at the repo
# root -- byte-identical machinery to the CI `bench` job, so the committed
# trajectory and a local run are comparable
bench:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.ci_bench

bench-micro:
	JAX_PLATFORMS=cpu $(PY) -m benchmarks.compressor_bench

docs-test:
	JAX_PLATFORMS=cpu $(PY) -m pytest -q --doctest-glob='*.md' docs/

docs-check: docs-test
	$(PY) tools/check_links.py docs README.md

# the repo-invariant static analyzer (docs/static_analysis.md): AST rules
# over src/ + tests/ pinned against the committed golden counts, the docs
# link/doctest census, and the dense-free proof for every registered pack
# kernel.  Mirrors the CI `lint` job.
lint:
	$(PY) -m repro.analysis src/ tests/ --golden ANALYSIS_GOLDEN.json
	$(PY) -m repro.analysis --docs
	JAX_PLATFORMS=cpu $(PY) -m repro.analysis --hlo-gate

# dynamic sanitizer (repro.analysis.sanitize): one smoke step of each
# trainer under jax_debug_nans + forced Pallas interpret mode
sanitize-smoke:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train --arch qwen2-0.5b --smoke \
	    --mesh 2x2 --steps 2 --global-batch 8 --seq 32 \
	    --compressor block_topk:256,16 --agg sparse_allgather --sanitize
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.finetune \
	    --spec examples/specs/finetune_moe.json --steps 2 \
	    --global-batch 8 --seq 32 --eval-every 2 --sanitize

smoke:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train --arch qwen2-0.5b --smoke \
	    --mesh 2x2 --steps 4 --global-batch 8 --seq 32 \
	    --compressor block_topk:256,16 --agg sparse_allgather

smoke-federated:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train --arch qwen2-0.5b --smoke \
	    --mesh 2x2 --steps 4 --global-batch 8 --seq 32 \
	    --compressor block_topk:256,16 --agg sparse_allgather \
	    --participation bernoulli:0.5 --local-batch-resample

smoke-bidirectional:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train --arch qwen2-0.5b --smoke \
	    --mesh 2x2 --steps 4 --global-batch 8 --seq 32 \
	    --compressor qsgd:16 --agg sparse_allgather --downlink qsgd:16

# spec-file driven run: the whole experiment from one committed
# ExperimentSpec JSON (docs/api.md)
smoke-spec:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train \
	    --spec examples/specs/qsgd_bidirectional.json --smoke \
	    --global-batch 8 --seq 32

# pipelined (one-round-stale) schedule: the committed depth:1 spec drives a
# double-buffered train step (docs/algorithms.md#pipelined-rounds)
smoke-pipelined:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train \
	    --spec examples/specs/pipelined_blocktopk.json --smoke \
	    --global-batch 8 --seq 32

# pytree-native wire: the committed mixed per-leaf codec spec
# (docs/wire_format.md#per-leaf-codecs-the-pytree-native-wire)
smoke-tree:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.train \
	    --spec examples/specs/tree_mixed_codecs.json --smoke \
	    --global-batch 8 --seq 32

# staged fine-tuning harness: the committed MoE spec (smallest MoE config,
# expert-sparse per-leaf wire, shard_map backend) through all four stages, with
# the multi-host-shaped mesh simulated at 2 processes (docs/finetuning.md)
smoke-finetune:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.finetune \
	    --spec examples/specs/finetune_moe.json --steps 2 \
	    --global-batch 8 --seq 32 --processes 2 --eval-every 2

# compressed-delta serving: the committed serve spec drives a simulated
# replica fleet reconstructing w from versioned downlink pushes, bitwise
# (docs/serving.md)
smoke-serve:
	JAX_PLATFORMS=cpu $(PY) -m repro.launch.serve \
	    --spec examples/specs/serve_delta.json
