"""End-to-end behaviour tests for the paper's system: the full train driver
(EF-BV in the loop) and the serve driver, on reduced configs."""

import importlib.util
import os

import pytest

from conftest import REPO, run_with_devices


@pytest.mark.slow
def test_train_driver_end_to_end():
    """repro.launch.train with EF-BV + sparse wire on a 2x2 mesh learns."""
    out = run_with_devices("""
        from repro.launch.train import main
        loss = main(["--arch", "qwen2-0.5b", "--smoke", "--mesh", "2x2",
                     "--steps", "40", "--global-batch", "8", "--seq", "64",
                     "--lr", "3e-3", "--algo", "efbv",
                     "--compressor", "block_topk:256,64",
                     "--agg", "sparse_allgather", "--log-every", "20"])
        assert loss < 7.0, loss   # started ~log(1024)=6.93, must not blow up
        print("TRAIN_DRIVER_OK", loss)
    """, n_devices=4, timeout=1200)
    assert "TRAIN_DRIVER_OK" in out


@pytest.mark.slow
def test_train_driver_smoke_both_agg_modes():
    """Regression: launch/train.py --smoke must run under BOTH aggregation
    wire formats (the sparse path is the fused-payload pipeline)."""
    out = run_with_devices("""
        from repro.launch.train import main
        for agg in ["dense_psum", "sparse_allgather"]:
            loss = main(["--arch", "qwen2-0.5b", "--smoke", "--mesh", "2x2",
                         "--steps", "2", "--global-batch", "8", "--seq", "32",
                         "--algo", "efbv", "--compressor", "block_topk:256,16",
                         "--agg", agg, "--log-every", "10"])
            assert loss < 8.0, (agg, loss)
            print("AGG_OK", agg)
    """, n_devices=4, timeout=1200)
    assert out.count("AGG_OK") == 2


def test_serve_driver_end_to_end(capsys):
    from repro.launch.serve import main
    gen = main(["--arch", "mamba2-130m", "--smoke", "--batch", "2",
                "--prompt-len", "4", "--gen", "6"])
    assert gen.shape == (2, 6)


def test_serve_driver_zero_prompt_len(capsys):
    """Regression: --prompt-len 0 used to NameError (generation read the
    never-assigned prefill token); an empty prompt now generates from a
    BOS-style zero token."""
    from repro.launch.serve import main
    gen = main(["--arch", "mamba2-130m", "--smoke", "--batch", "2",
                "--prompt-len", "0", "--gen", "4"])
    assert gen.shape == (2, 4)


def test_checkpoint_from_train_driver(tmp_path):
    from repro.launch.train import main
    main(["--arch", "mamba2-130m", "--smoke", "--mesh", "1x1", "--steps", "3",
          "--global-batch", "2", "--seq", "32", "--ckpt-dir", str(tmp_path),
          "--log-every", "100"])
    from repro.checkpoint import latest_step, restore_checkpoint, saved_spec
    from repro.launch.train import parse_args, spec_from_args

    assert latest_step(str(tmp_path)) == 3
    # the driver embedded its ExperimentSpec: same flags -> same fingerprint
    args = parse_args(["--arch", "mamba2-130m", "--smoke", "--mesh", "1x1",
                       "--steps", "3", "--global-batch", "2", "--seq", "32"])
    spec = spec_from_args(args, n=1)
    assert saved_spec(str(tmp_path), 3) == spec
    # a different experiment is refused at restore time
    import dataclasses
    import jax.numpy as jnp
    import pytest as _pytest
    other = dataclasses.replace(spec, compressor="qsgd:16")
    with _pytest.raises(ValueError, match="refusing resume"):
        restore_checkpoint(str(tmp_path), 3,
                           {"params": {"x": jnp.zeros(1)}}, spec=other)


@pytest.mark.slow
def test_train_driver_spec_file_smoke(tmp_path):
    """--spec path.json drives the whole run from a serialized
    ExperimentSpec (the CI spec-smoke job runs the committed canonical
    file; this pins the same path with a locally-written spec)."""
    import json
    import os

    spec_path = os.path.join(str(tmp_path), "spec.json")
    with open(spec_path, "w") as f:
        json.dump({"compressor": "qsgd:16", "agg": "sparse_allgather",
                   "downlink": "qsgd:16", "backend": "shard_map",
                   "problem": "qwen2-0.5b", "mesh": "2x2", "n": 2,
                   "d": 131072, "steps": 2, "seed": 0}, f)
    out = run_with_devices(f"""
        from repro.launch.train import main
        loss = main(["--spec", {spec_path!r}, "--smoke", "--global-batch",
                     "8", "--seq", "32", "--log-every", "10"])
        assert loss < 8.0, loss
        print("SPEC_SMOKE_OK", loss)
    """, n_devices=4, timeout=1200)
    assert "SPEC_SMOKE_OK" in out


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("env, why", [
    ({}, "no TPU"),
    ({"REPRO_SANITIZE": "1"}, "REPRO_SANITIZE=1"),
    ({"REPRO_WIRE_KERNEL": "oracle"}, "REPRO_WIRE_KERNEL='oracle'"),
], ids=["no-tpu", "sanitize", "oracle-kernel"])
def test_chip_smoke_refuses_off_the_device_path(monkeypatch, capsys, env,
                                                why):
    """chip_smoke.py exits nonzero, printing no result line, where JAX finds
    no TPU or a switch would move the kernels off the device."""
    from repro.launch import runtime

    for k in ("REPRO_SANITIZE", "REPRO_WIRE_KERNEL"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    monkeypatch.setattr(runtime, "compile_cache", lambda: "")
    assert _chip_smoke().main([]) == 1
    out, err = capsys.readouterr()
    assert why in err
    assert '"ok"' not in out


@pytest.mark.parametrize("env_dir", [True, False], ids=["env", "checkout"])
def test_compile_cache_placement(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; unset, the
    cache sits at one fixed path inside the checkout."""
    import jax

    from repro.launch import runtime

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert runtime.compile_cache() == str(tmp_path)
        assert updates == []
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert runtime.CACHE_DIR == want
        assert runtime.compile_cache() == want
        assert updates == [("jax_compilation_cache_dir", want)]


def test_train_driver_mesh_larger_than_devices(monkeypatch):
    """A mesh wider than the process's devices (the default 2x2 on one
    chip) is a clear exit that names the device count."""
    from repro.launch import runtime, train

    monkeypatch.setattr(runtime, "compile_cache", lambda: "")
    monkeypatch.setattr(runtime, "cpu_devices", lambda n: None)
    with pytest.raises(SystemExit,
                       match=r"mesh 2x2 needs 4 devices, but this process "
                             r"has 1 cpu device\(s\)"):
        train.main(["--arch", "mamba2-130m", "--smoke", "--mesh", "2x2",
                    "--steps", "1"])
