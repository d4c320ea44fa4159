"""`chip_smoke.py` off the chip: its phase functions at toy width on the
CPU mesh (the rehearsal that finds wrong paths, arguments and control flow
before a chip call is spent on them), its refusal to run without a TPU,
and the compile-cache helper it starts with."""

import dataclasses
import json
import os
import subprocess
import sys
import warnings

import jax
import pytest

import chip_smoke
from horovod_tpu import runtime
from horovod_tpu.ops.flash_attention import KernelFallbackWarning

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Same code path, toy sizes: still a multiple of the kernel's 128-row floor
# so the (interpreted) flash kernel runs, not the dense path. The learning
# rate is the one thing scaled UP: the update a step makes shrinks with
# the width.
TOY = dataclasses.replace(
    chip_smoke.SmokeConfig(), d_model=64, n_layers=2, n_heads=2, vocab=128,
    seq_len=128, n_sequences=64, steps_per_epoch=4, dp_steps=3,
    learning_rate=3e-3,
)


@pytest.fixture
def no_kernel_fallback():
    with warnings.catch_warnings():
        warnings.simplefilter("error", KernelFallbackWarning)
        yield


def test_one_chip_phase_at_toy_width(no_kernel_fallback):
    gates = chip_smoke.one_chip_phase(TOY, jax.devices()[0])
    # Off-TPU the kernel is interpreted — ordinary JAX, no custom call —
    # so that one gate is what keeps the smoke from passing here.
    assert gates == {
        "kernel_matches_dense": True,
        "losses_finite": True,
        "loss_fell": True,
        "kernel_compiled": False,
    }


def test_four_chip_phase_at_toy_width(no_kernel_fallback):
    gates = chip_smoke.four_chip_phase(TOY, jax.devices()[:4])
    assert gates == {
        "params_on_every_device": True,
        "batch_split_evenly": True,
        "all_reduce_compiled": True,
        "losses_finite": True,
        "trajectories_agree": True,
    }


def run_python(args, **env):
    child_env = {
        k: v for k, v in os.environ.items()
        if k not in ("JAX_COMPILATION_CACHE_DIR",
                     "JAX_ENABLE_COMPILATION_CACHE")
    }
    child_env.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, env=child_env,
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]], ids=["1", "4"])
def test_refuses_to_run_off_tpu(argv, tmp_path):
    proc = run_python(
        ["chip_smoke.py", *argv], JAX_COMPILATION_CACHE_DIR=str(tmp_path)
    )
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "needs a TPU" in proc.stderr


class TestCompilationCacheHelper:
    FIXED = os.path.join(REPO, ".jax_cache")
    PROBE = (
        "import jax; from horovod_tpu import runtime; "
        "print(runtime.use_compilation_cache()); "
        "print(jax.config.jax_compilation_cache_dir); "
        "print(jax.config.jax_enable_compilation_cache)"
    )

    def test_env_set_is_left_alone(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        before = jax.config.jax_compilation_cache_dir
        assert runtime.use_compilation_cache() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

    def test_unset_is_one_fixed_path_in_the_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        before = jax.config.jax_compilation_cache_dir
        try:
            first = runtime.use_compilation_cache()
            assert jax.config.jax_compilation_cache_dir == self.FIXED
            second = runtime.use_compilation_cache()
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        other_pid = run_python(["-c", self.PROBE]).stdout.split()
        assert first == second == self.FIXED
        assert other_pid == [self.FIXED, self.FIXED, "True"]

    def test_disabling_the_cache_keeps_winning(self):
        out = run_python(
            ["-c", self.PROBE], JAX_ENABLE_COMPILATION_CACHE="0"
        ).stdout.split()
        assert out[2] == "False"
