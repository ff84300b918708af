"""chip_smoke.py and the entry-point plumbing it drives, on the CPU."""

import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache, serve, train

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


@pytest.mark.parametrize("alone", [False, True],
                         ids=["in-checkout", "script-alone"])
def test_chip_smoke_refuses_without_tpu(tmp_path, alone):
    script = SMOKE
    if alone:
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SMOKE, script)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, script], cwd=tmp_path, env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path,
                                   restore_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    assert first == os.path.join(REPO, ".jax_cache")
    assert compile_cache.use_compile_cache() == first
    assert jax.config.jax_compilation_cache_dir == first


def test_train_launcher_returns_summary(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    summary = train.main(["--reduced", "--steps", "3", "--global-batch", "2",
                          "--seq-len", "16", "--log-interval", "1"])
    assert summary["final_step"] == 3
    assert summary["compile_s"] > 0
    assert [row["step"] for row in summary["metrics_log"]] == [1, 2, 3]


def test_serve_launcher_returns_requests(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    done = serve.main(["--reduced", "--num-requests", "3", "--max-batch", "2",
                       "--max-seq", "32", "--max-new-tokens", "4",
                       "--prompt-len", "8", "12"])
    assert sorted(r.uid for r in done) == [0, 1, 2]
    assert all(8 <= len(r.prompt) < 12 for r in done)
    assert all(len(r.out_tokens) == 4 for r in done)
