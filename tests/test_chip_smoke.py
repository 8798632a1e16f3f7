"""chip_smoke.py: refuses to report without a TPU, and its one-chip train
phase runs end to end at a tiny size on the CPU."""
import os
import pathlib
import shutil
import subprocess
import sys

import jax

import chip_smoke
from repro.configs import get_config

REPO = pathlib.Path(__file__).resolve().parent.parent

# qwen3's layer structure (GQA, qk-norm, tied embeddings) at toy widths.
TINY = get_config("qwen3-1.7b").replace(
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
    vocab_size=512, logits_chunk=32)


def _run(script: pathlib.Path, cwd: pathlib.Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_refuses_without_tpu():
    proc = _run(REPO / "chip_smoke.py", REPO)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_refuses_outside_a_checkout(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    proc = _run(tmp_path / "chip_smoke.py", tmp_path)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_train_phase_matches_reference_on_cpu(capsys):
    chip_smoke.train_phase(TINY, jax.devices()[:1], steps=3, seq_len=32,
                           per_device_batch=2)
    out = capsys.readouterr().out
    assert "ok: params match the plain reference after 1 step(s)" in out
    assert "ok: 3 losses finite" in out


def test_planner_phase_prints_its_pick(capsys):
    chip_smoke.planner_phase(chip_smoke.param_count(TINY))
    out = capsys.readouterr().out
    assert out.count("planner chose") == 4


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    from repro.launch import cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert cache.enable_compile_cache() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_keeps_the_environments_directory(monkeypatch, tmp_path):
    from repro.launch import cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
