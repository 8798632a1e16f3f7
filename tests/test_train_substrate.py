"""Training substrate: optimizer, schedules, data, checkpoint, train step."""
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.data import DataConfig, SyntheticLM
from repro.models import build_model
from repro.optim import (AdamWConfig, constant, cosine, global_norm,
                         init_state, update, warmup_stable_decay)
from repro.train import init_train_state, make_gspmd_train_step
from repro.checkpoint import latest_step, restore, save

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                   param_dtype="float32", compute_dtype="float32",
                   logits_chunk=32)


def test_adamw_reduces_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    cfg = AdamWConfig(weight_decay=0.0, clip_norm=0.0)
    state = init_state(params, cfg)
    for _ in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = update(params, grads, state, 0.05, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.2


def test_adamw_moment_dtype():
    params = {"w": jnp.ones((4,), jnp.float32)}
    cfg = AdamWConfig(moment_dtype="bfloat16")
    state = init_state(params, cfg)
    assert state["mu"]["w"].dtype == jnp.bfloat16
    _, state, _ = update(params, {"w": jnp.ones(4)}, state, 1e-3, cfg)
    assert state["nu"]["w"].dtype == jnp.bfloat16


def test_clip_norm():
    g = {"a": jnp.full((10,), 100.0)}
    cfg = AdamWConfig(clip_norm=1.0)
    p = {"a": jnp.zeros(10)}
    s = init_state(p, cfg)
    p2, _, gnorm = update(p, g, s, 1.0, cfg)
    assert float(gnorm) == pytest.approx(float(global_norm(g)), rel=1e-5)
    assert np.isfinite(np.asarray(p2["a"])).all()


def test_schedules():
    wsd = warmup_stable_decay(1.0, warmup=10, stable=50, decay=40)
    assert float(wsd(0)) == 0.0
    assert float(wsd(10)) == pytest.approx(1.0)
    assert float(wsd(40)) == pytest.approx(1.0)
    assert float(wsd(100)) == pytest.approx(0.1, rel=1e-3)
    cos = cosine(1.0, warmup=5, total=100)
    assert float(cos(5)) == pytest.approx(1.0)
    assert float(cos(100)) == pytest.approx(0.1, rel=1e-3)


def test_data_determinism_and_sharding():
    cfg = DataConfig(vocab_size=128, seq_len=16, global_batch=8, seed=3)
    full = SyntheticLM(cfg)
    b1 = full.batch(7)
    b2 = full.batch(7)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    # shards partition the global batch deterministically
    shards = [SyntheticLM(cfg, shard_id=i, num_shards=4) for i in range(4)]
    got = np.concatenate([s.batch(7)["tokens"] for s in shards])
    np.testing.assert_array_equal(got, b1["tokens"])
    # labels are next tokens
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["labels"][:, :-1])


def test_checkpoint_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "nested": {"b": jnp.ones((4,), jnp.bfloat16)}}
    save(tmp_path, 5, tree, meta={"note": "x"})
    save(tmp_path, 9, jax.tree.map(lambda x: x * 2, tree))
    assert latest_step(tmp_path) == 9
    out, meta = restore(tmp_path, tree)
    np.testing.assert_allclose(np.asarray(out["a"], np.float32),
                               np.arange(6.0).reshape(2, 3) * 2)
    assert meta["step"] == 9
    out5, meta5 = restore(tmp_path, tree, step=5)
    assert meta5["note"] == "x"


def test_checkpoint_atomicity(tmp_path):
    tree = {"a": jnp.zeros(3)}
    save(tmp_path, 1, tree)
    # a stale tmp dir from a crashed save must not count as a checkpoint
    (tmp_path / ".tmp_step_2").mkdir()
    assert latest_step(tmp_path) == 1


def test_checkpoint_shape_mismatch_rejected(tmp_path):
    save(tmp_path, 1, {"a": jnp.zeros((2, 2))})
    with pytest.raises(ValueError, match="shape mismatch"):
        restore(tmp_path, {"a": jnp.zeros((3, 3))})


def test_train_step_learns_and_resumes(tmp_path):
    model = build_model(TINY)
    opt = AdamWConfig(weight_decay=0.01)
    state = init_train_state(model, opt)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    step = jax.jit(make_gspmd_train_step(model, mesh, opt, constant(1e-2)))
    data = SyntheticLM(DataConfig(vocab_size=256, seq_len=64,
                                  global_batch=8))
    losses = []
    for i in range(60):
        b = data.batch(i)
        state, m = step(state, jax.tree.map(jnp.asarray, b))
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3, (losses[0], losses[-1])
    assert all(np.isfinite(losses))

    # checkpoint -> restore -> identical continuation (restart determinism)
    save(tmp_path, 60, state)
    state2, _ = restore(tmp_path, state)
    b = data.batch(60)
    s_a, m_a = step(state, jax.tree.map(jnp.asarray, b))
    s_b, m_b = step(state2, jax.tree.map(jnp.asarray, b))
    assert float(m_a["loss"]) == pytest.approx(float(m_b["loss"]), abs=1e-6)


def test_microbatched_step_matches_plain():
    model = build_model(TINY)
    opt = AdamWConfig(weight_decay=0.0, clip_norm=0.0)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    data = SyntheticLM(DataConfig(vocab_size=256, seq_len=32,
                                  global_batch=8))
    b = jax.tree.map(jnp.asarray, data.batch(0))
    s1 = init_train_state(model, opt, seed=1)
    s2 = init_train_state(model, opt, seed=1)
    plain = jax.jit(make_gspmd_train_step(model, mesh, opt, constant(1e-3)))
    micro = jax.jit(make_gspmd_train_step(model, mesh, opt, constant(1e-3),
                                          num_microbatches=4))
    s1, m1 = plain(s1, b)
    s2, m2 = micro(s2, b)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                     s1.params, s2.params)
    assert max(jax.tree.leaves(d)) < 2e-5


def test_fail_at_needs_three_devices():
    """Asked to inject a NIC fault on fewer than 3 devices, the launcher
    exits non-zero instead of training without the failover path."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = dict(os.environ, PYTHONPATH=str(repo / "src"), JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "qwen3-1.7b",
         "--smoke", "--steps", "2", "--fail-at", "1"],
        capture_output=True, text=True, env=env, timeout=300, cwd=repo)
    assert proc.returncode != 0
    assert "needs a DP ring of >= 3 devices" in proc.stderr
    assert "done" not in proc.stdout
