"""Driver executed in a subprocess with 8 forced host devices.

Must set XLA_FLAGS before importing jax - which is why these checks cannot
run inside the main pytest process (smoke tests there must see 1 device).
Prints 'ALL-OK' on success; any assertion failure raises. With the
argument `bitexact` it runs only `optcc_bit_exact` and prints 'BITEXACT-OK'.
"""
import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", ""))

import sys  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402
from jax import shard_map  # noqa: E402

from repro.comms import (compressed_psum, optcc_allreduce,  # noqa: E402
                         optcc_allreduce_tree, ring_all_gather,
                         ring_allreduce, ring_reduce_scatter)


def main():
    assert jax.device_count() == 8, jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("dp",))
    p = 8
    rng = np.random.default_rng(0)
    n = 1000
    x = rng.standard_normal((p, n)).astype(np.float32)
    expected = x.sum(0)

    def run(fn):
        sharded = shard_map(fn, mesh=mesh, in_specs=P("dp"),
                            out_specs=P("dp"))
        return jax.jit(sharded)(x)

    # --- ring allreduce == psum ---------------------------------------
    def f_ring(xs):
        return ring_allreduce(xs[0], "dp")[None]
    out = run(f_ring)
    for r in range(p):
        np.testing.assert_allclose(out[r], expected, rtol=1e-5, atol=1e-5)
    print("ring_allreduce OK")

    # --- ring RS + AG halves ------------------------------------------
    def f_rs(xs):
        chunk = ring_reduce_scatter(xs[0], "dp")
        return ring_all_gather(chunk, "dp")[None]
    out = run(f_rs)
    for r in range(p):
        np.testing.assert_allclose(out[r], expected, rtol=1e-5, atol=1e-5)
    print("ring RS/AG OK")

    # --- optcc_allreduce for every straggler position ------------------
    for straggler in (0, 3, 7):
        def f_optcc(xs):
            return optcc_allreduce(xs[0], "dp", straggler, p)[None]
        out = run(f_optcc)
        for r in range(p):
            np.testing.assert_allclose(out[r], expected, rtol=1e-5,
                                       atol=1e-5)
    print("optcc_allreduce OK")

    # --- optcc on a pytree (gradient-like) ------------------------------
    tree = {"w": x[:, :600].reshape(p, 20, 30),
            "b": x[:, 600:607]}
    def f_tree(t):
        sub = jax.tree.map(lambda a: a[0], t)
        out = optcc_allreduce_tree(sub, "dp", 2, p)
        return jax.tree.map(lambda a: a[None], out)
    sharded = shard_map(f_tree, mesh=mesh,
                        in_specs=(P("dp"),), out_specs=P("dp"))
    out = jax.jit(sharded)(tree)
    np.testing.assert_allclose(out["w"][0], x[:, :600].sum(0).reshape(20, 30),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out["b"][3], x[:, 600:607].sum(0),
                               rtol=1e-5, atol=1e-5)
    print("optcc_allreduce_tree OK")

    # --- straggler link volume: count ppermute bytes touching straggler --
    # Structural check on the jaxpr: the optcc program contains exactly
    # 2 ppermutes whose permutation includes the straggler (in + out).
    def f_s(xs):
        return optcc_allreduce(xs[0], "dp", 0, p)[None]
    jaxpr = jax.make_jaxpr(
        shard_map(f_s, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x)
    text = str(jaxpr)
    n_perm_with_straggler = text.count("(0, 1)") + text.count("(1, 0)")
    assert n_perm_with_straggler >= 2, text[:500]
    print("straggler-volume structure OK")

    # --- compressed psum with error feedback ----------------------------
    def f_comp(xs):
        out, err = compressed_psum(xs[0], "dp")
        return out[None], err[None]
    sharded = shard_map(f_comp, mesh=mesh, in_specs=P("dp"),
                        out_specs=(P("dp"), P("dp")))
    out, err = jax.jit(sharded)(x)
    rel = np.abs(out[0] - expected) / (np.abs(expected) + 1e-3)
    assert rel.mean() < 0.05, rel.mean()   # int8 quantization error bound
    # error feedback: next-step correction reduces bias
    assert np.abs(err).sum() > 0
    print("compressed_psum OK")

    failover_equivalence()
    chip_smoke_four_chip_phases()

    print("ALL-OK")


def optcc_subring_model(xs: np.ndarray, straggler: int) -> np.ndarray:
    """OptCC's sum of the rows of `xs` (p, n) in float32, in the order of
    its additions: the straggler's row joins its peer's first (peer +
    straggler); then chunk k's partial starts at ring position k and each
    next position adds its own chunk to the partial it receives."""
    from repro.comms.collectives import CHUNK_ALIGN
    p, n = xs.shape
    healthy = [r for r in range(p) if r != straggler]
    ph = p - 1
    pad = (-n) % (ph * CHUNK_ALIGN)
    c = (n + pad) // ph
    v = {h: np.pad(xs[h], (0, pad)) for h in healthy}
    v[healthy[0]] = v[healthy[0]] + np.pad(xs[straggler], (0, pad))
    out = []
    for k in range(ph):
        part = v[healthy[k]][k * c:(k + 1) * c]
        for j in range(1, ph):
            part = v[healthy[(k + j) % ph]][k * c:(k + 1) * c] + part
        out.append(part)
    return np.concatenate(out)[:n]


def optcc_bit_exact():
    """optcc_allreduce == optcc_subring_model, bit for bit, on every member
    and at every straggler position: p = 8 (ph = 7), and p = 4 (ph = 3)
    at n = 1,000,003, which is no multiple of the pad's."""
    rng = np.random.default_rng(14)
    for p, n in ((8, 50_001), (4, 1_000_003)):
        mesh = Mesh(np.array(jax.devices()[:p]), ("dp",))
        x = rng.standard_normal((p, n)).astype(np.float32)
        for straggler in range(p):
            def f(xs, straggler=straggler, p=p):
                return optcc_allreduce(xs[0], "dp", straggler, p)[None]
            out = np.asarray(jax.jit(shard_map(
                f, mesh=mesh, in_specs=P("dp"), out_specs=P("dp")))(x))
            want = optcc_subring_model(x, straggler)
            assert out.dtype == want.dtype == np.float32
            for r in range(p):
                np.testing.assert_array_equal(out[r], want,
                                              err_msg=f"p={p} s={straggler}")
            # the model is no sum in another order: it differs from one
            assert not np.array_equal(want, x.sum(0, dtype=np.float32))
    print("BITEXACT-OK")


def failover_equivalence():
    """Degraded-mode (OptCC) training == healthy (psum) training, bitwise
    up to fp tolerance: 3 steps each on 8 DP shards."""
    from repro.configs.base import ModelConfig
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.optim.schedules import constant
    from repro.train import init_train_state, make_dp_failover_step
    from repro.comms.fault import FaultState
    from repro.data import DataConfig, SyntheticLM

    cfg = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      param_dtype="float32", compute_dtype="float32",
                      logits_chunk=16)
    model = build_model(cfg)
    opt = AdamWConfig(weight_decay=0.0)
    mesh = Mesh(np.array(jax.devices()), ("data",))
    data = SyntheticLM(DataConfig(vocab_size=128, seq_len=32,
                                  global_batch=8))
    healthy = make_dp_failover_step(model, mesh, opt, constant(1e-3),
                                    FaultState(axis_size=8))
    degraded = make_dp_failover_step(model, mesh, opt, constant(1e-3),
                                     FaultState(axis_size=8, straggler=3,
                                                ell=1.75))
    s_h = init_train_state(model, opt, seed=7, mesh=mesh)
    s_d = init_train_state(model, opt, seed=7, mesh=mesh)
    for i in range(3):
        b = jax.tree.map(jnp.asarray, data.batch(i))
        s_h, m_h = healthy(s_h, b)
        s_d, m_d = degraded(s_d, b)
        assert abs(float(m_h["loss"]) - float(m_d["loss"])) < 1e-5
    diffs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                         s_h.params, s_d.params)
    assert max(jax.tree.leaves(diffs)) < 1e-5, diffs
    print("failover-equivalence OK")


def chip_smoke_four_chip_phases():
    """chip_smoke.py --chips 4's phases, at toy widths on 4 host devices."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
    import chip_smoke
    from repro.configs import get_config
    cfg = get_config("qwen3-1.7b").replace(
        n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, head_dim=32,
        d_ff=256, vocab_size=512, logits_chunk=32)
    chip_smoke.allreduce_phase(chip_smoke.param_count(cfg), jax.devices()[:4])
    chip_smoke.failover_phase(cfg, jax.devices()[:4], seq_len=64,
                              per_device_batch=2)
    print("chip_smoke four-chip phases OK")


if __name__ == "__main__":
    if sys.argv[1:] == ["bitexact"]:
        optcc_bit_exact()
    else:
        main()
