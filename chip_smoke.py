"""Bring-up check on TPU: the DP failover train step and the OptCC AllReduce.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips: the collectives only

One chip: the host planner's pick for a degraded 4-member ring; qwen3-1.7b
at its published widths (depth cut, random weights from a seed) trained
for a few steps through the launcher's factories (`build_model`,
`init_train_state`, `make_dp_failover_step`), with step 0 checked against
a plain `value_and_grad` + AdamW reference; then the three Pallas kernels,
compiled for the chip (never interpreted), each against its reference.

Four chips: on a ("data",) mesh of four, `lax.psum`, the ring program and
OptCC at every straggler position on a gradient-sized f32 vector, checked
against psum and a host numpy sum; the failover step healthy vs degraded
from one init; the healthy -> degraded -> healthy rebuild that
`launch/train.py` does, timed to the first step after each switch.

Every phase runs in this one process, which holds the chips. Exits
non-zero, printing no result, when JAX finds no TPU. The last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}}. Times
and peak memory are printed as information only.
"""
from __future__ import annotations

import argparse
import functools
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-1.7b"
N_LAYERS = 7            # of 28: the deepest whose step compiles for one v5e
SEQ_LEN = 2048
PER_DEVICE_BATCH = 1
STEPS = 4
LR = 1e-3
SEED = 0
ELL = 2.0
STRAGGLER = 1           # the degraded member in the 4-chip train check
BF16_TOL = 2.0 ** -7    # bf16 epsilon: one rounding of the result
F32_TOL = 1e-4          # f32 results whose summation order differs
# Two runs whose bf16 gradient sums round differently (psum vs OptCC's f32
# sum) drift apart. After 3 steps at LR the parameter difference was 7.2%
# of the update in norm on four v5e chips (3.8% on CPU); an element whose
# gradient sign flips moves two opposite AdamW updates (2*LR) apart per
# step. A dropped or misplaced chunk moves the whole update; a missing 1/p
# scale shows in grad_norm.
PARAM_RTOL = 0.15


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"FAIL: {what}")
    log(f"  ok: {what}")


def qwen3_cut(n_layers: int):
    from repro.configs import get_config
    cfg = get_config(ARCH)
    return cfg.replace(n_layers=n_layers), cfg.n_layers


def param_count(cfg) -> int:
    """Parameters of `cfg`'s model, from shapes alone."""
    import jax
    from repro.models import build_model
    shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(SEED))
    return sum(x.size for x in jax.tree.leaves(shapes))


def timed(fn, *args):
    """(result, seconds) of fn(*args) run to completion on the device."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def host_params(params) -> list:
    """One replica of every parameter leaf, as f32 numpy arrays: compared
    on the host, so that no second copy of the model sits on the device."""
    import jax
    import numpy as np
    return [np.asarray(leaf.addressable_shards[0].data, np.float32)
            for leaf in jax.tree.leaves(params)]


def param_gap(a: list, b: list) -> tuple[float, int, float, float]:
    """(||a - b||_2, elements that differ, max |a - b|, max of |a - b|
    less one bf16 ulp of b) over two lists of bf16 values held in f32."""
    import numpy as np
    sq, n, top, past_ulp = 0.0, 0, 0.0, 0.0
    for x, y in zip(a, b):
        d = np.abs(x - y)
        sq += float(np.sum(np.square(d, dtype=np.float64)))
        n += int(np.count_nonzero(d))
        top = max(top, float(d.max(initial=0.0)))
        ulp = np.spacing(np.abs(y)) * 2.0 ** 16    # f32 -> bf16 spacing
        past_ulp = max(past_ulp, float((d - ulp).max(initial=0.0)))
    return sq ** 0.5, n, top, past_ulp


def params_match(a: list, b: list, moved: float, steps: int,
                 what: str) -> None:
    """Check two runs' parameters after `steps` steps agree up to the
    drift bf16 rounding causes: PARAM_RTOL of the update `moved` in norm,
    and no element more than one bf16 ulp plus 2*LR per step apart."""
    d, n, top, past_ulp = param_gap(a, b)
    log(f"  ||params - {what}|| {d:.6g} against an update of {moved:.6g}; "
        f"{n} of {sum(x.size for x in b)} elements differ, by at most "
        f"{top:.6g}")
    check(d <= PARAM_RTOL * moved
          and past_ulp <= 2 * LR * steps * (1 + BF16_TOL),
          f"params match {what} after {steps} step(s)")


# ----------------------------------------------------------------------------
# phases
# ----------------------------------------------------------------------------

def planner_phase(n_grad: int) -> None:
    from repro.comms.fault import FaultState
    log(f"planner: degraded 4-member ring, l={ELL}, n={n_grad} elements")
    for s in range(4):
        plan = FaultState(axis_size=4, straggler=s, ell=ELL).plan(n_grad)
        log(f"  straggler {s}: planner chose {plan.algo}, predicted "
            f"overhead {plan.predicted_overhead:.4f}x, generated in "
            f"{plan.gen_seconds * 1e3:.3f} ms")


def train_phase(cfg, devices, steps: int = STEPS, seq_len: int = SEQ_LEN,
                per_device_batch: int = PER_DEVICE_BATCH) -> None:
    """Train `steps` steps on a ("data",) mesh of `devices` through the
    launcher's factories; check step 0 against the plain reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comms.fault import FaultState
    from repro.data import DataConfig, SyntheticLM
    from repro.models import build_model
    from repro.optim import AdamWConfig, update
    from repro.optim.schedules import constant
    from repro.train import init_train_state, make_dp_failover_step

    model = build_model(cfg)
    mesh = Mesh(np.array(devices), ("data",))
    opt = AdamWConfig(weight_decay=0.01)
    lr_fn = constant(LR)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                                  global_batch=per_device_batch * len(devices)))
    to_mesh = NamedSharding(mesh, P("data"))
    batches = [jax.device_put(data.batch(i), to_mesh) for i in range(steps)]

    # Plain reference for step 0: no shard_map, no collective.
    @functools.partial(jax.jit, donate_argnums=0)
    def reference_step(state, batch):
        loss, grads = jax.value_and_grad(model.loss)(state.params, batch)
        new_params, _, _ = update(state.params, grads, state.opt_state,
                                  lr_fn(state.step), opt)
        moved = jnp.sqrt(sum(
            jnp.sum(jnp.square(a.astype(jnp.float32) - b.astype(jnp.float32)))
            for a, b in zip(jax.tree.leaves(new_params),
                            jax.tree.leaves(state.params))))
        return new_params, loss, moved

    state = init_train_state(model, opt, seed=SEED, mesh=mesh)
    n_grad = sum(x.size for x in jax.tree.leaves(state.params))
    log(f"train: {cfg.name} {n_grad} parameters, d_model {cfg.d_model}, "
        f"heads {cfg.n_heads}/{cfg.n_kv_heads} x {cfg.hd}, d_ff {cfg.d_ff}, "
        f"vocab {cfg.vocab_size}, {cfg.param_dtype}; batch "
        f"{per_device_batch}x{seq_len} per device on {len(devices)} device(s)")
    (ref_params, ref_loss, ref_moved), t = timed(reference_step, state,
                                                 batches[0])
    log(f"  reference step 0 (compile + run): {t:.3f} s, loss "
        f"{float(ref_loss):.6f}")
    ref_params = host_params(ref_params)
    ref_loss, ref_moved = float(ref_loss), float(ref_moved)

    state = init_train_state(model, opt, seed=SEED, mesh=mesh)
    step = make_dp_failover_step(model, mesh, opt, lr_fn,
                                 FaultState(axis_size=len(devices)))
    t0 = time.perf_counter()
    lowered = step.lower(state, batches[0])
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0
    log(f"  failover step: trace+lower {t_lower:.3f} s, compile "
        f"{t_compile:.3f} s")

    losses, times = [], []
    for i in range(steps):
        (state, metrics), t = timed(compiled, state, batches[i])
        losses.append(float(metrics["loss"]))
        times.append(t)
        log(f"  step {i}: loss {losses[-1]:.6f} grad_norm "
            f"{float(metrics['grad_norm']):.4f} ({t:.4f} s)")
        if i == 0:
            check(abs(losses[0] - ref_loss) <= BF16_TOL * abs(ref_loss),
                  f"step 0 loss matches the plain reference ({ref_loss:.6f})")
            params_match(host_params(state.params), ref_params, ref_moved, 1,
                         "the plain reference")
            del ref_params
    check(all(np.isfinite(losses)), f"{steps} losses finite")
    log(f"  warm step time: median {sorted(times[1:])[len(times[1:]) // 2]:.4f} s"
        f" over steps 1..{steps - 1}")
    stats = devices[0].memory_stats() or {}
    log(f"  device 0 peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
        f"bytes_limit {stats.get('bytes_limit')}")


def kernels_phase(n_grad: int, device) -> None:
    """Each Pallas kernel compiled for the chip against its reference."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.chunk_reduce.ops import chunk_reduce
    from repro.kernels.chunk_reduce.ref import chunk_reduce_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    from repro.kernels.wkv.ops import wkv
    from repro.kernels.wkv.ref import wkv_ref
    from repro.configs import get_config

    keys = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))

    @jax.jit
    def max_err(a, b):                 # max |a - b| over max |b|, in f32
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        return jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))

    def rel_err(a, b) -> float:
        return float(max_err(a, b))

    def normal(shape, dtype):
        return jax.device_put(jax.random.normal(next(keys), shape, dtype),
                              device)

    log("kernels (interpret=False):")
    # Small integers, made in one fused pass on the device: the sums are
    # exact in bf16, so kernel and reference must agree bit for bit.
    parts = jax.jit(lambda: (jax.lax.broadcasted_iota(
        jnp.int32, (4, n_grad), 1) * jnp.arange(3, 11, 2)[:, None] % 17
        - 8).astype(jnp.bfloat16),
        out_shardings=jax.sharding.SingleDeviceSharding(device))()
    ref = jax.jit(chunk_reduce_ref)(parts)      # fused: no f32 copy
    out, t = timed(chunk_reduce, parts)
    err = rel_err(out, ref)
    log(f"  chunk_reduce W=4 N={n_grad} bf16: {t:.3f} s (compile + run), "
        f"max err {err:.3g} of max |ref|")
    check(err == 0.0, "chunk_reduce equals its reference")
    del parts, out, ref

    q3 = get_config(ARCH)
    S = 4096
    q = normal((1, S, q3.n_heads, q3.hd), jnp.bfloat16)
    k = normal((1, S, q3.n_kv_heads, q3.hd), jnp.bfloat16)
    v = normal((1, S, q3.n_kv_heads, q3.hd), jnp.bfloat16)
    out, t = timed(functools.partial(flash_attention, causal=True), q, k, v)
    with jax.default_matmul_precision("highest"):
        ref = flash_attention_ref(q.astype(jnp.float32), k.astype(jnp.float32),
                                  v.astype(jnp.float32), causal=True)
    err = rel_err(out, ref)
    log(f"  flash_attention S={S} H={q3.n_heads} KV={q3.n_kv_heads} "
        f"hd={q3.hd} bf16: {t:.3f} s, max err {err:.3g}")
    check(err <= 2 * BF16_TOL, "flash_attention matches its reference")

    rw = get_config("rwkv6-7b")
    H, hd = rw.d_model // rw.ssm_state, rw.ssm_state
    S = 2048
    r, kk, vv = (0.5 * normal((1, S, H, hd), jnp.float32) for _ in range(3))
    w = jax.nn.sigmoid(4.0 + normal((1, S, H, hd), jnp.float32))
    u = 0.5 * normal((H, hd), jnp.float32)
    (out, st), t = timed(wkv, r, kk, vv, w, u)
    with jax.default_matmul_precision("highest"):
        ref_out, ref_st = wkv_ref(r, kk, vv, w, u)
    err = max(rel_err(out, ref_out), rel_err(st, ref_st))
    log(f"  wkv S={S} H={H} hd={hd} f32: {t:.3f} s, max err {err:.3g}")
    check(err <= F32_TOL, "wkv matches its reference")


def allreduce_phase(n_grad: int, devices) -> None:
    """psum, ring and OptCC at every straggler on a gradient-sized vector."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comms import optcc_allreduce, ring_allreduce

    p = len(devices)
    mesh = Mesh(np.array(devices), ("data",))
    n = -(-n_grad // p) * p
    # Small integers: every summation order gives the same f32 sum, so each
    # program must reproduce numpy's sum exactly.
    rng = np.random.default_rng(SEED)
    host = rng.integers(-64, 64, size=(p, n), dtype=np.int8)
    want = host.sum(0, dtype=np.int32)
    # One flat (p*n,) array: chip i holds row i, in a 1-D layout that pads
    # nothing (a (1, n) int8 block would be padded to 32-bit tiles).
    x = jax.device_put(host.reshape(-1), NamedSharding(mesh, P("data")))
    del host
    log(f"allreduce: f32 vector of {n} elements on {p} chips")

    def program(fn):
        return jax.jit(jax.shard_map(
            lambda xs: fn(xs.astype(jnp.float32)), mesh=mesh,
            in_specs=P("data"), out_specs=P("data"), check_vma=False))

    algos = [("psum", lambda v: lax.psum(v, "data")),
             ("ring", lambda v: ring_allreduce(v, "data"))] + [
        (f"optcc straggler {s}",
         lambda v, s=s: optcc_allreduce(v, "data", s, p)) for s in range(p)]
    for name, fn in algos:
        prog = program(fn)
        out, t_first = timed(prog, x)
        del out
        out, t_warm = timed(prog, x)
        # Compared on the host, one chip's row at a time, so that the
        # device holds no second result beside the program's own buffers.
        rows = {s.device: np.asarray(s.data) for s in out.addressable_shards}
        del out
        check(set(rows) == set(devices)
              and all(np.array_equal(r, want) for r in rows.values()),
              f"{name} equals the host numpy sum on all {p} chips "
              f"(first call {t_first:.4f} s, warm {t_warm:.4f} s)")
        del rows


def failover_phase(cfg, devices, steps: int = 3, seq_len: int = SEQ_LEN,
                   per_device_batch: int = PER_DEVICE_BATCH) -> None:
    """Healthy vs degraded failover step from one init, then the rebuild."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.comms.fault import FaultState
    from repro.data import DataConfig, SyntheticLM
    from repro.launch.train import rebuild_step
    from repro.models import build_model
    from repro.optim import AdamWConfig
    from repro.optim.schedules import constant
    from repro.train import init_train_state

    p = len(devices)
    model = build_model(cfg)
    mesh = Mesh(np.array(devices), ("data",))
    opt = AdamWConfig(weight_decay=0.01)
    lr_fn = constant(LR)
    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                                  global_batch=per_device_batch * p))
    to_mesh = NamedSharding(mesh, P("data"))
    healthy = FaultState(axis_size=p)
    degraded = FaultState(axis_size=p, straggler=STRAGGLER, ell=ELL)

    def replicated(state) -> bool:
        return all(leaf.sharding.device_set == set(devices)
                   and leaf.sharding.is_fully_replicated
                   for leaf in jax.tree.leaves(state))

    def run(fault, state, first, count):
        """The launcher's reaction to a fault change (re-plan, rebuild),
        then `count` steps. Also returns the seconds from the change to the
        end of each step: the first includes compilation."""
        t0 = time.perf_counter()
        step, plan = rebuild_step(model, mesh, opt, lr_fn, fault, n_grad)
        metrics, ends = [], []
        for i in range(first, first + count):
            state, m = step(state, jax.device_put(data.batch(i), to_mesh))
            metrics.append((float(m["loss"]), float(m["grad_norm"])))
            ends.append(time.perf_counter() - t0)
        return state, np.array(metrics), plan, np.diff(ends, prepend=0.0)

    state = init_train_state(model, opt, seed=SEED, mesh=mesh)
    n_grad = sum(x.size for x in jax.tree.leaves(state.params))
    check(replicated(state), f"train state replicated over all {p} chips")
    log(f"failover: {cfg.name} {n_grad} parameters, batch "
        f"{per_device_batch}x{seq_len} per chip, straggler {STRAGGLER} "
        f"at l={ELL}")
    init = host_params(state.params)
    state, m_h, _, t_h = run(healthy, state, 0, steps)
    params_h = host_params(state.params)
    del state
    state = init_train_state(model, opt, seed=SEED, mesh=mesh)
    state, m_d, plan, t_d = run(degraded, state, 0, steps)
    log(f"  healthy  (loss, grad_norm) per step {m_h.tolist()}; step "
        f"seconds {t_h.round(4).tolist()} (the first with compile)")
    log(f"  degraded (loss, grad_norm) per step {m_d.tolist()}; step "
        f"seconds {t_d.round(4).tolist()} (planner: {plan.algo})")
    check(np.allclose(m_h, m_d, rtol=BF16_TOL, atol=0),
          "degraded losses and grad norms match healthy")
    params_match(host_params(state.params), params_h,
                 param_gap(params_h, init)[0], steps, "the healthy run")
    check(replicated(state),
          f"degraded-step state replicated over all {p} chips")
    del init, params_h

    # healthy -> degraded -> healthy, continuing the degraded run.
    first = steps
    for fault in (healthy, degraded, healthy):
        state, m, plan, t = run(fault, state, first, 1)
        first += 1
        what = f"degraded ({plan.algo})" if plan else "healthy (psum)"
        check(np.isfinite(m).all(),
              f"switch to {what}: first step done in {t[0]:.3f} s "
              f"(plan + rebuild + compile + step), loss {m[0, 0]:.6f}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: train step + kernels; 4: the collectives and "
                         "the failover step across four chips")
    args = ap.parse_args()
    try:
        from repro.launch.cache import enable_compile_cache
    except ImportError as e:
        raise SystemExit(f"chip_smoke.py needs the repository's src/ "
                         f"beside it: {e}")
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke.py needs a TPU; JAX found "
                         f"{dev.platform} ({dev.device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips}: only {len(devices)} TPU "
                         f"device(s) visible")
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"using {args.chips}; compile cache {cache}")
    cfg, full_depth = qwen3_cut(N_LAYERS)
    log(f"reduced: n_layers {cfg.n_layers}/{full_depth}")
    n_grad = param_count(cfg)
    t0 = time.perf_counter()
    if args.chips == 1:
        planner_phase(n_grad)
        train_phase(cfg, devices[:1])
        kernels_phase(n_grad, dev)
    else:
        allreduce_phase(n_grad, devices[:4])
        failover_phase(cfg, devices[:4])
    log(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
