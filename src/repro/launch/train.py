"""End-to-end training driver with fault tolerance.

Runs data-parallel training with:
  * checkpoint/restart (atomic, auto-resume from the latest step),
  * deterministic failure injection (NIC degradation events) -> on each
    event the OptCC planner produces the new collective schedule and the
    train step is re-built (re-jit), mirroring NCCL communicator re-init,
  * straggler mitigation = the paper's algorithm (degraded mode syncs
    gradients with optcc_allreduce instead of psum);
  * on each switch, the plan's time and the new step's trace / lower /
    compile (or cache load) seconds from `repro.obs.compiles`.

Works on any device count >= 1 (the DP axis is however many devices jax
sees; force more with XLA_FLAGS=--xla_force_host_platform_device_count=8).
--fail-at needs >= 3 devices and exits non-zero on fewer.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 200 --fail-at 60 --repair-at 120 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.checkpoint import latest_step, restore, save
from repro.comms.fault import FailureInjector, FaultState
from repro.configs import get_config
from repro.data import DataConfig, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.models import build_model
from repro.obs import compiles
from repro.optim import AdamWConfig
from repro.optim.schedules import warmup_stable_decay
from repro.train import init_train_state, make_dp_failover_step


def rebuild_step(model, mesh, opt, lr_fn, fault: FaultState, n_grad: int):
    """React to a changed fault state: plan the collective for `n_grad`
    gradient elements (None when healthy) and build the new step - the
    NCCL communicator re-init analogue. The step compiles on first call."""
    plan = fault.plan(n_grad) if fault.degraded else None
    return make_dp_failover_step(model, mesh, opt, lr_fn, fault), plan


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject NIC degradation at this step")
    ap.add_argument("--repair-at", type=int, default=None)
    ap.add_argument("--ell", type=float, default=1.5,
                    help="slowdown factor of the injected degradation")
    ap.add_argument("--straggler", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--lose-node-at", type=int, default=None,
                    help="simulate losing half the DP members at this "
                         "step: checkpoint, rebuild the mesh on the "
                         "survivors, restore, continue (elastic rescale)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    model = build_model(cfg)
    dp = jax.device_count()
    mesh = Mesh(np.array(jax.devices()), ("data",))
    opt = AdamWConfig(weight_decay=0.01)
    lr_fn = warmup_stable_decay(args.lr, warmup=20,
                                stable=max(args.steps - 60, 10), decay=40)

    data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size,
                                  seq_len=args.seq_len,
                                  global_batch=args.global_batch))
    injector = None
    if args.fail_at is not None:
        if dp < 3:
            sys.exit(f"--fail-at needs a DP ring of >= 3 devices for OptCC; "
                     f"{dp} visible. On CPU, add more with XLA_FLAGS="
                     "--xla_force_host_platform_device_count=8.")
        injector = FailureInjector.nic_loss(
            dp, args.fail_at, args.straggler % dp, args.ell,
            repair_step=args.repair_at)

    fault = FaultState(axis_size=dp)
    step_fn = make_dp_failover_step(model, mesh, opt, lr_fn, fault)
    state = init_train_state(model, opt, mesh=mesh)
    n_grad = sum(x.size for x in jax.tree.leaves(state.params))
    start = 0
    if args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        state, meta = restore(args.ckpt_dir, state)
        state = jax.device_put(state, NamedSharding(mesh, P()))
        start = int(meta["step"])
        print(f"resumed from checkpoint at step {start}")

    t0 = time.time()
    step = start
    while step < args.steps:
        if args.lose_node_at is not None and step == args.lose_node_at \
                and dp > 1:
            # Elastic rescale: half the DP members "fail". Checkpoint,
            # rebuild mesh + step on the survivors, restore, continue.
            # (Batches stay deterministic: the pipeline is keyed on
            # (seed, step), not on the shard layout.)
            ckpt = args.ckpt_dir or tempfile.mkdtemp(
                prefix="repro_elastic_ckpt_")
            save(ckpt, step, state)
            dp = max(dp // 2, 1)
            devices = jax.devices()[:dp]
            mesh = Mesh(np.array(devices), ("data",))
            fault = FaultState(axis_size=dp)
            injector = None   # old ring is gone
            step_fn = make_dp_failover_step(model, mesh, opt, lr_fn,
                                            fault)
            state, _ = restore(ckpt, state)
            state = jax.device_put(state, NamedSharding(mesh, P()))
            print(f"step {step}: NODE LOSS - resumed on {dp} devices "
                  f"(elastic reshard from checkpoint)")
        built = None
        if injector is not None:
            new_fault = injector.at_step(step, fault)
            if new_fault != fault:
                fault = new_fault
                built = compiles.snapshot()
                step_fn, plan = rebuild_step(model, mesh, opt, lr_fn,
                                             fault, n_grad)
                if plan is not None:
                    print(f"step {step}: DEGRADED (straggler="
                          f"{fault.straggler}, l={fault.ell}); planner "
                          f"chose {plan.algo}, predicted overhead "
                          f"{plan.predicted_overhead:.3f}x, plan built in "
                          f"{plan.gen_seconds * 1e3:.2f} ms")
                else:
                    print(f"step {step}: REPAIRED; back to native psum")
        batch = jax.tree.map(jnp.asarray, data.batch(step))
        state, metrics = step_fn(state, batch)
        if built is not None:             # the new program's first step
            c = compiles.since(built)
            print(f"step {step}: new step built in {c['total_s']:.2f} s: "
                  f"trace {c['trace_s']:.2f} s, lower {c['lower_s']:.2f} s, "
                  f"compile {c['compile_s']:.2f} s ({int(c['cache_loads'])} "
                  f"of {int(c['compiles'])} programs from the cache in "
                  f"{c['cache_load_s']:.2f} s)")
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} "
                  f"({(time.time() - t0):.1f}s)", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            save(args.ckpt_dir, step + 1, state)
        step += 1
    print("done")
    return state


if __name__ == "__main__":
    main()
