"""Kernel microbenchmarks (interpret-mode shapes: correctness-scale only;
wall times on CPU are NOT TPU perf - the derived column reports the
kernel's modeled HBM traffic advantage vs the unfused jnp path instead).
Interpret mode only, so it refuses to run where JAX sees a TPU.
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import row


def run():
    import jax
    import jax.numpy as jnp
    if jax.default_backend() == "tpu":
        raise SystemExit("kernels_micro times interpret-mode kernels on the "
                         "host; it is not a TPU benchmark")
    rows = []
    rng = np.random.default_rng(0)

    # chunk_reduce: modeled traffic ratio = (W reads + 1 write) vs
    # jnp pairwise adds ((2 reads + 1 write) * (W-1)).
    from repro.kernels.chunk_reduce.ops import chunk_reduce
    W, N = 8, 1 << 16
    x = jnp.asarray(rng.standard_normal((W, N)), jnp.float32)
    t0 = time.perf_counter()
    chunk_reduce(x, interpret=True).block_until_ready()
    dt = time.perf_counter() - t0
    traffic_kernel = (W + 1) * N * 4
    traffic_jnp = 3 * (W - 1) * N * 4
    rows.append(row("kernel_chunk_reduce_w8", dt,
                    traffic_jnp / traffic_kernel, "modeled HBM advantage"))

    # flash attention: traffic advantage vs materialized scores at S=4096.
    from repro.kernels.flash_attention.ops import flash_attention
    B, S, H, KV, hd = 1, 128, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((B, S, KV, hd)), jnp.bfloat16)
    t0 = time.perf_counter()
    flash_attention(q, k, v, bq=64, bkv=64, interpret=True
                    ).block_until_ready()
    dt = time.perf_counter() - t0
    S_big = 4096
    qkv_bytes = 4 * S_big * hd * 2                 # q,k,v,o per head
    scores_bytes = 2 * S_big * S_big * 4           # s write+read, fp32
    rows.append(row("kernel_flash_attention", dt,
                    (qkv_bytes + scores_bytes) / qkv_bytes,
                    "modeled HBM advantage at S=4096"))

    # wkv: state stays in VMEM -> advantage = state round-trips avoided.
    from repro.kernels.wkv.ops import wkv
    B, S, H, hd = 1, 64, 2, 16
    r, kk, vv = [jnp.asarray(rng.standard_normal((B, S, H, hd)),
                             jnp.float32) for _ in range(3)]
    w = jnp.asarray(rng.uniform(0.5, 0.99, (B, S, H, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, hd)), jnp.float32)
    t0 = time.perf_counter()
    wkv(r, kk, vv, w, u, interpret=True)[0].block_until_ready()
    dt = time.perf_counter() - t0
    hd_big = 64
    io_bytes = 5 * hd_big * 4                      # r,k,v,w,o per token
    state_bytes = 2 * hd_big * hd_big * 4          # state r+w per token
    rows.append(row("kernel_wkv", dt,
                    (io_bytes + state_bytes) / io_bytes,
                    "modeled HBM advantage (state in VMEM)"))
    return rows
