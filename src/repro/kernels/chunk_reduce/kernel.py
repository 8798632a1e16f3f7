"""Pallas TPU kernel: multiway chunk reduction (the AllReduce combine op).

The paper's data plane repeatedly applies `acc += incoming_flow` over large
gradient segments (Stage-1 ring hops, Stage-2 straggler folds, star-block
accumulation). On TPU this is an HBM-bandwidth-bound streaming reduce; the
kernel tiles the element axis into lane-aligned VMEM blocks and
fp32-accumulates the W incoming ways per block, so each output element is
written once and each input element read once.

Grid: one program per element block. BlockSpec keeps the W-way stack of
one block resident in VMEM, double-buffered, next to its fp32 upcast.
The block is capped so that this stays within VMEM_BUDGET, half of the
16 MiB a v5e kernel may use by default: a larger one is refused by the
compiler (e.g. W=16 f32 at 131072 elements).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
TILE = 8 * LANES               # one (8, 128) vreg tile of elements
DEFAULT_BLOCK = 16 * 1024
VMEM_BUDGET = 8 * 1024 * 1024


def max_block(ways: int, in_dtype, out_dtype) -> int:
    """Largest block (a multiple of TILE) whose buffers fit VMEM_BUDGET:
    double-buffered (W, block) input and (block,) output, plus the
    (W, block) fp32 upcast."""
    per_elem = (2 * (ways * jnp.dtype(in_dtype).itemsize
                     + jnp.dtype(out_dtype).itemsize) + 4 * ways)
    return max(TILE, VMEM_BUDGET // per_elem // TILE * TILE)


def _kernel(x_ref, o_ref):
    # x_ref: (W, BLOCK) VMEM; o_ref: (BLOCK,) VMEM
    acc = x_ref[...].astype(jnp.float32).sum(axis=0)
    o_ref[...] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret", "out_dtype"))
def chunk_reduce_pallas(parts: jax.Array, block: int = DEFAULT_BLOCK,
                        interpret: bool = False, out_dtype=None):
    W, N = parts.shape
    out_dtype = out_dtype or parts.dtype
    block = min(block, max_block(W, parts.dtype, out_dtype),
                max(LANES, ((N + LANES - 1) // LANES) * LANES))
    pad = (-N) % block
    if pad:
        parts = jnp.pad(parts, ((0, 0), (0, pad)))
    npad = parts.shape[1]
    out = pl.pallas_call(
        _kernel,
        grid=(npad // block,),
        in_specs=[pl.BlockSpec((W, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((npad,), out_dtype),
        interpret=interpret,
    )(parts)
    return out[:N]
