"""jit'd public wrapper for the chunk_reduce kernel."""
from __future__ import annotations

import jax

from repro.kernels.chunk_reduce.kernel import (DEFAULT_BLOCK,
                                               chunk_reduce_pallas)
from repro.kernels.chunk_reduce.ref import chunk_reduce_ref


def chunk_reduce(parts: jax.Array, block: int = DEFAULT_BLOCK,
                 use_pallas: bool = True, interpret: bool = False,
                 out_dtype=None) -> jax.Array:
    """Sum W partial buffers: (W, N) -> (N,), fp32 accumulation.

    use_pallas=False runs the jnp oracle instead; interpret=True runs the
    kernel in the Pallas interpreter (no TPU). `block` is capped to what
    fits VMEM (kernel.max_block).
    """
    if not use_pallas:
        return chunk_reduce_ref(parts, out_dtype)
    return chunk_reduce_pallas(parts, block=block, interpret=interpret,
                               out_dtype=out_dtype)
