"""jit'd public wrapper for the flash attention kernel."""
from __future__ import annotations

import jax

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    bq: int = 128, bkv: int = 128,
                    use_pallas: bool = True,
                    interpret: bool = False) -> jax.Array:
    """Blockwise attention. use_pallas=False runs the jnp oracle instead;
    interpret=True runs the kernel in the Pallas interpreter (no TPU)."""
    if not use_pallas:
        return flash_attention_ref(q, k, v, causal=causal, window=window)
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  bq=bq, bkv=bkv, interpret=interpret)
