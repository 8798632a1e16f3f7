"""Pallas TPU kernel: rwkv6 wkv recurrence with VMEM-resident state.

The jnp scan pays HBM round-trips for the (hd x hd) per-head state every
token - the dominant memory term of rwkv6-7b training/prefill cells. This
kernel keeps the state in VMEM scratch across the whole sequence: grid
(batch, head, seq_block) with the sequence axis innermost and sequential,
one HBM read per input element and one write per output element.

Layout: inputs are moved to (B, H, S, hd) so every block's last two dims
are (seq_block, hd) - a multiple of 8 rows by the array's full head dim,
as the TPU's (8, 128) tiling rule requires. The recurrence walks each
block 8 tokens (one sublane tile) at a time. A token's k, r and w enter
as columns of the (hd, hd) state update; the column is taken from the
row with a diagonal mask and a lane reduction (exact in f32, no transpose
of an unaligned (1, hd) row).

VMEM per program: 5 double-buffered (seq_block, hd) f32 blocks padded to
128 lanes (2.5 MiB at seq_block=512) plus the (hd, hd) state - independent
of S.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 8            # tokens per recurrence iteration: one sublane tile
SEQ_BLOCK = 512     # tokens per grid step (VMEM bound, see module doc)


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref,
                sout_ref, state_ref, *, n_tiles):
    hd = state_ref.shape[0]
    eye = (lax.broadcasted_iota(jnp.int32, (hd, hd), 0)
           == lax.broadcasted_iota(jnp.int32, (hd, hd), 1))
    row_ix = lax.broadcasted_iota(jnp.int32, (TILE, hd), 0)
    u = u_ref[0]                                   # (hd, 1)

    def col(row):                                  # (1, hd) -> (hd, 1)
        return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0]

    def body(t, state):
        rows = pl.ds(pl.multiple_of(t * TILE, TILE), TILE)
        r, k, v, w = (ref[0, 0, rows, :] for ref in (r_ref, k_ref, v_ref,
                                                     w_ref))
        out = jnp.zeros((TILE, hd), jnp.float32)
        for i in range(TILE):
            kv = col(k[i:i + 1]) * v[i:i + 1]      # (hd, hd) = k^T v
            o = jnp.sum((state + u * kv) * col(r[i:i + 1]), axis=0,
                        keepdims=True)
            out = jnp.where(row_ix == i, o, out)
            state = col(w[i:i + 1]) * state + kv
        o_ref[0, 0, rows, :] = out
        return state

    state_ref[...] = lax.fori_loop(0, n_tiles, body, state_ref[...])

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _finish():
        sout_ref[0, 0] = state_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_pallas(r, k, v, w, u, state0=None, interpret: bool = False):
    """r,k,v,w: (B, S, H, hd) fp32; u: (H, hd); state0: (B, H, hd, hd)."""
    B, S, H, hd = r.shape
    if state0 is None:
        state0 = jnp.zeros((B, H, hd, hd), jnp.float32)
    sb = min(SEQ_BLOCK, -(-S // TILE) * TILE)
    pad = (-S) % sb

    def to_bhsd(x, fill):
        # Padded tokens have k = 0 and w = 1, so they leave the state as
        # it is; their outputs are cut off below.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)),
                    constant_values=fill)
        return x.transpose(0, 2, 1, 3)

    io_spec = pl.BlockSpec((1, 1, sb, hd), lambda b, h, s: (b, h, s, 0))
    st_spec = pl.BlockSpec((1, 1, hd, hd), lambda b, h, s: (b, h, 0, 0))
    out, sout = pl.pallas_call(
        functools.partial(_wkv_kernel, n_tiles=sb // TILE),
        grid=(B, H, (S + pad) // sb),
        in_specs=[io_spec, io_spec, io_spec, io_spec,
                  pl.BlockSpec((1, hd, 1), lambda b, h, s: (h, 0, 0)),
                  st_spec],
        out_specs=[io_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((B, H, S + pad, hd), jnp.float32),
                   jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(to_bhsd(r, 0.0), to_bhsd(k, 0.0), to_bhsd(v, 0.0), to_bhsd(w, 1.0),
      u.reshape(H, hd, 1), state0)
    return out.transpose(0, 2, 1, 3)[:, :S], sout
