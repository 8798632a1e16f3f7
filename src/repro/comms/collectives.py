"""Software collectives: the paper's schedules as JAX (shard_map) programs.

XLA's built-in all-reduce assumes symmetric link bandwidth and emits its own
ring/tree schedule. To control the flow structure under degraded links we
express gradient sync as explicit `lax.ppermute` steps inside `shard_map`:

  * ring_reduce_scatter / ring_all_gather - the NCCL ring baseline;
  * optcc_allreduce - OptCC's stage structure for a single degraded member
    of the axis: the straggler's data enters the healthy subring once
    (ordering B: "the straggler uploads its local value first"), the
    p-1 healthy members reduce-scatter + allgather among themselves on
    their full-bandwidth links, and exactly one flow returns the result to
    the straggler. The straggler link therefore carries 2n elements total -
    the information-theoretic minimum (Lemma 5) - instead of the 2n(p-1)/p
    it would carry inside a symmetric ring.

On real hardware the fine-grained segment pipelining of Section 4.2 is the
transport layer's concern (core.schedule / core.simulator model it); at the
XLA level what matters is which links carry how many bytes, which is what
this module controls. Functional equivalence with psum is tested on 8 host
devices (tests/test_collectives_multidev.py) and on four TPU chips
(`chip_smoke.py --chips 4`).

Also here: hierarchical cross-pod psum and int8-compressed gradient sync
with error feedback (distributed-optimization extras used by train.step).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax import named_scope as scope

from repro.obs import scopes


# OptCC pads its vector to a multiple of (subring size) * CHUNK_ALIGN, so that
# each subring chunk starts on a whole tile of a 1-D f32 vector (TPU lays one
# out in tiles of 1024 elements).
CHUNK_ALIGN = 1024


def _axis_size(axis_name: str) -> int:
    return lax.axis_size(axis_name)


def _healthy_ring(axis_size: int, straggler: int) -> list[int]:
    return [r for r in range(axis_size) if r != straggler]


# ----------------------------------------------------------------------------
# ring reduce-scatter / all-gather over a named axis (NCCL-ring baseline)
# ----------------------------------------------------------------------------

def ring_reduce_scatter(x: jax.Array, axis_name: str) -> jax.Array:
    """Flat-vector ring reduce-scatter; returns this member's reduced chunk.

    x: (n,) identical-shape vector on every axis member (n % p == 0).
    Member i returns chunk (i+1) mod p of sum_j x_j, matching the classic
    ring schedule (Patarasuk-Yuan): at step t member i sends chunk (i-t).
    """
    p = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    n = x.shape[0]
    assert n % p == 0, "pad the vector to a multiple of the axis size"
    chunks = x.reshape(p, n // p)
    perm = [(i, (i + 1) % p) for i in range(p)]
    acc = chunks
    for t in range(p - 1):
        send_ix = (idx - t) % p
        send = lax.dynamic_index_in_dim(acc, send_ix, axis=0,
                                        keepdims=False)
        recv = lax.ppermute(send, axis_name, perm)
        recv_ix = (idx - t - 1) % p
        acc = lax.dynamic_update_index_in_dim(
            acc, lax.dynamic_index_in_dim(acc, recv_ix, 0, False) + recv,
            recv_ix, axis=0)
    own = (idx + 1) % p
    return lax.dynamic_index_in_dim(acc, own, 0, keepdims=False)


def ring_all_gather(chunk: jax.Array, axis_name: str) -> jax.Array:
    """Inverse of ring_reduce_scatter: member i contributes chunk (i+1)."""
    p = _axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    out = jnp.zeros((p,) + chunk.shape, chunk.dtype)
    out = lax.dynamic_update_index_in_dim(out, chunk, (idx + 1) % p, axis=0)
    perm = [(i, (i + 1) % p) for i in range(p)]
    cur = chunk
    for t in range(p - 1):
        cur = lax.ppermute(cur, axis_name, perm)
        # after t+1 hops we hold the chunk originating at (idx - t - 1),
        # i.e. chunk index (idx - t) mod p.
        cix = (idx - t) % p
        out = lax.dynamic_update_index_in_dim(out, cur, cix, axis=0)
    return out.reshape(-1)


def ring_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """Reference ring AllReduce (== psum) built from the two halves."""
    return ring_all_gather(ring_reduce_scatter(x, axis_name), axis_name)


# ----------------------------------------------------------------------------
# OptCC AllReduce: one degraded axis member
# ----------------------------------------------------------------------------

def optcc_allreduce(x: jax.Array, axis_name: str, straggler: int,
                    axis_size: int) -> jax.Array:
    """AllReduce where axis member `straggler` has a degraded link.

    Flow structure (per the planner's schedule): the straggler sends its
    vector once to its successor on the healthy subring and receives the
    final sum once - total 2n elements over the slow link (the Lemma-5
    minimum). All remaining traffic runs on the p-1 healthy members' ring.

    `straggler` and `axis_size` must be static (the program is re-jitted
    when the fault state changes - the moral equivalent of NCCL
    communicator re-initialization after failover).

    The healthy subring keeps its data flat, in no (ph, n/ph) buffer. S1
    carries a running partial: at hop t a member adds the partial it
    receives to its own chunk (hpos - t - 1) % ph, read by a dynamic
    slice of the padded vector, and sends the sum on; the additions, and
    their order, are those of the classic ring. S4 writes the reduced
    chunk and each one it receives into that vector, dead once S1 is
    done: chunk k at offset k * c. The pad reaches a multiple of
    ph * CHUNK_ALIGN, so every chunk starts on a whole tile.

    Each stage runs under its `repro.obs.scopes` name: the pad under
    `flatten`, then `S3`, `S1` and `S4` (one `hop<t>` scope per round),
    `S2`, and the slice under `unflatten`.
    """
    p = axis_size
    if p < 3:
        raise ValueError("optcc_allreduce needs axis size >= 3")
    idx = lax.axis_index(axis_name)
    healthy = _healthy_ring(p, straggler)
    ph = p - 1
    peer = healthy[0]
    n = x.shape[0]
    pad = (-n) % (ph * CHUNK_ALIGN)
    c = (n + pad) // ph                                   # chunk length
    with scope(scopes.FLATTEN):
        xp = jnp.pad(x, (0, pad))

    # Stage "S3'" (ordering B): straggler -> peer; peer folds it in.
    with scope(scopes.S3):
        from_straggler = lax.ppermute(xp, axis_name, [(straggler, peer)])
        xp = jnp.where(idx == peer, xp + from_straggler, xp)

    # Stages S1/S4 on the healthy subring. Healthy member h = healthy[i]
    # plays ring position i; the straggler executes the same SPMD code but
    # is in no permutation pair, so it moves no data.
    hpos = jnp.where(idx > straggler, idx - 1, idx)      # ring position
    perm_h = [(healthy[i], healthy[(i + 1) % ph]) for i in range(ph)]

    def chunk(k):
        return lax.dynamic_slice_in_dim(xp, k * c, c)

    with scope(scopes.S1):                                # reduce-scatter
        part = chunk(hpos)
        for t in range(ph - 1):
            with scope(scopes.hop(t)):
                recv = lax.ppermute(part, axis_name, perm_h)
                part = chunk((hpos - t - 1) % ph) + recv

    with scope(scopes.S4):                                # allgather
        full = lax.dynamic_update_slice_in_dim(xp, part,
                                               (hpos + 1) % ph * c, 0)
        for t in range(ph - 1):
            with scope(scopes.hop(t)):
                part = lax.ppermute(part, axis_name, perm_h)
                full = lax.dynamic_update_slice_in_dim(
                    full, part, (hpos - t) % ph * c, 0)

    # Stage "S2'": one healthy member returns the sum to the straggler.
    with scope(scopes.S2):
        to_straggler = lax.ppermute(full, axis_name, [(peer, straggler)])
        full = jnp.where(idx == straggler, to_straggler, full)
    if not pad:
        return full
    with scope(scopes.UNFLATTEN):
        return full[:n]


def optcc_allreduce_tree(tree, axis_name: str, straggler: int,
                         axis_size: int):
    """OptCC AllReduce over a pytree: flatten-concat, one collective, split.

    Concatenating all gradient leaves into one flat vector both matches the
    paper's single-buffer model and amortizes the per-ppermute latency.
    The concatenation runs under the `flatten` scope, the split under
    `unflatten`."""
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [leaf.size for leaf in leaves]
    with scope(scopes.FLATTEN):
        flat = jnp.concatenate([leaf.reshape(-1).astype(jnp.float32)
                                for leaf in leaves])
    summed = optcc_allreduce(flat, axis_name, straggler, axis_size)
    outs, off = [], 0
    with scope(scopes.UNFLATTEN):
        for leaf, size in zip(leaves, sizes):
            outs.append(summed[off:off + size].reshape(leaf.shape)
                        .astype(leaf.dtype))
            off += size
    return jax.tree.unflatten(treedef, outs)


# ----------------------------------------------------------------------------
# hierarchical + compressed gradient sync
# ----------------------------------------------------------------------------

def hierarchical_psum(x: jax.Array, inner_axis: str,
                      outer_axis: Optional[str]) -> jax.Array:
    """psum within the pod, then across pods (DCN-friendly ordering)."""
    y = lax.psum(x, inner_axis)
    if outer_axis is not None:
        y = lax.psum(y, outer_axis)
    return y


def quantize_int8(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric per-tensor int8 quantization (scale in fp32)."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale


def compressed_psum(x: jax.Array, axis_name: str,
                    error: Optional[jax.Array] = None
                    ) -> tuple[jax.Array, jax.Array]:
    """AllReduce with int8-compressed allgather half + error feedback.

    reduce-scatter runs at full precision (sums must not saturate); each
    member quantizes its reduced shard to int8 and the shards are
    allgathered at 1/4 the bytes. Returns (result, new_error) where
    new_error is this member's local quantization residual (add it to the
    next step's gradient - standard error-feedback compression).
    """
    p = _axis_size(axis_name)
    n = x.shape[0]
    if error is not None:
        x = x + error
    pad = (-n) % p
    xp = jnp.pad(x, (0, pad))
    shard = lax.psum_scatter(xp.reshape(p, -1), axis_name,
                             scatter_dimension=0, tiled=False)
    q, scale = quantize_int8(shard)
    deq_own = dequantize_int8(q, scale)
    new_error_shard = shard - deq_own
    qs = lax.all_gather(q, axis_name, axis=0)
    scales = lax.all_gather(scale, axis_name, axis=0)
    full = (qs.astype(jnp.float32) * scales[:, None]).reshape(-1)
    # Scatter the residual back to full length for simple state handling.
    idx = lax.axis_index(axis_name)
    err_full = jnp.zeros_like(xp.reshape(p, -1))
    err_full = lax.dynamic_update_index_in_dim(err_full, new_error_shard,
                                               idx, axis=0).reshape(-1)
    return full[:n], err_full[:n]
