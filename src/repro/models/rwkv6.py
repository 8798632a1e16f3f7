"""RWKV-6 "Finch": attention-free RNN with data-dependent decay.

Per layer: time-mix (the wkv recurrence over a per-head (hd x hd) state
with data-dependent decay w_t, driven by r/k/v/g projections with
token-shift) and channel-mix (token-shifted squared-ReLU MLP). State is
O(1) in sequence length, so `long_500k` decode carries only
(L, B, H, hd, hd) + shift states - no KV cache.

Training runs the recurrence with lax.scan over time (one compiled step);
decode reuses the same cell on a single token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models.common import (chunked_scan, chunked_softmax_xent,
                                 embed_tokens, init_dense, rms_norm)


def _dims(cfg: ModelConfig):
    hd = cfg.ssm_state or 64
    H = cfg.d_model // hd
    return H, hd


def init_params(cfg: ModelConfig, key) -> dict:
    d, f, L = cfg.d_model, cfg.d_ff, cfg.n_layers
    H, hd = _dims(cfg)
    ks = jax.random.split(key, 16)
    dt = jnp.dtype(cfg.param_dtype)

    def W(i, shape):
        return init_dense(ks[i], (L,) + shape, dtype=dt)

    blocks = {
        "ln1": jnp.zeros((L, d), dt),
        "mix_rkvwg": 0.5 * jnp.ones((L, 5, d), dt),   # token-shift lerp
        "wr": W(0, (d, d)), "wk": W(1, (d, d)), "wv": W(2, (d, d)),
        "wg": W(3, (d, d)), "wo": W(4, (d, d)),
        # data-dependent decay: low-rank w = base + tanh(x A) B
        "w_base": -6.0 * jnp.ones((L, H, hd), jnp.float32),
        "w_lora_a": W(5, (d, 64)),
        "w_lora_b": init_dense(ks[6], (L, 64, d), scale=0.01, dtype=dt),
        "bonus": jnp.zeros((L, H, hd), jnp.float32),   # "u" first-token boost
        "ln_x": jnp.zeros((L, d), dt),                 # per-head group norm
        "ln2": jnp.zeros((L, d), dt),
        "ck": W(7, (d, f)), "cv": W(8, (f, d)), "cr": W(9, (d, d)),
        "mix_c": 0.5 * jnp.ones((L, 2, d), dt),
    }
    params = {
        "embed": init_dense(ks[10], (cfg.vocab_size, d), scale=0.02,
                            dtype=dt),
        "blocks": blocks,
        "final_norm": jnp.zeros((d,), dt),
        "lm_head": init_dense(ks[11], (d, cfg.vocab_size), scale=0.02,
                              dtype=dt),
    }
    return params


def _time_mix_cell(cfg, bp, x_t, x_prev, state):
    """One token of wkv6. x_t: (B, d); state: (B, H, hd, hd)."""
    H, hd = _dims(cfg)
    B, d = x_t.shape
    mix = bp["mix_rkvwg"].astype(jnp.float32)            # (5, d)
    xf, pf = x_t.astype(jnp.float32), x_prev.astype(jnp.float32)
    sx = [pf + mix[i] * (xf - pf) for i in range(5)]
    r = (sx[0] @ bp["wr"].astype(jnp.float32)).reshape(B, H, hd)
    k = (sx[1] @ bp["wk"].astype(jnp.float32)).reshape(B, H, hd)
    v = (sx[2] @ bp["wv"].astype(jnp.float32)).reshape(B, H, hd)
    g = jax.nn.silu(sx[4] @ bp["wg"].astype(jnp.float32))
    # data-dependent decay (Finch): w_t in (0,1), per channel
    w_dd = jnp.tanh(sx[3] @ bp["w_lora_a"].astype(jnp.float32)) \
        @ bp["w_lora_b"].astype(jnp.float32)
    w = jnp.exp(-jnp.exp(bp["w_base"].reshape(1, H, hd)
                         + w_dd.reshape(B, H, hd)))
    u = bp["bonus"].reshape(1, H, hd)
    # out_t = r . (S + u * k^T v);  S' = diag(w) S + k^T v
    kv = jnp.einsum("bhk,bhv->bhkv", k, v)
    out = jnp.einsum("bhk,bhkv->bhv", r, state + u[..., None] * kv)
    new_state = w[..., None] * state + kv
    out = rms_norm(out.reshape(B, H * hd), bp["ln_x"], cfg.norm_eps)
    out = (out * g) @ bp["wo"].astype(jnp.float32)
    return out.astype(x_t.dtype), new_state


def _channel_mix_cell(cfg, bp, x_t, x_prev):
    mix = bp["mix_c"].astype(jnp.float32)
    xf, pf = x_t.astype(jnp.float32), x_prev.astype(jnp.float32)
    xk = pf + mix[0] * (xf - pf)
    xr = pf + mix[1] * (xf - pf)
    kk = jnp.square(jax.nn.relu(xk @ bp["ck"].astype(jnp.float32)))
    rr = jax.nn.sigmoid(xr @ bp["cr"].astype(jnp.float32))
    return (rr * (kk @ bp["cv"].astype(jnp.float32))).astype(x_t.dtype)


def _layer_parallel(cfg, bp, x):
    """One rwkv6 layer over (B, S, d), sequence-parallel formulation.

    All projections (r/k/v/g/w, channel-mix) are batched matmuls over the
    whole sequence - token shift is a parallel roll - so TP collectives
    happen once per layer, not once per token. Only the elementwise wkv
    recurrence runs under (chunk-rematted) lax.scan, with no matmuls or
    collectives in its body. Returns (x_out, (tshift, cshift, wkv_state)).
    """
    B, S, d = x.shape
    H, hd = _dims(cfg)

    h = rms_norm(x, bp["ln1"], cfg.norm_eps).astype(jnp.float32)
    h_prev = jnp.pad(h, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    mix = bp["mix_rkvwg"].astype(jnp.float32)            # (5, d)
    sx = [h_prev + mix[i] * (h - h_prev) for i in range(5)]
    r = (sx[0] @ bp["wr"].astype(jnp.float32)).reshape(B, S, H, hd)
    k = (sx[1] @ bp["wk"].astype(jnp.float32)).reshape(B, S, H, hd)
    v = (sx[2] @ bp["wv"].astype(jnp.float32)).reshape(B, S, H, hd)
    g = jax.nn.silu(sx[4] @ bp["wg"].astype(jnp.float32))
    w_dd = jnp.tanh(sx[3] @ bp["w_lora_a"].astype(jnp.float32)) \
        @ bp["w_lora_b"].astype(jnp.float32)
    w = jnp.exp(-jnp.exp(bp["w_base"].reshape(1, 1, H, hd)
                         + w_dd.reshape(B, S, H, hd)))
    u = bp["bonus"].reshape(1, H, hd)

    def step(state, inp):
        r_t, k_t, v_t, w_t = inp                     # (B,H,hd) each
        kv = jnp.einsum("bhk,bhv->bhkv", k_t, v_t)
        out = jnp.einsum("bhk,bhkv->bhv", r_t, state + u[..., None] * kv)
        state = w_t[..., None] * state + kv
        return state, out

    init = jnp.zeros((B, H, hd, hd), jnp.float32)
    if cfg.use_wkv_kernel:
        # Pallas wkv kernel: state stays in VMEM across the sequence
        # (forward/serving path; training uses the differentiable scan).
        from repro.kernels.wkv.ops import wkv as wkv_kernel
        outs_bshd, wkv = wkv_kernel(
            r, k, v, w, bp["bonus"].astype(jnp.float32).reshape(H, hd))
        outs = outs_bshd.swapaxes(0, 1)
    else:
        wkv, outs = chunked_scan(
            step, init,
            (r.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
             w.swapaxes(0, 1)), cfg.ssm_chunk)
    out = rms_norm(outs.swapaxes(0, 1).reshape(B, S, H * hd),
                   bp["ln_x"], cfg.norm_eps)
    out = (out * g) @ bp["wo"].astype(jnp.float32)
    x = x + out.astype(x.dtype)
    tshift = h[:, -1].astype(x.dtype)

    # channel mix: fully parallel (token shift is a roll)
    h2 = rms_norm(x, bp["ln2"], cfg.norm_eps).astype(jnp.float32)
    h2_prev = jnp.pad(h2, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    mixc = bp["mix_c"].astype(jnp.float32)
    xk = h2_prev + mixc[0] * (h2 - h2_prev)
    xr = h2_prev + mixc[1] * (h2 - h2_prev)
    kk = jnp.square(jax.nn.relu(xk @ bp["ck"].astype(jnp.float32)))
    rr = jax.nn.sigmoid(xr @ bp["cr"].astype(jnp.float32))
    x = x + (rr * (kk @ bp["cv"].astype(jnp.float32))).astype(x.dtype)
    cshift = h2[:, -1].astype(x.dtype)
    return x, (tshift, cshift, wkv)


def forward(cfg: ModelConfig, params, tokens, positions=None,
            prefix_embeds=None) -> jax.Array:
    x = embed_tokens(params["embed"], tokens,
                     jnp.dtype(cfg.compute_dtype))

    def body(carry, bp):
        from repro.models.shardctx import constrain_batch
        out, _states = _layer_parallel(cfg, bp, constrain_batch(carry))
        return out, None

    x, _ = lax.scan(body, x, params["blocks"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def loss_fn(cfg: ModelConfig, params, batch) -> jax.Array:
    h = forward(cfg, params, batch["tokens"])
    return chunked_softmax_xent(h, params["lm_head"], batch["labels"],
                                chunk=cfg.logits_chunk)


# ----------------------------------------------------------------------------
# serving: O(1) state
# ----------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    H, hd = _dims(cfg)
    L, d = cfg.n_layers, cfg.d_model
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "wkv": jnp.zeros((L, batch, H, hd, hd), jnp.float32),
        "tshift": jnp.zeros((L, batch, d), dt),
        "cshift": jnp.zeros((L, batch, d), dt),
    }


def decode_step(cfg: ModelConfig, params, cache, tokens, pos):
    """tokens: (B, 1) -> (logits (B, V), cache)."""
    B = tokens.shape[0]
    x = embed_tokens(params["embed"], tokens,
                     jnp.dtype(cfg.compute_dtype))[:, 0]

    def body(carry, inp):
        x = carry
        bp, wkv, tsh, csh = inp
        h = rms_norm(x, bp["ln1"], cfg.norm_eps)
        out, wkv = _time_mix_cell(cfg, bp, h, tsh, wkv)
        x = x + out
        h2 = rms_norm(x, bp["ln2"], cfg.norm_eps)
        out2 = _channel_mix_cell(cfg, bp, h2, csh)
        x = x + out2
        return x, (wkv, h, h2)

    x, (wkv, tsh, csh) = lax.scan(
        body, x, (params["blocks"], cache["wkv"], cache["tshift"],
                  cache["cshift"]))
    cache = {"wkv": wkv, "tshift": tsh, "cshift": csh}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x.astype(jnp.float32),
                        params["lm_head"].astype(jnp.float32))
    return logits, cache


def prefill(cfg: ModelConfig, params, tokens):
    """Parallel prefill: sequence-parallel layers, recurrent state out."""
    B, S = tokens.shape
    x = embed_tokens(params["embed"], tokens,
                     jnp.dtype(cfg.compute_dtype))

    def layer_body(carry, bp):
        from repro.models.shardctx import constrain_batch
        out, (tsh, csh, wkv) = _layer_parallel(cfg, bp,
                                               constrain_batch(carry))
        return out, (wkv, tsh, csh)

    x, (wkv, tsh, csh) = lax.scan(layer_body, x, params["blocks"])
    cache = {"wkv": wkv, "tshift": tsh, "cshift": csh}
    x = rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bd,dv->bv", x.astype(jnp.float32),
                        params["lm_head"].astype(jnp.float32))
    return logits, cache
