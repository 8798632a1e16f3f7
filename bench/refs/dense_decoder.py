"""Plain reference of a dense decoder-only transformer and its AdamW step.

Llama-style blocks as InternLM2 and MiniCPM publish them: RMSNorm, rotary
positions (rotate-half), grouped-query causal attention, SwiGLU, then a
final RMSNorm and the vocabulary head (tied to the embedding or not). The
loss is the mean next-token cross-entropy over every token of the batch.

Everything is float32 at the highest matmul precision. It imports nothing
of the program. It reads weights laid out as `shapes` gives them and
`bench/weights.py` makes them: stacked per layer, norm weights stored as
the offset from 1. `flops_per_token` is PaLM's count for this layout, and
`program_settings` the program's settings for it (`bench/refs/__init__.py`
lists what a reference module gives the harness).

`fp8=True` is the lower-precision control: every product with a weight
(and the vocabulary head) takes operands rounded to float8 e4m3 with one
scale per tensor, and its backward products take the cotangent rounded to
float8 e5m2 the same way; everything else stays float32.

Memory: the layers are rematerialised one at a time, attention runs over
query blocks and the loss over token blocks, so one chip holds the
reference at the benchmark's widths. The AdamW moments live on the host
between steps (`adamw_step`), which keeps them off the device while the
gradient is computed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block
T_BLOCK = 512          # tokens per block of the vocabulary head


# ----------------------------------------------------------------------------
# what the harness reads of this kind of model
# ----------------------------------------------------------------------------

def shapes(conf: dict) -> dict:
    """{name: shape} of every leaf, `blocks/<leaf>` stacked over layers."""
    L = conf["num_hidden_layers"]
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    out = {"embed": (V, d), "final_norm": (d,)}
    if not conf["tie_word_embeddings"]:
        out["lm_head"] = (d, V)
    out.update({
        "blocks/ln1": (L, d), "blocks/wq": (L, d, H * hd),
        "blocks/wk": (L, d, KV * hd), "blocks/wv": (L, d, KV * hd),
        "blocks/wo": (L, H * hd, d), "blocks/ln2": (L, d),
        "blocks/w_gate": (L, d, f), "blocks/w_up": (L, d, f),
        "blocks/w_down": (L, f, d),
    })
    return out


def matmul_params(conf: dict) -> int:
    """N: weights that enter a matrix product, per token, forward."""
    d, f, V = conf["hidden_size"], conf["intermediate_size"], conf["vocab_size"]
    H, KV = conf["num_attention_heads"], conf["num_key_value_heads"]
    hd = d // H
    per_layer = d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f
    return conf["num_hidden_layers"] * per_layer + d * V


def flops_per_token(conf: dict, seq_len: int) -> int:
    """Forward and backward FLOPs of one token at sequence length T, by
    PaLM's formula (Chowdhery et al. 2022, appendix B): 6N + 12 L H Q T,
    where N is `matmul_params` (every weight that enters a matrix product,
    the vocabulary head included, the embedding lookup not) and the second
    term is attention's two products over the whole sequence T. Recomputed
    work does not count."""
    L, H = conf["num_hidden_layers"], conf["num_attention_heads"]
    Q = conf["hidden_size"] // H
    return 6 * matmul_params(conf) + 12 * L * H * Q * seq_len


def program_settings(conf: dict) -> dict:
    """The program's ModelConfig fields for the decoder's published keys."""
    H = conf["num_attention_heads"]
    return dict(
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=H, n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        head_dim=conf["hidden_size"] // H,
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        norm_eps=float(conf["rms_norm_eps"]))


# ----------------------------------------------------------------------------
# the reference
# ----------------------------------------------------------------------------


def _scaled_round(x, dtype, top):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(eq, a, b):
    return jnp.einsum(eq, _scaled_round(a, jnp.float8_e4m3fn, 448.0),
                      _scaled_round(b, jnp.float8_e4m3fn, 448.0),
                      precision=HIGHEST)


def _mm8_fwd(eq, a, b):
    qa = _scaled_round(a, jnp.float8_e4m3fn, 448.0)
    qb = _scaled_round(b, jnp.float8_e4m3fn, 448.0)
    return jnp.einsum(eq, qa, qb, precision=HIGHEST), (qa, qb)


def _mm8_bwd(eq, res, ct):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(eq, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(_scaled_round(ct, jnp.float8_e5m2, 57344.0))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def _mm(eq, a, b, fp8):
    if fp8:
        return _mm8(eq, a, b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, theta):
    """x: (B, S, heads, hd), positions 0..S-1, rotate-half pairing."""
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal softmax attention, one block of queries at a time."""
    B, S, H, hd = q.shape
    rep = H // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    blk = min(Q_BLOCK, S)
    n = S // blk
    qb = q.reshape(B, n, blk, H, hd).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HIGHEST)
        s = s / np.sqrt(hd)
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HIGHEST)

    out = lax.map(one, (jnp.arange(n), qb))
    return out.swapaxes(0, 1).reshape(B, S, H, hd)


def _block(cfg, fp8, x, bp):
    B, S, d = x.shape
    H, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    eps = cfg["rms_norm_eps"]
    h = _rms_norm(x, bp["ln1"], eps)
    q = _mm("bsd,dh->bsh", h, bp["wq"], fp8).reshape(B, S, H, hd)
    k = _mm("bsd,dh->bsh", h, bp["wk"], fp8).reshape(B, S, KV, hd)
    v = _mm("bsd,dh->bsh", h, bp["wv"], fp8).reshape(B, S, KV, hd)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    o = _attention(q, k, v).reshape(B, S, H * hd)
    x = x + _mm("bsh,hd->bsd", o, bp["wo"], fp8)
    h = _rms_norm(x, bp["ln2"], eps)
    g = _mm("bsd,df->bsf", h, bp["w_gate"], fp8)
    u = _mm("bsd,df->bsf", h, bp["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, bp["w_down"], fp8)


def loss(cfg: dict, fp8: bool, params: dict, tokens, labels):
    """Mean next-token cross-entropy of (B, S) int32 tokens and labels."""
    x = params["embed"][tokens]
    block = jax.checkpoint(functools.partial(_block, cfg, fp8))
    x, _ = lax.scan(lambda c, bp: (block(c, bp), None), x, params["blocks"])
    x = _rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
    w = (params["embed"].T if cfg["tie_word_embeddings"]
         else params["lm_head"])
    d = x.shape[-1]
    h = x.reshape(-1, d)
    y = labels.reshape(-1)
    blk = min(T_BLOCK, h.shape[0])
    n = h.shape[0] // blk

    @jax.checkpoint
    def nll(total, inp):
        hb, yb = inp
        logits = _mm("td,dv->tv", hb, w, fp8)
        gold = jnp.take_along_axis(logits, yb[:, None], -1)[:, 0]
        return total + jnp.sum(jax.nn.logsumexp(logits, -1) - gold), None

    total, _ = lax.scan(nll, jnp.zeros((), jnp.float32),
                        (h.reshape(n, blk, d), y.reshape(n, blk)))
    return total / h.shape[0]


@functools.partial(jax.jit, static_argnums=(0, 1))
def _loss_and_grad(cfg_items, fp8, params, tokens, labels):
    cfg = dict(cfg_items)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    return jax.value_and_grad(functools.partial(loss, cfg, fp8))(
        p32, tokens, labels)


def loss_and_grad(cfg: dict, params, tokens, labels, fp8: bool = False):
    """(loss, float32 gradient) of the whole batch, as one jitted call.
    `params` may be stored in any float type; the maths is float32."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, bool, str))))
    return _loss_and_grad(items, fp8, params, tokens, labels)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4, 5),
                   donate_argnums=(6, 8, 9))
def _adamw_leaf(lr, b1, b2, eps, wd, out_dtype, p, g, mu, nu, scale, count):
    g = g * scale
    mu = b1 * mu + (1 - b1) * g
    nu = b2 * nu + (1 - b2) * g * g
    b1c = 1.0 - b1 ** count
    b2c = 1.0 - b2 ** count
    upd = (mu / b1c) / (jnp.sqrt(nu / b2c) + eps)
    pf = p.astype(jnp.float32)
    return (pf - lr * (upd + wd * pf)).astype(out_dtype), mu, nu


def adamw_step(opt: dict, params, grads, moments: list, count: int,
               param_dtype, keep_clipped: bool = False):
    """One AdamW step with global-norm clipping, leaf by leaf on the device.

    `moments` holds one host (mu, nu) float32 pair per leaf, None before
    the first step; it is updated in place and lives on the host between
    calls. Returns (new params, the clipped gradient as host float32
    leaves when `keep_clipped`, else None)."""
    leaves, tdef = jax.tree.flatten(params)
    gl = jax.tree.leaves(grads)
    sq = sum(float(jnp.sum(jnp.square(g))) for g in gl)
    norm = float(np.sqrt(sq))
    scale = (min(1.0, opt["clip_norm"] / max(norm, 1e-12))
             if opt["clip_norm"] > 0 else 1.0)
    dev = next(iter(gl[0].devices()))
    new, clipped = [], [] if keep_clipped else None
    for i, (p, g) in enumerate(zip(leaves, gl)):
        if moments[i] is None:
            mu = jnp.zeros(g.shape, jnp.float32, device=dev)
            nu = jnp.zeros(g.shape, jnp.float32, device=dev)
        else:
            mu = jax.device_put(moments[i][0], dev)
            nu = jax.device_put(moments[i][1], dev)
        if keep_clipped:
            clipped.append(np.asarray(g * np.float32(scale)))
        p, mu, nu = _adamw_leaf(opt["lr"], opt["b1"], opt["b2"], opt["eps"],
                                opt["weight_decay"], jnp.dtype(param_dtype),
                                p, g, mu, nu, np.float32(scale),
                                np.float32(count))
        moments[i] = (np.asarray(mu), np.asarray(nu))
        new.append(p)
    return jax.tree.unflatten(tdef, new), clipped
