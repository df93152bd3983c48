"""Plain float32 forward of the dense-attention decoder: the model-level
reference the serving path is checked against.

It shares no code with ``repro.models`` beyond the parameter tree it reads:
no einsum frontend, no policy context, no kernels, no caches.  Every
contraction is a ``jnp.einsum`` at ``Precision.HIGHEST`` on float32
operands, so on a TPU it is fp32 arithmetic and not one bf16 pass.  It
follows the same conventions as the model (embedding scaled by
``sqrt(d_model)``, RMSNorm with a ``1 + scale`` gain, rotate-half rope,
grouped-query heads ``h = kv_head * rep + r``, gated FFN, tied or untied
LM head).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig

__all__ = ["reference_logits"]

_ACTS = {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}


def reference_logits(params, tokens: jnp.ndarray,
                     cfg: ArchConfig) -> jnp.ndarray:
    """Last-position logits ``(b, vocab)`` of ``tokens (b, s)`` for a
    decoder-only config whose every block is GQA attention + a dense FFN."""
    if (cfg.encoder_layers or cfg.vision_tokens
            or any(b.mixer != "attn" or b.ffn != "dense"
                   for b in cfg.pattern)):
        raise NotImplementedError(
            f"{cfg.name}: the reference covers dense-attention decoders only")
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, s = tokens.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    rep, half = h // kvh, hd // 2
    act = _ACTS[cfg.act]

    freq = cfg.rope_theta ** (-jnp.arange(half, dtype=f32) / half)
    ang = jnp.arange(s, dtype=f32)[:, None] * freq           # (s, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def rope(t):                                              # (b, s, n, hd)
        t1, t2 = t[..., :half], t[..., half:]
        return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)

    def norm(t, gain):
        var = jnp.mean(t * t, axis=-1, keepdims=True)
        return t * jax.lax.rsqrt(var + cfg.norm_eps) * (1.0 + gain.astype(f32))

    def linear(t, w, bias=None):
        y = jnp.einsum("bsd,df->bsf", t, w.astype(f32), precision=hi)
        return y if bias is None else y + bias.astype(f32)

    def block(x, p):
        m = p["mixer"]
        a = norm(x, p["norm1"])
        q = rope(linear(a, m["wq"], m.get("bq")).reshape(b, s, h, hd))
        k = rope(linear(a, m["wk"], m.get("bk")).reshape(b, s, kvh, hd))
        v = linear(a, m["wv"], m.get("bv")).reshape(b, s, kvh, hd)
        q = q.reshape(b, s, kvh, rep, hd)
        sc = jnp.einsum("bqgrd,bkgd->bgrqk", q, k, precision=hi) / hd ** 0.5
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bgrqk,bkgd->bqgrd", pr, v, precision=hi)
        x = x + linear(o.reshape(b, s, h * hd), m["wo"])
        f = p["ffn"]
        a = norm(x, p["norm2"])
        gated = act(linear(a, f["w_gate"])) * linear(a, f["w_up"])
        return x + linear(gated, f["w_down"])

    def group(x, gp):
        for i in range(len(cfg.pattern)):
            x = block(x, gp[f"pos{i}"])
        return x, None

    embed = params["embed"].astype(f32)
    x = embed[tokens] * cfg.d_model ** 0.5
    x, _ = jax.lax.scan(group, x, params["blocks"])
    last = norm(x, params["final_norm"])[:, -1]
    if cfg.tie_embeddings:
        return jnp.einsum("bd,vd->bv", last, embed, precision=hi)
    return jnp.einsum("bd,dv->bv", last, params["lm_head"].astype(f32),
                      precision=hi)
