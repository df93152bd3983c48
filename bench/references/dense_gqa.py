"""Plain forward of a dense grouped-query decoder, for deciding `correct`.

Straightforward ``jax.numpy``, no caches, kernels or batching, and nothing
imported from the system under test.  It reads the weight tree the
benchmark made (``bench.weights``), in the layout the engine is handed.
Conventions, which the engine shares and the checkpoints of the published
models do not all have: the embedding is scaled by ``sqrt(d)``, RMSNorm
multiplies by ``1 + gain``, rope rotates halves, and query head ``h`` reads
kv head ``h // (heads / kv_heads)``.

``mode`` sets the arithmetic of every contraction:

* ``"highest"``: float32 operands at ``Precision.HIGHEST``, the reference;
* ``"bf16x3"``: three bf16 products (hi*hi + hi*lo + lo*hi), float32
  accumulation: the three-pass step below float32 at highest;
* ``"int8"``: symmetric int8 operands, per row of the left and per column
  of the right, int32 accumulation: the step below bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["hidden", "logits", "MODES"]

MODES = ("highest", "bf16x3", "int8")
_F32 = jnp.float32
_Q_BLOCK = 512


def mm(a, b, mode: str):
    """``a (..., m, k) @ b (..., k, n)`` in float32 under ``mode``."""
    a = a.astype(_F32)
    b = b.astype(_F32)
    if mode == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    if mode == "bf16x3":
        # the high word by reduce_precision: a compiler may fold the
        # round trip f32(bf16(x)) to x, which would zero the low word
        bf = jnp.bfloat16
        hi = lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                                mantissa_bits=7)
        a_hi, b_hi = hi(a), hi(b)
        a_lo, b_lo = (a - a_hi).astype(bf), (b - b_hi).astype(bf)
        a_hi, b_hi = a_hi.astype(bf), b_hi.astype(bf)
        dot = lambda x, y: jnp.matmul(x, y, preferred_element_type=_F32)
        return dot(a_lo, b_hi) + dot(a_hi, b_lo) + dot(a_hi, b_hi)
    if mode == "int8":
        sa = jnp.max(jnp.abs(a), axis=-1, keepdims=True) / 127.0
        sb = jnp.max(jnp.abs(b), axis=-2, keepdims=True) / 127.0
        sa = jnp.where(sa > 0, sa, 1.0)
        sb = jnp.where(sb > 0, sb, 1.0)
        qa = jnp.clip(jnp.rint(a / sa), -127, 127).astype(jnp.int8)
        qb = jnp.clip(jnp.rint(b / sb), -127, 127).astype(jnp.int8)
        acc = jnp.matmul(qa, qb, preferred_element_type=jnp.int32)
        return acc.astype(_F32) * sa * sb
    raise ValueError(f"unknown mode {mode!r}")


def _norm(x, gain, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + gain.astype(_F32))


def _rope(t, pos, theta):
    """``t (s, n, hd)`` at absolute positions ``pos (s,)``."""
    half = t.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    ang = pos.astype(_F32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], -1)


def _attention(q, k, v, mode):
    """Causal attention of ``q (s, h, hd)`` over ``k, v (s, kvh, hd)``,
    in blocks of queries so that the scores of a long sequence fit."""
    s, h, hd = q.shape
    kvh = k.shape[1]
    rep = h // kvh
    kt = k.transpose(1, 2, 0)                        # (kvh, hd, s)
    vt = v.transpose(1, 0, 2)                        # (kvh, s, hd)
    outs = []
    for q0 in range(0, s, _Q_BLOCK):
        qb = q[q0:q0 + _Q_BLOCK]
        n = qb.shape[0]
        qg = qb.reshape(n, kvh, rep, hd).transpose(1, 0, 2, 3) \
            .reshape(kvh, n * rep, hd)
        sc = mm(qg, kt, mode).reshape(kvh, n, rep, s) / hd ** 0.5
        rows = q0 + jnp.arange(n)[:, None, None]
        cols = jnp.arange(s)[None, None, :]
        sc = jnp.where((cols <= rows)[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1).reshape(kvh, n * rep, s)
        o = mm(p, vt, mode).reshape(kvh, n, rep, hd)
        outs.append(o.transpose(1, 0, 2, 3).reshape(n, h * hd))
    return jnp.concatenate(outs, axis=0)


def hidden(params, tokens, arch, mode: str = "highest"):
    """Final-normed hidden states ``(s, d)`` of ``tokens (s,)``."""
    s = tokens.shape[0]
    h, kvh, hd = arch.heads, arch.kv_heads, arch.head_dim
    pos = jnp.arange(s)

    def layer(x, p):
        m, f = p["mixer"], p["ffn"]
        a = _norm(x, p["norm1"], arch.eps)

        def proj(w, b):
            y = mm(a, w, mode)
            return y if b is None else y + b.astype(_F32)

        q = _rope(proj(m["wq"], m.get("bq")).reshape(s, h, hd), pos,
                  arch.rope_theta)
        k = _rope(proj(m["wk"], m.get("bk")).reshape(s, kvh, hd), pos,
                  arch.rope_theta)
        v = proj(m["wv"], m.get("bv")).reshape(s, kvh, hd)
        x = x + mm(_attention(q, k, v, mode), m["wo"], mode)
        a = _norm(x, p["norm2"], arch.eps)
        g = jax.nn.silu(mm(a, f["w_gate"], mode)) * mm(a, f["w_up"], mode)
        return x + mm(g, f["w_down"], mode), None

    x = params["embed"][tokens].astype(_F32) * arch.d ** 0.5
    x, _ = jax.lax.scan(layer, x, params["blocks"]["pos0"])
    return _norm(x, params["final_norm"], arch.eps)


def logits(params, hid, arch, mode: str = "highest"):
    """LM head over hidden rows ``(n, d)`` -> ``(n, vocab)``."""
    if arch.tied:
        return mm(hid, params["embed"].T, mode)
    return mm(hid, params["lm_head"], mode)
