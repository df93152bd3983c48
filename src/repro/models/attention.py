"""Attention mixers: GQA/MQA/MHA and DeepSeek-V2 MLA, with KV caches.

Memory discipline follows the paper's principle: the (sq, skv) score matrix
is never materialized at full size — training/prefill run a chunked online-
softmax (the XLA-compilable twin of ``kernels/flash_attention``; the Pallas
kernel is used on real TPUs), and causal masks are generated from their
structural rule (iota comparison) instead of being loaded.

Attention is a first-class TCEC site: every QK^T/PV (and MLA absorbed)
contraction resolves the ``"attn"`` policy from the active
``policy_scope`` and runs ``repro.tcec.einsum`` — ``bf16x3``/``bf16x6``
recover ~fp24/~fp32 accuracy on the matrix unit via the shared split
schedule, ``fp32_vpu`` runs plain fp32, and the plain bf16 policy keeps
the native matrix-unit fast path.  A policy with
``kernel == "pallas"`` additionally dispatches ``chunked_attention`` onto
the fused flash Pallas kernel, so one ``policy_scope("bf16x6_pallas")``
flips the whole hot path.  Prefill, decode and the kernel share one
schedule, so cached decode stays numerically consistent with prefill.

Cache layout: ``{"k": (b, S, kv_heads, hd), "v": ...}``; MLA caches the
*compressed* latent ``{"c_kv": (b, S, kv_lora), "k_rope": (b, S, rope_dim)}``
and decodes through the absorbed-projection path (matmul-chain restructuring:
no per-step K/V re-expansion).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tcec
from repro.configs.base import ArchConfig
from repro.core.context import resolve_policy
from repro.core.policy import TcecPolicy
from .base import PSpec, dense, rms_norm, rope_cos_sin, apply_rope, shard_hint

NEG_INF = -1e30


def _attn_einsum(eq: str, a: jnp.ndarray, b: jnp.ndarray,
                 pol: TcecPolicy) -> jnp.ndarray:
    """Deprecated: policy-routed attention einsum.  ``repro.tcec.einsum``
    is the same contract — ``"native"`` precision keeps the plain bf16 MXU
    policy on the matrix unit's native dtype while corrected policies and
    vpu run the shared TCEC split schedule, identical to the flash kernel's
    in-VREG arithmetic."""
    import warnings
    warnings.warn(
        "_attn_einsum is deprecated; use repro.tcec.einsum(eq, a, b, "
        "policy=pol) (or site=\"attn\")",
        DeprecationWarning, stacklevel=2)
    return tcec.einsum(eq, a, b, policy=pol)


def _plain(pol: TcecPolicy) -> bool:
    return pol.backend == "mxu" and not pol.error_correction


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (pure JAX, memory-bounded).
# ---------------------------------------------------------------------------

def chunked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                      causal: bool, q_chunk: int = 512,
                      kv_chunk: int = 1024, kv_len: Optional[int] = None,
                      policy: TcecPolicy | str | None = None) -> jnp.ndarray:
    """q (b, sq, h, d), k/v (b, skv, kvh, d) -> (b, sq, h, d).

    GQA: h % kvh == 0; kv heads are repeated logically via reshape (no copy
    materialized beyond the chunk).

    ``policy`` (default: the context's ``"attn"`` policy) selects the
    QK^T/PV precision; ``kernel == "pallas"`` dispatches to the fused flash
    kernel.  ``kv_len`` masks kv positions >= kv_len (right-padded
    cross-attention); fully-masked rows emit zeros.

    Causal self-attention (sq == skv) skips fully-masked (q, kv) chunk pairs
    entirely (a pair-list scan over the lower triangle) — ~2x fewer MXU
    passes and score tiles than the mask-everything loop (§Perf H1)."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    dv = v.shape[-1]
    rep = h // kvh
    scale = 1.0 / (d ** 0.5)
    pol = resolve_policy(policy, "attn")
    if pol.kernel == "pallas" and pol.backend == "mxu":
        # Kernel-backend dispatch (the attention analogue of base.dense's
        # Pallas routing): run the fused Mosaic kernel — native on TPU,
        # interpret mode elsewhere.  Lazy import + module attribute lookup
        # so tests can monkeypatch the kernel entry point.
        import importlib
        _fa = importlib.import_module("repro.kernels.flash_attention")
        # The flash kernel is a tuned site: repro.tune picks
        # (block_q, block_kv) from the staging-roofline model (kernel
        # defaults when REPRO_TUNE=off).
        from repro import tune
        tplan = tune.attention_plan(sq, skv, d, dv, policy=pol, b=b, h=h,
                                    causal=causal)
        blocks = {} if tplan is None else dict(block_q=tplan.block_q,
                                               block_k=tplan.block_kv)
        o = _fa.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), causal=causal, policy=pol,
            kv_len=kv_len, interpret=jax.default_backend() != "tpu",
            **blocks)
        return o.transpose(0, 2, 1, 3)
    from .base import largest_divisor_leq
    q_chunk = largest_divisor_leq(sq, q_chunk)
    kv_chunk = largest_divisor_leq(skv, kv_chunk)
    nq, nk = sq // q_chunk, skv // kv_chunk
    if causal and sq == skv and nq > 1 and kv_len is None:
        return _causal_pair_attention(q, k, v, q_chunk, kv_chunk, scale, pol)

    q = shard_hint(q, "batch", None, "heads", None)
    k = shard_hint(k, "batch", None, "kv", None)
    v = shard_hint(v, "batch", None, "kv", None)
    qc = shard_hint(q.reshape(b, nq, q_chunk, kvh, rep, d),
                    "batch", None, None, "kv", None, None)
    kc = shard_hint(k.reshape(b, nk, kv_chunk, kvh, d),
                    "batch", None, None, "kv", None)
    vc = shard_hint(v.reshape(b, nk, kv_chunk, kvh, dv),
                    "batch", None, None, "kv", None)

    def q_step(_, qi):
        q_blk, q_off = qi                                 # (b, qc, kvh, rep, d)
        q32 = q_blk

        def kv_step(carry, ki):
            m, l, acc = carry
            k_blk, v_blk, k_off = ki
            s = shard_hint(tcec.einsum("bqgrd,bkgd->bgrqk", q32, k_blk, site="attn", policy=pol),
                           "batch", "kv", None, None, None) * scale
            if causal or kv_len is not None:
                rows = q_off + jax.lax.broadcasted_iota(
                    jnp.int32, (q_chunk, kv_chunk), 0)
                cols = k_off + jax.lax.broadcasted_iota(
                    jnp.int32, (q_chunk, kv_chunk), 1)
                valid = jnp.ones((q_chunk, kv_chunk), bool)
                if kv_len is not None:
                    valid = valid & (cols < kv_len)
                if causal:
                    valid = valid & (rows >= cols)
                s = jnp.where(valid[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, -1))
            alpha = jnp.exp(m - m_new)
            # rows with no valid column yet (m_new == NEG_INF) must not
            # attend: exp(s - m_new) would be 1 at every masked position
            p = jnp.where((m_new > 0.5 * NEG_INF)[..., None],
                          jnp.exp(s - m_new[..., None]), 0.0)
            l_new = l * alpha + jnp.sum(p, -1)
            pv = tcec.einsum("bgrqk,bkgd->bgrqd", p, v_blk, site="attn", policy=pol)
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        init = (
            shard_hint(jnp.full((b, kvh, rep, q_chunk), NEG_INF, jnp.float32),
                       "batch", "kv", None, None),
            shard_hint(jnp.zeros((b, kvh, rep, q_chunk), jnp.float32),
                       "batch", "kv", None, None),
            shard_hint(jnp.zeros((b, kvh, rep, q_chunk, dv), jnp.float32),
                       "batch", "kv", None, None, None))
        k_offs = jnp.arange(nk, dtype=jnp.int32) * kv_chunk
        # checkpoint: probability tiles are recomputed in backward, not saved
        (m, l, acc), _ = jax.lax.scan(
            jax.checkpoint(kv_step), init,
            (kc.swapaxes(0, 1), vc.swapaxes(0, 1), k_offs))
        # fully-masked rows (l == 0): emit zeros, never divide by the
        # empty softmax sum
        out = jnp.where((l > 0.0)[..., None],
                        acc / jnp.maximum(l, 1e-30)[..., None],
                        0.0)                             # (b, g, r, qc, d)
        return None, out

    q_offs = jnp.arange(nq, dtype=jnp.int32) * q_chunk
    # Rematerialize per-q-chunk: the (q_chunk, kv_chunk) probability tiles are
    # recomputed in the backward pass (flash-attention-style), never saved.
    _, outs = jax.lax.scan(jax.checkpoint(q_step), None,
                           (qc.swapaxes(0, 1), q_offs))
    # outs: (nq, b, kvh, rep, q_chunk, dv) -> (b, sq, h, dv)
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)
    return out if not _plain(pol) else out.astype(q.dtype)


def _causal_pair_attention(q, k, v, q_chunk, kv_chunk, scale, pol):
    """Causal chunked attention visiting only lower-triangular chunk pairs.

    The (q_chunk_idx, kv_chunk_idx) pairs with kv_end <= q_end are enumerated
    in q-major order and scanned once; (m, l, acc) carries reset at each new
    q chunk and the finished q block is emitted on its last pair (§Perf H1:
    halves attention FLOPs + score-tile traffic vs masking everything).
    Score tiles stay fp32 in-register; probability tiles are written bf16
    (§Perf H2)."""
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    dv = v.shape[-1]
    rep = h // kvh
    nq, nk = sq // q_chunk, skv // kv_chunk

    q = shard_hint(q, "batch", None, "heads", None)
    k = shard_hint(k, "batch", None, "kv", None)
    v = shard_hint(v, "batch", None, "kv", None)
    qc = q.reshape(b, nq, q_chunk, kvh, rep, d).swapaxes(0, 1)
    kc = k.reshape(b, nk, kv_chunk, kvh, d).swapaxes(0, 1)
    vc = v.reshape(b, nk, kv_chunk, kvh, dv).swapaxes(0, 1)

    # static pair list: for q chunk i, kv chunks j with j*kv_chunk < (i+1)*q
    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if j * kv_chunk < (i + 1) * q_chunk]
    pi = jnp.asarray([p[0] for p in pairs], jnp.int32)
    pj = jnp.asarray([p[1] for p in pairs], jnp.int32)
    is_first = jnp.asarray(
        [idx == 0 or pairs[idx - 1][0] != p[0] for idx, p in enumerate(pairs)])
    is_last = jnp.asarray(
        [idx == len(pairs) - 1 or pairs[idx + 1][0] != p[0]
         for idx, p in enumerate(pairs)])

    def hint_c(x):
        return shard_hint(x, "batch", "kv", None, None) if x.ndim == 4 else \
            shard_hint(x, "batch", "kv", None, None, None)

    def pair_step(carry, xs):
        m, l, acc, outs = carry
        i, j, first, last = xs
        q_blk = jax.lax.dynamic_index_in_dim(qc, i, 0, keepdims=False)
        k_blk = jax.lax.dynamic_index_in_dim(kc, j, 0, keepdims=False)
        v_blk = jax.lax.dynamic_index_in_dim(vc, j, 0, keepdims=False)
        m = jnp.where(first, jnp.full_like(m, NEG_INF), m)
        l = jnp.where(first, jnp.zeros_like(l), l)
        acc = jnp.where(first, jnp.zeros_like(acc), acc)

        s = tcec.einsum("bqgrd,bkgd->bgrqk", q_blk, k_blk, site="attn", policy=pol) * scale
        rows = i * q_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (q_chunk, kv_chunk), 0)
        cols = j * kv_chunk + jax.lax.broadcasted_iota(
            jnp.int32, (q_chunk, kv_chunk), 1)
        s = jnp.where(rows[None, None, None] >= cols[None, None, None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, -1))
        alpha = jnp.exp(m - m_new)
        p = jnp.where((m_new > 0.5 * NEG_INF)[..., None],
                      jnp.exp(s - m_new[..., None]), 0.0)
        if _plain(pol):
            p = p.astype(jnp.bfloat16)       # bf16 probability tile (§Perf H2)
        l = l * alpha + jnp.sum(p, -1, dtype=jnp.float32)
        pv = tcec.einsum("bgrqk,bkgd->bgrqd", p, v_blk, site="attn", policy=pol)
        acc = acc * alpha[..., None] + pv
        m = m_new

        # write the running result for q chunk i; later pairs of the same i
        # overwrite it in place, so the final write is the complete block
        out_blk = jnp.where((l > 0.0)[..., None],
                            acc / jnp.maximum(l, 1e-30)[..., None], 0.0)
        outs = jax.lax.dynamic_update_index_in_dim(
            outs, out_blk.astype(outs.dtype), i, 0)
        return (m, l, acc, outs), None

    m0 = hint_c(jnp.full((b, kvh, rep, q_chunk), NEG_INF, jnp.float32))
    l0 = hint_c(jnp.zeros((b, kvh, rep, q_chunk), jnp.float32))
    acc0 = hint_c(jnp.zeros((b, kvh, rep, q_chunk, dv), jnp.float32))
    outs0 = jnp.zeros((nq, b, kvh, rep, q_chunk, dv),
                      q.dtype if _plain(pol) else jnp.float32)
    (_, _, _, outs), _ = jax.lax.scan(
        jax.checkpoint(pair_step), (m0, l0, acc0, outs0),
        (pi, pj, is_first, is_last))
    out = outs.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, dv)
    return out if not _plain(pol) else out.astype(q.dtype)


def mla_absorbed_attention(q_c: jnp.ndarray, q_rope: jnp.ndarray,
                           c_cache: jnp.ndarray, r_cache: jnp.ndarray,
                           valid: jnp.ndarray, scale: float,
                           policy: TcecPolicy | str | None = None
                           ) -> jnp.ndarray:
    """The MLA absorbed-decode attention core: ``softmax((q_c c^T + q_r r^T)
    * scale) c`` over the *compressed* latent cache.

    ONE implementation shared by contiguous decode (``mla_apply``) and the
    paged XLA twin (``repro.serving.paged_attention``), so paged-vs-
    contiguous parity is exact per policy by construction.  ``q_c (b, sq,
    h, lora)``, ``q_rope (b, sq, h, rope)``; ``c_cache (b, S, lora)``,
    ``r_cache (b, S, rope)``; ``valid`` broadcastable to ``(b, sq, S)``.
    Fully-masked rows emit zeros.  Returns ``o_c (b, sq, h, lora)`` —
    the caller applies ``W_uv``.
    """
    pol = resolve_policy(policy, "attn")
    s_nope = tcec.einsum("bqhl,bsl->bqhs", q_c, c_cache,
                         site="attn", policy=pol)
    s_rope = tcec.einsum("bqhr,bsr->bqhs", q_rope, r_cache,
                         site="attn", policy=pol)
    scores = (s_nope + s_rope) * scale
    scores = jnp.where(valid[:, :, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    # rows with no valid cache position degenerate to uniform — emit zeros
    probs = jnp.where(jnp.any(valid, -1)[:, :, None, None], probs, 0.0)
    return tcec.einsum("bqhs,bsl->bqhl", probs, c_cache,
                       site="attn", policy=pol)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                     cache_index: jnp.ndarray,
                     policy: TcecPolicy | str | None = None) -> jnp.ndarray:
    """One-token attention against a cache.

    q (b, 1, h, d); k/v_cache (b, S, kvh, d); positions > cache_index masked.
    QK/PV run the context-resolved ``"attn"`` policy's split schedule, so
    decode matches prefill numerics per policy.  A negative ``cache_index``
    (no valid positions) emits zeros.
    """
    b, _, h, d = q.shape
    _, S, kvh, _ = k_cache.shape
    rep = h // kvh
    scale = 1.0 / (d ** 0.5)
    pol = resolve_policy(policy, "attn")
    qh = shard_hint(q.reshape(b, kvh, rep, d), "batch", "kv", None, None)
    k_cache = shard_hint(k_cache, "batch", "seq", "kv", None)
    v_cache = shard_hint(v_cache, "batch", "seq", "kv", None)
    s = shard_hint(tcec.einsum("bgrd,bsgd->bgrs", qh, k_cache, site="attn", policy=pol) * scale,
                   "batch", "kv", None, "seq")
    valid = jnp.arange(S, dtype=jnp.int32)[None] <= cache_index[:, None]
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # fully-masked rows: softmax of all-NEG_INF degenerates to uniform —
    # emit zeros instead of averaging the (invalid) cache
    p = jnp.where(jnp.any(valid, -1)[:, None, None, None], p, 0.0)
    o = tcec.einsum("bgrs,bsgd->bgrd", p, v_cache, site="attn", policy=pol)
    o = o.reshape(b, 1, h, d)
    return o if not _plain(pol) else o.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA block
# ---------------------------------------------------------------------------

def gqa_params(cfg: ArchConfig) -> Dict[str, PSpec]:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    dt = cfg.param_dtype
    p = {
        "wq": PSpec((d, h * hd), ("embed", "heads"), dt),
        "wk": PSpec((d, kvh * hd), ("embed", "kv"), dt),
        "wv": PSpec((d, kvh * hd), ("embed", "kv"), dt),
        "wo": PSpec((h * hd, d), ("heads", "embed"), dt),
    }
    if cfg.qkv_bias:
        p.update({
            "bq": PSpec((h * hd,), ("heads",), dt, init="zeros"),
            "bk": PSpec((kvh * hd,), ("kv",), dt, init="zeros"),
            "bv": PSpec((kvh * hd,), ("kv",), dt, init="zeros"),
        })
    return p


def gqa_apply(p, x: jnp.ndarray, cfg: ArchConfig, positions: jnp.ndarray,
              cache: Optional[Dict] = None,
              cache_index: Optional[jnp.ndarray] = None,
              causal: bool = True,
              kv_source: Optional[jnp.ndarray] = None,
              is_cross: bool = False,
              emit_kv: bool = False,
              kv_len: Optional[int] = None,
              block_table: Optional[jnp.ndarray] = None,
              seq_lens: Optional[jnp.ndarray] = None,
              layer=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """GQA attention. cache given -> decode (x is (b, 1, d)), returns updated
    cache.  is_cross: cross-attention (kv from kv_source at prefill, from the
    precomputed cache at decode; no rope).  kv_len masks right-padded
    kv_source positions; fully-masked query rows attend to nothing (zeros).

    A *paged* cache (``{"k_pages", "v_pages"}`` page pools, see
    ``repro.serving``) decodes through the block table: the new K/V are
    appended at each request's ``seq_lens`` position and attention gathers
    pages via ``paged_decode_attention`` (s == 1) or the chunked-prefill
    path (s > 1), at the same ``"attn"``-site policy as the dense path.
    The pools arrive whole, stacked over layer groups ``(g, P, page, kvh,
    w)`` (rows lane-padded to ``w >= d``), with this layer's index
    ``layer``: the append writes that layer in place and attention reads it
    there, and the updated stacked pools are returned (``None``:
    single-layer pools ``(P, page, kvh, w)``)."""
    b, s, d = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    pol = "attn"
    q = shard_hint(dense(x, p["wq"], pol, p.get("bq")).reshape(b, s, h, hd),
                   "batch", None, "heads", None)

    if is_cross:
        if cache is not None:   # decode against precomputed source KV
            S = cache["k"].shape[1]
            o = decode_attention(q, cache["k"], cache["v"],
                                 jnp.full((b,), S - 1, jnp.int32))
            new_cache = cache
        else:                   # train / prefill: KV from encoder states
            skv = kv_source.shape[1]
            k = dense(kv_source, p["wk"], pol, p.get("bk")).reshape(b, skv, kvh, hd)
            v = dense(kv_source, p["wv"], pol, p.get("bv")).reshape(b, skv, kvh, hd)
            o = chunked_attention(q, k, v, causal=False, kv_len=kv_len)
            new_cache = {"k": k, "v": v}
        y = dense(o.reshape(b, s, h * hd), p["wo"], pol)
        return y.astype(x.dtype), new_cache

    k = shard_hint(dense(x, p["wk"], pol, p.get("bk")).reshape(b, s, kvh, hd),
                   "batch", None, "kv", None)
    v = shard_hint(dense(x, p["wv"], pol, p.get("bv")).reshape(b, s, kvh, hd),
                   "batch", None, "kv", None)
    cos, sin = rope_cos_sin(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if cache is not None and "k_pages" in cache:
        # paged decode / chunked prefill: append to the page pools, gather
        # through the block table (lazy import: serving depends on models)
        from repro.serving import paged_cache as _pc
        from repro.serving import paged_attention as _pa
        # pool sharding: kv heads over "model" (when divisible), layer,
        # page and offset axes never — under a mesh the constraint keeps
        # GSPMD from re-replicating the appended pool across the model axis
        # mid-step (matches parallel.sharding.paged_cache_pspecs).
        k_scales = v_scales = None
        if "k_scales" in cache:
            # quantized pools: int8 payload + per-page fp32 scale sidecar
            kp, k_scales = _pc.append_pages(cache["k_pages"], k, block_table,
                                            seq_lens,
                                            scales=cache["k_scales"],
                                            layer=layer)
            vp, v_scales = _pc.append_pages(cache["v_pages"], v, block_table,
                                            seq_lens,
                                            scales=cache["v_scales"],
                                            layer=layer)
        else:
            kp = _pc.append_pages(cache["k_pages"], k, block_table, seq_lens,
                                  layer=layer)
            vp = _pc.append_pages(cache["v_pages"], v, block_table, seq_lens,
                                  layer=layer)
        lead = (None,) * (kp.ndim - 3)
        k_pages = shard_hint(kp, *lead, None, "kv", None)
        v_pages = shard_hint(vp, *lead, None, "kv", None)
        if s == 1:
            o = _pa.paged_decode_attention(
                q[:, 0], k_pages, v_pages, block_table,
                seq_lens.astype(jnp.int32) + 1,
                k_scales=k_scales, v_scales=v_scales, layer=layer)[:, None]
        else:
            row_pos = seq_lens[:, None].astype(jnp.int32) \
                + jnp.arange(s, dtype=jnp.int32)[None]
            o = _pa.paged_prefill_attention(q, k_pages, v_pages,
                                            block_table, row_pos,
                                            k_scales=k_scales,
                                            v_scales=v_scales, layer=layer)
        o = shard_hint(o, "batch", None, "heads", None)
        y = dense(o.reshape(b, s, h * hd), p["wo"], pol)
        new_cache = {"k_pages": k_pages, "v_pages": v_pages}
        if k_scales is not None:
            new_cache["k_scales"] = k_scales
            new_cache["v_scales"] = v_scales
        return y.astype(x.dtype), new_cache

    if cache is not None:
        # decode: insert k/v at cache_index, attend against full cache
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), cache_index, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), cache_index, axis=1)
        idx = jnp.full((b,), cache_index, jnp.int32)
        o = decode_attention(q, k_cache, v_cache, idx)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        o = chunked_attention(q, k, v, causal=causal)
        new_cache = {"k": k, "v": v} if emit_kv else None
    y = dense(o.reshape(b, s, h * hd), p["wo"], pol)
    return y.astype(x.dtype), new_cache


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V2) block
# ---------------------------------------------------------------------------

def mla_params(cfg: ArchConfig) -> Dict[str, PSpec]:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = cfg.param_dtype
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    p = {
        "wkv_a": PSpec((d, m.kv_lora_rank + m.qk_rope_head_dim), ("embed", None), dt),
        "kv_norm": PSpec((m.kv_lora_rank,), (None,), dt, init="zeros"),
        "wkv_b": PSpec((m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)),
                       (None, "heads"), dt),
        "wo": PSpec((h * m.v_head_dim, d), ("heads", "embed"), dt),
    }
    if m.q_lora_rank:
        p["wq_a"] = PSpec((d, m.q_lora_rank), ("embed", None), dt)
        p["q_norm"] = PSpec((m.q_lora_rank,), (None,), dt, init="zeros")
        p["wq_b"] = PSpec((m.q_lora_rank, h * qk), (None, "heads"), dt)
    else:
        p["wq"] = PSpec((d, h * qk), ("embed", "heads"), dt)
    return p


def _mla_q(p, x, cfg):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    pol = "attn"
    if m.q_lora_rank:
        cq = rms_norm(dense(x, p["wq_a"], pol), p["q_norm"], cfg.norm_eps)
        q = dense(cq, p["wq_b"], pol)
    else:
        q = dense(x, p["wq"], pol)
    q = q.reshape(b, s, h, qk)
    return q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]


def mla_apply(p, x: jnp.ndarray, cfg: ArchConfig, positions: jnp.ndarray,
              cache: Optional[Dict] = None,
              cache_index: Optional[jnp.ndarray] = None,
              causal: bool = True, kv_source=None,
              block_table: Optional[jnp.ndarray] = None,
              seq_lens: Optional[jnp.ndarray] = None,
              layer=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """MLA attention; a paged cache (``{"c_pages", "r_pages"}``) follows
    ``gqa_apply``'s contract: pools stacked over layer groups with this
    layer's index ``layer``, written and read in place."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    pol = "attn"
    apol = resolve_policy(None, "attn")   # attn-site policy for the absorbed
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q_nope, q_rope = _mla_q(p, x, cfg)
    cos, sin = rope_cos_sin(positions, rope_d, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)

    kv_a = dense(x, p["wkv_a"], pol)                      # (b, s, lora+rope)
    c_kv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(kv_a[..., None, m.kv_lora_rank:], cos, sin)[:, :, 0]

    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, h, nope + vd)
    w_uk = wkv_b[..., :nope]                              # (lora, h, nope)
    w_uv = wkv_b[..., nope:]                              # (lora, h, vd)

    scale = 1.0 / ((nope + rope_d) ** 0.5)

    if cache is not None and "c_pages" in cache:
        # --- paged absorbed decode: latent cache lives in page pools ---
        from repro.serving import paged_cache as _pc
        from repro.serving import paged_attention as _pa
        c_scales = r_scales = None
        if "c_scales" in cache:
            c_pages, c_scales = _pc.append_pages(
                cache["c_pages"], c_kv, block_table, seq_lens,
                scales=cache["c_scales"], layer=layer)
            r_pages, r_scales = _pc.append_pages(
                cache["r_pages"], k_rope, block_table, seq_lens,
                scales=cache["r_scales"], layer=layer)
        else:
            c_pages = _pc.append_pages(cache["c_pages"], c_kv, block_table,
                                       seq_lens, layer=layer)
            r_pages = _pc.append_pages(cache["r_pages"], k_rope, block_table,
                                       seq_lens, layer=layer)
        q_c = tcec.einsum("bqhn,lhn->bqhl", q_nope, w_uk,
                          site="attn", policy=apol)
        if s == 1:
            o_c = _pa.paged_mla_decode_attention(
                q_c[:, 0], q_rope[:, 0], c_pages, r_pages, block_table,
                seq_lens.astype(jnp.int32) + 1, scale=scale,
                policy=apol, c_scales=c_scales, r_scales=r_scales,
                layer=layer)[:, None]
        else:                                   # chunked prefill
            row_pos = seq_lens[:, None].astype(jnp.int32) \
                + jnp.arange(s, dtype=jnp.int32)[None]
            c = _pc.gather_pages(c_pages, block_table, scales=c_scales,
                                 layer=layer, width=c_kv.shape[-1])
            r = _pc.gather_pages(r_pages, block_table, scales=r_scales,
                                 layer=layer, width=k_rope.shape[-1])
            valid = jnp.arange(c.shape[1], dtype=jnp.int32)[None, None] \
                <= row_pos[..., None]
            o_c = mla_absorbed_attention(q_c, q_rope, c, r, valid, scale,
                                         apol)
        o = shard_hint(
            tcec.einsum("bqhl,lhv->bqhv", o_c, w_uv, site="attn", policy=apol),
            "batch", None, "heads", None)
        y = dense(o.reshape(b, s, h * vd).astype(x.dtype), p["wo"], pol)
        new_cache = {"c_pages": c_pages, "r_pages": r_pages}
        if c_scales is not None:
            new_cache["c_scales"] = c_scales
            new_cache["r_scales"] = r_scales
        return y.astype(x.dtype), new_cache

    if cache is not None:
        # --- absorbed decode: never re-expand K/V from the latent cache ---
        c_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), cache_index, axis=1)
        r_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), cache_index, axis=1)
        S = c_cache.shape[1]
        # absorb W_uk into q: q_c (b, 1, h, lora) — the whole absorbed chain
        # runs the attn-site split schedule (the shared core) so decode
        # matches prefill AND the paged twin bit-for-bit per policy
        q_c = tcec.einsum("bqhn,lhn->bqhl", q_nope, w_uk,
                          site="attn", policy=apol)
        # emit zeros for rows with no valid cache position (cache_index < 0)
        valid = (jnp.arange(S, dtype=jnp.int32)[None, None]
                 <= cache_index)                 # (1, 1, S) or (b, 1, S)
        o_c = mla_absorbed_attention(q_c, q_rope, c_cache, r_cache, valid,
                                     scale, apol)
        o = tcec.einsum("bqhl,lhv->bqhv", o_c, w_uv, site="attn", policy=apol)
        y = dense(o.reshape(b, 1, h * vd).astype(x.dtype), p["wo"], pol)
        return y.astype(x.dtype), {"c_kv": c_cache, "k_rope": r_cache}

    # --- train/prefill: expand K/V, chunked attention ---
    # expansion precision follows the attn policy (fp32 words under
    # corrected policies keep prefill consistent with absorbed decode)
    kv_dt = x.dtype if _plain(apol) else jnp.float32
    k_nope = tcec.einsum("bsl,lhn->bshn", c_kv, w_uk, site="attn", policy=apol).astype(kv_dt)
    v = tcec.einsum("bsl,lhv->bshv", c_kv, w_uv, site="attn", policy=apol).astype(kv_dt)
    k_rope_b = jnp.broadcast_to(k_rope[:, :, None, :], (b, s, h, rope_d))
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_full = jnp.concatenate([k_nope, k_rope_b.astype(kv_dt)], axis=-1)
    o = chunked_attention(q_full, k_full, v, causal=causal)
    y = dense(o.reshape(b, s, h * vd), p["wo"], pol)
    new_cache = {"c_kv": c_kv, "k_rope": k_rope}
    return y.astype(x.dtype), new_cache
