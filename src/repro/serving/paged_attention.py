"""Paged decode attention — block-table gathers inside the kernel body,
QK^T/PV on the shared TCEC split schedule.

Decode attention is the extreme memory-bound case of the paper's thesis:
per generated token the kernel streams the whole KV cache once and does two
rank-1-ish contractions, so the win comes from *not staging* dead cache.
The Pallas kernel therefore never materializes the gathered cache: the
block table rides as a scalar-prefetch operand and the kv ``BlockSpec``
index map resolves ``block_table[b, j]`` per grid step, DMA-ing exactly the
pages a request owns.  The model step hands every consumer the whole pool
stacked over layer groups, ``(g, P, page, *tail)``, with the layer index:
the kernel takes the index as one more scalar-prefetch value and its index
map picks ``(layer, block_table[b, j])``, so no layer's pool is sliced out
(a custom-call operand would make XLA copy it out first).  Softmax runs
online with ``(m, l, acc)`` scratch carried across the page axis, and the
length mask is generated from its structural rule (``col < seq_len`` iota
comparison) — the same ``foreach_ij`` discipline as the flash kernel.

Both contractions run ``tcec_core.policy_dot``: the policy resolved from
the ``"attn"`` site selects fp32-VPU, plain bf16, or the bf16x3/bf16x6
split schedules, identically to prefill.  The XLA twin gathers the pages
(``gather_pages``) and calls the *same contiguous implementations*
(``models.attention.decode_attention`` / ``mla_absorbed_attention``), so
paged-vs-contiguous parity is exact by construction per policy.

GQA decode and MLA absorbed decode share one kernel: MLA is the
``kvh == 1`` instance whose score is the sum of a latent (``c_kv``) and a
rope (``k_rope``) contraction — the kernel takes an optional second
(q2, k2) operand pair added into the score before the online softmax.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.context import resolve_policy
from repro.core.policy import TcecPolicy
from repro.kernels.tcec_core import compiler_params, dot_params, policy_dot
from repro import tcec
from .paged_cache import gather_pages

__all__ = [
    "paged_decode_attention", "paged_decode_attention_pallas",
    "paged_decode_attention_xla", "paged_mla_decode_attention",
    "paged_prefill_attention",
]

NEG_INF = -1e30

# q (rep, d) x k (page, d) -> s (rep, page): contract d on both.
_QK_DN = (((1,), (1,)), ((), ()))
# p (rep, page) x v (page, dv) -> o (rep, dv).
_PV_DN = (((1,), (0,)), ((), ()))


def _corrected(pol: TcecPolicy) -> bool:
    return pol.error_correction or pol.backend == "vpu"


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _paged_kernel(bt_ref, sl_ref, _layer_ref, *refs, page, npages, kvh,
                  scale, dot_kw, has_rope, quantized, heads):
    refs = list(refs)
    ks_ref = vs_ref = k2s_ref = None
    if quantized:
        # per-(request, logical page) scales, scalar-prefetched into SMEM
        ks_ref, vs_ref = refs[:2]
        refs = refs[2:]
        if has_rope:
            k2s_ref, refs = refs[0], refs[1:]
    q_ref, k_ref, v_ref = refs[:3]
    refs = refs[3:]
    q2_ref = k2_ref = None
    if has_rope:
        q2_ref, k2_ref = refs[:2]
        refs = refs[2:]
    o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    ji = pl.program_id(1)

    @pl.when(ji == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = sl_ref[bi]

    def rows(ref, g, width):                         # (page, width) of head g
        row = ref[0, :, g, :width] if heads else ref[0, :, :width]
        return row.astype(jnp.float32)

    # One block holds every kv head of the page (the pool's trailing
    # (kvh, lane-padded d) dims are whole, which is what lets Mosaic tile
    # the DMA); the heads are a static loop over the leading scratch axis,
    # each reading its rows as written, without their lane padding.  A
    # headless pool (MLA's latent pages) is the one-head case.
    d, d2 = q_ref.shape[-1], None if q2_ref is None else q2_ref.shape[-1]
    for g in range(kvh):
        q = q_ref[0, g].astype(jnp.float32)          # (rep, d)
        k = rows(k_ref, g, d)                        # (page, d)
        v = rows(v_ref, g, d)                        # (page, d)
        if quantized:
            # int8 page payloads: dequantize at this page's scale right
            # after the page DMA — the gather twin multiplies the same
            # factor.
            k = k * ks_ref[bi, ji]
            v = v * vs_ref[bi, ji]

        # QK^T at policy-selected precision (split words live in VREGs).
        s = policy_dot(q, k, _QK_DN, **dot_kw)
        if has_rope:
            q2 = q2_ref[0, g].astype(jnp.float32)    # (rep, d2)
            k2 = rows(k2_ref, g, d2)                 # (page, d2)
            if quantized:
                k2 = k2 * k2s_ref[bi, ji]
            s = s + policy_dot(q2, k2, _QK_DN, **dot_kw)
        s = s * scale

        # Structural-rule length mask: col = absolute kv position.
        cols = ji * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < seq_len, s, NEG_INF)

        m_prev = m_ref[g]                            # (rep, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # Rows with no valid column yet (m_new == NEG_INF) must contribute
        # nothing: exp(s - m_new) would be 1 at every masked position.
        p = jnp.where(m_new > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        l_ref[g] = l_ref[g] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_ref[g] = acc_ref[g] * alpha + policy_dot(p, v, _PV_DN, **dot_kw)
        m_ref[g] = m_new

    @pl.when(ji == npages - 1)
    def _done():
        l = l_ref[...]
        # Fully-masked rows (seq_len == 0) emit exact zeros, not 0/0.
        o_ref[0] = jnp.where(
            l > 0.0, acc_ref[...] / jnp.where(l > 0.0, l, 1.0), 0.0)


@functools.partial(
    jax.jit, static_argnames=("policy", "scale", "interpret"))
def _paged_pallas(q, k_pages, v_pages, q2, k2_pages, block_table, seq_lens,
                  layer, policy: TcecPolicy, scale: float, interpret: bool,
                  k_scales=None, v_scales=None, k2_scales=None):
    b, kvh, rep, d = q.shape
    page = k_pages.shape[2]
    npages = block_table.shape[1]
    has_rope = q2 is not None
    quantized = k_scales is not None
    heads = k_pages.ndim == 5                    # (g, P, page, kvh, w)
    block_table = block_table.astype(jnp.int32)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    # Grid (request, logical page): the page axis is innermost and
    # 'arbitrary' so the (m, l, acc) scratch carries across a request's
    # pages.  Each step DMAs one physical page of the prefetched layer
    # with all its kv heads (GQA: h = kvh * rep, no repeated-head copies);
    # the stacked pool's layer axis is squeezed out of the block.
    def kv_map(b_, j, bt, _sl, ly, *_):
        return (ly[0], bt[b_, j]) + (0,) * (k_pages.ndim - 2)

    def q_map(b_, j, *_):
        del j
        return (b_, 0, 0, 0)

    def kv_spec(pool):
        return pl.BlockSpec((pl.squeezed, 1) + pool.shape[2:], kv_map)

    prefetch = [block_table, seq_lens.astype(jnp.int32), layer]
    if quantized:
        # Per-page scales gathered to the block table's (b, npages) shape
        # ride in SMEM beside it: one scalar per grid step, no VMEM tile.
        prefetch += [k_scales[layer[0], block_table],
                     v_scales[layer[0], block_table]]
        if has_rope:
            prefetch += [k2_scales[layer[0], block_table]]
    in_specs = [
        pl.BlockSpec((1, kvh, rep, d), q_map),
        kv_spec(k_pages),
        kv_spec(v_pages),
    ]
    operands = [q, k_pages, v_pages]
    if has_rope:
        d2 = q2.shape[-1]
        in_specs += [
            pl.BlockSpec((1, kvh, rep, d2), q_map),
            kv_spec(k2_pages),
        ]
        operands += [q2, k2_pages]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(b, npages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kvh, rep, d), q_map),
        scratch_shapes=[
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, 1), jnp.float32),
            pltpu.VMEM((kvh, rep, d), jnp.float32),
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, page=page, npages=npages, kvh=kvh,
                          scale=scale, dot_kw=dot_params(policy),
                          has_rope=has_rope, quantized=quantized,
                          heads=heads),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, rep, d), jnp.float32),
        compiler_params=compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(*prefetch, *operands)


def paged_decode_attention_pallas(q, k_pages, v_pages, block_table, seq_lens,
                                  *, scale: Optional[float] = None,
                                  policy: TcecPolicy | str | None = None,
                                  interpret: Optional[bool] = None,
                                  q2=None, k2_pages=None,
                                  k_scales=None, v_scales=None,
                                  k2_scales=None, layer=None) -> jnp.ndarray:
    """Fused paged decode attention (one query token per request).

    q ``(b, h, d)``; ``k_pages``/``v_pages (P, page, kvh, w)``, rows of
    width ``w >= d`` of which the first ``d`` are read (the rest is the
    pool's lane padding, ``paged_cache.lane_width``); ``block_table (b,
    npages)``; ``seq_lens (b,)`` — request
    ``i`` attends to its first ``seq_lens[i]`` logical positions; a zero
    length emits zeros.  ``(q2, k2_pages)`` is the optional second score
    operand pair (MLA's rope term, added before the online softmax).
    ``k_scales``/``v_scales``/``k2_scales`` ``(P,)`` fp32 mark int8 pools:
    each page dequantizes at its own scale right after its DMA (int8 page
    reads stream half the bytes of bf16, a quarter of fp32).
    ``layer`` marks every pool stacked over layer groups, ``(g, P, page,
    kvh, d)`` with scales ``(g, P)``: the kernel takes the whole stacked
    pool as its operand and reads layer ``layer`` (prefetched beside the
    block table) through its index map.  Pools one axis short have no
    kv-head axis, ``(P, page, w)``: MLA's latent pages, one kv head.
    Returns ``(b, h, d)`` fp32 for corrected/vpu policies, ``q.dtype``
    for the plain bf16 policy (the framework-wide dtype contract).
    """
    pol = resolve_policy(policy, "attn")
    b, h, d = q.shape
    heads = k_pages.ndim == (4 if layer is None else 5)
    if layer is None:                    # one layer: a stack of one
        k_pages, v_pages = k_pages[None], v_pages[None]
        k2_pages = None if k2_pages is None else k2_pages[None]
        k_scales, v_scales, k2_scales = (
            None if t is None else t[None]
            for t in (k_scales, v_scales, k2_scales))
        layer = 0
    kvh = k_pages.shape[3] if heads else 1
    if h % kvh:
        raise ValueError(f"q heads {h} not a multiple of kv heads {kvh}")
    rep = h // kvh
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    qh = q.reshape(b, kvh, rep, d)
    q2h = None if q2 is None else q2.reshape(b, kvh, rep, q2.shape[-1])
    out = _paged_pallas(qh, k_pages, v_pages, q2h, k2_pages,
                        block_table, seq_lens, layer, pol, float(scale),
                        bool(interpret), k_scales=k_scales,
                        v_scales=v_scales, k2_scales=k2_scales)
    out = out.reshape(b, h, d)
    return out if _corrected(pol) else out.astype(q.dtype)


# ---------------------------------------------------------------------------
# XLA twin + dispatch
# ---------------------------------------------------------------------------

def paged_decode_attention_xla(q, k_pages, v_pages, block_table, seq_lens,
                               *, policy: TcecPolicy | str | None = None,
                               k_scales=None, v_scales=None,
                               layer=None) -> jnp.ndarray:
    """XLA twin: gather the block table's pages and run the *contiguous*
    ``decode_attention`` on the virtual cache — identical arithmetic to the
    dense decode path by construction (parity is exact per policy).
    Quantized pools (``k_scales``/``v_scales`` given) dequantize during the
    gather, so the kernel and twin see identical fp32 page values.
    ``layer`` marks the pools stacked over layer groups (``gather_pages``
    reads that layer's pages in one gather)."""
    from repro.models.attention import decode_attention
    pol = resolve_policy(policy, "attn")
    d = q.shape[-1]
    kv = gather_pages(k_pages, block_table, scales=k_scales, layer=layer,
                      width=d)                        # (b, Sv, kvh, d)
    vv = gather_pages(v_pages, block_table, scales=v_scales, layer=layer,
                      width=d)
    o = decode_attention(q[:, None], kv, vv,
                         seq_lens.astype(jnp.int32) - 1, policy=pol)
    return o[:, 0]


def paged_decode_attention(q, k_pages, v_pages, block_table, seq_lens,
                           *, policy: TcecPolicy | str | None = None,
                           interpret: Optional[bool] = None,
                           k_scales=None, v_scales=None,
                           layer=None) -> jnp.ndarray:
    """Policy-dispatching paged decode attention (GQA/MHA).

    Resolves the ``"attn"`` site from the active ``policy_scope``: a policy
    with ``kernel == "pallas"`` runs the fused Mosaic kernel (native on
    TPU, interpret elsewhere), anything else the gather-based XLA twin.
    ``layer`` marks the pools stacked over layer groups; both paths read
    that layer in place.
    """
    pol = resolve_policy(policy, "attn")
    if pol.kernel == "pallas" and pol.backend == "mxu":
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, block_table, seq_lens, policy=pol,
            interpret=interpret, k_scales=k_scales, v_scales=v_scales,
            layer=layer)
    return paged_decode_attention_xla(q, k_pages, v_pages, block_table,
                                      seq_lens, policy=pol,
                                      k_scales=k_scales, v_scales=v_scales,
                                      layer=layer)


def paged_mla_decode_attention(q_c, q_rope, c_pages, r_pages, block_table,
                               seq_lens, *, scale: float,
                               policy: TcecPolicy | str | None = None,
                               interpret: Optional[bool] = None,
                               c_scales=None, r_scales=None,
                               layer=None) -> jnp.ndarray:
    """Paged MLA absorbed decode: ``softmax(q_c c^T + q_r r^T) c``.

    ``q_c (b, h, lora)``, ``q_rope (b, h, rope)``; ``c_pages (P, page,
    >= lora)``, ``r_pages (P, page, >= rope)`` (rows lane-padded, see
    ``paged_decode_attention_pallas``) hold the *compressed* latent cache
    (never re-expanded — the absorbed matmul-chain restructuring).  Returns
    ``o_c (b, h, lora)``; the caller applies ``W_uv``.  The Pallas path is
    the GQA kernel at ``kvh == 1`` with the rope term as the second score
    operand; the XLA twin calls the same ``mla_absorbed_attention`` core the
    contiguous decode path runs, so parity is exact per policy.
    ``c_scales``/``r_scales`` ``(P,)`` mark quantized latent pools (the
    latent page serves as both K and V, so its scale applies to both).
    ``layer`` marks the pools stacked over layer groups, ``(g, P, page,
    lora)``; the kernel and the twin read that layer in place.
    """
    pol = resolve_policy(policy, "attn")
    if pol.kernel == "pallas" and pol.backend == "mxu":
        return paged_decode_attention_pallas(
            q_c, c_pages, c_pages, block_table,
            seq_lens, scale=scale, policy=pol, interpret=interpret,
            q2=q_rope, k2_pages=r_pages, layer=layer,
            k_scales=c_scales, v_scales=c_scales, k2_scales=r_scales)
    from repro.models.attention import mla_absorbed_attention
    c = gather_pages(c_pages, block_table, scales=c_scales, layer=layer,
                     width=q_c.shape[-1])              # (b, Sv, lora)
    r = gather_pages(r_pages, block_table, scales=r_scales, layer=layer,
                     width=q_rope.shape[-1])
    sv = c.shape[1]
    valid = jnp.arange(sv, dtype=jnp.int32)[None, None] \
        < seq_lens.astype(jnp.int32)[:, None, None]       # (b, 1, Sv)
    o = mla_absorbed_attention(q_c[:, None], q_rope[:, None], c, r, valid,
                               scale, pol)
    return o[:, 0]


def paged_prefill_attention(q, k_pages, v_pages, block_table, row_pos,
                            *, policy: TcecPolicy | str | None = None,
                            k_scales=None, v_scales=None,
                            layer=None) -> jnp.ndarray:
    """Chunked-prefill attention against a paged cache (XLA).

    ``q (b, s, h, d)`` is a prompt chunk whose tokens sit at absolute
    positions ``row_pos (b, s)``; their K/V must already be appended to the
    pools.  Each row attends causally to every cache position ``<= row_pos``
    (prefix + intra-chunk causal in one mask).  ``layer`` marks the pools
    stacked over layer groups (read in place by ``gather_pages``).
    Returns ``(b, s, h, dv)``.
    """
    pol = resolve_policy(policy, "attn")
    b, sq, h, d = q.shape
    kv = gather_pages(k_pages, block_table, scales=k_scales, layer=layer,
                      width=d)                        # (b, Sv, kvh, d)
    vv = gather_pages(v_pages, block_table, scales=v_scales, layer=layer,
                      width=d)
    kvh = kv.shape[2]
    rep = h // kvh
    sv = kv.shape[1]
    scale = 1.0 / (d ** 0.5)
    qh = q.reshape(b, sq, kvh, rep, d)
    s = tcec.einsum("bqgrd,bsgd->bgrqs", qh, kv, site="attn",
                    policy=pol) * scale
    valid = jnp.arange(sv, dtype=jnp.int32)[None, None] \
        <= row_pos.astype(jnp.int32)[..., None]           # (b, sq, Sv)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.any(valid, -1)[:, None, None, :, None], p, 0.0)
    o = tcec.einsum("bgrqs,bsgd->bgrqd", p, vv, site="attn", policy=pol)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, vv.shape[-1])
    return o if _corrected(pol) else o.astype(q.dtype)
