"""Paged KV-cache primitives: fixed-size pages in a shared pool, addressed
through per-request block tables.

A dense decode cache leaf is ``(b, S, *tail)`` with the sequence on axis 1
(the layout contract of ``models/attention.py``).  Its paged twin drops the
batch/sequence axes for a shared pool ``(num_pages, page_size, *tail)``;
a request owns an ordered list of physical page ids (its *block table*
row), and logical position ``t`` of request ``i`` lives at
``pool[block_table[i, t // page_size], t % page_size]``.  A pool's last
axis may be wider than the rows written into it (``lane_width``): appends
pad a row with zeros and readers take its first ``width`` entries.

Everything here is a pure function on arrays (jit-friendly); ownership and
free-list bookkeeping are the scheduler's job (``repro.serving.scheduler``).
Physical page ``NULL_PAGE`` (= 0) is reserved as a scratch page: inactive
block-table slots point at it, so speculative writes from idle decode lanes
land somewhere harmless instead of corrupting live pages.
"""
from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp

from repro.core.quant import TINY, quantize_q

#: Reserved scratch page.  The allocator never hands it out; block-table
#: entries of unallocated/finished slots point here.
NULL_PAGE = 0

#: Lane width of a TPU vector register: the minor tile of every layout.
LANES = 128


def lane_width(width: int) -> int:
    """A pool row's stored width: ``width`` rounded up to whole lane tiles.

    A TPU lays out an array whose last axis is narrower than a lane tile
    with another axis minor (the page axis, for a page pool), which
    neither a per-row scatter nor the paged kernel's page blocks can use
    as it stands: every step would copy the pools into a row-major layout
    and back.  Rows of whole lane tiles get the row-major layout by
    default, the layout the row-major tiles of a narrower row occupy
    anyway, so padding costs no memory on the chip.  (A pinned
    non-default layout would do the same, but JAX's persistent
    compilation cache loses pinned layouts.)"""
    return -(-width // LANES) * LANES


def _pad_rows(x: jnp.ndarray, width: int) -> jnp.ndarray:
    """Zero-pad the last axis of ``x`` to ``width``."""
    pad = width - x.shape[-1]
    if pad == 0:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)])


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pages required to hold ``n_tokens`` positions."""
    return -(-n_tokens // page_size)


def init_pool(num_pages: int, page_size: int, tail: Tuple[int, ...],
              dtype, sharding=None, quantized: bool = False) -> jnp.ndarray:
    """Zero page pool ``(num_pages, page_size, *tail)``.

    ``sharding`` (an optional ``NamedSharding``) places the pool on a
    device mesh.  The pool-sharding contract: only *tail* axes (kv heads)
    may shard — the page axis and in-page offset never do, because any
    device must be able to resolve any physical page id a block table
    names (``repro.parallel.sharding.paged_cache_pspecs`` encodes this).

    ``quantized=True`` makes the payload int8 (``dtype`` is ignored): page
    values are symmetric int8 at a per-page fp32 scale kept in the parallel
    ``init_page_scales`` sidecar, so page ids, block tables, COW and the
    sharding contract are untouched while decode streams ~2-4x fewer cache
    bytes."""
    if num_pages < 2:
        raise ValueError(
            f"num_pages must be >= 2 (page {NULL_PAGE} is the reserved "
            f"scratch page), got {num_pages}")
    if quantized:
        dtype = jnp.int8
    pool = jnp.zeros((num_pages, page_size) + tuple(tail), dtype)
    if sharding is not None:
        import jax
        pool = jax.device_put(pool, sharding)
    return pool


def init_page_scales(num_pages: int) -> jnp.ndarray:
    """Zero per-page scale sidecar ``(num_pages,)`` fp32 for a quantized
    pool.  A ``(P,)`` array parallel to the pool's page axis: scale ``0``
    means "no live magnitude yet" (an all-zero page round-trips bitwise);
    appends only ever *grow* a page's scale (scatter-max), requantizing the
    page's existing payload by the exact ratio so untouched pages stay
    bitwise-stable."""
    return jnp.zeros((num_pages,), jnp.float32)


def _token_amax(new: jnp.ndarray, lead: int) -> jnp.ndarray:
    """Per-token finite-masked ``max|.|`` over the tail axes (the quantity
    a page's scale must cover once the token lands there)."""
    mag = jnp.where(jnp.isfinite(new), jnp.abs(new), 0.0).astype(jnp.float32)
    return jnp.max(mag.reshape(mag.shape[:lead] + (-1,)), axis=-1)


def _requantize(pool: jnp.ndarray, old_scales: jnp.ndarray,
                new_scales: jnp.ndarray) -> jnp.ndarray:
    """Rescale int8 pages from per-page ``old_scales`` to the grown
    ``new_scales`` (both ``(..., P)``, pages ``(..., P, page, *tail)``: a
    whole pool, or the pages an append gathered).

    Untouched pages have ``new == old`` so the ratio is exactly ``1.0`` and
    ``round(q * 1.0) == q`` — they round-trip bitwise, which is what keeps
    the prefix-sharing / COW contracts intact under quantization."""
    ratio = jnp.where(new_scales > 0.0,
                      old_scales / jnp.where(new_scales > 0.0,
                                             new_scales, 1.0), 1.0)
    ratio = ratio.reshape(ratio.shape + (1,) * (pool.ndim - ratio.ndim))
    q = jnp.round(pool.astype(jnp.float32) * ratio)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def append_pages(pool: jnp.ndarray, new: jnp.ndarray,
                 block_table: jnp.ndarray,
                 seq_lens: jnp.ndarray, scales=None, layer=None):
    """Write ``new (b, s, *tail)`` at logical positions ``seq_lens[i] ..
    seq_lens[i] + s`` of each request into the pool.

    ``block_table (b, npages)`` int32 maps logical page -> physical page;
    ``seq_lens (b,)`` int32 is each request's current length (the append
    offset).  Returns the updated pool.  Requests whose row should not
    grow (idle slots) must point at ``NULL_PAGE`` so their write is
    absorbed by the scratch page.  Rows narrower than the pool's last axis
    are zero-padded to it (``lane_width``).

    ``layer`` (a scalar, possibly traced) marks the pool *stacked* over
    layer groups, ``(g, P, page, *tail)`` with ``scales (g, P)``: the rows
    land in layer ``layer`` as ``pool.at[layer, phys, off]``, a scatter
    into the whole stacked pool that XLA performs in place, so no layer's
    pool is ever sliced out or written back.  ``None`` is the single-layer
    pool ``(P, page, *tail)``.

    ``scales (P,)`` fp32 marks the pool quantized (int8 payload): each
    destination page's scale grows to cover the incoming tokens' amax
    (scatter-max — scales never shrink mid-residency), the destination
    pages' existing payload is requantized by the exact old/new ratio
    (every other page keeps its scale, so it is left as it is), and the
    new tokens quantize at the final scale; returns ``(pool, scales)``.
    Ghost-lane/speculative writes may conservatively inflate a page's
    scale before being overwritten — error stays bounded by the inflated
    ``scale / 2`` per element, never corrupted.

    Contract: a logical position past the block-table row (``pos //
    page_size >= npages``) is redirected to the scratch page, NOT clamped.
    Unguarded, JAX's scatter clamp would silently alias such writes onto
    the row's *last* physical page and corrupt it — with copy-on-write
    prefix sharing that last page may even be another request's boundary
    copy.  Right-padded prefill tail chunks rely on this redirect.
    """
    lead = () if layer is None else (layer,)
    new = _pad_rows(new, pool.shape[-1])
    b, s = new.shape[0], new.shape[1]
    page_size = pool.shape[len(lead) + 1]
    npages = block_table.shape[1]
    pos = seq_lens[:, None].astype(jnp.int32) + jnp.arange(s, dtype=jnp.int32)
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    logical = pos // page_size                          # (b, s) logical page
    phys = block_table[rows, jnp.clip(logical, 0, npages - 1)]
    phys = jnp.where(logical < npages, phys, NULL_PAGE)
    off = pos % page_size
    if scales is None:
        return pool.at[lead + (phys, off)].set(new.astype(pool.dtype))
    tok = _token_amax(new, 2) / 127.0                   # (b, s)
    new_scales = scales.at[lead + (phys,)].max(tok)
    old_tok, new_tok = scales[lead + (phys,)], new_scales[lead + (phys,)]
    # Requantize just the destination pages (b, s, page, *tail); a page
    # named twice gets the same values twice.
    pool = pool.at[lead + (phys,)].set(
        _requantize(pool[lead + (phys,)], old_tok, new_tok))
    s_tok = jnp.maximum(new_tok, TINY)
    s_tok = s_tok.reshape(s_tok.shape + (1,) * (new.ndim - 2))
    return (pool.at[lead + (phys, off)].set(quantize_q(new, s_tok)),
            new_scales)


def append_prefix_pages(pool: jnp.ndarray, prefix: jnp.ndarray,
                        block_row: jnp.ndarray,
                        stacked: bool = False, scales=None):
    """Scatter one request's whole prefix into the pool starting at logical
    position 0.

    ``block_row (npages,)`` is the request's block-table row.  With
    ``stacked=False`` the pool is ``(P, page, *tail)`` and the prefix
    ``(s, *tail)``; with ``stacked=True`` both carry a leading layer-group
    axis — pool ``(g, P, page, *tail)``, prefix ``(g, s, *tail)``, scales
    ``(g, P)`` (the layout ``model.init_paged_decode_caches`` produces).
    ``scales`` marks the pool quantized — same scatter-max / ratio-requant
    / quantize-at-final-scale contract as ``append_pages``; returns
    ``(pool, scales)``.  Positions past the block row go to the scratch
    page (same contract as ``append_pages``), and rows are zero-padded to
    the pool's width as there.
    """
    prefix = _pad_rows(prefix, pool.shape[-1])
    s = prefix.shape[1] if stacked else prefix.shape[0]
    page_size = pool.shape[2] if stacked else pool.shape[1]
    npages = block_row.shape[0]
    pos = jnp.arange(s, dtype=jnp.int32)
    logical = pos // page_size
    phys = block_row[jnp.clip(logical, 0, npages - 1)]
    phys = jnp.where(logical < npages, phys, NULL_PAGE)
    off = pos % page_size
    if scales is None:
        if stacked:
            return pool.at[:, phys, off].set(prefix.astype(pool.dtype))
        return pool.at[phys, off].set(prefix.astype(pool.dtype))
    if stacked:
        tok = _token_amax(prefix, 2) / 127.0            # (g, s)
        new_scales = scales.at[:, phys].max(tok)
        pool = _requantize(pool, scales, new_scales)
        s_tok = jnp.maximum(
            jnp.take_along_axis(new_scales, phys[None].astype(jnp.int32),
                                axis=1), TINY)          # (g, s)
        s_tok = s_tok.reshape(s_tok.shape + (1,) * (prefix.ndim - 2))
        return pool.at[:, phys, off].set(quantize_q(prefix, s_tok)), new_scales
    tok = _token_amax(prefix, 1) / 127.0                # (s,)
    new_scales = scales.at[phys].max(tok)
    pool = _requantize(pool, scales, new_scales)
    s_tok = jnp.maximum(new_scales[phys], TINY)
    s_tok = s_tok.reshape(s_tok.shape + (1,) * (prefix.ndim - 1))
    return pool.at[phys, off].set(quantize_q(prefix, s_tok)), new_scales


#: Dense cache leaf -> paged pool leaf (the cache layout contract of
#: ``models/attention.py`` / ``models/blocks.py``).
PAGED_KEYS = {"k": "k_pages", "v": "v_pages",
              "c_kv": "c_pages", "k_rope": "r_pages"}

#: Pool leaf -> its per-page fp32 scale sidecar leaf (quantized mode only).
SCALE_KEYS = {"k_pages": "k_scales", "v_pages": "v_scales",
              "c_pages": "c_scales", "r_pages": "r_scales"}


def write_prefill_prefix(paged_caches, prefill_caches, block_row, slot):
    """Scatter one request's batch-1 ``prefill`` cache tree into the paged
    tree: sequence-shaped leaves go to that request's pages (``block_row``),
    recurrent-state leaves to its decode slot row.  Trees are the
    group-stacked layouts of ``model.init_paged_decode_caches`` /
    ``model.prefill`` — quantized trees carry ``*_scales`` sidecar leaves,
    updated together with their pool."""
    def rec(pg, dn):
        out = {}
        for key, val in dn.items():
            if isinstance(val, dict):
                out[key] = rec(pg[key], val)
            elif PAGED_KEYS.get(key) in pg:
                pk = PAGED_KEYS[key]
                sk = SCALE_KEYS[pk]
                if sk in pg:
                    out[pk], out[sk] = append_prefix_pages(
                        pg[pk], val[:, 0], block_row, stacked=True,
                        scales=pg[sk])
                else:
                    out[pk] = append_prefix_pages(pg[pk], val[:, 0],
                                                  block_row, stacked=True)
            else:
                out[key] = pg[key].at[:, slot].set(
                    val[:, 0].astype(pg[key].dtype))
        return out
    return rec(paged_caches, prefill_caches)


def copy_page(paged_caches, src, dst):
    """Clone physical page ``src`` into ``dst`` across every *pool* leaf of
    the group-stacked paged cache tree (``(g, P, page, *tail)`` leaves named
    by ``PAGED_KEYS``, plus their ``(g, P)`` scale sidecars when the pool is
    quantized — the clone must read back at the source's scale); per-slot
    recurrent-state leaves pass through.

    This is the copy-on-write boundary-page copy: a request whose prompt
    diverges inside a cached, partially-filled page receives a private
    clone of just that page and writes its divergent tokens there, leaving
    the shared source read-only.
    """
    pool_keys = frozenset(PAGED_KEYS.values()) | frozenset(SCALE_KEYS.values())

    def rec(node):
        out = {}
        for key, val in node.items():
            if isinstance(val, dict):
                out[key] = rec(val)
            elif key in pool_keys:
                out[key] = val.at[:, dst].set(val[:, src])
            else:
                out[key] = val
        return out
    return rec(paged_caches)


def reset_page_scales(paged_caches, page_ids):
    """Zero the scale sidecar entries of freshly allocated pages across
    every quantized pool leaf (``(g, P)`` scale leaves; no-op tree-copy when
    the caches are unquantized).

    Freed pages keep their stale payload AND stale scale (nothing is zeroed
    on eviction); without this reset a recycled page's scale could only
    ratchet upward across tenants, degrading every later tenant's
    quantization.  ``page_ids`` may repeat and may include ``NULL_PAGE``
    (resetting the scratch page's scale is harmless), so callers can pad to
    a fixed length for one compiled shape."""
    scale_keys = frozenset(SCALE_KEYS.values())
    ids = jnp.asarray(page_ids, jnp.int32)

    def rec(node):
        out = {}
        for key, val in node.items():
            if isinstance(val, dict):
                out[key] = rec(val)
            elif key in scale_keys:
                out[key] = val.at[:, ids].set(0.0)
            else:
                out[key] = val
        return out
    return rec(paged_caches)


def gather_pages(pool: jnp.ndarray, block_table: jnp.ndarray,
                 scales=None, layer=None, width=None) -> jnp.ndarray:
    """Materialize the virtual contiguous cache ``(b, npages * page_size,
    *tail)`` a block table describes (the XLA-twin path; the Pallas kernel
    performs the same gather through its index map without materializing).
    ``width`` keeps the first ``width`` entries of each row (the rows as
    written, without their lane padding); ``None`` keeps the whole row.

    ``layer`` marks the pool stacked over layer groups, ``(g, P, page,
    *tail)`` with ``scales (g, P)``: the rows come from layer ``layer`` in
    one gather, ``pool[layer, block_table]``, without slicing the layer's
    pool out first.

    ``scales (P,)`` marks the pool quantized: the gathered int8 payload is
    dequantized by each page's scale (fp32 out) — the in-kernel twin
    multiplies the same per-page scalar after its page DMA."""
    lead = () if layer is None else (layer,)
    b, npages = block_table.shape
    page_size = pool.shape[len(lead) + 1]
    out = pool[lead + (block_table,)]            # (b, npages, page, *tail)
    out = out[..., :width]
    tail = out.shape[3:]
    if scales is not None:
        s = scales[lead + (block_table,)]        # (b, npages)
        out = out.astype(jnp.float32) \
            * s.reshape(s.shape + (1,) * (len(tail) + 1))
    return out.reshape((b, npages * page_size) + tail)
