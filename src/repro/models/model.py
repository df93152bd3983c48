"""Model assembly: embeddings -> scanned block groups -> norm -> LM head.

The layer stack is folded as ``lax.scan`` over *pattern groups* (HLO size is
O(pattern), compile time independent of depth — required for CPU dry-runs of
60–72-layer configs).  Three execution modes:

  * ``loss_fn``     — training forward + chunked cross-entropy (the LM-head
                      matmul runs the policy resolved for the "lm_head" site,
                      fp32-accurate without an fp32 weight copy).
  * ``prefill``     — forward emitting per-block KV/state caches.
  * ``decode_step`` — one-token step consuming/updating the caches.

TCEC precision policies are no longer threaded through as strings: every
entry point installs the config's ``site_policies()`` as *defaults* in the
policy context (``repro.core.context``), and each matmul carries a site tag.
An active ``policy_scope`` always beats the config defaults, so sweeps and
per-site overrides need zero model/config surgery.

Encoder-decoder (whisper) and VLM (internvl2) wrap the same machinery: the
modality frontends are stubs per the assignment — ``frames``/``patches``
arrive as precomputed embeddings.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import tcec
from repro.configs.base import ArchConfig, BlockSpec
from repro.core.context import policy_defaults
from .base import PSpec, abstract, initialize, logical_axes_tree, dense, rms_norm, shard_hint
from .blocks import block_param_specs, block_apply, block_cache_spec

Params = Any
LOSS_CHUNK = 512


# ---------------------------------------------------------------------------
# Parameter declaration
# ---------------------------------------------------------------------------

def _stack_specs(tree, n: int):
    return jax.tree.map(
        lambda s: PSpec((n,) + s.shape, (None,) + s.logical_axes, s.dtype,
                        s.init, s.init_scale),
        tree, is_leaf=lambda x: isinstance(x, PSpec))


def param_specs(cfg: ArchConfig) -> Dict:
    d, v = cfg.d_model, cfg.vocab
    dt = cfg.param_dtype
    is_encdec = cfg.encoder_layers > 0
    group = {f"pos{i}": block_param_specs(cfg, spec, cross_attn=is_encdec)
             for i, spec in enumerate(cfg.pattern)}
    specs: Dict = {
        "embed": PSpec((v, d), ("vocab", "embed"), dt),
        "blocks": _stack_specs(group, cfg.n_groups),
        "final_norm": PSpec((d,), (None,), dt, init="zeros"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = PSpec((d, v), ("embed", "vocab"), dt)
    if is_encdec:
        enc_group = {"pos0": block_param_specs(cfg, BlockSpec("attn", "dense"))}
        specs["encoder"] = {
            "blocks": _stack_specs(enc_group, cfg.encoder_layers),
            "final_norm": PSpec((d,), (None,), dt, init="zeros"),
        }
    return specs


def abstract_params(cfg: ArchConfig):
    return abstract(param_specs(cfg))


def init_params(rng: jax.Array, cfg: ArchConfig):
    return initialize(rng, param_specs(cfg))


def logical_axes(cfg: ArchConfig):
    return logical_axes_tree(param_specs(cfg))


def param_count(cfg: ArchConfig) -> int:
    import numpy as np
    leaves = jax.tree.leaves(abstract_params(cfg))
    return int(sum(np.prod(l.shape) for l in leaves))


# ---------------------------------------------------------------------------
# Block-stack execution
# ---------------------------------------------------------------------------

def _remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)


#: Page-pool leaves of a paged cache tree (payloads and their per-page
#: scale sidecars): shared by every slot, written in place by each step.
_POOL_KEYS = frozenset(("k_pages", "v_pages", "c_pages", "r_pages",
                        "k_scales", "v_scales", "c_scales", "r_scales"))


def _split_pools(tree):
    """``(pools, rest)``: the ``_POOL_KEYS`` leaves of a cache tree and all
    other leaves, as two trees of the same nesting."""
    pools, rest = {}, {}
    for key, val in tree.items():
        if isinstance(val, dict):
            p, r = _split_pools(val)
            if p:
                pools[key] = p
            if r:
                rest[key] = r
        elif key in _POOL_KEYS:
            pools[key] = val
        else:
            rest[key] = val
    return pools, rest


def _merge_trees(a, b):
    """Inverse of ``_split_pools``."""
    out = dict(a)
    for key, val in b.items():
        out[key] = _merge_trees(out[key], val) if key in out else val
    return out


def _run_blocks(blocks, x, cfg: ArchConfig, positions, causal=True,
                enc_out=None, caches=None, cache_index=None,
                emit_cache=False, use_remat=False,
                block_table=None, seq_lens=None, active=None):
    """Scan over pattern groups.  Returns (x, new_caches_or_None).

    Cache leaves stacked over groups ride the scan as inputs and outputs
    (sliced per group), except page pools: those (``_POOL_KEYS``) ride the
    scan *carry* whole, ``(g, P, page, *tail)``, and each group gets its
    index ``layer`` to append into and read from them in place.  Sliced per
    group, a pool would be copied out of and back into the stack on every
    step; carried, one token's rows per layer are all a step writes."""

    def group_body(x, gparams, gcaches, layer=None):
        new_caches = {}
        for i, spec in enumerate(cfg.pattern):
            key = f"pos{i}"
            cache_i = None if gcaches is None else gcaches.get(key)
            x, nc = block_apply(gparams[key], x, cfg, spec, positions,
                                cache=cache_i, cache_index=cache_index,
                                causal=causal, enc_out=enc_out,
                                emit_cache=emit_cache,
                                block_table=block_table, seq_lens=seq_lens,
                                active=active, layer=layer)
            if nc is not None:
                new_caches[key] = nc
        return x, new_caches

    if caches is not None:
        pools, states = _split_pools(caches)

        def body(carry, xs):
            x, pools = carry
            gp, gs, layer = xs
            x, nc = group_body(x, gp, _merge_trees(pools, gs), layer)
            pools, states = _split_pools(nc)
            return (x, pools), states
        if use_remat:
            body = _remat(body, cfg)
        n = jax.tree.leaves(blocks)[0].shape[0]
        (x, pools), states = jax.lax.scan(
            body, (x, pools), (blocks, states, jnp.arange(n, dtype=jnp.int32)))
        return x, _merge_trees(pools, states)

    if emit_cache:
        def body(x, gp):
            return group_body(x, gp, None)
        if use_remat:
            body = _remat(body, cfg)
        x, new_caches = jax.lax.scan(body, x, blocks)
        return x, new_caches

    def body(x, gp):
        y, _ = group_body(x, gp, None)
        return y, None
    if use_remat:
        body = _remat(body, cfg)
    x, _ = jax.lax.scan(body, x, blocks)
    return x, None


def _embed_tokens(params, tokens: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    e = jnp.take(params["embed"], tokens, axis=0)
    e = shard_hint(e, "batch", None, None)
    return (e.astype(jnp.float32) * (cfg.d_model ** 0.5)).astype(e.dtype)


def _encode(params, frames: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    """Whisper encoder over stubbed frame embeddings (bidirectional)."""
    b, s, _ = frames.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    enc = params["encoder"]
    x, _ = _run_blocks(enc["blocks"], frames.astype(jnp.dtype(cfg.param_dtype)),
                       cfg, positions, causal=False)
    return rms_norm(x, enc["final_norm"], cfg.norm_eps)


def _prepend_vision(params, embeds, batch, cfg: ArchConfig):
    patches = batch["patches"].astype(embeds.dtype)
    return jnp.concatenate([patches, embeds], axis=1)


def backbone(params, batch: Dict, cfg: ArchConfig, *, emit_cache=False,
             use_remat=False) -> Tuple[jnp.ndarray, Optional[Any], Optional[jnp.ndarray]]:
    """Token/frontend embeddings -> final hidden states.

    Returns (hidden (b, s_total, d), caches, enc_out)."""
    with policy_defaults(cfg.site_policies()):
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = _embed_tokens(params, tokens, cfg)
        if cfg.vision_tokens:
            x = _prepend_vision(params, x, batch, cfg)
        s_total = x.shape[1]
        positions = jnp.broadcast_to(
            jnp.arange(s_total, dtype=jnp.int32)[None], (b, s_total))
        enc_out = None
        if cfg.encoder_layers:
            enc_out = _encode(params, batch["frames"], cfg)
        x, caches = _run_blocks(params["blocks"], x, cfg, positions,
                                causal=True, enc_out=enc_out,
                                emit_cache=emit_cache, use_remat=use_remat)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, caches, enc_out


def _logits(params, h: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    if cfg.tie_embeddings:
        # h (..., d) against the (v, d) embedding — contract d on both;
        # wide_weight_policy keeps fp32 embeddings unrounded under
        # uncorrected policies (same contract as base.dense).
        import string
        from repro.core.context import resolve
        w = params["embed"]
        pol = tcec.wide_weight_policy(resolve("lm_head"), w.dtype)
        lead = string.ascii_lowercase[:h.ndim - 1]
        return tcec.einsum(f"{lead}y,zy->{lead}z", h, w,
                           site="lm_head", policy=pol)
    return dense(h, params["lm_head"], "lm_head").astype(jnp.float32)


# ---------------------------------------------------------------------------
# Training loss (chunked cross-entropy)
# ---------------------------------------------------------------------------

def loss_fn(params, batch: Dict, cfg: ArchConfig,
            use_remat: bool = True) -> Tuple[jnp.ndarray, Dict]:
    """Next-token cross-entropy.  labels < 0 are masked out."""
    h, _, _ = backbone(params, batch, cfg, use_remat=use_remat)
    labels = batch["labels"]
    if cfg.vision_tokens:                      # loss only on text positions
        h = h[:, cfg.vision_tokens:]
    b, s, d = h.shape
    chunk = min(LOSS_CHUNK, s)
    assert s % chunk == 0
    nc = s // chunk
    hc = h.reshape(b, nc, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, nc, chunk).swapaxes(0, 1)

    def chunk_loss(carry, xs):
        hcj, lcj = xs
        logits = shard_hint(_logits(params, hcj, cfg),
                            "batch", None, "vocab")      # (b, c, v) fp32
        lse = jax.nn.logsumexp(logits, axis=-1)
        lab = jnp.clip(lcj, 0, cfg.vocab - 1)
        gold = jnp.take_along_axis(logits, lab[..., None], axis=-1)[..., 0]
        mask = (lcj >= 0).astype(jnp.float32)
        tot, cnt = carry
        return (tot + jnp.sum((lse - gold) * mask), cnt + jnp.sum(mask)), None

    # Rematerialize per-chunk: (b, chunk, vocab) logits are recomputed in the
    # backward pass instead of being saved across the scan (vocab is huge).
    with policy_defaults(cfg.site_policies()):
        (tot, cnt), _ = jax.lax.scan(
            jax.checkpoint(chunk_loss),
            (jnp.float32(0.0), jnp.float32(0.0)), (hc, lc))
    loss = tot / jnp.maximum(cnt, 1.0)
    return loss, {"loss": loss, "tokens": cnt}


# ---------------------------------------------------------------------------
# Inference: prefill + decode
# ---------------------------------------------------------------------------

def prefill(params, batch: Dict, cfg: ArchConfig) -> Tuple[jnp.ndarray, Any]:
    """Forward over the prompt, emitting caches.  Returns (last-position
    logits (b, v), caches)."""
    h, caches, _ = backbone(params, batch, cfg, emit_cache=True)
    with policy_defaults(cfg.site_policies()):
        logits = _logits(params, h[:, -1:], cfg)[:, 0]
    return logits, caches


def decode_step(params, token: jnp.ndarray, caches: Any,
                cache_index: jnp.ndarray, cfg: ArchConfig) -> Tuple[jnp.ndarray, Any]:
    """One decode step.  token (b, 1) int32; cache_index scalar int32.
    Returns (logits (b, v), updated caches)."""
    b = token.shape[0]
    with policy_defaults(cfg.site_policies()):
        x = _embed_tokens(params, token, cfg)
        positions = jnp.full((b, 1), cache_index, jnp.int32)
        x, new_caches = _run_blocks(params["blocks"], x, cfg, positions,
                                    causal=True, caches=caches,
                                    cache_index=cache_index)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _logits(params, x, cfg)[:, 0]
    return logits, new_caches


def decode_step_paged(params, tokens: jnp.ndarray, caches: Any,
                      block_table: jnp.ndarray, seq_lens: jnp.ndarray,
                      cfg: ArchConfig,
                      active: Optional[jnp.ndarray] = None,
                      logit_index: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, Any]:
    """One continuous-batching step against *paged* caches.

    ``tokens (b, s)`` int32 — ``s == 1`` is the decode step, ``s > 1`` a
    chunked-prefill step (the chunk attends causally to each request's
    cache prefix; recurrent mixers only support ``s == 1``).
    ``block_table (b, npages)`` maps each slot's logical pages to physical
    pages of the shared pools; ``seq_lens (b,)`` is each slot's current
    cache length (the new tokens are appended there).  Per-slot rope
    positions follow ``seq_lens`` — slots at different depths coexist in
    one batch.  ``active (b,)`` bool marks the slots actually decoding this
    tick: idle lanes' paged KV writes are absorbed/overwritten harmlessly,
    but *recurrent* per-slot states are accumulating, so inactive slots
    keep their old state.  ``logit_index`` selects which chunk positions'
    logits to return: a ``(b,)`` int32 vector picks ONE position per slot
    (right-padded prefill chunks pass the last *real* position; padded
    tail rows are causally inert for earlier rows but their logits are
    garbage) and returns ``(b, v)``; a ``(b, m)`` per-slot index *vector*
    picks ``m`` positions per slot and returns ``(b, m, v)`` — the
    multi-position contract speculative verification scores through
    (the scalar form silently assumed one position per slot).  ``None``
    means the last position, ``(b, v)``.
    Returns (selected-position logits, updated caches).
    """
    b, s = tokens.shape
    with policy_defaults(cfg.site_policies()):
        x = _embed_tokens(params, tokens, cfg)
        positions = seq_lens[:, None].astype(jnp.int32) \
            + jnp.arange(s, dtype=jnp.int32)[None]
        x, new_caches = _run_blocks(params["blocks"], x, cfg, positions,
                                    causal=True, caches=caches,
                                    block_table=block_table,
                                    seq_lens=seq_lens, active=active)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if logit_index is None:
            logits = _logits(params, x[:, -1:], cfg)[:, 0]
        elif logit_index.ndim == 1:
            sel = x[jnp.arange(b), logit_index.astype(jnp.int32)][:, None]
            logits = _logits(params, sel, cfg)[:, 0]
        else:                                   # (b, m) -> (b, m, v)
            sel = jnp.take_along_axis(
                x, logit_index.astype(jnp.int32)[..., None], axis=1)
            logits = _logits(params, sel, cfg)
    return logits, new_caches


def _restore_recurrent_rows(new_caches, old_caches, n_acc, active):
    """Select each recurrent state leaf's per-position snapshot at the
    last *accepted* position.  Multi-token decode from state stacks the
    post-token state for every position on axis 1 after batch (leaves are
    ``(g, b, s, ...)`` once scanned over pattern groups); page pools are
    positional/overwrite-idempotent and pass through untouched.  Inactive
    slots keep their old state (the per-mixer active mask in ``blocks``
    skips stacked shapes — this is the one place it is applied)."""
    b = n_acc.shape[0]
    bi = jnp.arange(b)

    def rec(new, old):
        if isinstance(new, dict):
            return {k: (new[k] if k in _POOL_KEYS else rec(new[k], old[k]))
                    for k in new}
        sel = new[:, bi, n_acc]                   # (g, b, ...)
        if active is not None:
            mask = active.reshape((1, b) + (1,) * (sel.ndim - 2))
            sel = jnp.where(mask, sel, old)
        return sel

    return rec(new_caches, old_caches)


def verify_step_paged(params, tokens: jnp.ndarray, caches: Any,
                      block_table: jnp.ndarray, seq_lens: jnp.ndarray,
                      cfg: ArchConfig,
                      n_draft: jnp.ndarray,
                      active: Optional[jnp.ndarray] = None
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, Any]:
    """Speculative-verification step: score ``s = k + 1`` tokens per slot
    (position 0 = the slot's last committed token, positions 1.. = the
    proposer's drafts, right-padded past ``n_draft (b,)``) in ONE paged
    multi-token forward, then apply greedy acceptance on-device.

    Returns ``(targets (b, s) int32, n_acc (b,) int32, new_caches)``.
    ``targets[:, j]`` is the verifier's greedy argmax after consuming
    input ``j`` — computed through the same paged multi-token path,
    per-slot rope positions, and policy sites as sequential decode, so
    per policy it is exactly the token the non-speculative engine would
    emit there (the policy-aware acceptance contract: corrected policies
    like bf16x3/bf16x6 stay bitwise-identical to their own baseline).
    ``n_acc`` counts the leading drafts that matched; the executor
    commits ``targets[:, :n_acc + 1]`` — accepted-per-tick is
    ``n_acc + 1`` in ``[1, k + 1]`` (the +1 is the verifier's own
    bonus/corrected token, so progress is guaranteed every tick).

    Rollback of the rejected tail needs no pool surgery: paged KV
    appends are positional and overwrite-idempotent, attention reads
    mask by ``seq_lens``, and appends past a block-table row already
    redirect to the scratch page — the executor simply advances
    ``seq_lens`` by the committed count and refcounts are never touched.
    Recurrent (SSM) per-slot state IS accumulating, so the mixers
    snapshot their state after every position and this step restores the
    row at index ``n_acc`` — the state having consumed exactly the
    accepted inputs; inactive slots keep their old state untouched.
    """
    with policy_defaults(cfg.site_policies()):
        x = _embed_tokens(params, tokens, cfg)
        s = tokens.shape[1]
        positions = seq_lens[:, None].astype(jnp.int32) \
            + jnp.arange(s, dtype=jnp.int32)[None]
        x, new_caches = _run_blocks(params["blocks"], x, cfg, positions,
                                    causal=True, caches=caches,
                                    block_table=block_table,
                                    seq_lens=seq_lens, active=active)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _logits(params, x, cfg)          # (b, s, v) fp32
    targets = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    from repro.spec.acceptance import greedy_accept_counts
    n_acc = greedy_accept_counts(targets, tokens[:, 1:], n_draft)
    new_caches = _restore_recurrent_rows(new_caches, caches, n_acc, active)
    return targets, n_acc, new_caches


def decode_cache_specs(cfg: ArchConfig, b: int, max_len: int) -> Any:
    """Abstract cache pytree for serve_step lowering (stacked over groups)."""
    cross_len = cfg.encoder_len if cfg.encoder_layers else 0
    group = {}
    for i, spec in enumerate(cfg.pattern):
        c = block_cache_spec(cfg, spec, b, max_len, cross_len=cross_len)
        if c is not None:
            group[f"pos{i}"] = c
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.n_groups,) + s.shape, s.dtype),
        group)


def init_decode_caches(cfg: ArchConfig, b: int, max_len: int):
    """Concrete zero caches (for real decoding in examples/tests)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        decode_cache_specs(cfg, b, max_len))


def paged_cache_specs(cfg: ArchConfig, slots: int, num_pages: int,
                      page_size: int, quantized: bool = False) -> Any:
    """Abstract *paged* cache pytree (stacked over groups): attention KV /
    MLA latent caches as shared page pools, recurrent states per-slot.
    ``quantized=True`` makes the pools int8 with per-page fp32 scale
    sidecar leaves.  Encoder-decoder and vision frontends are not paged
    (no decode-time growth to page)."""
    if cfg.encoder_layers or cfg.vision_tokens:
        raise NotImplementedError(
            "paged serving covers decoder-only architectures")
    from .blocks import block_paged_cache_spec
    group = {}
    for i, spec in enumerate(cfg.pattern):
        c = block_paged_cache_spec(cfg, spec, slots, num_pages, page_size,
                                   quantized=quantized)
        if c is not None:
            group[f"pos{i}"] = c
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((cfg.n_groups,) + s.shape, s.dtype),
        group)


def init_paged_decode_caches(cfg: ArchConfig, slots: int, num_pages: int,
                             page_size: int, quantized: bool = False):
    """Concrete zero paged caches (pools + per-slot states)."""
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        paged_cache_specs(cfg, slots, num_pages, page_size,
                                          quantized=quantized))


def paged_cache_axes(cfg: ArchConfig, quantized: bool = False) -> Any:
    """Logical-axis tree matching ``paged_cache_specs`` (stacked: +'layers').

    Feeds ``repro.parallel.sharding.paged_cache_pspecs``: page pools shard
    only their kv-head axis (over ``model`` when divisible), per-slot
    recurrent states shard the slot axis over the data axes."""
    from .blocks import block_paged_cache_axes
    group = {}
    for i, spec in enumerate(cfg.pattern):
        a = block_paged_cache_axes(cfg, spec, quantized=quantized)
        if a is not None:
            group[f"pos{i}"] = a

    def stack(node):
        if isinstance(node, dict):
            return {k: stack(v) for k, v in node.items()}
        return ("layers",) + tuple(node)
    return stack(group)


def decode_cache_axes(cfg: ArchConfig) -> Any:
    """Logical-axis tree matching decode_cache_specs (stacked: +'layers')."""
    from .blocks import block_cache_axes
    cross_len = cfg.encoder_len if cfg.encoder_layers else 0
    group = {}
    for i, spec in enumerate(cfg.pattern):
        a = block_cache_axes(cfg, spec, cross_len=cross_len)
        if a is not None:
            group[f"pos{i}"] = a

    def stack(node):
        if isinstance(node, dict):
            return {k: stack(v) for k, v in node.items()}
        return ("layers",) + tuple(node)
    return stack(group)
