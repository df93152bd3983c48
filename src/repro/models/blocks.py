"""Transformer/hybrid block assembly: (norm -> mixer -> +res) [-> norm -> ffn -> +res].

Block kinds come from ``configs.base.BlockSpec`` (mixer x ffn).  Every dense
projection routes through the TCEC policy layer via tagged sites ("attn",
"ffn", "ssm", ...) resolved from the policy context.  Each block exposes:
  * ``block_param_specs(cfg, spec)``   -> PSpec tree
  * ``block_apply(p, x, cfg, spec, ...)`` -> (y, new_cache)
  * ``block_cache_spec(cfg, spec, b, S)`` -> ShapeDtypeStruct tree (decode)
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, BlockSpec
from .base import PSpec, dense, rms_norm, shard_hint
from . import attention, moe as moe_mod, ssm


def ffn_params(cfg: ArchConfig) -> Dict[str, PSpec]:
    d, ff = cfg.d_model, cfg.d_ff
    dt = cfg.param_dtype
    return {
        "w_gate": PSpec((d, ff), ("embed", "mlp"), dt),
        "w_up": PSpec((d, ff), ("embed", "mlp"), dt),
        "w_down": PSpec((ff, d), ("mlp", "embed"), dt),
    }


def ffn_apply(p, x: jnp.ndarray, cfg: ArchConfig) -> jnp.ndarray:
    # The gate activation is a fused epilogue on the gate matmul's fp32
    # accumulator; under the plain policy dense stores the gated tensor in
    # the compute dtype (bf16), keeping FFN activation traffic (and the
    # fp32 cotangents autodiff would otherwise flow) at bf16 width
    # (§Perf H3).  Corrected policies keep the fp32 gate, same as their
    # dense contract.
    gated = dense(x, p["w_gate"], "ffn", activation=cfg.act)
    h = gated * dense(x, p["w_up"], "ffn")
    return dense(h.astype(x.dtype), p["w_down"], "ffn").astype(x.dtype)


_MIXERS = {
    "attn": (attention.gqa_params, attention.gqa_apply),
    "mla": (attention.mla_params, attention.mla_apply),
    "mamba": (ssm.mamba_params, ssm.mamba_apply),
    "mlstm": (ssm.mlstm_params, ssm.mlstm_apply),
    "slstm": (ssm.slstm_params, ssm.slstm_apply),
}


def block_param_specs(cfg: ArchConfig, spec: BlockSpec,
                      cross_attn: bool = False) -> Dict:
    d = cfg.d_model
    dt = cfg.param_dtype
    p: Dict = {"norm1": PSpec((d,), (None,), dt, init="zeros")}
    p["mixer"] = _MIXERS[spec.mixer][0](cfg)
    if cross_attn:
        p["norm_x"] = PSpec((d,), (None,), dt, init="zeros")
        p["cross"] = attention.gqa_params(cfg)
    if spec.ffn != "none":
        p["norm2"] = PSpec((d,), (None,), dt, init="zeros")
        p["ffn"] = (moe_mod.moe_params(cfg) if spec.ffn == "moe"
                    else ffn_params(cfg))
    return p


def block_apply(p, x: jnp.ndarray, cfg: ArchConfig, spec: BlockSpec,
                positions: jnp.ndarray,
                cache: Optional[Dict] = None,
                cache_index=None,
                causal: bool = True,
                enc_out: Optional[jnp.ndarray] = None,
                emit_cache: bool = False,
                block_table=None,
                seq_lens=None,
                active=None,
                layer=None) -> Tuple[jnp.ndarray, Optional[Dict]]:
    """One block.  A paged cache's pools come stacked over layer groups
    with this block's group index ``layer`` (see ``attention.gqa_apply``);
    recurrent states come per layer."""
    x = shard_hint(x, "batch", None, None)
    h = rms_norm(x, p["norm1"], cfg.norm_eps)
    _, apply_fn = _MIXERS[spec.mixer]
    if spec.mixer == "attn":
        mixer_cache = cache.get("mixer") if cache else None
        y, new_mixer = apply_fn(p["mixer"], h, cfg, positions,
                                cache=mixer_cache, cache_index=cache_index,
                                causal=causal, emit_kv=emit_cache,
                                block_table=block_table, seq_lens=seq_lens,
                                layer=layer)
    elif spec.mixer == "mla":
        mixer_cache = cache.get("mixer") if cache else None
        y, new_mixer = apply_fn(p["mixer"], h, cfg, positions,
                                cache=mixer_cache, cache_index=cache_index,
                                causal=causal,
                                block_table=block_table, seq_lens=seq_lens,
                                layer=layer)
    else:
        mixer_cache = cache.get("mixer") if cache else None
        y, new_mixer = apply_fn(p["mixer"], h, cfg, state=mixer_cache)
        if active is not None and mixer_cache is not None \
                and new_mixer is not None and h.shape[1] == 1:
            # continuous batching: recurrent state is accumulating (unlike
            # the positional, overwrite-idempotent KV append), so slots not
            # decoding this tick must keep their old state — a ghost step
            # would consume their pending token twice.  In the multi-token
            # verify step (s > 1 with state) the mixers emit per-position
            # state stacks whose shapes no longer match the old state; the
            # caller (model.verify_step_paged) selects the accepted
            # position's row AND applies this mask in one place.
            new_mixer = jax.tree.map(
                lambda n, o: jnp.where(
                    active.reshape((-1,) + (1,) * (n.ndim - 1)), n, o),
                new_mixer, mixer_cache)
    x = x + y

    new_cache: Optional[Dict] = {"mixer": new_mixer} if new_mixer is not None else None

    if "cross" in p:
        h = rms_norm(x, p["norm_x"], cfg.norm_eps)
        cross_cache = cache.get("cross") if cache else None
        y, new_cross = attention.gqa_apply(
            p["cross"], h, cfg, positions, cache=cross_cache,
            causal=False, kv_source=enc_out, is_cross=True)
        x = x + y
        if new_cross is not None:
            new_cache = dict(new_cache or {})
            new_cache["cross"] = new_cross

    if "ffn" in p:
        h = rms_norm(x, p["norm2"], cfg.norm_eps)
        if spec.ffn == "moe":
            y = moe_mod.moe_apply(p["ffn"], h, cfg)
        else:
            y = ffn_apply(p["ffn"], h, cfg)
        x = x + y
    return x, new_cache


def block_cache_spec(cfg: ArchConfig, spec: BlockSpec, b: int, S: int,
                     cross_len: int = 0) -> Optional[Dict]:
    """Abstract decode-cache layout for one block."""
    kvh, hd = cfg.n_kv_heads, cfg.head_dim_
    dt = jnp.dtype(cfg.param_dtype)
    out: Dict = {}
    if spec.mixer == "attn":
        out["mixer"] = {
            "k": jax.ShapeDtypeStruct((b, S, kvh, hd), dt),
            "v": jax.ShapeDtypeStruct((b, S, kvh, hd), dt),
        }
    elif spec.mixer == "mla":
        m = cfg.mla
        out["mixer"] = {
            "c_kv": jax.ShapeDtypeStruct((b, S, m.kv_lora_rank), dt),
            "k_rope": jax.ShapeDtypeStruct((b, S, m.qk_rope_head_dim), dt),
        }
    elif spec.mixer == "mamba":
        d_in, _ = ssm._mamba_dims(cfg)
        out["mixer"] = {
            "h": jax.ShapeDtypeStruct((b, d_in, cfg.ssm.d_state), jnp.float32),
            "conv": jax.ShapeDtypeStruct((b, cfg.ssm.d_conv - 1, d_in), dt),
        }
    elif spec.mixer == "mlstm":
        d_in = int(cfg.xlstm.proj_factor_mlstm * cfg.d_model)
        nh = cfg.n_heads
        dh = d_in // nh
        out["mixer"] = {
            "C": jax.ShapeDtypeStruct((b, nh, dh, dh), jnp.float32),
            "n": jax.ShapeDtypeStruct((b, nh, dh), jnp.float32),
            "conv": jax.ShapeDtypeStruct((b, cfg.xlstm.conv_kernel - 1, d_in), dt),
        }
    elif spec.mixer == "slstm":
        nh = cfg.n_heads
        dh = cfg.d_model // nh
        out["mixer"] = {k: jax.ShapeDtypeStruct((b, nh, dh), jnp.float32)
                        for k in ("c", "n", "h", "m")}
    if cross_len:
        out["cross"] = {
            "k": jax.ShapeDtypeStruct((b, cross_len, kvh, hd), dt),
            "v": jax.ShapeDtypeStruct((b, cross_len, kvh, hd), dt),
        }
    return out or None


def block_paged_cache_spec(cfg: ArchConfig, spec: BlockSpec, slots: int,
                           num_pages: int, page_size: int,
                           quantized: bool = False) -> Optional[Dict]:
    """Paged decode-cache layout for one block (``repro.serving``).

    Sequence-shaped attention caches become shared page pools ``(num_pages,
    page_size, *tail)`` addressed through per-request block tables; the
    recurrent mixers' O(1) states keep their dense per-slot layout
    ``(slots, ...)`` (there is nothing sequence-shaped to page).

    ``quantized=True`` stores int8 page payloads plus a per-page fp32 scale
    sidecar per pool (``*_scales (num_pages,)`` — a parallel array, so page
    ids / block tables / COW / sharding are untouched).

    Each pool's last axis (head dim, latent or rope width) is stored at
    ``paged_cache.lane_width``: whole lane tiles, so the pool's default
    layout on a TPU is the row-major one the in-place append and the paged
    kernel both read."""
    # (lazy import: serving depends on models)
    from repro.serving.paged_cache import lane_width
    kvh, w = cfg.n_kv_heads, lane_width(cfg.head_dim_)
    dt = jnp.int8 if quantized else jnp.dtype(cfg.param_dtype)
    scale = jax.ShapeDtypeStruct((num_pages,), jnp.float32)
    if spec.mixer == "attn":
        out = {
            "k_pages": jax.ShapeDtypeStruct((num_pages, page_size, kvh, w), dt),
            "v_pages": jax.ShapeDtypeStruct((num_pages, page_size, kvh, w), dt),
        }
        if quantized:
            out["k_scales"] = scale
            out["v_scales"] = scale
        return {"mixer": out}
    if spec.mixer == "mla":
        m = cfg.mla
        out = {
            "c_pages": jax.ShapeDtypeStruct(
                (num_pages, page_size, lane_width(m.kv_lora_rank)), dt),
            "r_pages": jax.ShapeDtypeStruct(
                (num_pages, page_size, lane_width(m.qk_rope_head_dim)), dt),
        }
        if quantized:
            out["c_scales"] = scale
            out["r_scales"] = scale
        return {"mixer": out}
    # recurrent mixers: per-slot dense state, identical to the batch layout
    return block_cache_spec(cfg, spec, slots, 0)


def block_paged_cache_axes(cfg: ArchConfig, spec: BlockSpec,
                           quantized: bool = False) -> Optional[Dict]:
    """Logical axis names matching ``block_paged_cache_spec`` (pre-stacking).

    Pool leaves ``(num_pages, page_size, *tail)``: neither the page axis
    nor the in-page offset is ever sharded (any device may need to resolve
    any physical page id its block table names); the kv-head axis rides the
    ``kv`` rule — tensor-parallel over ``model`` when divisible, replicated
    otherwise.  MLA latent pools have no head axis and replicate.  Scale
    sidecars ``(num_pages,)`` replicate (they are page-axis-parallel, and
    the page axis never shards).  Per-slot recurrent states reuse the dense
    batch layout (slot axis == "batch")."""
    if spec.mixer == "attn":
        out = {"k_pages": (None, None, "kv", None),
               "v_pages": (None, None, "kv", None)}
        if quantized:
            out["k_scales"] = (None,)
            out["v_scales"] = (None,)
        return {"mixer": out}
    if spec.mixer == "mla":
        out = {"c_pages": (None, None, None),
               "r_pages": (None, None, None)}
        if quantized:
            out["c_scales"] = (None,)
            out["r_scales"] = (None,)
        return {"mixer": out}
    return block_cache_axes(cfg, spec)


def block_cache_axes(cfg: ArchConfig, spec: BlockSpec,
                     cross_len: int = 0) -> Optional[Dict]:
    """Logical axis names for each decode-cache tensor (pre-stacking)."""
    out: Dict = {}
    if spec.mixer == "attn":
        out["mixer"] = {"k": ("batch", "seq", "kv", None),
                        "v": ("batch", "seq", "kv", None)}
    elif spec.mixer == "mla":
        out["mixer"] = {"c_kv": ("batch", "seq", None),
                        "k_rope": ("batch", "seq", None)}
    elif spec.mixer == "mamba":
        out["mixer"] = {"h": ("batch", "mlp", None),
                        "conv": ("batch", None, "mlp")}
    elif spec.mixer == "mlstm":
        out["mixer"] = {"C": ("batch", "heads", None, None),
                        "n": ("batch", "heads", None),
                        "conv": ("batch", None, "mlp")}
    elif spec.mixer == "slstm":
        out["mixer"] = {k: ("batch", "heads", None)
                        for k in ("c", "n", "h", "m")}
    if cross_len:
        out["cross"] = {"k": ("batch", None, "kv", None),
                        "v": ("batch", None, "kv", None)}
    return out or None
