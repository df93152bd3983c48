"""Work counts from shapes: parameters, model FLOPs per step, and the
roofline bounds of the two Pallas kernels.

Model FLOPs count each contraction of the model once, whatever executes it:
correction passes of a TCEC policy do not count.  Attention counts over
the live context only, and the LM head only at positions that produce
logits.  A kernel's bound is the least time the chip could take for the
work as stated, the larger of its FLOPs at the bf16 peak and its bytes at
HBM bandwidth, so a kernel that skips dead work or a cheaper word schedule
raises its share and no change can push the share past 100%.
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping, Tuple

__all__ = ["Arch", "param_count", "matmul_shapes", "token_flops",
           "step_flops", "matmul_bound_s", "paged_decode_bound_s"]

_BYTES = {"float32": 4, "bfloat16": 2, "int8": 1}


@dataclasses.dataclass(frozen=True)
class Arch:
    """A dense grouped-query decoder, read from a configuration file."""
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    tied: bool
    qkv_bias: bool
    rope_theta: float
    eps: float
    dtype: str

    @classmethod
    def from_config(cls, c: Mapping) -> "Arch":
        d, h = c["hidden_size"], c["num_attention_heads"]
        return cls(layers=c["num_hidden_layers"], d=d, heads=h,
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or d // h,
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   tied=c["tie_word_embeddings"], qkv_bias=c["qkv_bias"],
                   rope_theta=c["rope_theta"], eps=c["rms_norm_eps"],
                   dtype=c["torch_dtype"])

    @property
    def itemsize(self) -> int:
        return _BYTES[self.dtype]


def matmul_shapes(a: Arch) -> Tuple[Tuple[str, int, int], ...]:
    """``(site, k, n)`` of each weight contraction of one layer."""
    q, kv = a.heads * a.head_dim, a.kv_heads * a.head_dim
    return (("attn", a.d, q), ("attn", a.d, kv), ("attn", a.d, kv),
            ("attn", q, a.d),
            ("ffn", a.d, a.ff), ("ffn", a.d, a.ff), ("ffn", a.ff, a.d))


def param_count(a: Arch) -> int:
    per_layer = sum(k * n for _, k, n in matmul_shapes(a)) + 2 * a.d
    if a.qkv_bias:
        per_layer += (a.heads + 2 * a.kv_heads) * a.head_dim
    head = 0 if a.tied else a.d * a.vocab
    return a.layers * per_layer + a.vocab * a.d + head + a.d


def token_flops(a: Arch, context: int, logits: bool) -> float:
    """FLOPs of one token through the model: every weight contraction,
    attention over ``context`` live positions (scores and values), and the
    LM head if the token's logits are produced."""
    weights = 2 * sum(k * n for _, k, n in matmul_shapes(a))
    attn = 2 * 2 * a.heads * a.head_dim * context
    head = 2 * a.d * a.vocab if logits else 0
    return a.layers * (weights + attn) + head


def step_flops(a: Arch, prefill: Iterable[Tuple[int, int, int, bool]],
               decode: Iterable[Tuple[int, int]]) -> float:
    """Model FLOPs of one engine tick from its recorded chunks and decode
    rows (``bench.drive.Tick`` fields).  A chunk of ``n`` real tokens from
    position ``s`` attends over ``s + 1 .. s + n`` positions and produces
    one row of logits."""
    total = 0.0
    for _rid, n, start, _last in prefill:
        ctx = n * start + n * (n + 1) / 2
        total += n * token_flops(a, 0, False) \
            + a.layers * 2 * 2 * a.heads * a.head_dim * ctx \
            + 2 * a.d * a.vocab
    for _rid, ctx in decode:
        total += token_flops(a, ctx, True)
    return total


def matmul_bound_s(m: int, k: int, n: int, itemsize: int,
                   peak_flops: float, hbm_bytes_s: float) -> float:
    """Least time of an ``(m, k) x (k, n)`` matmul whose operands and
    result are stored at ``itemsize`` bytes."""
    flops = 2 * m * n * k
    nbytes = (m * k + k * n + m * n) * itemsize
    return max(flops / peak_flops, nbytes / hbm_bytes_s)


def paged_decode_bound_s(a: Arch, contexts: Iterable[int], slots: int,
                         hbm_bytes_s: float) -> float:
    """Least time of one layer's paged decode attention: the live K and V
    tokens of the decoding rows, plus q and the fp32 output of every row
    the kernel is given."""
    live = sum(contexts)
    kv = 2 * live * a.kv_heads * a.head_dim * a.itemsize
    qo = slots * a.heads * a.head_dim * (a.itemsize + 4)
    return (kv + qo) / hbm_bytes_s
