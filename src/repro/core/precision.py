"""bf16 multi-word splitting — the TPU analogue of the paper's FP16+Delta split.

Ootomo & Yokota split an FP32 matrix into ``A_f16 = toFP16(A)`` and a scaled
residual ``dA = toFP16((A - toFP32(A_f16)) * 2^11)`` so that three Tensor-Core
passes recover FP32-level accuracy.  FP16 needs the ``2^11`` scale because of
its 5-bit exponent; bf16 shares FP32's 8-bit exponent, so the residual words
need no range scaling (scale == 1.0).  What changes on TPU is the mantissa
budget: bf16 carries 8 significand bits (vs 11 for fp16), so a 2-word split
captures ~16 bits and a 3-word split captures ~24 bits (full FP32).

All splits are Dekker-exact: ``r = a - f32(bf16(a))`` is exactly representable
in FP32 under round-to-nearest, so the words satisfy
``a ≈ hi + mid (+ lo)`` with reconstruction error bounded by the last word's
truncation (see tests/test_precision_property.py for the Hypothesis bounds).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

# Mantissa bits contributed per bf16 word.
BF16_MANTISSA_BITS = 8
# Relative reconstruction error bounds (per element, vs FP32 source).
SPLIT2_REL_ERR = 2.0 ** (-16)
SPLIT3_REL_ERR = 2.0 ** (-24)
# Largest finite bf16 value.  fp32 magnitudes in (BF16_MAX, fp32 max] round
# to ±inf under a plain cast, which used to make the residual
# ``a - f32(±inf) = ∓inf`` and poison every later word with NaN.
BF16_MAX = float(jnp.finfo(jnp.bfloat16).max)


def _to_bf16(x: jnp.ndarray) -> jnp.ndarray:
    return x.astype(jnp.bfloat16)


def _back(x_bf16: jnp.ndarray) -> jnp.ndarray:
    return x_bf16.astype(jnp.float32)


def bf16_round(x: jnp.ndarray) -> jnp.ndarray:
    """The bf16 rounding of fp32 ``x``, kept in fp32 (round to nearest
    even), with finite overflow saturated to ±BF16_MAX.

    The XLA splits take their residuals from this value, never from a
    ``f32(bf16(x))`` round trip: XLA may fold that round trip to ``x``
    (excess precision), which zeroes every residual word and silently turns
    a corrected policy into bf16x1 — what the XLA twin of bf16x6 attention
    measured on a TPU v5e.  ``reduce_precision`` has no such rewrite.
    """
    w = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    return jnp.where(jnp.isfinite(x) & jnp.isinf(w),
                     jnp.sign(x) * jnp.float32(BF16_MAX), w)


def bf16_word(rest: jnp.ndarray) -> jnp.ndarray:
    """bf16 rounding of a split residual, saturating finite overflow — the
    in-kernel form (Mosaic has no ``reduce_precision``; it does not fold
    the cast round trip either).

    A *finite* fp32 value above BF16_MAX saturates to ±BF16_MAX instead of
    rounding to ±inf, so the Dekker residual ``rest - f32(word)`` stays
    finite (and exact: the difference of two representable fp32 values this
    close is representable).  Non-finite inputs pass through unchanged —
    exact ±inf/NaN propagation is handled at the dot level (the non-finite
    guard), not by clamping them away here.
    """
    w = _to_bf16(rest)
    sat = jnp.isfinite(rest) & jnp.isinf(_back(w))
    return jnp.where(sat, jnp.where(rest > 0, jnp.float32(BF16_MAX),
                                    jnp.float32(-BF16_MAX)).astype(jnp.bfloat16),
                     w)


def split2(a: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """FP32 -> (hi, lo) bf16 words; a ~= hi + lo with ~2^-16 rel err."""
    a = a.astype(jnp.float32)
    hi = bf16_round(a)
    return _to_bf16(hi), _to_bf16(a - hi)


def split3(a: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """FP32 -> (hi, mid, lo) bf16 words; a ~= hi + mid + lo with ~2^-24 rel err."""
    a = a.astype(jnp.float32)
    hi = bf16_round(a)
    r1 = a - hi
    mid = bf16_round(r1)
    return _to_bf16(hi), _to_bf16(mid), _to_bf16(r1 - mid)


def reconstruct(*words: jnp.ndarray) -> jnp.ndarray:
    """Sum bf16 words back to FP32 (smallest-first for accuracy)."""
    acc = jnp.zeros(words[0].shape, jnp.float32)
    for w in reversed(words):
        acc = acc + _back(w)
    return acc
