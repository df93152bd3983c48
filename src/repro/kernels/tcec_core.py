"""Shared TCEC split/accumulate core — ONE split implementation for every
error-corrected matmul site (standalone matmul kernels AND attention).

The paper's WMMAe-TCEC insight is a *data-flow* property: the bf16 words of
an FP32 operand are generated in registers, never staged as separate
buffers.  That property is independent of which kernel consumes the words,
so the split/accumulate machinery lives here and is imported by

  * ``kernels/tcec_matmul.py``   — the standalone Pallas matmul family,
  * ``kernels/flash_attention.py`` — QK^T and PV inside the fused flash
    kernel (policy-selected precision per MXU pass schedule),
  * ``repro.tcec`` (the einsum frontend) — the XLA-twin executor that the
    attention/SSM/MoE model code calls, so prefill, decode and the Pallas
    kernel run the same split arithmetic.

``policy_dot(a, b, dn, n_words=, schedule=, vpu=)`` is the static-parameter
form for Pallas kernel bodies (everything but the operands is a Python
constant; the splits are plain jnp ops on VREG values).  The old einsum
form, ``tcec_einsum``, is a deprecation shim over ``repro.tcec.einsum``.

The pass-pair tables (``SCHEDULES``) are re-exported from ``core/tcec.py``
(smallest-magnitude-first ordering, the RZ-avoidance schedule).
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.policy import TcecPolicy
from repro.core.precision import bf16_word
from repro.core.quant import split_int8 as split_int8_vregs
from repro.core.tcec import _SCHEDULES as SCHEDULES

__all__ = [
    "SCHEDULES", "MATMUL_DN", "round_up", "split_vregs", "split_int8_vregs",
    "mma_passes", "mma_passes_int8", "policy_dot", "dot_params",
    "tcec_einsum", "compiler_params",
]

# (m, k) @ (k, n) dimension_numbers — the default contraction.
MATMUL_DN = (((1,), (0,)), ((), ()))


def round_up(x: int, mult: int) -> int:
    """Round x up to a multiple of mult (block/tile alignment)."""
    return -(-x // mult) * mult


def split_vregs(x: jnp.ndarray, n_words: int) -> List[jnp.ndarray]:
    """Split an FP32 value into bf16 words without leaving registers.

    Iterative Dekker split: each word is the bf16 rounding of the running
    residual, so ``x ~= sum(words)`` with the error bounded by the last
    word's truncation (~2^-8 per word).  ``n_words == 1`` is the plain bf16
    cast (the uncorrected policy).

    Finite fp32 values above bf16 max saturate to ±BF16_MAX instead of
    rounding to ±inf (which used to make the residual ``inf - inf = NaN``
    and poison every later word and MXU pass); non-finite *inputs* still
    pass through, with exact ±inf/NaN output handled by the callers'
    non-finite guard.
    """
    if n_words == 1:
        return [x.astype(jnp.bfloat16)]
    words = []
    rest = x
    for _ in range(n_words - 1):
        w = bf16_word(rest)
        words.append(w)
        rest = rest - w.astype(jnp.float32)
    words.append(rest.astype(jnp.bfloat16))
    return words


def mma_passes(aw: Sequence[jnp.ndarray], bw: Sequence[jnp.ndarray],
               schedule, dn=MATMUL_DN) -> jnp.ndarray:
    """Run the MXU pass schedule over split words; fp32 partial sum.

    ``schedule`` is a tuple of (a_word_idx, b_word_idx) pairs in
    smallest-magnitude-first order so the FP32 accumulation keeps low bits.
    """
    acc = None
    for (i, j) in schedule:
        term = jax.lax.dot_general(
            aw[i], bw[j], dn, preferred_element_type=jnp.float32)
        acc = term if acc is None else acc + term
    return acc


def mma_passes_int8(aw: Sequence[jnp.ndarray], sa: Sequence[jnp.ndarray],
                    bw: Sequence[jnp.ndarray], sb: Sequence[jnp.ndarray],
                    schedule, dn=MATMUL_DN) -> jnp.ndarray:
    """The int8 pass schedule: int32 MMA accumulation rescaled to fp32.

    Each pass contracts two int8 words into int32 (the quantized MMA data
    path) and rescales by the product of the words' per-tile scales; scale
    products shrink by ~2^-8 per schedule level, so the shared
    smallest-magnitude-first ordering keeps low bits exactly as in the bf16
    tables.
    """
    acc = None
    for (i, j) in schedule:
        term = jax.lax.dot_general(
            aw[i], bw[j], dn,
            preferred_element_type=jnp.int32).astype(jnp.float32)
        term = term * (sa[i] * sb[j])
        acc = term if acc is None else acc + term
    return acc


def policy_dot(a: jnp.ndarray, b: jnp.ndarray, dn=MATMUL_DN, *,
               n_words: int, schedule, vpu: bool,
               word_dtype: str = "bf16") -> jnp.ndarray:
    """Policy-selected-precision dot for Pallas kernel bodies.

    All policy facets arrive as static Python values (``dot_params``
    derives them from a ``TcecPolicy``), so this traces inside a kernel
    body exactly like hand-written splitting: vpu = plain fp32 VPU dot;
    ``word_dtype == "int8"`` quantizes the running residual per tile (the
    tile being whatever block the kernel hands in) and rescales int32 MMA
    passes; otherwise split both operands into bf16 words in VREGs and
    accumulate the scheduled MXU passes.
    """
    if vpu:
        # HIGHEST: a default-precision fp32 dot is one bf16 MXU pass on TPU.
        return jax.lax.dot_general(
            a.astype(jnp.float32), b.astype(jnp.float32), dn,
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    if word_dtype == "int8":
        aw, sa = split_int8_vregs(a.astype(jnp.float32), n_words)
        bw, sb = split_int8_vregs(b.astype(jnp.float32), n_words)
        return mma_passes_int8(aw, sa, bw, sb, schedule, dn)
    aw = split_vregs(a.astype(jnp.float32), n_words)
    bw = split_vregs(b.astype(jnp.float32), n_words)
    return mma_passes(aw, bw, schedule, dn)


def dot_params(policy: TcecPolicy) -> Dict:
    """Static ``policy_dot`` kwargs for a policy (kernel-launch helper)."""
    return dict(n_words=policy.n_words, schedule=policy.schedule,
                vpu=policy.backend == "vpu", word_dtype=policy.word_dtype)


def tcec_einsum(eq: str, a: jnp.ndarray, b: jnp.ndarray,
                policy: TcecPolicy) -> jnp.ndarray:
    """Deprecated: the split schedule as an einsum (the XLA-twin form).

    ``repro.tcec.einsum`` with ``precision="strict"`` is the same contract:
    vpu runs one fp32 einsum; MXU policies split both operands into bf16
    words (``passes == 1`` is the plain bf16 cast) and accumulate the
    scheduled cross-term einsums in fp32, smallest-magnitude terms first —
    with the same ``custom_vjp`` backward (summed-out labels broadcast;
    corrected-policy cotangents stay fp32-level).
    """
    import dataclasses
    import warnings
    warnings.warn(
        "kernels.tcec_core.tcec_einsum is deprecated; use "
        "repro.tcec.einsum(eq, a, b, policy=..., precision=\"strict\")",
        DeprecationWarning, stacklevel=2)
    from repro.core.policy import get_policy
    from repro.tcec import einsum as _frontend_einsum
    pol = get_policy(policy)
    if pol.kernel != "xla":
        # tcec_einsum was always the XLA twin; the frontend owns dispatch.
        pol = dataclasses.replace(pol, kernel="xla")
    return _frontend_einsum(eq, a, b, policy=pol, precision="strict")


def compiler_params(semantics: Tuple[str, ...]):
    """Mosaic compiler params: the grid's dimension semantics."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(dimension_semantics=semantics)
