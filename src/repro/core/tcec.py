"""TCEC — FP32-accurate matmul emulation on the MXU (paper §4.4, TPU-adapted).

This module holds the split-word primitives (``split_words``, the
``_SCHEDULES`` pass tables, ``tc_dot_general``) that the einsum frontend
(``repro.tcec``) executes; ``tc_matmul`` itself is a deprecation shim over
the frontend.  The arithmetic: ``a @ b`` in FP32-level accuracy using only
bf16 MXU passes, following Ootomo & Yokota's error-correction scheme:

    A = A_hi + A_mid (+ A_lo)      (bf16 words, Dekker-exact split)
    C = sum of cross-term matmuls, accumulated smallest-first in FP32.

Pass schedules (word magnitudes: hi ~ 1, mid ~ 2^-8, lo ~ 2^-16):

    passes=1 : hh                                        (plain bf16)
    passes=3 : hh + hm + mh                              (~2^-16 rel err)
    passes=6 : hh + hm + mh + hl + mm + lh               (~2^-24 ≈ FP32)
    passes=9 : all 3x3 terms                             (>= FP32)

``fragment_gen="staged"`` reproduces the WMMA-API-only data flow from the
paper's Fig. 6: the split words are materialized as real buffers (an
``optimization_barrier`` stops XLA from fusing the conversion into the
matmul), doubling staging-tier traffic.  ``"on_the_fly"`` is the WMMAe data
flow: splits stay fusible into the matmul operands (and the Pallas kernel in
``repro.kernels.tcec_matmul`` performs them inside VMEM/VREGs explicitly).

``policy`` may be a preset/registered name, a ``TcecPolicy`` instance, or
``None`` — in which case the policy is resolved from the active
``repro.core.context`` scope for the (optional) ``site`` tag.  Resolution
happens before tracing-sensitive machinery (the frontend's custom_vjp static
argument is always the concrete ``TcecPolicy``), so jit caches key on the
resolved policy, never on the mutable context.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .policy import SCHEDULES, TcecPolicy
from .context import resolve_policy
from .precision import split2, split3
from .quant import split_int8

__all__ = ["tc_matmul", "tc_dot_general", "split_words", "sanitize_nonfinite",
           "nonfinite_guard"]


def split_words(a: jnp.ndarray, n_words: int, staged: bool) -> Sequence[jnp.ndarray]:
    """Split an FP32 array into bf16 words per policy.

    staged=True forces the words to be materialized (WMMA-API baseline data
    flow); otherwise XLA is free to fuse the conversions (WMMAe data flow).
    """
    if n_words == 1:
        words = (a.astype(jnp.bfloat16),)
    elif n_words == 2:
        words = split2(a)
    elif n_words == 3:
        words = split3(a)
    else:
        raise ValueError(f"n_words must be 1..3, got {n_words}")
    if staged:
        words = jax.lax.optimization_barrier(tuple(words))
    return words


# Back-compat view of the bf16 pass tables.  The single source of truth is
# ``core.policy.SCHEDULES`` keyed on (word_dtype, passes) — shared with the
# Pallas kernel family (repro.kernels.tcec_matmul) and the einsum frontend
# (repro.tcec), whose shared custom_vjp backward runs dA = g@B^T /
# dB = A^T@g through the same pass table.  ``TcecPolicy.schedule`` /
# ``TcecPolicy.n_words`` are derived from that table, so this alias exists
# only for external callers of the old name.
_SCHEDULES = {p: sched for (dt, p), sched in SCHEDULES.items()
              if dt == "bf16"}


def _dot(a, b, dimension_numbers, preferred, precision=None):
    return jax.lax.dot_general(
        a, b, dimension_numbers=dimension_numbers,
        preferred_element_type=preferred, precision=precision,
    )


def sanitize_nonfinite(x: jnp.ndarray) -> jnp.ndarray:
    """Zero out ±inf/NaN so split schedules never see them.

    A split word of a non-finite value poisons every later word (the
    residual becomes ``inf - inf = NaN``); the sanitized operands keep the
    schedule finite and ``nonfinite_guard`` restores the fp32 reference's
    exact ±inf/NaN pattern on the output.  For all-finite inputs this is the
    identity (bitwise), so guarded paths stay bitwise-stable.
    """
    return jnp.where(jnp.isfinite(x), x, 0.0).astype(jnp.float32)


def nonfinite_guard(out: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
                    ref_fn) -> jnp.ndarray:
    """Make a split-schedule result propagate ±inf/NaN exactly like the fp32
    reference dot.

    ``out`` must be computed from sanitized operands (finite everywhere).
    When any input element is non-finite, ``ref_fn(a, b)`` computes the fp32
    reference contraction on the *original* operands and its ±inf/NaN output
    pattern replaces ``out`` at exactly those positions.  The reference dot
    lives inside a ``lax.cond`` so the common all-finite case never pays for
    it at runtime.
    """
    ok = jnp.all(jnp.isfinite(a)) & jnp.all(jnp.isfinite(b))

    def _fix(ops):
        o, a_, b_ = ops
        ref = ref_fn(a_, b_)
        return jnp.where(jnp.isfinite(ref), o, ref)

    return jax.lax.cond(ok, lambda ops: ops[0], _fix, (out, a, b))


def tc_dot_general(
    a: jnp.ndarray,
    b: jnp.ndarray,
    dimension_numbers,
    policy: TcecPolicy | str | None = None,
    site: Optional[str] = None,
) -> jnp.ndarray:
    """Policy-dispatched dot_general (no custom_vjp — used as the primitive).

    ``policy=None`` resolves from the active policy context for ``site``."""
    policy = resolve_policy(policy, site)
    if policy.backend == "vpu":
        # "FP32 SIMT" analogue: plain FP32 dot on the vector unit.
        return _dot(a.astype(jnp.float32), b.astype(jnp.float32),
                    dimension_numbers, jnp.float32,
                    jax.lax.Precision.HIGHEST)

    def _ref(a_, b_):
        return _dot(a_.astype(jnp.float32), b_.astype(jnp.float32),
                    dimension_numbers, jnp.float32,
                    jax.lax.Precision.HIGHEST)

    if policy.word_dtype == "int8":
        # Per-tile-scaled int8 words of the running residual; int32 MMA
        # accumulation rescaled to fp32 per pass (smallest scale product
        # first — the schedule ordering is shared with the bf16 tables).
        a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
        aw, sa = split_int8(a32, policy.n_words)
        bw, sb = split_int8(b32, policy.n_words)
        acc = None
        for (i, j) in policy.schedule:
            term = _dot(aw[i], bw[j], dimension_numbers,
                        jnp.int32).astype(jnp.float32) * (sa[i] * sb[j])
            acc = term if acc is None else acc + term
        return nonfinite_guard(acc, a32, b32, _ref)

    if policy.passes == 1 and a.dtype == jnp.bfloat16 and b.dtype == jnp.bfloat16:
        return _dot(a, b, dimension_numbers, jnp.float32)

    staged = policy.fragment_gen == "staged"
    if not policy.error_correction:
        # Plain single-word cast: ±inf/NaN propagate through the bf16 dot
        # naturally, no guard needed.
        aw = split_words(a, 1, staged)
        bw = split_words(b, 1, staged)
        return _dot(aw[0], bw[0], dimension_numbers, jnp.float32)

    a32, b32 = a.astype(jnp.float32), b.astype(jnp.float32)
    aw = split_words(sanitize_nonfinite(a32), policy.n_words, staged)
    bw = split_words(sanitize_nonfinite(b32), policy.n_words, staged)
    acc = None
    for (i, j) in policy.schedule:
        term = _dot(aw[i], bw[j], dimension_numbers, jnp.float32)
        acc = term if acc is None else acc + term
    return nonfinite_guard(acc, a32, b32, _ref)


def tc_matmul(a: jnp.ndarray, b: jnp.ndarray,
              policy: TcecPolicy | str | None = None,
              site: Optional[str] = None) -> jnp.ndarray:
    """Deprecated: emulated FP32 matmul ``a @ b`` on the MXU.

    ``repro.tcec.einsum``/``repro.tcec.matmul`` with ``precision="strict"``
    is the same contract — a: (..., m, k), b: (k, n) or batched, fp32 out,
    policy resolved from the context for ``site`` when not explicit, and a
    shared ``custom_vjp`` running the backward matmuls through the same
    split schedule."""
    import dataclasses
    import warnings
    warnings.warn(
        "core.tcec.tc_matmul is deprecated; use repro.tcec.matmul(a, b, "
        "policy=..., site=..., precision=\"strict\") (or repro.tcec.einsum)",
        DeprecationWarning, stacklevel=2)
    from repro.tcec import matmul as _frontend_matmul
    pol = resolve_policy(policy, site)
    if pol.kernel != "xla":
        # tc_matmul was always the XLA split path; keep the shim faithful
        # (the frontend is where kernel dispatch lives).
        pol = dataclasses.replace(pol, kernel="xla")
    return _frontend_matmul(a, b, policy=pol, precision="strict")
