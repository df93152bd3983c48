"""Pallas TPU kernel: TCEC emulated-FP32 matmul with in-VMEM/VREG splitting.

This is the paper's headline data-flow (Fig. 6, bottom) on the TPU memory
hierarchy.  The WMMA-API baseline stages the split matrices ``A_16`` and
``dA_16`` in shared memory; the WMMAe version generates both fragments
directly from the FP32 source.  Here:

  * HBM -> VMEM moves only the FP32 source blocks of A and B
    (``BlockSpec``-pipelined, double-buffered by Mosaic);
  * the bf16 words (hi/mid/lo) are produced *inside the kernel body* — they
    live in VREGs / kernel-local values, never as separate staged buffers;
  * 1/3/6/9 MXU passes accumulate into an FP32 VMEM scratch accumulator,
    smallest-magnitude terms first (the RZ-avoidance ordering).

VMEM working set per grid step (block sizes bm, bn, bk):
    on_the_fly : 4*(bm*bk + bk*bn) + 4*bm*bn          (fp32 src + fp32 acc)
    staged     : 2*w*(bm*bk + bk*bn) + 4*bm*bn        (w bf16 word buffers)
For w=3 the staged footprint of the inputs is 1.5x the on-the-fly one; the
saved bytes translate directly to a higher staging-roofline exactly as in
paper §4.4.1 (52.0 -> 104.0 TFlop/s on A100; see benchmarks/ai_curves.py for
the v5e numbers).

The kernel family is **batched, differentiable and shape-robust**:

  * ``(b, m, k) @ (b, k, n)`` and broadcast ``(b, m, k) @ (k, n)`` run as a
    single ``pallas_call`` over grid ``(b, m/bm, n/bn, k/bk)`` — the
    batched-SGEMM regime where the paper's 54.2 TFlop/s headline lives
    (staging-tier bandwidth, not the MMA unit, caps throughput there).
  * dims that don't divide the block are zero-padded up to the next block
    multiple and the result sliced back — no divisibility asserts.
  * ``tcec_matmul_pallas_grad`` is a ``custom_vjp`` wrapper whose backward
    runs dA = g @ B^T and dB = A^T @ g through the same batched kernel with
    the same policy, mirroring ``core/tcec.py``'s backward schedule.

The staged variant is also provided (as ``tcec_matmul_staged``) as the
faithful WMMA-API-baseline: split words are materialized in HBM by the host
function and streamed through VMEM as separate inputs.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import TcecPolicy
from repro.core.context import resolve_policy
from repro.core.tcec import (nonfinite_guard, sanitize_nonfinite,
                             split_words)
# The split/accumulate arithmetic is shared with the flash-attention kernel
# and the XLA attention twins — one implementation in kernels/tcec_core.
from .tcec_core import split_vregs as _split_vregs, mma_passes as _mma_passes
from .tcec_core import (split_int8_vregs as _split_int8_vregs,
                        mma_passes_int8 as _mma_passes_int8)
from .tcec_core import compiler_params as _shared_compiler_params
from .tcec_core import round_up as _round_up

__all__ = [
    "tcec_matmul_pallas", "tcec_matmul_staged", "tcec_matmul_staged_db",
    "tcec_matmul_pallas_grad", "tcec_matmul_fused", "tcec_matmul_auto",
    "default_blocks", "pad_amounts",
]


def _block2d(ref):
    """The (bm, bk)/(bk, bn) tile of a possibly batch-led ref."""
    return ref[0] if len(ref.shape) == 3 else ref[...]


def _tcec_kernel(a_ref, b_ref, o_ref, acc_ref, *, n_words, schedule, nk, vpu,
                 word_dtype="bf16"):
    """Grid: (b, m/bm, n/bn, k/bk); k innermost ('arbitrary')."""
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _block2d(a_ref).astype(jnp.float32)
    b = _block2d(b_ref).astype(jnp.float32)
    if vpu:
        # "FP32 SIMT" analogue: plain fp32 dot, no splitting, no MXU passes.
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    elif word_dtype == "int8":
        # Quantized TCEC: per-(bm,bk)/(bk,bn)-tile int8 words generated in
        # VREGs, int32 MMA passes rescaled to fp32 per schedule term.
        aw, sa = _split_int8_vregs(a, n_words)
        bw, sb = _split_int8_vregs(b, n_words)
        acc_ref[...] += _mma_passes_int8(aw, sa, bw, sb, schedule)
    else:
        # The footprint reduction: split in VREGs, no staged word buffers.
        aw = _split_vregs(a, n_words)
        bw = _split_vregs(b, n_words)
        acc_ref[...] += _mma_passes(aw, bw, schedule)

    @pl.when(k_idx == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...]


def _staged_kernel(*refs, n_words, schedule, nk):
    """WMMA-API baseline: split words arrive as separate staged inputs."""
    a_refs = refs[:n_words]
    b_refs = refs[n_words:2 * n_words]
    o_ref, acc_ref = refs[2 * n_words], refs[2 * n_words + 1]
    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    aw = [_block2d(r) for r in a_refs]
    bw = [_block2d(r) for r in b_refs]
    acc_ref[...] += _mma_passes(aw, bw, schedule)

    @pl.when(k_idx == nk - 1)
    def _done():
        o_ref[0] = acc_ref[...]


def default_blocks(m: int, n: int, k: int, chip=None) -> Tuple[int, int, int]:
    """MXU-aligned, staging-capacity-derived default blocks.

    The per-axis caps come from the active backend's ``ChipSpec`` via
    ``core.roofline.derive_block_caps`` — the B/F crossover for bm/bn and
    the staging budget for bk (the v5e derivation reproduces the previously
    hardcoded (128, 128, 512)).  Dims smaller than a full tile get a
    sublane-aligned block; dims that don't divide the chosen block are
    zero-padded by the host wrapper.
    """
    from repro.core.roofline import LANE, SUBLANE, derive_block_caps
    bm_cap, bn_cap, bk_cap = derive_block_caps(chip)
    bm = min(_round_up(m, SUBLANE), bm_cap)
    bn = min(_round_up(n, LANE), bn_cap)
    bk = min(_round_up(k, LANE), bk_cap)
    return bm, bn, bk


def pad_amounts(m: int, n: int, k: int,
                block: Tuple[int, int, int]) -> Tuple[int, int, int]:
    """Padded (m, n, k) — each rounded up to its block multiple."""
    bm, bn, bk = block
    return _round_up(m, bm), _round_up(n, bn), _round_up(k, bk)


def _pad_last2(x: jnp.ndarray, rows: int, cols: int) -> jnp.ndarray:
    """Zero-pad the trailing two dims of ``x`` up to (rows, cols)."""
    pr, pc = rows - x.shape[-2], cols - x.shape[-1]
    if pr == 0 and pc == 0:
        return x
    widths = [(0, 0)] * (x.ndim - 2) + [(0, pr), (0, pc)]
    return jnp.pad(x, widths)


def _check_shapes(a: jnp.ndarray, b: jnp.ndarray) -> Tuple[int, int, int, int]:
    """Validate (m,k)@(k,n) | (b,m,k)@(b,k,n) | (b,m,k)@(k,n); return
    (batch, m, n, k)."""
    if a.ndim not in (2, 3) or b.ndim not in (2, 3):
        raise ValueError(
            f"tcec matmul expects 2-D or 3-D operands, got {a.shape} @ {b.shape}")
    if a.ndim == 2 and b.ndim == 3:
        raise ValueError(
            f"broadcasting a 2-D lhs against a batched rhs is not supported: "
            f"{a.shape} @ {b.shape}")
    m, k = a.shape[-2:]
    k2, n = b.shape[-2:]
    if k != k2:
        raise ValueError(f"contracting dims disagree: {a.shape} @ {b.shape}")
    if a.ndim == 3 and b.ndim == 3 and a.shape[0] != b.shape[0]:
        raise ValueError(f"batch dims disagree: {a.shape} @ {b.shape}")
    nb = a.shape[0] if a.ndim == 3 else 1
    return nb, m, n, k


def _in_spec(ndim: int, rows: int, cols: int, kind: str):
    """BlockSpec for a possibly batch-led operand.

    kind: "a" blocks index (i, kk); "b" blocks index (kk, j).  Batched
    operands carry the grid's batch coordinate; broadcast (2-D) operands
    reuse the same block for every batch index.
    """
    if kind == "a":
        if ndim == 3:
            return pl.BlockSpec((1, rows, cols), lambda bi, i, j, kk: (bi, i, kk))
        return pl.BlockSpec((rows, cols), lambda bi, i, j, kk: (i, kk))
    if ndim == 3:
        return pl.BlockSpec((1, rows, cols), lambda bi, i, j, kk: (bi, kk, j))
    return pl.BlockSpec((rows, cols), lambda bi, i, j, kk: (kk, j))


def _compiler_params():
    return _shared_compiler_params(
        ("parallel", "parallel", "parallel", "arbitrary"))


def _needs_guard(pol: TcecPolicy) -> bool:
    """Split-schedule policies need the host-level non-finite guard.

    Plain bf16 casts and vpu fp32 dots propagate ±inf/NaN through the kernel
    naturally; corrected bf16 splits and int8 quantization do not (the
    split/quantize of a non-finite word poisons the schedule), so the host
    wrapper sanitizes the operands and restores the fp32 reference's exact
    ±inf/NaN pattern afterwards.
    """
    return pol.backend == "mxu" and (pol.error_correction
                                     or pol.word_dtype == "int8")


def _matmul_ref(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    return jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32))


def tcec_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray,
                       policy: TcecPolicy | str | None = None,
                       block: Tuple[int, int, int] | None = None,
                       interpret: bool = False) -> jnp.ndarray:
    """C = A @ B with FP32-level accuracy via in-kernel bf16 splitting.

    a: (m, k) or (batch, m, k); b: (k, n) or (batch, k, n) — a batched rhs
    requires a batched lhs.  Returns fp32 (m, n) / (batch, m, n).  Dims that
    don't divide the block are zero-padded and the result sliced back.
    ``policy=None`` resolves from the active policy context *before* the jit
    boundary, so the compile cache keys on the concrete policy, never on the
    mutable context.
    """
    return _tcec_matmul_pallas(a, b, resolve_policy(policy), block, interpret)


@functools.partial(jax.jit, static_argnames=("policy", "block", "interpret"))
def _tcec_matmul_pallas(a: jnp.ndarray, b: jnp.ndarray,
                        policy: TcecPolicy,
                        block: Tuple[int, int, int] | None = None,
                        interpret: bool = False) -> jnp.ndarray:
    pol = policy
    nb, m, n, k = _check_shapes(a, b)
    a0, b0 = a.astype(jnp.float32), b.astype(jnp.float32)
    guarded = _needs_guard(pol)
    if guarded:
        a, b = sanitize_nonfinite(a0), sanitize_nonfinite(b0)
    bm, bn, bk = block or default_blocks(m, n, k)
    mp, np_, kp = pad_amounts(m, n, k, (bm, bn, bk))
    a = _pad_last2(a.astype(jnp.float32), mp, kp)
    b = _pad_last2(b.astype(jnp.float32), kp, np_)
    a3 = a if a.ndim == 3 else a[None]
    nk = kp // bk
    grid = (nb, mp // bm, np_ // bn, nk)
    kernel = functools.partial(
        _tcec_kernel, n_words=pol.n_words,
        schedule=pol.schedule, nk=nk,
        vpu=pol.backend == "vpu", word_dtype=pol.word_dtype)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            _in_spec(3, bm, bk, "a"),
            _in_spec(b.ndim, bk, bn, "b"),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j, kk: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(a3, b)
    out = out[:, :m, :n]
    out = out if a.ndim == 3 else out[0]
    if guarded:
        out = nonfinite_guard(out, a0, b0, _matmul_ref)
    return out


def tcec_matmul_staged(a: jnp.ndarray, b: jnp.ndarray,
                       policy: TcecPolicy | str | None = None,
                       block: Tuple[int, int, int] | None = None,
                       interpret: bool = False) -> jnp.ndarray:
    """WMMA-API-baseline data flow: split words are materialized in HBM and
    each streamed through VMEM as its own staged buffer (Fig. 6, top).
    Accepts the same 2-D/batched/broadcast shapes as ``tcec_matmul_pallas``."""
    return _tcec_matmul_staged(a, b, resolve_policy(policy), block, interpret)


@functools.partial(jax.jit, static_argnames=("policy", "block", "interpret"))
def _tcec_matmul_staged(a: jnp.ndarray, b: jnp.ndarray,
                        policy: TcecPolicy,
                        block: Tuple[int, int, int] | None = None,
                        interpret: bool = False) -> jnp.ndarray:
    pol = policy
    if pol.backend == "vpu":
        raise ValueError(
            "tcec_matmul_staged stages bf16 split words by construction; a "
            "vpu (plain-fp32) policy has no staged data flow — use "
            "tcec_matmul_pallas, which honors backend=\"vpu\" exactly")
    if pol.word_dtype != "bf16":
        raise ValueError(
            "tcec_matmul_staged stages bf16 split words by construction; "
            f"word_dtype={pol.word_dtype!r} policies generate per-tile-"
            "scaled words on the fly — use tcec_matmul_pallas")
    nb, m, n, k = _check_shapes(a, b)
    a0, b0 = a.astype(jnp.float32), b.astype(jnp.float32)
    guarded = _needs_guard(pol)
    if guarded:
        a, b = sanitize_nonfinite(a0), sanitize_nonfinite(b0)
    bm, bn, bk = block or default_blocks(m, n, k)
    mp, np_, kp = pad_amounts(m, n, k, (bm, bn, bk))
    a = _pad_last2(a.astype(jnp.float32), mp, kp)
    b = _pad_last2(b.astype(jnp.float32), kp, np_)
    nk = kp // bk
    grid = (nb, mp // bm, np_ // bn, nk)
    # Zero padding splits to all-zero words, so splitting after padding is
    # exact.  The batch dim (if any) rides along elementwise.
    aw = split_words(a if a.ndim == 3 else a[None], pol.n_words, staged=True)
    bw = split_words(b, pol.n_words, staged=True)
    kernel = functools.partial(
        _staged_kernel, n_words=pol.n_words,
        schedule=pol.schedule, nk=nk)
    in_specs = (
        [_in_spec(3, bm, bk, "a")] * pol.n_words
        + [_in_spec(b.ndim, bk, bn, "b")] * pol.n_words
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j, kk: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*aw, *bw)
    out = out[:, :m, :n]
    out = out if a.ndim == 3 else out[0]
    if guarded:
        out = nonfinite_guard(out, a0, b0, _matmul_ref)
    return out


# ---------------------------------------------------------------------------
# Double-buffered staged variant: the WMMA-API data flow with software
# pipelining.  Mosaic double-buffers BlockSpec inputs automatically; here the
# split-word tiles are fetched with *explicit* async copies into a two-slot
# VMEM scratch so the next k-block's DMA overlaps the current MXU passes.
# Footprint: 2 slots x 2w bf16 word tiles (no fp32 source resident), i.e.
# 2*(2w)*(bm*bk + bk*bn) bytes vs Mosaic-staged 2*(4w)* — the tuner's third
# point on the staging-footprint/overlap trade-off curve.
# ---------------------------------------------------------------------------

def _staged_db_kernel(*refs, n_words, schedule, nk, bm, bn, bk, rhs_batched):
    """Grid: (b, m/bm, n/bn); the k loop lives inside with 2-slot DMA."""
    a_refs = refs[:n_words]
    b_refs = refs[n_words:2 * n_words]
    o_ref = refs[2 * n_words]
    scratch = refs[2 * n_words + 1:]
    a_scr = scratch[:n_words]
    b_scr = scratch[n_words:2 * n_words]
    a_sem, b_sem = scratch[2 * n_words], scratch[2 * n_words + 1]
    bi, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    def a_copy(w, kk, slot):
        return pltpu.make_async_copy(
            a_refs[w].at[bi, pl.ds(i * bm, bm), pl.ds(kk * bk, bk)],
            a_scr[w].at[slot], a_sem.at[w, slot])

    def b_copy(w, kk, slot):
        src = (b_refs[w].at[bi, pl.ds(kk * bk, bk), pl.ds(j * bn, bn)]
               if rhs_batched else
               b_refs[w].at[pl.ds(kk * bk, bk), pl.ds(j * bn, bn)])
        return pltpu.make_async_copy(src, b_scr[w].at[slot],
                                     b_sem.at[w, slot])

    # Warm-up: fill slot 0 for k-block 0.
    for w in range(n_words):
        a_copy(w, 0, 0).start()
        b_copy(w, 0, 0).start()

    def step(kk, acc):
        slot = jax.lax.rem(kk, 2)

        @pl.when(kk + 1 < nk)
        def _prefetch():
            for w in range(n_words):
                a_copy(w, kk + 1, 1 - slot).start()
                b_copy(w, kk + 1, 1 - slot).start()

        for w in range(n_words):
            a_copy(w, kk, slot).wait()
            b_copy(w, kk, slot).wait()
        aw = [a_scr[w][slot] for w in range(n_words)]
        bw = [b_scr[w][slot] for w in range(n_words)]
        return acc + _mma_passes(aw, bw, schedule)

    acc = jax.lax.fori_loop(0, nk, step, jnp.zeros((bm, bn), jnp.float32))
    o_ref[0] = acc


def tcec_matmul_staged_db(a: jnp.ndarray, b: jnp.ndarray,
                          policy: TcecPolicy | str | None = None,
                          block: Tuple[int, int, int] | None = None,
                          interpret: bool = False) -> jnp.ndarray:
    """Double-buffered staged matmul: split words in HBM, two-slot explicit
    DMA so the next k-tile's copy overlaps the current MXU passes.  Same
    shapes, policies and (bitwise) results as ``tcec_matmul_staged``."""
    return _tcec_matmul_staged_db(a, b, resolve_policy(policy), block,
                                  interpret)


@functools.partial(jax.jit, static_argnames=("policy", "block", "interpret"))
def _tcec_matmul_staged_db(a: jnp.ndarray, b: jnp.ndarray,
                           policy: TcecPolicy,
                           block: Tuple[int, int, int] | None = None,
                           interpret: bool = False) -> jnp.ndarray:
    pol = policy
    if pol.backend == "vpu":
        raise ValueError(
            "tcec_matmul_staged_db stages bf16 split words by construction; "
            "a vpu (plain-fp32) policy has no staged data flow — use "
            "tcec_matmul_pallas, which honors backend=\"vpu\" exactly")
    if pol.word_dtype != "bf16":
        raise ValueError(
            "tcec_matmul_staged_db stages bf16 split words by construction; "
            f"word_dtype={pol.word_dtype!r} policies generate per-tile-"
            "scaled words on the fly — use tcec_matmul_pallas")
    nb, m, n, k = _check_shapes(a, b)
    a0, b0 = a.astype(jnp.float32), b.astype(jnp.float32)
    guarded = _needs_guard(pol)
    if guarded:
        a, b = sanitize_nonfinite(a0), sanitize_nonfinite(b0)
    bm, bn, bk = block or default_blocks(m, n, k)
    mp, np_, kp = pad_amounts(m, n, k, (bm, bn, bk))
    a = _pad_last2(a.astype(jnp.float32), mp, kp)
    b = _pad_last2(b.astype(jnp.float32), kp, np_)
    nk = kp // bk
    grid = (nb, mp // bm, np_ // bn)
    aw = split_words(a if a.ndim == 3 else a[None], pol.n_words, staged=True)
    bw = split_words(b, pol.n_words, staged=True)
    w_dt = aw[0].dtype
    kernel = functools.partial(
        _staged_db_kernel, n_words=pol.n_words,
        schedule=pol.schedule, nk=nk, bm=bm, bn=bn, bk=bk,
        rhs_batched=b.ndim == 3)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        # Word arrays stay in ANY (HBM on hardware); the kernel pulls tiles
        # itself, so Mosaic must not also stage them.
        in_specs=[pl.BlockSpec(memory_space=pltpu.ANY)] * (2 * pol.n_words),
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, mp, np_), jnp.float32),
        scratch_shapes=(
            [pltpu.VMEM((2, bm, bk), w_dt) for _ in range(pol.n_words)]
            + [pltpu.VMEM((2, bk, bn), w_dt) for _ in range(pol.n_words)]
            + [pltpu.SemaphoreType.DMA((pol.n_words, 2)),
               pltpu.SemaphoreType.DMA((pol.n_words, 2))]),
        compiler_params=_shared_compiler_params(
            ("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(*aw, *bw)
    out = out[:, :m, :n]
    out = out if a.ndim == 3 else out[0]
    if guarded:
        out = nonfinite_guard(out, a0, b0, _matmul_ref)
    return out


def tcec_matmul_auto(a: jnp.ndarray, b: jnp.ndarray,
                     policy: TcecPolicy | str | None = None,
                     interpret: bool = False,
                     site: str = "auto") -> jnp.ndarray:
    """Tuner-dispatched matmul: ``repro.tune`` picks (block, variant) over
    the full fused/staged/staged_db/vpu space and this wrapper routes to the
    matching kernel.  With ``REPRO_TUNE=off`` it is exactly
    ``tcec_matmul_pallas`` with default blocks."""
    pol = resolve_policy(policy)
    nb, m, n, k = _check_shapes(a, b)
    from repro import tune   # deferred: tune imports kernels for measurement
    plan = tune.matmul_plan(m, n, k, policy=pol, batch=nb,
                            rhs_batched=b.ndim == 3, site=site)
    if plan is None or plan.variant in ("fused", "vpu"):
        block = None if plan is None else plan.block
        return tcec_matmul_pallas(a, b, pol, block, interpret)
    if plan.variant == "staged":
        return tcec_matmul_staged(a, b, pol, plan.block, interpret)
    return tcec_matmul_staged_db(a, b, pol, plan.block, interpret)


# ---------------------------------------------------------------------------
# Fused kernel for the einsum frontend (repro.tcec): optional in-kernel
# fragment generation (rhs from a foreach_ij rule — paper Code 4/5) and an
# epilogue chain applied in the store block (the store_with_operation
# analogue: scale/bias/activation/residual/output-cast never round-trip an
# fp32 tensor through HBM).
# ---------------------------------------------------------------------------

# One activation table for the whole frontend: the names Epilogue accepts
# are exactly the names this kernel can fuse.
from repro.tcec.epilogue import ACTIVATIONS as _EPILOGUE_ACTS  # noqa: E402


def _fused_kernel(*refs, n_words, schedule, nk, vpu, word_dtype, frag_rule,
                  k_log, n_log, bk, bn, has_b, has_bias, has_res, scale,
                  activation):
    """Grid: (b, m/bm, n/bn, k/bk); k innermost ('arbitrary').

    refs: a, [b], [bias], [residual], o, acc-scratch.  When ``frag_rule`` is
    set the rhs block is generated in VREGs from the rule at its global
    (k, n) offsets — padded positions (>= the logical k_log/n_log) read 0.
    """
    idx = 1
    a_ref = refs[0]
    b_ref = refs[idx] if has_b else None
    idx += int(has_b)
    bias_ref = refs[idx] if has_bias else None
    idx += int(has_bias)
    res_ref = refs[idx] if has_res else None
    idx += int(has_res)
    o_ref, acc_ref = refs[idx], refs[idx + 1]

    k_idx = pl.program_id(3)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = _block2d(a_ref).astype(jnp.float32)
    if has_b:
        b = _block2d(b_ref).astype(jnp.float32)
    else:
        j_idx = pl.program_id(2)
        ig = k_idx * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 0)
        jg = j_idx * bn + jax.lax.broadcasted_iota(jnp.int32, (bk, bn), 1)
        b = jnp.where((ig < k_log) & (jg < n_log),
                      frag_rule(ig, jg).astype(jnp.float32), 0.0)
    if vpu:
        acc_ref[...] += jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
            precision=jax.lax.Precision.HIGHEST)
    elif word_dtype == "int8":
        aw, sa = _split_int8_vregs(a, n_words)
        bw, sb = _split_int8_vregs(b, n_words)
        acc_ref[...] += _mma_passes_int8(aw, sa, bw, sb, schedule)
    else:
        aw = _split_vregs(a, n_words)
        bw = _split_vregs(b, n_words)
        acc_ref[...] += _mma_passes(aw, bw, schedule)

    @pl.when(k_idx == nk - 1)
    def _done():
        y = acc_ref[...]
        if scale != 1.0:
            y = y * jnp.float32(scale)
        if has_bias:
            y = y + bias_ref[...].astype(jnp.float32)    # (1, bn) broadcasts
        if activation is not None:
            y = _EPILOGUE_ACTS[activation](y)
        if has_res:
            y = y + _block2d(res_ref).astype(jnp.float32)
        o_ref[0] = y.astype(o_ref.dtype)


def tcec_matmul_fused(a: jnp.ndarray, b: Optional[jnp.ndarray],
                      policy: TcecPolicy | str | None = None, *,
                      frag=None, bias: Optional[jnp.ndarray] = None,
                      residual: Optional[jnp.ndarray] = None,
                      scale: float = 1.0, activation: Optional[str] = None,
                      out_dtype: Optional[str] = None,
                      block: Tuple[int, int, int] | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """TCEC matmul with in-kernel epilogue and optional rhs fragment.

    Same shape family as ``tcec_matmul_pallas``; ``b`` may instead be a
    fragment (``frag``: an object with ``.rule(i, j)`` and 2-D ``.shape``)
    generated inside the kernel.  ``bias`` is (n,), ``residual`` matches the
    output.  Not differentiable by itself — ``repro.tcec`` owns the shared
    ``custom_vjp`` that backs every frontend path.
    """
    if (b is None) == (frag is None):
        raise ValueError("pass exactly one of b= and frag=")
    return _tcec_matmul_fused(a, b, resolve_policy(policy), frag, bias,
                              residual, float(scale), activation, out_dtype,
                              block, interpret)


@functools.partial(jax.jit, static_argnames=(
    "policy", "frag", "scale", "activation", "out_dtype", "block",
    "interpret"))
def _tcec_matmul_fused(a, b, policy: TcecPolicy, frag, bias, residual,
                       scale, activation, out_dtype, block, interpret):
    pol = policy
    if frag is not None:
        if len(frag.shape) != 2:
            raise ValueError(
                f"in-kernel fragments must be 2-D (k, n), got {frag.shape}")
        k_log, n_log = frag.shape
        if a.ndim not in (2, 3) or a.shape[-1] != k_log:
            raise ValueError(
                f"lhs {a.shape} does not contract with fragment {frag.shape}")
        nb = a.shape[0] if a.ndim == 3 else 1
        m, n, k = a.shape[-2], n_log, k_log
    else:
        nb, m, n, k = _check_shapes(a, b)
        k_log, n_log = k, n
    if bias is not None and bias.shape != (n,):
        raise ValueError(f"bias shape {bias.shape} != ({n},)")
    if residual is not None and residual.shape[-2:] != (m, n):
        raise ValueError(
            f"residual shape {residual.shape} does not match output "
            f"({m}, {n})")
    a0 = a.astype(jnp.float32)
    b0 = None if b is None else b.astype(jnp.float32)
    guarded = _needs_guard(pol)
    if guarded:
        a = sanitize_nonfinite(a0)
        if b is not None:
            b = sanitize_nonfinite(b0)
    bm, bn, bk = block or default_blocks(m, n, k)
    mp, np_, kp = pad_amounts(m, n, k, (bm, bn, bk))
    a = _pad_last2(a.astype(jnp.float32), mp, kp)
    a3 = a if a.ndim == 3 else a[None]
    nk = kp // bk
    grid = (nb, mp // bm, np_ // bn, nk)

    inputs = [a3]
    in_specs = [_in_spec(3, bm, bk, "a")]
    if frag is None:
        b = _pad_last2(b.astype(jnp.float32), kp, np_)
        inputs.append(b)
        in_specs.append(_in_spec(b.ndim, bk, bn, "b"))
    if bias is not None:
        bias2 = jnp.pad(bias.astype(jnp.float32), (0, np_ - n))[None]
        inputs.append(bias2)
        in_specs.append(pl.BlockSpec((1, bn), lambda bi, i, j, kk: (0, j)))
    if residual is not None:
        res = _pad_last2(residual, mp, np_)
        res3 = res if res.ndim == 3 else res[None]
        inputs.append(res3)
        in_specs.append(
            pl.BlockSpec((1, bm, bn), lambda bi, i, j, kk: (bi, i, j)))

    o_dt = jnp.dtype(out_dtype) if out_dtype is not None else jnp.float32
    kernel = functools.partial(
        _fused_kernel, n_words=pol.n_words, schedule=pol.schedule,
        nk=nk, vpu=pol.backend == "vpu", word_dtype=pol.word_dtype,
        frag_rule=None if frag is None else frag.rule,
        k_log=k_log, n_log=n_log, bk=bk, bn=bn,
        has_b=frag is None, has_bias=bias is not None,
        has_res=residual is not None, scale=scale, activation=activation)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bm, bn), lambda bi, i, j, kk: (bi, i, j)),
        out_shape=jax.ShapeDtypeStruct((nb, mp, np_), o_dt),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(*inputs)
    out = out[:, :m, :n]
    out = out if a.ndim == 3 else out[0]
    if guarded:
        # Epilogue-aware non-finite guard: wherever the fp32 reference *dot*
        # is ±inf/NaN, recompute the epilogue chain on the reference value
        # and substitute — the kernel saw sanitized operands, so its output
        # is finite (and exact) everywhere else.
        ok = jnp.all(jnp.isfinite(a0))
        if b0 is not None:
            ok = ok & jnp.all(jnp.isfinite(b0))

        def _fix(o):
            if b0 is None:
                ig = jax.lax.broadcasted_iota(jnp.int32, (k_log, n_log), 0)
                jg = jax.lax.broadcasted_iota(jnp.int32, (k_log, n_log), 1)
                bb = frag.rule(ig, jg).astype(jnp.float32)
            else:
                bb = b0
            ref = _matmul_ref(a0, bb)
            mask = jnp.isfinite(ref)
            if scale != 1.0:
                ref = ref * jnp.float32(scale)
            if bias is not None:
                ref = ref + bias.astype(jnp.float32)
            if activation is not None:
                ref = _EPILOGUE_ACTS[activation](ref)
            if residual is not None:
                ref = ref + residual.astype(jnp.float32)
            return jnp.where(mask, o, ref.astype(o.dtype))

        out = jax.lax.cond(ok, lambda o: o, _fix, out)
    return out


# ---------------------------------------------------------------------------
# Differentiable wrapper: backward runs the same batched kernel.
# ---------------------------------------------------------------------------

def tcec_matmul_pallas_grad(a: jnp.ndarray, b: jnp.ndarray,
                            policy: TcecPolicy | str | None = None,
                            block: Tuple[int, int, int] | None = None,
                            interpret: bool = False) -> jnp.ndarray:
    """Differentiable ``tcec_matmul_pallas``.

    The ``custom_vjp`` backward computes dA = g @ B^T and dB = A^T @ g
    through the *same* batched Pallas kernel with the *same* policy —
    mirroring ``core/tcec.py``'s backward schedule, so a model trained on
    the kernel uses the footprint-reduced emulation end-to-end.
    """
    return _tcec_pallas_vjp(a, b, resolve_policy(policy), block, interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _tcec_pallas_vjp(a, b, policy: TcecPolicy,
                     block: Optional[Tuple[int, int, int]],
                     interpret: bool):
    return _tcec_matmul_pallas(a, b, policy, block, interpret)


def _tcec_pallas_vjp_fwd(a, b, policy, block, interpret):
    return _tcec_pallas_vjp(a, b, policy, block, interpret), (a, b)


def _tcec_pallas_vjp_bwd(policy, block, interpret, res, g):
    a, b = res
    # The forward block tiling need not divide the transposed shapes —
    # let the default chooser (+ padding) pick backward blocks.
    da = _tcec_matmul_pallas(
        g, jnp.swapaxes(b, -1, -2), policy, None, interpret)
    if b.ndim == 2 and a.ndim == 3:
        # broadcast rhs: dB sums over the batch — fold batch into rows.
        a2 = a.reshape(-1, a.shape[-1])
        g2 = g.reshape(-1, g.shape[-1])
        db = _tcec_matmul_pallas(a2.T, g2, policy, None, interpret)
    else:
        db = _tcec_matmul_pallas(
            jnp.swapaxes(a, -1, -2), g, policy, None, interpret)
    return da.astype(a.dtype), db.astype(b.dtype)


_tcec_pallas_vjp.defvjp(_tcec_pallas_vjp_fwd, _tcec_pallas_vjp_bwd)
