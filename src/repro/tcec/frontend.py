"""``repro.tcec.einsum`` — the single policy-aware einsum frontend.

Every matrix contraction in the framework funnels through here.  The call

    tcec.einsum(eq, a, b, site="ffn", epilogue=Epilogue(bias=b_ffn))

1. resolves the ``TcecPolicy`` from the explicit argument or the active
   ``policy_scope`` for ``site`` (trace-time, before any jit boundary, so
   compile caches key on the concrete policy);
2. plans the backend (``repro.tcec.planner``): vpu fp32 / XLA split twin /
   batched Pallas kernel — absorbing the old ``kernels.ops._pallas_eligible``;
3. runs ONE shared ``custom_vjp`` whose backward pushes both operand
   cotangents (and the epilogue's bias/residual cotangents) through the same
   split schedule, so corrected-policy gradients stay fp32-level on every
   path — autodiff through the splits would round word cotangents to bf16.

Operands may be lazy ``FragmentOperand`` rules (generated in VREGs inside
the Pallas kernel body, or fused by XLA into the split pipeline — never
staged as a buffer), and a declarative ``Epilogue`` fuses
scale/bias/activation/residual/output-cast into the store (the
``store_with_operation`` analogue).

Plain (``passes == 1``, MXU) policies have two arithmetic conventions, kept
apart by ``precision=``:

  * ``"native"`` (default) — operands cast to the matrix unit's native
    dtype (``mma_dtype()``: bf16 on TPU, fp32 on the CPU test backend),
    fp32 accumulate.  This is the model fast path (the old ``mma_einsum``
    contract), and what keeps chunk-vs-decode numerics aligned per backend.
  * ``"strict"`` — operands always split into the policy's bf16 words,
    whatever the backend.  Backend-independent emulation semantics: the old
    ``tc_matmul`` / ``tcec_einsum`` contract, and what the accuracy tests
    measure.

Corrected and vpu policies are identical under both conventions.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import os
import string
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.context import resolve_policy
from repro.core.policy import TcecPolicy
from repro.core.quant import split_int8
from repro.core.tcec import nonfinite_guard, sanitize_nonfinite, split_words
from .epilogue import ACTIVATIONS, Epilogue, NO_EPILOGUE
from .operands import FragmentOperand
from .planner import Plan, parse_equation, plan_einsum

__all__ = ["einsum", "matmul", "mma_dtype", "trace_plans", "PlanRecord",
           "wide_weight_policy"]


def wide_weight_policy(pol: TcecPolicy, w_dtype) -> TcecPolicy:
    """The wide-weight contract for layer-level callers (``base.dense``,
    tied LM heads): an uncorrected XLA policy never silently rounds wide
    (fp32) weights to the matrix unit's native dtype — swap in the fp32
    vpu executor instead.  Pallas-kernel policies keep their path (the
    kernel's in-VREG split is the point of selecting it)."""
    if (pol.backend == "mxu" and not pol.error_correction
            and pol.word_dtype == "bf16"
            and pol.kernel != "pallas"
            and jnp.dtype(w_dtype) != jnp.bfloat16):
        return dataclasses.replace(pol, backend="vpu", kernel="xla")
    return pol


def mma_dtype() -> jnp.dtype:
    """Native input dtype of the matrix unit.

    bf16 on TPU (MXU) and during dry-run lowering (REPRO_MMA_DTYPE=bfloat16,
    so compiled byte counts reflect the real mixed-precision data flow);
    fp32 on the CPU test backend, whose dot thunks lack batched bf16 support.
    """
    env = os.environ.get("REPRO_MMA_DTYPE")
    if env:
        return jnp.dtype(env)
    return jnp.dtype(jnp.bfloat16) if jax.default_backend() == "tpu" \
        else jnp.dtype(jnp.float32)


# ---------------------------------------------------------------------------
# Plan tracing — lets tests/benchmarks assert which sites the frontend saw.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PlanRecord:
    eq: str
    site: Optional[str]
    policy: TcecPolicy
    backend: str
    # Tuner-chosen tiling for pallas-planned sites (None: defaults/off/xla).
    block: Optional[Tuple[int, int, int]] = None
    variant: Optional[str] = None


_TRACE: contextvars.ContextVar[Optional[List[PlanRecord]]] = \
    contextvars.ContextVar("repro_tcec_trace", default=None)


@contextlib.contextmanager
def trace_plans():
    """Record every frontend call planned inside the context (trace-time:
    calls served from an already-cached jit trace do not re-plan)."""
    log: List[PlanRecord] = []
    token = _TRACE.set(log)
    try:
        yield log
    finally:
        _TRACE.reset(token)


# ---------------------------------------------------------------------------
# Contraction executors.
# ---------------------------------------------------------------------------

def _contract(eq: str, a: jnp.ndarray, b: jnp.ndarray, pol: TcecPolicy,
              precision: str, emit=None) -> jnp.ndarray:
    """One policy-selected contraction, fp32 result (the XLA executor).

    ``emit`` (native-plain path only) narrows the dot's emitted dtype — the
    backward uses it so the dx cotangent leaves the matrix unit at bf16
    width on TPU (§Perf H5: the tensor-parallel all-reduce of dx then runs
    at bf16 wire width instead of reducing fp32 and casting after).
    """
    f32, HIGHEST = jnp.float32, jax.lax.Precision.HIGHEST
    # fp32 contractions stand for the fp32 reference: HIGHEST keeps them
    # fp32 on the TPU, where default precision is one bf16 MXU pass.
    if pol.backend == "vpu":
        return jnp.einsum(eq, a.astype(f32), b.astype(f32),
                          preferred_element_type=f32, precision=HIGHEST)

    def _ref(a_, b_):
        return jnp.einsum(eq, a_.astype(f32), b_.astype(f32),
                          preferred_element_type=f32, precision=HIGHEST)

    if pol.word_dtype == "int8":
        # Per-tile-scaled int8 words of the running residual (both
        # precision conventions: quantization IS the int8 contract), int32
        # MMA passes rescaled to fp32, with exact ±inf/NaN propagation via
        # the non-finite guard (quantization would otherwise absorb them).
        a32, b32 = a.astype(f32), b.astype(f32)
        aw, sa = split_int8(a32, pol.n_words)
        bw, sb = split_int8(b32, pol.n_words)
        acc = None
        for (i, j) in pol.schedule:
            term = jnp.einsum(eq, aw[i], bw[j],
                              preferred_element_type=jnp.int32).astype(f32)
            term = term * (sa[i] * sb[j])
            acc = term if acc is None else acc + term
        return nonfinite_guard(acc, a32, b32, _ref)

    if pol.passes == 1 and precision == "native":
        dt = mma_dtype()
        return jnp.einsum(eq, a.astype(dt), b.astype(dt),
                          preferred_element_type=emit or f32)
    staged = pol.fragment_gen == "staged"
    if not pol.error_correction:
        # Plain single-word cast: ±inf/NaN propagate through the bf16 dot
        # naturally.
        aw = split_words(a.astype(f32), 1, staged)
        bw = split_words(b.astype(f32), 1, staged)
        return jnp.einsum(eq, aw[0], bw[0], preferred_element_type=f32)
    a32, b32 = a.astype(f32), b.astype(f32)
    aw = split_words(sanitize_nonfinite(a32), pol.n_words, staged)
    bw = split_words(sanitize_nonfinite(b32), pol.n_words, staged)
    acc = None
    for (i, j) in pol.schedule:
        term = jnp.einsum(eq, aw[i], bw[j], preferred_element_type=f32)
        acc = term if acc is None else acc + term
    return nonfinite_guard(acc, a32, b32, _ref)


def _bwd_operand(lhs_labels: str, lhs, rhs_labels: str, rhs,
                 target_labels: str, target_shape, pol: TcecPolicy,
                 precision: str, emit=None) -> jnp.ndarray:
    """d(target) = <lhs, rhs> through the split schedule.

    A target label absent from both inputs was summed out in the forward
    (e.g. the q axis of MLA's absorbed "bqhn,lhn->bhl"): its cotangent
    broadcasts, so contract the reduced equation and broadcast back.
    """
    missing = [c for c in target_labels
               if c not in lhs_labels and c not in rhs_labels]
    reduced = "".join(c for c in target_labels if c not in missing)
    d = _contract(f"{lhs_labels},{rhs_labels}->{reduced}", lhs, rhs, pol,
                  precision, emit)
    if missing:
        for ax, c in enumerate(target_labels):
            if c in missing:
                d = jnp.expand_dims(d, ax)
        d = jnp.broadcast_to(d, target_shape)
    return d


# ---------------------------------------------------------------------------
# The shared custom_vjp core.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Spec:
    """Static execution spec (hashable: rides as a nondiff argument)."""
    ia: str
    ib: str
    out: str
    backend: str                 # "xla" | "pallas" | "pallas_fragment"
    pattern: Optional[str]       # pallas reshape strategy
    precision: str               # "native" | "strict"
    scale: float
    activation: Optional[str]
    out_dtype: Optional[str]
    has_bias: bool
    has_residual: bool
    interpret: bool
    fragment: Optional[FragmentOperand] = None
    block: Optional[Tuple[int, int, int]] = None   # tuner-chosen tiling

    @property
    def eq(self) -> str:
        return f"{self.ia},{self.ib}->{self.out}"


def _apply_epilogue(y: jnp.ndarray, spec: _Spec, ep: Dict) -> jnp.ndarray:
    """XLA-path epilogue: emitted on the accumulator so XLA fuses the chain
    into the matmul consumer (no fp32 HBM round-trip)."""
    if spec.scale != 1.0:
        y = y * jnp.asarray(spec.scale, y.dtype)
    if spec.has_bias:
        y = y + ep["bias"].astype(y.dtype)
    if spec.activation is not None:
        y = ACTIVATIONS[spec.activation](y)
    if spec.has_residual:
        y = y + ep["residual"].astype(y.dtype)
    if spec.out_dtype is not None:
        y = y.astype(spec.out_dtype)
    return y


def _run_pallas(spec: _Spec, pol: TcecPolicy, a, b, ep: Dict) -> jnp.ndarray:
    """Pallas executor: fused kernel with in-kernel epilogue (and in-kernel
    fragment generation for ``pallas_fragment``)."""
    from repro.kernels.tcec_matmul import tcec_matmul_fused
    bias = ep.get("bias")
    residual = ep.get("residual")
    kw = dict(frag=spec.fragment, bias=bias, scale=spec.scale,
              activation=spec.activation, out_dtype=spec.out_dtype,
              block=spec.block, interpret=spec.interpret)
    if spec.pattern == "fold":
        lead = a.shape[:-1]
        a2 = a.reshape(-1, a.shape[-1])
        r2 = residual.reshape(-1, residual.shape[-1]) \
            if residual is not None else None
        out = tcec_matmul_fused(a2, b, pol, residual=r2, **kw)
        return out.reshape(*lead, out.shape[-1])
    return tcec_matmul_fused(a, b, pol, residual=residual, **kw)


def _core_impl(spec: _Spec, pol: TcecPolicy, a, b, ep: Dict) -> jnp.ndarray:
    if spec.backend in ("pallas", "pallas_fragment"):
        return _run_pallas(spec, pol, a, b, ep)
    y = _contract(spec.eq, a, b, pol, spec.precision)
    return _apply_epilogue(y, spec, ep)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _einsum_core(spec: _Spec, pol: TcecPolicy, a, b, ep: Dict):
    return _core_impl(spec, pol, a, b, ep)


def _einsum_core_fwd(spec, pol, a, b, ep):
    return _einsum_core(spec, pol, a, b, ep), (a, b, ep)


def _reduce_to(g: jnp.ndarray, shape: Tuple[int, ...]) -> jnp.ndarray:
    """Sum ``g`` down to ``shape`` (transpose of broadcasting)."""
    extra = g.ndim - len(shape)
    if extra:
        g = jnp.sum(g, axis=tuple(range(extra)))
    axes = tuple(i for i, (gs, ss) in enumerate(zip(g.shape, shape))
                 if ss == 1 and gs != 1)
    if axes:
        g = jnp.sum(g, axis=axes, keepdims=True)
    return g


def _pallas_bwd(spec: _Spec, pol: TcecPolicy, a, b, g):
    """Backward matmuls through the same batched Pallas kernel/policy,
    mirroring ``kernels.tcec_matmul.tcec_matmul_pallas_grad``."""
    from repro.kernels.tcec_matmul import _tcec_matmul_pallas as pmm
    interp = spec.interpret
    if spec.pattern == "fold":
        a2 = a.reshape(-1, a.shape[-1])
        g2 = g.reshape(-1, g.shape[-1])
        da = pmm(g2, b.T, pol, None, interp).reshape(a.shape)
        db = pmm(a2.T, g2, pol, None, interp)
        return da, db
    da = pmm(g, jnp.swapaxes(b, -1, -2), pol, None, interp)
    db = pmm(jnp.swapaxes(a, -1, -2), g, pol, None, interp)
    return da, db


def _einsum_core_bwd(spec: _Spec, pol: TcecPolicy, res, g):
    a, b, ep = res
    g = g.astype(jnp.float32)
    d_ep: Dict[str, jnp.ndarray] = {}
    if spec.has_residual:
        d_ep["residual"] = g.astype(ep["residual"].dtype)
    if spec.activation is not None:
        # Recompute the pre-activation value through the same split schedule
        # (flash-attention-style rematerialization: nothing extra is saved).
        bb = b if b is not None else spec.fragment.build()
        y2 = _contract(spec.eq, a, bb, pol, spec.precision)
        if spec.scale != 1.0:
            y2 = y2 * jnp.asarray(spec.scale, y2.dtype)
        if spec.has_bias:
            y2 = y2 + ep["bias"].astype(y2.dtype)
        _, act_vjp = jax.vjp(ACTIVATIONS[spec.activation], y2)
        (g,) = act_vjp(g)
    if spec.has_bias:
        d_ep["bias"] = _reduce_to(g, ep["bias"].shape).astype(ep["bias"].dtype)
    if spec.scale != 1.0:
        g = g * jnp.asarray(spec.scale, g.dtype)
    if spec.backend == "pallas":
        da, db = _pallas_bwd(spec, pol, a, b, g)
    else:
        bb = b if b is not None else spec.fragment.build()
        # §Perf H5 (native plain only): emit the dx dot at the matrix unit's
        # native width so the TP all-reduce of dx runs at bf16 wire width;
        # db keeps fp32 accumulation (it contracts the long token dim).
        emit_da = mma_dtype() if (pol.backend == "mxu" and pol.passes == 1
                                  and pol.word_dtype == "bf16"
                                  and spec.precision == "native") else None
        da = _bwd_operand(spec.out, g, spec.ib, bb, spec.ia, a.shape, pol,
                          spec.precision, emit=emit_da)
        db = None if b is None else _bwd_operand(
            spec.ia, a, spec.out, g, spec.ib, b.shape, pol, spec.precision)
    da = da.astype(a.dtype)
    if db is not None:
        db = db.astype(b.dtype)
    return da, db, d_ep


_einsum_core.defvjp(_einsum_core_fwd, _einsum_core_bwd)


# ---------------------------------------------------------------------------
# Public frontend.
# ---------------------------------------------------------------------------

def _dim_map(ia: str, ib: str, a_shape, b_shape) -> Dict[str, int]:
    dims: Dict[str, int] = {}
    for labels, shape, what in ((ia, a_shape, "lhs"), (ib, b_shape, "rhs")):
        if len(labels) != len(shape):
            raise ValueError(
                f"operand rank mismatch: {what} labels {labels!r} vs shape "
                f"{tuple(shape)}")
        for c, s in zip(labels, shape):
            if dims.setdefault(c, s) != s:
                raise ValueError(
                    f"size mismatch for label {c!r}: {dims[c]} vs {s}")
    return dims


def einsum(eq: str, a, b, *, site: Optional[str] = None,
           policy: TcecPolicy | str | None = None,
           epilogue: Optional[Epilogue] = None,
           precision: str = "native",
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """Policy-aware, differentiable two-operand einsum (fp32 accumulate).

    ``a``/``b`` are arrays or ``FragmentOperand`` rules; ``policy`` is a
    registered name, a ``TcecPolicy``, or ``None`` (resolve from the active
    ``policy_scope`` for ``site``); ``epilogue`` fuses
    scale/bias/activation/residual/output-cast into the store.  See the
    module docstring for ``precision``.  Returns fp32 unless
    ``epilogue.out_dtype`` says otherwise.
    """
    if precision not in ("native", "strict"):
        raise ValueError(f"precision must be 'native' or 'strict', "
                         f"got {precision!r}")
    pol = resolve_policy(policy, site)
    ia, ib, out = parse_equation(eq)
    ep = epilogue if epilogue is not None else NO_EPILOGUE
    a_frag = isinstance(a, FragmentOperand)
    b_frag = isinstance(b, FragmentOperand)
    dims = _dim_map(ia, ib, a.shape, b.shape)
    out_shape = tuple(dims[c] for c in out)
    if ep.residual is not None and tuple(ep.residual.shape) != out_shape:
        raise ValueError(
            f"epilogue residual shape {tuple(ep.residual.shape)} != output "
            f"shape {out_shape} for {eq!r}")
    # The kernel streams a (n,)-bias block per store tile; other broadcast
    # shapes take the XLA path (residuals always fold/batch cleanly — their
    # shape was validated against the output above).
    bias_ok = ep.bias is None or tuple(ep.bias.shape) == (out_shape[-1],)
    plan = plan_einsum(
        ia, ib, out, pol, a_frag, b_frag, len(b.shape), bias_ok,
        b_frag_in_kernel_ok=not (b_frag and b.closes_over_arrays()))
    block = variant = None
    if plan.backend in ("pallas", "pallas_fragment"):
        # Trace-time, so the jit compile cache keys on the concrete block.
        # The fused kernel is the frontend's one data flow, so the search
        # space is tiles-only; REPRO_TUNE=off keeps the kernel defaults.
        from repro import tune
        if plan.pattern == "fold":
            mm = 1
            for c in ia[:-1]:
                mm *= dims[c]
            kk, nn, batch, rb = dims[ia[-1]], dims[ib[-1]], 1, False
        else:                      # "batched": bmk, bkn -> bmn
            batch, mm, kk = (dims[c] for c in ia)
            nn, rb = dims[ib[2]], True
        tplan = tune.matmul_plan(mm, nn, kk, policy=pol, batch=batch,
                                 rhs_batched=rb, site=site,
                                 variants=("fused",))
        if tplan is not None:
            block, variant = tplan.block, tplan.variant
    log = _TRACE.get()
    if log is not None:
        log.append(PlanRecord(f"{ia},{ib}->{out}", site, pol, plan.backend,
                              block, variant))
    if a_frag:
        a = a.build()
    frag = None
    if b_frag:
        if plan.backend == "pallas_fragment":
            frag, b = b, None
        else:
            b = b.build()
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    spec = _Spec(
        ia=ia, ib=ib, out=out, backend=plan.backend, pattern=plan.pattern,
        precision=precision, scale=float(ep.scale), activation=ep.activation,
        out_dtype=ep.out_dtype_str(), has_bias=ep.bias is not None,
        has_residual=ep.residual is not None, interpret=bool(interpret),
        fragment=frag, block=block)
    return _einsum_core(spec, pol, a, b, ep.arrays())


def _matmul_equation(a_ndim: int, b_ndim: int) -> str:
    """(..., m, k) @ (k, n) | batched — the ``tc_matmul`` shape family."""
    letters = string.ascii_lowercase
    if a_ndim < 2 or b_ndim < 2:
        raise ValueError(f"matmul needs >=2-D operands, got ranks "
                         f"{a_ndim} and {b_ndim}")
    if b_ndim == 2:
        lead = letters[:a_ndim - 1]
        return f"{lead}y,yz->{lead}z"
    if b_ndim > a_ndim:
        raise ValueError(
            f"rhs rank {b_ndim} > lhs rank {a_ndim} is not supported")
    nb = b_ndim - 2
    batch = letters[:nb]
    mid = letters[nb:a_ndim - 1]
    return f"{batch}{mid}y,{batch}yz->{batch}{mid}z"


def matmul(a, b, *, site: Optional[str] = None,
           policy: TcecPolicy | str | None = None,
           epilogue: Optional[Epilogue] = None,
           precision: str = "native",
           interpret: Optional[bool] = None) -> jnp.ndarray:
    """``a @ b`` through the frontend (equation derived from the ranks)."""
    return einsum(_matmul_equation(len(a.shape), len(b.shape)), a, b,
                  site=site, policy=policy, epilogue=epilogue,
                  precision=precision, interpret=interpret)
