#!/usr/bin/env python3
"""Smoke run of the paged serving path on a TPU, at qwen2-0.5b's full
published width (24 layers, d=896, 14 heads over 2 KV heads, d_ff=4864,
vocab 151,936; random bf16 weights from a seed).

    python chip_smoke.py               # one chip: kernel checks + two streams
    python chip_smoke.py --four-chips  # 2x2 mesh stream vs one device

One chip runs three phases:

1. kernels: the frontend matmul at the FFN width against numpy float64
   under ``fp32_vpu``, ``bf16x1``, and ``bf16x6``/``int8x3`` on both the
   XLA split path and the Pallas kernel;
   the flash kernel and the paged decode kernel (bf16 and int8 pools)
   against their XLA twins under ``bf16x6_pallas``;
2. serving: eight seeded requests (prompts of 64-512 tokens, 32 new tokens
   each, 16-token pages, 4 slots) through ``PagedServingEngine``, once
   under the default policies (XLA paths) and once under
   ``bf16x6_pallas`` (fused matmul, flash and paged decode kernels native);
3. logits: each policy's prefill logits against a plain float32 forward at
   ``Precision.HIGHEST`` (``repro.models.reference``).

``--four-chips`` runs only the mesh phase: the same stream on a
``("data", "model")`` 2x2 mesh and on one device in the same process.

Measured errors and timings print first; timings are informational smoke
figures, not benchmarks.  The last line is one JSON object naming the
device.  Any failed check exits non-zero; without a TPU, or without the
repository's ``src/`` next to this file, it exits non-zero before printing
a result.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

# The package is used from the checkout this script sits in.
sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
from repro import tcec  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.context import policy_scope  # noqa: E402
from repro.kernels.flash_attention import flash_attention  # noqa: E402
from repro.launch import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import init_params, prefill  # noqa: E402
from repro.models.attention import chunked_attention  # noqa: E402
from repro.models.base import activation_sharding  # noqa: E402
from repro.models.reference import reference_logits  # noqa: E402
from repro.parallel import sharding as shd  # noqa: E402
from repro.serving import (PagedServingEngine,  # noqa: E402
                           paged_decode_attention_pallas,
                           paged_decode_attention_xla)

ARCH = "qwen2-0.5b"
SEED = 0
# Four distinct prompt lengths, each twice: eight requests and four
# single-shot prefill shapes (one compile each) per policy.  200 and 333
# divide neither the flash blocks nor the 16-token page.
PROMPT_LENS = (64, 200, 333, 512) * 2
NEW_TOKENS = 32
PAGE_SIZE = 16
SLOTS = 4
PALLAS = "bf16x6_pallas"

# Max relative error of (256, 896) x (896, 4864) against float64, N(0, 1)
# inputs: each policy must land within 10x of its row of the error ladder
# (README "Attention precision policies" / "Quantized TCEC" tables), on the
# Pallas kernel and on the XLA split path alike.  The fp32 rows must also
# be at fp32 level (<= 1e-5), and bf16x1 must be visibly worse: within 10x
# of its own row, and 100x the fp32_vpu error.
MATMUL_LADDER = {"fp32_vpu": 5e-7, "bf16x1": 3e-3,
                 "bf16x6": 3e-7, "bf16x6_pallas": 3e-7,
                 "int8x3": 4.2e-7, "int8x3_pallas": 4.2e-7}
FP32_LEVEL = 1e-5
# Kernel vs XLA twin under bf16x6: both run one split schedule and differ
# only in accumulation order and the online-softmax rescaling, so they
# agree to fp32 roundoff (the interpret-mode tests hold the same bound).
TWIN_TOL = 1e-5
# Prefill logits vs the float32 reference, relative L2 per request.  The
# params are bf16 and the residual stream is stored in bf16, so each block
# adds ~2^-9 relative error.  At full width the default policy measured
# 6.2e-3 at 1 layer, 8.4e-3 at 2, 1.1e-2 at 4 and 1.25e-2 at 8 (CPU with
# bf16 matrix-unit operands), growing ~sqrt(layers): ~2.2e-2 expected at
# 24.  0.08 leaves ~4x margin; a wrong kernel gives errors of order 1.
LOGIT_TOL = 0.08


def _fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        _fail(msg)


def _max_rel_err(out, ref) -> float:
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.max(np.abs(out - ref)) / np.max(np.abs(ref)))


# ---------------------------------------------------------------------------
# Phase 1: kernels at real widths
# ---------------------------------------------------------------------------

def check_matmul(cfg, rng) -> None:
    a = rng.standard_normal((256, cfg.d_model)).astype(np.float32)
    b = rng.standard_normal((cfg.d_model, cfg.d_ff)).astype(np.float32)
    ref = a.astype(np.float64) @ b.astype(np.float64)
    err = {}
    for pol, ladder in MATMUL_LADDER.items():
        out = tcec.matmul(jnp.asarray(a), jnp.asarray(b), site="ffn",
                          policy=pol)
        err[pol] = _max_rel_err(out, ref)
        print(f"matmul {a.shape}x{b.shape} {pol}: max rel err "
              f"{err[pol]:.3e} (ladder {ladder:.1e})", flush=True)
        _check(err[pol] <= 10 * ladder,
               f"matmul {pol} error {err[pol]:.3e} > 10x ladder {ladder:.1e}")
    for pol in ("fp32_vpu", "bf16x6", "bf16x6_pallas"):
        _check(err[pol] <= FP32_LEVEL,
               f"matmul {pol} error {err[pol]:.3e} is not fp32 level")
    _check(err["bf16x1"] >= MATMUL_LADDER["bf16x1"] / 10
           and err["bf16x1"] >= 100 * err["fp32_vpu"],
           f"bf16x1 error {err['bf16x1']:.3e} is not visibly worse than "
           f"fp32_vpu {err['fp32_vpu']:.3e}")


def _attention_fp64(q, k, v, causal):
    """(b, h, sq, d) x (b, kvh, skv, d) softmax attention in float64."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    rep = q.shape[1] // k.shape[1]
    k, v = np.repeat(k, rep, axis=1), np.repeat(v, rep, axis=1)
    s = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(q.shape[-1])
    if causal:
        sq, sk = s.shape[-2:]
        s = np.where(np.tri(sq, sk, dtype=bool), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum("bhqk,bhkd->bhqd", p / p.sum(-1, keepdims=True), v)


def check_flash(cfg, rng) -> None:
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    for s in (200, 512):
        q = rng.standard_normal((1, h, s, d)).astype(np.float32)
        k = rng.standard_normal((1, kvh, s, d)).astype(np.float32)
        v = rng.standard_normal((1, kvh, s, d)).astype(np.float32)
        out = flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                              causal=True, policy=PALLAS, interpret=False)
        twin = chunked_attention(
            *(jnp.asarray(x.transpose(0, 2, 1, 3)) for x in (q, k, v)),
            causal=True, policy="bf16x6").transpose(0, 2, 1, 3)
        ref = _attention_fp64(q, k, v, causal=True)
        e_twin, e_ref = _max_rel_err(out, twin), _max_rel_err(out, ref)
        print(f"flash h={h} kvh={kvh} d={d} s={s} {PALLAS}: vs twin "
              f"{e_twin:.3e}, vs fp64 {e_ref:.3e}, twin vs fp64 "
              f"{_max_rel_err(twin, ref):.3e}", flush=True)
        _check(e_twin <= TWIN_TOL, f"flash s={s} differs from its twin")
        _check(e_ref <= FP32_LEVEL, f"flash s={s} error {e_ref:.3e} vs fp64")


def check_paged(cfg, rng) -> None:
    h, kvh, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    npages = -(-(max(PROMPT_LENS) + NEW_TOKENS) // PAGE_SIZE)
    pool_pages = 1 + SLOTS * npages
    cap = npages * PAGE_SIZE
    # an empty-but-one cache, a mid-page end, and a full block-table row
    seq_lens = np.asarray([1, cap // 5, 2 * cap // 3 + 1, cap], np.int32)
    # each slot owns a disjoint, shuffled run of physical pages
    block_table = (1 + rng.permutation(SLOTS * npages)).reshape(SLOTS, npages)
    q = rng.standard_normal((SLOTS, h, d)).astype(np.float32)
    shape = (pool_pages, PAGE_SIZE, kvh, d)
    pools = {
        "bf16": (rng.standard_normal(shape), rng.standard_normal(shape),
                 None, None),
        "int8": (rng.integers(-127, 128, shape), rng.integers(-127, 128, shape),
                 rng.uniform(0.5, 2.0, pool_pages) / 127,
                 rng.uniform(0.5, 2.0, pool_pages) / 127),
    }
    for name, (kp, vp, ks, vs) in pools.items():
        dt = jnp.int8 if name == "int8" else jnp.bfloat16
        args = (jnp.asarray(q), jnp.asarray(kp, dt), jnp.asarray(vp, dt),
                jnp.asarray(block_table, jnp.int32), jnp.asarray(seq_lens))
        scales = dict(k_scales=None if ks is None else jnp.asarray(ks, jnp.float32),
                      v_scales=None if vs is None else jnp.asarray(vs, jnp.float32))
        out = paged_decode_attention_pallas(*args, policy=PALLAS,
                                            interpret=False, **scales)
        twin = paged_decode_attention_xla(*args, policy="bf16x6", **scales)
        err = _max_rel_err(out, twin)
        print(f"paged decode {name} pool h={h} kvh={kvh} d={d} page="
              f"{PAGE_SIZE} lens={seq_lens.tolist()} {PALLAS}: vs twin "
              f"{err:.3e}", flush=True)
        _check(err <= TWIN_TOL, f"paged decode ({name} pool) differs from "
               f"its twin")


# ---------------------------------------------------------------------------
# Phase 2-3: serving streams and prefill logits
# ---------------------------------------------------------------------------

def make_prompts(cfg, seed: int = SEED):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).tolist() for n in PROMPT_LENS]


def _policy(name):
    return policy_scope(name) if name else contextlib.nullcontext()


def serve_stream(cfg, params, prompts, policy=None, mesh=None):
    """Serve ``prompts`` twice through one engine: cold (compiles) then
    warm.  Returns (streams of the warm pass, plan records, timings)."""
    max_seq = max(len(p) for p in prompts) + NEW_TOKENS
    with _policy(policy), tcec.trace_plans() as log:
        eng = PagedServingEngine(cfg, params, page_size=PAGE_SIZE,
                                 max_concurrency=SLOTS, max_seq_len=max_seq,
                                 mesh=mesh)
        walls, streams = [], None
        for _ in range(2):
            rids = [eng.submit(p, NEW_TOKENS) for p in prompts]
            t0 = time.perf_counter()
            done = eng.run()
            walls.append(time.perf_counter() - t0)
            streams = [done[r] for r in rids]
    for rid, toks in enumerate(streams):
        _check(len(toks) == NEW_TOKENS
               and all(0 <= t < cfg.vocab for t in toks),
               f"request {rid} returned {len(toks)} tokens under "
               f"{policy or 'default'}")
    n_tok = len(prompts) * NEW_TOKENS
    timing = {"cold_s": walls[0], "warm_s": walls[1],
              "compile_s": walls[0] - walls[1], "tok_s": n_tok / walls[1]}
    return streams, log, timing


def prefill_logits(cfg, params, tokens, policy=None):
    with _policy(policy):
        logits, _ = jax.jit(lambda p, t: prefill(p, {"tokens": t}, cfg))(
            params, tokens)
    return logits


def rel_l2(out, ref):
    out = np.asarray(out, np.float64)
    ref = np.asarray(ref, np.float64)
    return np.linalg.norm(out - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


def check_logits(label, logits, ref_logits):
    err = rel_l2(logits, ref_logits)
    print(f"prefill logits {label} vs fp32 reference: rel L2 "
          f"{[float(f'{e:.3e}') for e in err]} (tol {LOGIT_TOL})", flush=True)
    _check(float(err.max()) <= LOGIT_TOL,
           f"{label} prefill logits rel L2 {err.max():.3e} > {LOGIT_TOL}")


def _print_timing(label, timing):
    print(f"[informational smoke figures, not a benchmark] {label}: "
          f"cold {timing['cold_s']:.2f} s, warm {timing['warm_s']:.2f} s, "
          f"compile ~{timing['compile_s']:.2f} s, warm "
          f"{timing['tok_s']:.1f} tok/s", flush=True)


def _reference(cfg, params, prompts):
    """The two 64-token prompts as one batch, and their fp32 logits."""
    short = [p for p in prompts if len(p) == min(PROMPT_LENS)]
    tokens = jnp.asarray(short, jnp.int32)
    ref = jax.jit(reference_logits, static_argnums=2)(params, tokens, cfg)
    return tokens, ref


def run_one_chip(cfg, params) -> None:
    rng = np.random.default_rng(SEED)
    check_matmul(cfg, rng)
    check_flash(cfg, rng)
    check_paged(cfg, rng)

    prompts = make_prompts(cfg)
    ref_tokens, ref_logits = _reference(cfg, params, prompts)
    for policy in (None, PALLAS):
        label = policy or "default policies"
        _, log, timing = serve_stream(cfg, params, prompts, policy)
        backends = {}
        for rec in log:
            backends.setdefault(rec.site, set()).add(rec.backend)
        print(f"serve {label}: {len(prompts)} requests x {NEW_TOKENS} "
              f"tokens, sites {dict(sorted((k, sorted(v)) for k, v in backends.items()))}",
              flush=True)
        if policy == PALLAS:
            for site in ("attn", "ffn"):
                _check(backends.get(site) == {"pallas"},
                       f"{PALLAS} did not put site {site!r} on the Pallas "
                       f"kernel: {backends.get(site)}")
        _print_timing(f"serve {label}", timing)
        check_logits(label, prefill_logits(cfg, params, ref_tokens, policy),
                     ref_logits)


def _bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def run_four_chips(cfg, params) -> None:
    devices = jax.devices()
    _check(len(devices) == 4, f"--four-chips needs 4 devices, found "
           f"{len(devices)}")
    prompts = make_prompts(cfg)
    ref_tokens, ref_logits = _reference(cfg, params, prompts)
    single, _, t_single = serve_stream(cfg, params, prompts)
    _print_timing("serve one device", t_single)

    mesh = make_mesh((2, 2), ("data", "model"))
    before = _bytes_in_use(devices)
    sharded = jax.device_put(
        params, shd.shardings_of(shd.param_pspecs(cfg, mesh), mesh))
    jax.block_until_ready(sharded)
    held = [a - b for a, b in zip(_bytes_in_use(devices), before)]
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"sharded params: {param_bytes} bytes in all; bytes added per "
          f"device {held}", flush=True)
    _check(min(held) >= param_bytes / 8,
           f"a device holds less than half its share of the sharded params: "
           f"{held}")
    meshed, _, t_mesh = serve_stream(cfg, sharded, prompts, mesh=mesh)
    _print_timing("serve 2x2 mesh", t_mesh)
    with mesh, activation_sharding(mesh):
        mesh_logits = prefill_logits(cfg, sharded, ref_tokens)
    one_logits = prefill_logits(cfg, params, ref_tokens)
    check_logits("one device", one_logits, ref_logits)
    check_logits("2x2 mesh", mesh_logits, ref_logits)
    print(f"prefill logits 2x2 mesh vs one device: rel L2 "
          f"{rel_l2(mesh_logits, one_logits).tolist()}", flush=True)
    same = sum(a == b for s, m in zip(single, meshed) for a, b in zip(s, m))
    first = sum(s[0] == m[0] for s, m in zip(single, meshed))
    print(f"token agreement mesh vs one device: {same}/"
          f"{len(prompts) * NEW_TOKENS} tokens, first tokens {first}/"
          f"{len(prompts)} (greedy streams part at near-ties once the "
          f"all-reduce order changes the last bits)", flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 mesh phase against one device")
    args = ap.parse_args(argv)
    use_compile_cache()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {device}", flush=True)
    _check(dev.platform == "tpu", f"no TPU: JAX platform is {dev.platform!r}")

    cfg = get_config(ARCH)
    params = init_params(jax.random.PRNGKey(SEED), cfg)
    if args.four_chips:
        run_four_chips(cfg, params)
    else:
        run_one_chip(cfg, params)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
