"""Batched serving launcher: prefill + decode with dense OR paged KV caches.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b --reduced \
        --batch 4 --prompt-len 16 --gen 32

Dense mode demonstrates the classic serving path on any mesh: sharded
params, prefill emits caches, decode_step consumes/updates them in place
(donated buffers).  ``--paged`` switches to the continuous-batching engine
(``repro.serving.PagedServingEngine``): KV lives in fixed-size pages of a
shared pool addressed through per-request block tables, so decode stages
only *allocated* cache instead of ``batch x max_len`` dense buffers —
``--page-size`` sets the page granularity (16–64 tokens is the sweet spot:
small enough that a short request wastes < 1 page of slack, large enough
that the gather's DMA blocks stay MXU/VMEM-aligned) and
``--max-concurrency`` the number of decode slots requests are multiplexed
onto.

The ``--policy`` / ``--site-policy`` flags reach every TCEC site including
attention on BOTH paths: ``--site-policy attn=bf16x6`` runs fp32-accurate
QK^T/PV in prefill AND (paged or dense) decode — one split schedule
everywhere — and ``--policy bf16x6_pallas`` additionally routes prefill
attention through the fused flash kernel and paged decode through the
fused paged-attention kernel (block-table gathers inside the kernel body).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, ARCH_IDS
from repro.data.pipeline import make_frontend_inputs
from repro.launch import (add_policy_args, policy_scope_from_args,
                          use_compile_cache)
from repro.launch.mesh import make_host_mesh, make_mesh, parse_mesh_shape
from repro.models import init_params, prefill, decode_step, init_decode_caches
from repro.models.base import activation_sharding
from repro.models.model import decode_cache_axes
from repro.parallel import sharding as shd


def write_prefill_caches(caches, prefill_caches, cfg=None, axes=None):
    """Insert prompt-length prefill caches into max-length decode caches.

    The sequence axis of every cache leaf is *explicit*: ``axes`` is a tree
    of logical-axis-name tuples matching the cache tree (derived from the
    config via ``model.decode_cache_axes`` when ``cfg`` is given), and the
    write targets the axis labeled ``"seq"``.  Leaves without a sequence
    axis (recurrent states) must match shapes exactly — a mismatch raises
    instead of silently passing the wrong-shaped cache through, which is
    what the old ndim/prefix-matching heuristic did when a cache's feature
    dim collided with the prompt length (e.g. an MLA latent cache with
    ``kv_lora_rank == prompt_len``).
    """
    if axes is None:
        if cfg is None:
            raise TypeError("write_prefill_caches needs cfg (to derive each "
                            "leaf's seq axis) or an explicit axes tree")
        axes = decode_cache_axes(cfg)

    def write(dst, src, ax):
        ax = tuple(ax)
        if "seq" in ax:
            axis = ax.index("seq")
            if src.shape[axis] > dst.shape[axis]:
                raise ValueError(
                    f"prefill cache seq length {src.shape[axis]} exceeds "
                    f"decode cache capacity {dst.shape[axis]} (axes {ax})")
            return jax.lax.dynamic_update_slice_in_dim(
                dst, src.astype(dst.dtype), 0, axis=axis)
        if src.shape != dst.shape:
            raise ValueError(
                f"cache leaf without a seq axis must match shapes exactly: "
                f"prefill {src.shape} vs decode {dst.shape} (axes {ax})")
        return src.astype(dst.dtype)

    def rec(dst, src, ax):
        if isinstance(dst, dict):
            return {k: rec(dst[k], src[k], ax[k]) for k in dst}
        return write(dst, src, ax)

    return rec(caches, prefill_caches, axes)


def generate(cfg, params, tokens, max_len, gen_steps, batch_extras=None,
             greedy=True, rng=None):
    """Prefill + decode loop.  Returns (generated tokens, tokens/sec)."""
    b, prompt_len = tokens.shape
    batch = {"tokens": tokens}
    batch.update(batch_extras or {})
    logits, pf_caches = jax.jit(
        lambda p, bt: prefill(p, bt, cfg))(params, batch)
    caches = init_decode_caches(cfg, b, max_len)
    caches = write_prefill_caches(caches, pf_caches, cfg)

    step_fn = jax.jit(
        lambda p, t, c, i: decode_step(p, t, c, i, cfg),
        donate_argnums=(2,))

    out = []
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    n_prompt = prompt_len + (cfg.vision_tokens or 0)
    t0 = time.time()
    for i in range(gen_steps):
        out.append(tok)
        logits, caches = step_fn(params, tok, caches,
                                 jnp.int32(n_prompt + i))
        if greedy:
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        else:
            rng, k = jax.random.split(rng)
            tok = jax.random.categorical(k, logits)[:, None].astype(jnp.int32)
    jax.block_until_ready(tok)
    dt = time.time() - t0
    return jnp.concatenate(out, axis=1), b * gen_steps / dt


def generate_paged(cfg, params, prompts, gen_steps, *, page_size=16,
                   max_concurrency=4, prefill_chunk=None,
                   prefix_cache=False, mesh=None, stats=None,
                   speculative=None, quantized_kv=False):
    """Continuous-batching generation over paged caches.

    ``prompts`` is a list of token lists (mixed lengths welcome — that is
    the point).  ``prefix_cache=True`` shares cached prompt-prefix pages
    across requests (refcounted, copy-on-write boundary pages) and skips
    their prefill; pass a dict as ``stats`` to receive the scheduler's
    cache counters (``hit_rate``, ``cached_tokens``, ...) and — with
    ``speculative=SpecConfig(...)`` — the engine's accept-rate counters
    (``spec_accept_rate``, ``spec_tokens_per_tick``, ...).  ``mesh``
    (a ``("data", "model")`` mesh) runs every batched model step SPMD over
    the devices — tensor-parallel params/pools per the logical-axis rules,
    host scheduler untouched, token streams identical to the single-device
    engine.  ``speculative`` (a ``repro.spec.SpecConfig``) commits up to
    ``k + 1`` tokens per decode tick with streams bitwise-identical per
    policy to the plain engine.  ``quantized_kv=True`` stores KV pages as
    int8 with per-page fp32 scales (~2-4x fewer decode cache bytes at a
    bounded logit perturbation; off by default — the off path is bitwise-
    identical to an engine without the feature).  Returns ({rid: tokens},
    tokens/sec)."""
    from repro.serving import PagedServingEngine
    max_seq = max(len(p) for p in prompts) + gen_steps + 1
    eng = PagedServingEngine(cfg, params, page_size=page_size,
                             max_concurrency=max_concurrency,
                             max_seq_len=max_seq,
                             prefill_chunk=prefill_chunk,
                             prefix_cache=prefix_cache, mesh=mesh,
                             speculative=speculative,
                             quantized_kv=quantized_kv)
    for pr in prompts:
        eng.submit(pr, gen_steps)
    t0 = time.time()
    out = eng.run()
    dt = time.time() - t0
    if stats is not None:
        stats.update(eng.scheduler.prefix_stats)
        if eng.spec_stats is not None:
            stats.update(eng.spec_stats.as_dict())
    n_tok = sum(len(v) for v in out.values())
    return out, n_tok / dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCH_IDS), default="qwen2-0.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="serve through the paged KV cache + continuous-"
                         "batching engine (repro.serving) instead of dense "
                         "per-request max_len caches")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (paged mode)")
    ap.add_argument("--max-concurrency", type=int, default=4,
                    help="decode slots the paged engine multiplexes "
                         "requests onto")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunk long prefills to this many tokens per "
                         "engine step (paged mode, attention archs)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="share cached prompt-prefix pages across requests "
                         "(paged mode, attention archs): admission installs "
                         "matching pages by reference, clones only the "
                         "copy-on-write boundary page, and prefill starts "
                         "at the first uncached position")
    ap.add_argument("--spec-ngram", action="store_true",
                    help="speculative decoding with the self-speculative "
                         "n-gram/prompt-lookup proposer (paged mode): up to "
                         "--spec-k tokens verified per slot per tick, token "
                         "streams bitwise-identical to the plain engine")
    ap.add_argument("--spec-draft", default=None, metavar="ARCH",
                    help="speculative decoding with a draft-model proposer "
                         "(paged mode): the named arch (reduced, fresh "
                         "random params — pair with --reduced targets) "
                         "drafts greedily, the target verifies")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max draft tokens verified per slot per tick")
    ap.add_argument("--quantized-kv", action="store_true",
                    help="store paged KV as int8 pages with per-page fp32 "
                         "scales (paged mode): ~2-4x fewer decode cache "
                         "bytes at a bounded logit perturbation")
    ap.add_argument("--mesh", default=None, metavar="DATAxMODEL",
                    help="device mesh shape, e.g. 4x2 (data=4, model=2): "
                         "params/pools shard by the logical-axis rules and "
                         "the batched steps run SPMD over the mesh.  The "
                         "default all-devices (n, 1) host mesh never "
                         "exercises tensor parallelism — pass an explicit "
                         "model dim (with XLA_FLAGS=--xla_force_host_"
                         "platform_device_count=N on CPU) to turn it on")
    add_policy_args(ap)
    args = ap.parse_args(argv)
    use_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.mesh:
        mesh = make_mesh(parse_mesh_shape(args.mesh), ("data", "model"))
    else:
        mesh = make_host_mesh()
    rng = jax.random.PRNGKey(args.seed)
    params = init_params(rng, cfg)
    pspecs = shd.param_pspecs(cfg, mesh)
    params = jax.device_put(params, jax.tree.map(
        lambda p: NamedSharding(mesh, p), pspecs,
        is_leaf=lambda x: isinstance(x, P)))

    tokens = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                cfg.vocab, dtype=jnp.int32)
    if args.paged:
        # mixed-length stream: trim each prompt to a different length
        rs = np.random.default_rng(args.seed)
        lens = rs.integers(max(1, args.prompt_len // 2), args.prompt_len + 1,
                           args.batch)
        prompts = [list(np.asarray(tokens[i, :lens[i]])) for i in
                   range(args.batch)]
        if args.prefix_cache:
            # production-shaped stream: one shared "system prompt" ahead of
            # each request's own tail, so the cache has something to hit
            system = list(np.asarray(tokens[0, :max(1, args.prompt_len // 2)]))
            prompts = [system + p for p in prompts]
        spec = None
        if args.spec_ngram and args.spec_draft:
            ap.error("--spec-ngram and --spec-draft are mutually exclusive")
        if args.spec_ngram or args.spec_draft:
            from repro.spec import SpecConfig
            if args.spec_draft:
                draft_cfg = get_config(args.spec_draft, reduced=True)
                draft_params = init_params(jax.random.PRNGKey(args.seed + 1),
                                           draft_cfg)
                spec = SpecConfig(k=args.spec_k, proposer="draft",
                                  draft_cfg=draft_cfg,
                                  draft_params=draft_params)
            else:
                spec = SpecConfig(k=args.spec_k, proposer="ngram")
        stats = {}
        with policy_scope_from_args(args):
            out, tps = generate_paged(
                cfg, params, prompts, args.gen, page_size=args.page_size,
                max_concurrency=args.max_concurrency,
                prefill_chunk=args.prefill_chunk,
                prefix_cache=args.prefix_cache, mesh=mesh, stats=stats,
                speculative=spec, quantized_kv=args.quantized_kv)
        mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        print(f"generated {sum(len(v) for v in out.values())} tokens over "
              f"{len(out)} requests at {tps:.1f} tok/s (paged, "
              f"page={args.page_size}, slots={args.max_concurrency}, "
              f"mesh={mesh_shape})")
        if args.prefix_cache:
            print(f"prefix cache: hit rate {stats['hit_rate']:.1%} "
                  f"({stats['cached_tokens']}/{stats['prompt_tokens']} prompt "
                  f"tokens skipped, {stats['shared_pages']} pages shared, "
                  f"{stats['boundary_copies']} COW boundary copies)")
        if spec is not None:
            print(f"speculative ({spec.proposer}, k={spec.k}): accept rate "
                  f"{stats['spec_accept_rate']:.1%}, "
                  f"{stats['spec_tokens_per_tick']:.2f} tokens/tick over "
                  f"{stats['spec_ticks']} verify ticks")
        print("sample:", out[0][:16])
        return out

    extras = {k: jnp.asarray(v) for k, v in make_frontend_inputs(
        cfg, args.batch, 0, args.seed).items()}
    max_len = args.prompt_len + (cfg.vision_tokens or 0) + args.gen + 1
    with policy_scope_from_args(args), mesh, activation_sharding(mesh):
        gen, tps = generate(cfg, params, tokens, max_len, args.gen,
                            batch_extras=extras, greedy=True)
    print(f"generated {gen.shape} tokens at {tps:.1f} tok/s")
    print("sample:", np.asarray(gen[0][:16]))
    return gen


if __name__ == "__main__":
    main()
