"""Paged vs dense serving: tokens/sec and decode-time cache-bytes-touched.

The serving analogue of the paper's staging analysis: dense decode streams
``batch x max_len`` of KV per step whether or not positions hold tokens;
paged decode streams only the *allocated* pages.  For a mixed-length
request stream the touched-bytes ratio is the mean occupancy of the dense
cache — the bandwidth the paged layout hands back to the memory-bound
decode kernel.  Timings run the reduced config on CPU (relative, not
absolute, numbers); the bytes rows are analytic from the request stream.
"""
import time

import numpy as np

def _timed_stream(cfg, params, prompts, gen_steps, *, page_size,
                  speculative=None):
    """Serve ``prompts`` twice through ONE engine — the first pass warms
    every jitted step (compiles dominate CPU wall time and would drown the
    decode-loop difference speculation targets), the second is timed.
    Returns (streams in submission order, timed-pass seconds, spec stats).
    """
    from repro.serving import PagedServingEngine
    max_seq = max(len(p) for p in prompts) + gen_steps + 1
    eng = PagedServingEngine(cfg, params, page_size=page_size,
                             max_concurrency=len(prompts),
                             max_seq_len=max_seq, speculative=speculative)
    for p in prompts:
        eng.submit(p, gen_steps)
    eng.run()
    rids = [eng.submit(p, gen_steps) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run()
    dt = time.perf_counter() - t0
    stats = eng.spec_stats.as_dict() if eng.spec_stats is not None else {}
    return [out[r] for r in rids], dt, stats


def _spec_rows(cfg, params, rng):
    """Speculative vs plain decode on a repetitive-continuation stream.

    The prompts repeat a short token pattern, and greedy decode of the
    tiny random-weight config locks into short cycles — both are exactly
    what the prompt-lookup proposer catches, so the accept rate is high
    and the verify tick commits several tokens for ~one tick's worth of
    weight/pool traffic.  Streams are asserted bitwise-identical to the
    plain engine per policy (the acceptance contract), so the rows
    measure pure wall-clock, not quality drift."""
    import dataclasses

    import jax
    from repro.core.context import policy_scope
    from repro.models import init_params
    from repro.spec import SpecConfig

    page_size, gen_steps = 8, 16
    pat = [list(rng.integers(0, cfg.vocab, 3)) for _ in range(4)]
    prompts = [p * 5 for p in pat]              # 15-token repeating prompts

    rows = []
    for policy in ("fp32_vpu", "bf16x6"):
        with policy_scope(policy):
            base, base_dt, _ = _timed_stream(cfg, params, prompts, gen_steps,
                                             page_size=page_size)
            spec, spec_dt, st = _timed_stream(
                cfg, params, prompts, gen_steps, page_size=page_size,
                speculative=SpecConfig(k=4, proposer="ngram"))
        assert base == spec, \
            f"speculative stream diverged from baseline under {policy}"
        n_tok = sum(len(s) for s in spec)
        rows.append((f"{policy}.spec_ngram_tok_s", n_tok / spec_dt))
        rows.append((f"{policy}.spec_ngram_speedup", base_dt / spec_dt))
        rows.append((f"{policy}.spec_ngram_accept_rate",
                     st["spec_accept_rate"]))
        rows.append((f"{policy}.spec_ngram_tokens_per_tick",
                     st["spec_tokens_per_tick"]))

    # draft-model proposer: a 1-layer slice of the same architecture with
    # fresh random params — a deliberately weak draft, so these rows
    # track the verify machinery's overhead at low accept rates rather
    # than a tuned draft's speedup.
    draft_cfg = dataclasses.replace(cfg, name=cfg.name + "-draft", n_layers=1)
    draft_params = init_params(jax.random.PRNGKey(7), draft_cfg)
    with policy_scope("bf16x6"):
        base, base_dt, _ = _timed_stream(cfg, params, prompts, gen_steps,
                                         page_size=page_size)
        spec, spec_dt, st = _timed_stream(
            cfg, params, prompts, gen_steps, page_size=page_size,
            speculative=SpecConfig(k=4, proposer="draft",
                                   draft_cfg=draft_cfg,
                                   draft_params=draft_params))
    assert base == spec, "draft-spec stream diverged from baseline"
    rows.append(("spec_draft_tok_s", sum(len(s) for s in spec) / spec_dt))
    rows.append(("spec_draft_speedup", base_dt / spec_dt))
    rows.append(("spec_draft_accept_rate", st["spec_accept_rate"]))
    return rows


def _cache_bytes_per_step(cfg, lens, page_size, paged, quantized=False):
    """Bytes of K+V (or latent) cache read by one decode step.

    Only KV-bearing layers hold pages: the width sums over the *full*
    pattern (attn/mla mixers), times the pattern-group repeat count.
    Keying the width on ``pattern[0]`` and multiplying by ``n_layers``
    counted phantom KV bytes for the recurrent layers of hybrid
    attention+SSM patterns (whose state is per-slot, not paged).

    ``quantized`` prices the int8 pool: 1 byte per element plus one fp32
    scale per (page, pool leaf, group) — the per-page sidecar the kernel
    reads alongside each page.  Only meaningful with ``paged=True``."""
    width = 0
    n_pools = 0
    for spec in cfg.pattern:
        if spec.mixer == "mla":
            width += cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            n_pools += 2                        # c_pages + r_pages
        elif spec.mixer == "attn":
            width += 2 * cfg.n_kv_heads * cfg.head_dim_
            n_pools += 2                        # k_pages + v_pages
    if quantized:
        dt = 1
    else:
        dt = np.dtype("float32").itemsize if cfg.param_dtype == "float32" \
            else 2
    per_tok = width * dt * cfg.n_groups
    if paged:
        pages = sum(-(-n // page_size) for n in lens)
        scale_b = pages * n_pools * 4 * cfg.n_groups if quantized else 0
        return pages * page_size * per_tok + scale_b
    return len(lens) * max(lens) * per_tok


def run():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core.context import policy_scope
    from repro.launch.serve import generate, generate_paged
    from repro.models import init_params

    cfg = get_config("qwen2-0.5b", reduced=True)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    page_size, gen_steps, batch = 8, 4, 4
    lens = [5, 12, 8, 3]
    prompts = [list(rng.integers(0, cfg.vocab, n)) for n in lens]
    max_len = max(lens) + gen_steps + 1

    rows = []
    for policy in ("bf16x1", "bf16x6", "fp32_vpu"):
        with policy_scope(policy):
            # dense: one uniform batch padded to the longest prompt.
            # tok/s for BOTH paths is end-to-end wall time around the call
            # (prefill + compiles + decode loop) so the rows are
            # methodologically comparable — generate()'s internal
            # decode-only tok/s would flatter the dense path.
            tokens = jnp.asarray(
                [p + [0] * (max(lens) - len(p)) for p in prompts], jnp.int32)
            t0 = time.perf_counter()
            _, _ = generate(cfg, params, tokens, max_len, gen_steps)
            dt = time.perf_counter() - t0
            rows.append((f"{policy}.dense_serve_us", dt * 1e6))
            rows.append((f"{policy}.dense_tok_s", batch * gen_steps / dt))
            t0 = time.perf_counter()
            out, _ = generate_paged(cfg, params, prompts, gen_steps,
                                    page_size=page_size,
                                    max_concurrency=batch)
            dt = time.perf_counter() - t0
            rows.append((f"{policy}.paged_serve_us", dt * 1e6))
            rows.append((f"{policy}.paged_tok_s",
                         sum(len(v) for v in out.values()) / dt))

    # prefix caching: a shared-prefix stream (one system prompt, distinct
    # tails) served cold vs with --prefix-cache.  Hit rate / skipped
    # prefill tokens come from the scheduler's counters; the token streams
    # are bitwise-identical either way, so the rows isolate the prefill
    # work the cache removes.
    shared = list(rng.integers(0, cfg.vocab, 2 * page_size + 3))
    pc_prompts = [shared + list(rng.integers(0, cfg.vocab, k))
                  for k in (2, 5, 1, 7)]
    # 2 slots for 4 requests: later admissions happen after earlier
    # prefills complete and registered their pages — with full residency
    # every request would admit on tick 1, before anything is cached.
    with policy_scope("bf16x6"):
        t0 = time.perf_counter()
        cold_out, _ = generate_paged(cfg, params, pc_prompts, gen_steps,
                                     page_size=page_size,
                                     max_concurrency=2,
                                     prefill_chunk=page_size)
        rows.append(("prefix_cold_serve_us",
                     (time.perf_counter() - t0) * 1e6))
        stats = {}
        t0 = time.perf_counter()
        hot_out, _ = generate_paged(cfg, params, pc_prompts, gen_steps,
                                    page_size=page_size,
                                    max_concurrency=2,
                                    prefill_chunk=page_size,
                                    prefix_cache=True, stats=stats)
        rows.append(("prefix_cached_serve_us",
                     (time.perf_counter() - t0) * 1e6))
    assert cold_out == hot_out, "prefix cache changed the token streams"
    rows.append(("prefix_hit_rate", stats["hit_rate"]))
    rows.append(("prefill_tokens_skipped", stats["cached_tokens"]))
    rows.append(("prefix_shared_pages", stats["shared_pages"]))
    rows.append(("prefix_boundary_copies", stats["boundary_copies"]))

    # analytic decode-traffic comparison at the end of generation
    final = [n + gen_steps for n in lens]
    dense_b = _cache_bytes_per_step(cfg, final, page_size, paged=False)
    paged_b = _cache_bytes_per_step(cfg, final, page_size, paged=True)
    rows.append(("dense_cache_bytes_per_step", dense_b))
    rows.append(("paged_cache_bytes_per_step", paged_b))
    rows.append(("paged_traffic_ratio", paged_b / dense_b))
    # the same stream at production shapes (full config, 8k context cap):
    full = get_config("qwen2-0.5b")
    prod_lens = [257, 1891, 733, 94]
    rows.append(("prod_paged_traffic_ratio",
                 _cache_bytes_per_step(full, prod_lens, 64, True)
                 / _cache_bytes_per_step(full, [8192] * 4, 64, False)))

    # quantized KV: int8 page payloads + per-page fp32 scales.  The decode
    # stream reads half the payload bytes of the bf16 pool (a quarter of
    # dense fp32) plus a ~1% scale sidecar; tok/s is measured on the same
    # stream so regressions in the dequantizing gather show up here.
    with policy_scope("bf16x6"):
        t0 = time.perf_counter()
        qout, _ = generate_paged(cfg, params, prompts, gen_steps,
                                 page_size=page_size, max_concurrency=batch,
                                 quantized_kv=True)
        dt = time.perf_counter() - t0
    rows.append(("kv_quant_serve_us", dt * 1e6))
    rows.append(("kv_quant_tok_s", sum(len(v) for v in qout.values()) / dt))
    quant_b = _cache_bytes_per_step(cfg, final, page_size, paged=True,
                                    quantized=True)
    rows.append(("kv_quant_cache_bytes_per_step", quant_b))
    rows.append(("kv_quant_traffic_ratio", quant_b / dense_b))
    rows.append(("kv_quant_vs_paged_ratio", quant_b / paged_b))
    rows.append(("prod_kv_quant_traffic_ratio",
                 _cache_bytes_per_step(full, prod_lens, 64, True,
                                       quantized=True)
                 / _cache_bytes_per_step(full, [8192] * 4, 64, False)))

    rows.extend(_spec_rows(cfg, params, rng))
    return rows


if __name__ == "__main__":
    for k, v in run():
        print(k, v)
