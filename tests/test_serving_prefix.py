"""Prefix-cache correctness: radix-index matching semantics, refcounting
allocator accounting under page sharing, property/seed-sweep invariants
over shared-prefix request streams (no page leaked, no live page with two
writers, refcounts decompose into owner + sharers + index pin), and the
golden contract — prefix caching changes which physical page a read
resolves to, never a token stream, so cached and uncached engine output is
bitwise-identical per policy."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.serving import (NO_MATCH, PageAllocator, PrefixIndex, Request,
                           Scheduler)
from repro.serving.paged_cache import NULL_PAGE, pages_needed

try:        # property tests need hypothesis; the rest of the file does not
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                        # pragma: no cover
    HAVE_HYPOTHESIS = False

    def given(*a, **k):
        return lambda f: pytest.mark.skip("hypothesis not installed")(f)

    def settings(*a, **k):
        return lambda f: f

    class _StStub:
        def __getattr__(self, name):
            return lambda *a, **k: None
    st = _StStub()


# ---------------------------------------------------------------------------
# allocator: sharing, pinning, deferred free
# ---------------------------------------------------------------------------

def test_share_defers_free_until_refcount_zero():
    al = PageAllocator(6)
    pages = al.alloc(0, 2)
    al.share(1, pages)
    assert all(al.refcount(p) == 2 for p in pages)
    al.free(0)                       # owner gone, sharer keeps pages alive
    assert al.n_free == 3
    assert all(al.refcount(p) == 1 for p in pages)
    al.free(1)
    assert al.n_free == 5
    assert all(al.refcount(p) == 0 for p in pages)


def test_retain_release_pin_semantics():
    al = PageAllocator(4)
    [p, _] = al.alloc(0, 2)
    al.retain(p)
    assert al.refcount(p) == 2 and p in al.pinned
    with pytest.raises(ValueError):       # at most one pin per page
        al.retain(p)
    al.free(0)
    assert al.refcount(p) == 1            # pin alone keeps it live
    assert al.n_free == 2
    al.release(p)
    assert al.n_free == 3 and al.refcount(p) == 0


def test_share_and_retain_reject_dead_pages():
    al = PageAllocator(4)
    with pytest.raises(ValueError):
        al.share(0, [1])
    with pytest.raises(ValueError):
        al.retain(1)
    pages = al.alloc(0, 1)
    al.free(0)
    with pytest.raises(ValueError):       # freed -> dead again
        al.share(1, pages)


def test_unshare_all_rolls_back_failed_admission():
    al = PageAllocator(5)
    pages = al.alloc(0, 3)
    al.share(1, pages[:2])
    al.unshare_all(1)
    assert all(al.refcount(p) == 1 for p in pages)
    al.unshare_all(1)                     # idempotent on empty
    al.free(0)
    assert al.n_free == 4


# ---------------------------------------------------------------------------
# prefix index: match/register semantics
# ---------------------------------------------------------------------------

def _register(idx, al, rid, prompt):
    """Register ``prompt`` the way a completed prefill does: owner pages
    from the allocator, one index pin per page span."""
    pages = al.alloc(rid, pages_needed(len(prompt), idx.page_size))
    idx.register(prompt, pages, al)
    return pages


def test_cold_index_matches_nothing():
    idx = PrefixIndex(4)
    assert idx.match([1, 2, 3, 4, 5]) is NO_MATCH
    assert idx.n_nodes == 0


def test_full_chain_match_and_unrelated_tail():
    al = PageAllocator(16)
    idx = PrefixIndex(4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 9]
    pages = _register(idx, al, 0, prompt)        # 2 full nodes + 1 partial
    assert idx.n_nodes == 3
    # same 8-token prefix, tail sharing nothing with the partial span
    m = idx.match(prompt[:8] + [7, 7, 7])
    assert m.shared_pages == tuple(pages[:2])
    assert m.boundary_src is None and m.cached_upto == 8


def test_partial_span_match_yields_cow_boundary():
    al = PageAllocator(16)
    idx = PrefixIndex(4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 9]
    pages = _register(idx, al, 0, prompt)
    # diverges INSIDE the partial page: shares its first token (9)
    m = idx.match(prompt[:8] + [9, 5, 5])
    assert m.shared_pages == tuple(pages[:2])
    assert m.boundary_src == pages[2]            # clone source
    assert m.cached_upto == 9                    # 8 full + 1 matched in page


def test_identical_prompt_recomputes_exactly_one_token():
    al = PageAllocator(16)
    idx = PrefixIndex(4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 9]
    pages = _register(idx, al, 0, prompt)
    m = idx.match(prompt)
    assert m.shared_pages == tuple(pages[:2])
    assert m.boundary_src == pages[2]
    assert m.cached_upto == len(prompt) - 1      # always < len(prompt)


def test_page_aligned_full_coverage_demotes_last_page():
    al = PageAllocator(16)
    idx = PrefixIndex(4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]            # exactly 2 pages, no partial
    pages = _register(idx, al, 0, prompt)
    assert idx.n_nodes == 2
    m = idx.match(prompt)
    # the completing prefill chunk must still run >= 1 token for its
    # logits, and that run WRITES — the last page is a COW copy, not a ref
    assert m.shared_pages == (pages[0],)
    assert m.boundary_src == pages[1]
    assert m.cached_upto == 7


def test_shorter_prompt_never_cached_to_its_full_length():
    al = PageAllocator(16)
    idx = PrefixIndex(4)
    _register(idx, al, 0, [1, 2, 3, 4, 5, 6, 7, 8, 9, 9])
    # a 4-token prompt equal to the first cached page: demote, not full skip
    m = idx.match([1, 2, 3, 4])
    assert m.shared_pages == () and m.cached_upto == 3
    # a 2-token prompt lives inside a cached FULL page; full nodes match
    # whole spans only, so it stays cold (page-granularity contract)
    assert idx.match([1, 2]) is NO_MATCH


def test_register_is_idempotent_and_lru_touches():
    al = PageAllocator(16)
    idx = PrefixIndex(4)
    p0 = _register(idx, al, 0, [1, 2, 3, 4, 5])
    n = idx.n_nodes
    # same spans from another owner: only touched, duplicate pages die
    # with their owner
    p1 = al.alloc(1, 2)
    assert idx.register([1, 2, 3, 4, 5], p1, al) == 0
    assert idx.n_nodes == n
    assert idx.match([1, 2, 3, 4, 6]).shared_pages == (p0[0],)


def test_reclaim_is_lru_and_leaf_first_and_skips_live_pages():
    al = PageAllocator(16)
    idx = PrefixIndex(2)
    a = _register(idx, al, 0, [1, 2, 3, 4])      # chain: [1,2] -> [3,4]
    b = _register(idx, al, 1, [5, 6])            # independent leaf
    idx.match([5, 6, 9])                         # touch b: a's leaf is LRU
    al.free(0)
    al.free(1)                                   # only index pins remain
    assert al.n_free == 16 - 1 - 3               # 3 pinned pages live
    freed = idx.reclaim(al, al.n_free + 1)
    assert freed == 1
    # LRU leaf was a's [3,4] tail, NOT its root (leaf-first) and NOT b
    assert idx.match([1, 2, 9]).shared_pages == (a[0],)
    assert idx.match([5, 6, 9]).shared_pages == (b[0],)
    # pages still referenced by a live request are never reclaimed
    c = _register(idx, al, 2, [7, 8])
    freed = idx.reclaim(al, 99)
    assert al.refcount(c[0]) == 2                # owner + pin survive
    assert idx.match([7, 8, 9]).shared_pages == (c[0],)


# ---------------------------------------------------------------------------
# property: page accounting under sharing across shared-prefix streams
# ---------------------------------------------------------------------------

def _check_sharing_invariants(sched: Scheduler, num_pages: int):
    al = sched.allocator
    live = sorted(al._ref)
    # free list + live pages partition 1..num_pages-1 (no leak, no alias)
    assert sorted(live + al._free) == list(range(1, num_pages))
    assert all(al.refcount(p) >= 1 for p in live)
    # a live page has at most ONE writer
    flat = [p for pages in al._owned.values() for p in pages]
    assert len(flat) == len(set(flat))
    assert NULL_PAGE not in flat and NULL_PAGE not in al.pinned
    # every refcount decomposes exactly: owner + sharers + index pin
    for p in live:
        holds = sum(pages.count(p) for pages in al._owned.values())
        holds += sum(pages.count(p) for pages in al._shared.values())
        holds += int(p in al.pinned)
        assert al.refcount(p) == holds, p
    # block-table structure: shared head (read-only refs), private tail
    for rid, stt in sched.active.items():
        row = stt.block_row
        expect = row[:stt.n_shared] + (
            [stt.boundary_src] if stt.boundary_src is not None else [])
        assert al.shared(rid) == expect
        assert row[stt.n_shared:] == al.owned(rid)
        assert stt.cached_upto >= stt.n_shared * sched.page_size
        assert stt.cached_upto < len(stt.req.prompt)


def _drive_prefix_stream(draw_int, draw_bool, num_pages, page_size, slots,
                         chunk, max_pages_per_seq):
    """Random shared-prefix admit/diverge/evict stream against a fake
    executor, checking the sharing invariants after every tick.  Prompts
    come from a 3-token alphabet with a common base prefix so full-chain,
    boundary-COW and demote matches all occur.  Returns total cached
    tokens (so sweeps can assert sharing actually happened)."""
    sched = Scheduler(num_pages=num_pages, page_size=page_size,
                      max_concurrency=slots,
                      max_pages_per_seq=max_pages_per_seq,
                      prefill_chunk=chunk, prefix_cache=True)
    base = [draw_int(1, 3) for _ in range(draw_int(1, 3 * page_size))]
    n_requests = draw_int(2, 8)
    submitted = 0
    rejected = 0
    for step in range(300):
        while submitted + rejected < n_requests and draw_bool():
            rid = submitted + rejected
            prompt = base[:draw_int(1, len(base))] \
                + [draw_int(1, 3) for _ in range(draw_int(0, 4))]
            req = Request(rid=rid, prompt=prompt,
                          max_new_tokens=draw_int(1, 4))
            need = pages_needed(req.max_len, page_size)
            if need > sched.max_pages_per_seq or need >= num_pages:
                rejected += 1     # can never fit: would starve the queue
            else:
                sched.submit(req)
                submitted += 1
        plan = sched.step()
        for c in plan.prefill:
            assert c.start >= c.cached_upto >= 0
            sched.record_prefill(c.rid, c.end,
                                 first_token=7 if c.last else None)
        for rid, slot in plan.decode:
            sched.record_decode(rid, 7)
        _check_sharing_invariants(sched, num_pages)
        if sched.done and submitted + rejected == n_requests:
            break
    assert sched.done, "stream did not drain"
    assert len(sched.completed) == submitted
    al = sched.allocator
    # drained: every live page is held by the index alone (refcount 1, one
    # pin); free + pinned partition the pool
    assert al.n_free + len(al.pinned) == num_pages - 1
    assert all(al.refcount(p) == 1 for p in al.pinned)
    assert sched.stats["cached_tokens"] <= sched.stats["prompt_tokens"]
    return sched.stats["cached_tokens"]


@settings(max_examples=60, deadline=None)
@given(
    data=st.data(),
    num_pages=st.integers(4, 14),
    page_size=st.integers(1, 5),
    slots=st.integers(1, 3),
    chunk=st.one_of(st.none(), st.integers(1, 4)),
)
def test_prefix_sharing_never_leaks_or_double_writes(data, num_pages,
                                                     page_size, slots, chunk):
    """Property form: hypothesis drives the shared-prefix stream."""
    _drive_prefix_stream(
        lambda lo, hi: data.draw(st.integers(lo, hi)),
        lambda: data.draw(st.booleans()),
        num_pages, page_size, slots, chunk,
        max_pages_per_seq=data.draw(st.integers(1, 5)))


def test_prefix_sharing_invariants_seed_sweep():
    """The same driver over a deterministic seed sweep — keeps the
    invariant coverage alive even where hypothesis is unavailable — and
    asserts the sweep exercised actual sharing (cached tokens > 0)."""
    total_cached = 0
    for seed in range(25):
        rng = np.random.default_rng(seed)
        total_cached += _drive_prefix_stream(
            lambda lo, hi: int(rng.integers(lo, hi + 1)),
            lambda: bool(rng.integers(0, 2)),
            num_pages=int(rng.integers(4, 15)),
            page_size=int(rng.integers(1, 6)),
            slots=int(rng.integers(1, 4)),
            chunk=None if rng.integers(0, 2) else int(rng.integers(1, 5)),
            max_pages_per_seq=int(rng.integers(1, 6)))
    assert total_cached > 0, "sweep never hit the prefix cache"


# ---------------------------------------------------------------------------
# golden: cached and uncached engines emit bitwise-identical streams
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs.base import ArchConfig, BlockSpec
    return ArchConfig(
        name="tiny-serve", family="dense", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2, d_ff=64, vocab=128,
        pattern=(BlockSpec("attn", "dense"),), qkv_bias=True,
        tie_embeddings=True, remat="none")


@pytest.fixture(scope="module")
def tiny_model():
    from repro.models import init_params
    cfg = _tiny_cfg()
    return cfg, init_params(jax.random.PRNGKey(0), cfg)


def _shared_prefix_stream(cfg):
    """One 11-token system prefix, three divergent tails, one exact
    duplicate — hits full-chain, boundary-COW and identical-prompt cases
    once admissions serialize over 2 slots."""
    rng = np.random.default_rng(0)
    shared = list(rng.integers(0, cfg.vocab, 11))
    prompts = [shared + list(rng.integers(0, cfg.vocab, k))
               for k in (3, 5, 2)]
    prompts.append(list(prompts[0]))
    return prompts


def _run_engine(cfg, params, prompts, gens, *, prefix_cache, prefill_chunk,
                **kw):
    from repro.serving import PagedServingEngine
    eng = PagedServingEngine(cfg, params, page_size=4, max_concurrency=2,
                             max_seq_len=24, prefill_chunk=prefill_chunk,
                             prefix_cache=prefix_cache, **kw)
    for p, g in zip(prompts, gens):
        eng.submit(p, g)
    return eng, eng.run()


@pytest.mark.parametrize("policy", ["fp32_vpu", "bf16x1", "bf16x6"])
def test_prefix_cached_streams_bitwise_match_uncached(tiny_model, policy):
    """The acceptance gate: per policy, the engine with prefix caching ON
    emits byte-identical token streams to the engine with it OFF, while
    actually skipping prefill work (cached_tokens > 0)."""
    from repro.core.context import policy_scope
    cfg, params = tiny_model
    prompts = _shared_prefix_stream(cfg)
    gens = [4, 3, 5, 4]
    with policy_scope(policy):
        _, cold = _run_engine(cfg, params, prompts, gens,
                              prefix_cache=False, prefill_chunk=4)
        eng, hot = _run_engine(cfg, params, prompts, gens,
                               prefix_cache=True, prefill_chunk=4)
    assert hot == cold
    stats = eng.scheduler.prefix_stats
    assert stats["cached_tokens"] > 0 and stats["hit_rate"] > 0
    assert stats["shared_pages"] > 0
    # the exact-duplicate prompt must produce a COW boundary copy
    assert stats["boundary_copies"] > 0


def test_prefix_cached_matches_single_request_golden(tiny_model):
    """Under fp32_vpu every cached stream equals the single-request dense
    ``generate()`` output — transitively pins cached == uncached == dense,
    including with single-shot (unchunked) prefill, which prefix caching
    reroutes through the paged multi-token path."""
    from repro.core.context import policy_scope
    from repro.launch.serve import generate
    cfg, params = tiny_model
    prompts = _shared_prefix_stream(cfg)
    gens = [4, 3, 5, 4]
    with policy_scope("fp32_vpu"):
        for chunk in (None, 4):
            eng, out = _run_engine(cfg, params, prompts, gens,
                                   prefix_cache=True, prefill_chunk=chunk)
            assert eng.scheduler.prefix_stats["cached_tokens"] > 0
            for rid, (p, g) in enumerate(zip(prompts, gens)):
                ref, _ = generate(cfg, params,
                                  jnp.asarray([p], jnp.int32),
                                  len(p) + g + 1, g)
                assert out[rid] == [int(t) for t in np.asarray(ref[0])], rid


def test_prefix_cached_golden_under_page_backpressure(tiny_model):
    """A tight pool forces index reclaim during admission; streams still
    match the uncached engine and no page leaks."""
    from repro.core.context import policy_scope
    cfg, params = tiny_model
    prompts = _shared_prefix_stream(cfg)
    gens = [4, 3, 5, 4]
    with policy_scope("fp32_vpu"):
        _, cold = _run_engine(cfg, params, prompts, gens,
                              prefix_cache=False, prefill_chunk=4,
                              num_pages=1 + 2 * 6)
        eng, hot = _run_engine(cfg, params, prompts, gens,
                               prefix_cache=True, prefill_chunk=4,
                               num_pages=1 + 2 * 6)
    assert hot == cold
    al = eng.scheduler.allocator
    assert al.n_free + len(al.pinned) == al.num_pages - 1


def test_chunked_prefill_compile_count_is_bounded(tiny_model):
    """Tail chunks are right-padded to ``prefill_chunk``, so each of the
    paged step's two programs compiles one shape — the chunk program the
    chunk shape, the decode program the decode shape — across arbitrary
    prompt lengths (regression: unpadded, every distinct final-chunk
    length re-traced)."""
    from repro.core.context import policy_scope
    cfg, params = tiny_model
    rng = np.random.default_rng(2)
    prompts = [list(rng.integers(0, cfg.vocab, n)) for n in (3, 5, 9, 11, 6)]
    gens = [2, 3, 2, 2, 3]
    with policy_scope("fp32_vpu"):
        eng, out = _run_engine(cfg, params, prompts, gens,
                               prefix_cache=True, prefill_chunk=4)
    assert sorted(out) == list(range(len(prompts)))
    assert eng._decode_jit._cache_size() <= 1
    assert eng._chunk_jit._cache_size() <= 1


def test_prefix_cache_rejects_recurrent_mixers():
    """A shared KV page cannot capture accumulating recurrent state."""
    from repro.configs import get_config
    from repro.serving import PagedServingEngine
    cfg = get_config("xlstm-1.3b", reduced=True)
    with pytest.raises(NotImplementedError, match="prefix caching"):
        PagedServingEngine(cfg, None, prefix_cache=True)
