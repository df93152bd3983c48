"""``PagedServingEngine`` — the executor gluing the pure-Python scheduler
to the model zoo's paged decode path.

One engine ``step()`` executes one scheduler tick:

  1. evictions: finished requests' slots are detached (their pages were
     freed by the scheduler; the stale pool contents are unreachable once
     no block table points at them — nothing is zeroed),
  2. admissions: the new request's block-table row is installed,
  3. prefill: either one ``model.prefill`` call per request (single-shot,
     exact ``generate()`` numerics) with the resulting caches scattered
     into its pages, or — with ``prefill_chunk`` set — one prompt chunk
     through the paged chunked-prefill path,
  4. decode: ONE batched ``decode_step_paged`` over every slot.

Each phase runs under a host span (``jax.profiler.TraceAnnotation``):
``serving.step`` holds one tick (stats ``n_admit``, ``n_prefill``,
``n_decode``) and, inside it, ``serving.schedule``, ``serving.admit``
(``rid``, ``slot``), ``serving.prefill`` (``rid``, ``start``, ``tokens``),
``serving.decode`` (``lanes``), ``serving.propose``, ``serving.sync``
(``kind`` ``first_token`` with its ``rid``, ``decode`` or ``verify``: the
host blocked on device results) and ``serving.record``.  The decode step,
the prefill chunk and the verify step are the programs
``jit_paged_decode_step``, ``jit_paged_prefill_chunk`` and
``jit_paged_verify_step``.

Slots not decoding this tick ride the batched step as ghost lanes.  Their
safety rests on two invariants, not on the scratch page alone: (a) *free*
slots point their whole block-table row at ``NULL_PAGE``, so their writes
land on the scratch page; (b) admitted-but-still-prefilling (and
just-prefilled) slots write into their *own* pages at exactly
``seq_lens[slot]`` — the position the next prefill chunk or real decode
step overwrites before anything reads it.  Both depend on step ordering
(prefill chunks run before the batched decode) — do not reorder.  KV
appends are positional and overwrite-idempotent, which is why this works;
*recurrent* per-slot state is accumulating, so the batched step carries an
active-slot mask and inactive slots keep their old state.

Greedy decoding only (argmax) — the deterministic contract the golden
token-stream tests pin.  Policies reach the engine through the ambient
``policy_scope`` exactly like the dense serve path.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.models import (decode_step_paged, init_paged_decode_caches,
                          prefill)
from repro.models.model import verify_step_paged
from .paged_cache import (NULL_PAGE, copy_page, pages_needed,
                          reset_page_scales, write_prefill_prefix)
from .scheduler import Request, Scheduler, StepPlan

__all__ = ["PagedServingEngine"]

# Host spans of one tick (``serving.*``), written into a running
# ``jax.profiler`` trace on the device planes' clock; without a profiler
# each costs about a microsecond.
_span = jax.profiler.TraceAnnotation

_SEQ_MIXERS = ("attn", "mla")


class PagedServingEngine:
    """Continuous-batching serving over paged KV caches.

    ``max_seq_len`` bounds prompt + generation per request (it sizes the
    block table); ``num_pages`` defaults to full residency (every slot can
    hold a ``max_seq_len`` sequence) — pass something smaller to exercise
    admission back-pressure.  ``prefill_chunk`` enables chunked prefill
    (attention/MLA-mixer architectures only: recurrent mixers have no
    multi-token decode step).

    ``page_size=None`` asks ``repro.tune`` for the page size (and, when
    ``prefill_chunk="auto"``, the prefill chunk = page_size x
    pages-per-step) from the paged-serving cost model over the engine's
    ``"attn"`` policy; with ``REPRO_TUNE=off`` the pre-tuner defaults
    (page_size=16, single-shot prefill) apply.

    ``prefix_cache=True`` turns on refcounted prefix sharing over the page
    pool (attention/MLA mixers only — a shared KV page cannot capture
    accumulating recurrent state): admission installs cached pages into the
    slot's block-table row by reference, clones the copy-on-write boundary
    page where a prompt diverges inside a cached page, and prefill starts
    at the first uncached position.  All prefill then runs through the
    paged multi-token path (never ``model.prefill``, which cannot start
    mid-prompt), so cached and uncached requests share one code path —
    sharing changes which physical page a read resolves to, never
    arithmetic, keeping token streams bitwise-identical per policy to the
    uncached engine.

    ``mesh=`` makes the engine multi-device: every batched model step
    (decode AND chunked/single-shot prefill) runs SPMD over the given
    ``("data", "model")`` mesh.  Params shard by the logical-axis rules of
    ``repro.parallel.sharding`` (TP over ``model`` on heads/mlp/vocab,
    FSDP-style over the data axes on ``embed``); the page pools shard
    their kv-head axis over ``model`` when divisible and replicate
    otherwise (``paged_cache_pspecs`` — the page axis itself is never
    sharded, so any device can resolve any physical page id its
    replicated block table names); per-slot recurrent states shard the
    slot axis over the data axes.  The pure-Python scheduler, prefix
    index and block-table bookkeeping stay on the host untouched — only
    the array programs are partitioned, so arithmetic per token is
    unchanged and single- vs multi-device engines emit identical token
    streams per policy (the golden-stream contract; TP all-reduces ride
    at bf16 wire width through the einsum frontend's emit-width
    discipline).  Control tensors (tokens, block table, seq lens, active
    mask) are replicated — they are bytes, not bandwidth.

    ``speculative=SpecConfig(...)`` turns decode ticks into speculative
    verify ticks (``repro.spec``): a host-side proposer drafts up to ``k``
    tokens per slot, ONE batched ``verify_step_paged`` scores all ``k+1``
    positions through the paged multi-token path, and greedy acceptance
    commits the matched prefix plus the verifier's bonus/corrected token
    — ``[1, k+1]`` tokens per tick, streams bitwise-identical per policy
    to the non-speculative engine.  Rollback is free: seq_lens advance by
    the committed count only, the rejected tail's positional KV appends
    are overwritten (or scratch-absorbed past the block row) before any
    read, refcounts untouched.  Ghost lanes stay safe for the same
    reason single-token ticks keep them safe: a position only becomes
    readable once a *real* append at it advances ``seq_lens`` past it,
    and every real append overwrites the position first.

    ``quantized_kv=True`` stores the page pools as int8 payloads with a
    per-page fp32 scale sidecar (``repro.serving.paged_cache``): decode
    streams ~2-4x fewer cache bytes; page ids, block tables, COW sharing
    and the sharding contract are untouched (the sidecar is a parallel
    ``(P,)`` array).  Scales grow by scatter-max during a page's residency
    and are zeroed for a request's fresh pages at admission (recycled pages
    would otherwise inherit the previous tenant's scale and only ratchet
    upward).  Off (the default), no code path changes — token streams stay
    bitwise-identical to an engine without the feature.
    """

    def __init__(self, cfg: ArchConfig, params, *,
                 page_size: Optional[int] = 16,
                 max_concurrency: int = 4, max_seq_len: int = 256,
                 num_pages: Optional[int] = None,
                 prefill_chunk=None,
                 prefix_cache: bool = False,
                 mesh=None,
                 eos_id: Optional[int] = None,
                 speculative=None,
                 quantized_kv: bool = False):
        tuned = None
        if page_size is None or prefill_chunk == "auto":
            tuned = self._tuned_plan(cfg, max_seq_len,
                                     quantized=quantized_kv)
        if page_size is None:
            page_size = 16 if tuned is None else tuned.page_size
        if prefill_chunk == "auto":
            prefill_chunk = None if tuned is None \
                else tuned.page_size * tuned.pages_per_step
        if cfg.encoder_layers or cfg.vision_tokens:
            raise NotImplementedError(
                "paged serving covers decoder-only architectures")
        if (prefill_chunk is not None or prefix_cache) and any(
                spec.mixer not in _SEQ_MIXERS for spec in cfg.pattern):
            raise NotImplementedError(
                "chunked prefill and prefix caching need attention/MLA "
                f"mixers only (pattern has {[s.mixer for s in cfg.pattern]})")
        self.cfg = cfg
        self.page_size = page_size
        self.prefix_cache = prefix_cache
        self.quantized_kv = quantized_kv
        self.eos_id = eos_id
        self.npages_per_seq = pages_needed(max_seq_len, page_size)
        if num_pages is None:
            num_pages = 1 + max_concurrency * self.npages_per_seq
        self.spec = speculative
        self.scheduler = Scheduler(num_pages, page_size, max_concurrency,
                                   self.npages_per_seq,
                                   prefill_chunk=prefill_chunk,
                                   prefix_cache=prefix_cache,
                                   spec_lookahead=(speculative.k
                                                   if speculative else 0))
        self.proposer = None
        self._spec_stats = None
        if speculative is not None:
            from repro.spec import SpecStats, build_proposer
            self.proposer = build_proposer(speculative, max_seq_len)
            self._spec_stats = SpecStats()
        self.caches = init_paged_decode_caches(cfg, max_concurrency,
                                               num_pages, page_size,
                                               quantized=quantized_kv)
        self.mesh = mesh
        self._replicated = None
        if mesh is not None:
            from repro.parallel import sharding as shd
            params = jax.device_put(
                params, shd.shardings_of(shd.param_pspecs(cfg, mesh), mesh))
            self.caches = jax.device_put(
                self.caches,
                shd.shardings_of(
                    shd.paged_cache_pspecs(cfg, mesh, max_concurrency,
                                           num_pages, page_size,
                                           quantized=quantized_kv), mesh))
            self._replicated = shd.replicated(mesh)
        self.params = params
        self.block_table = np.full((max_concurrency, self.npages_per_seq),
                                   NULL_PAGE, np.int32)
        self.seq_lens = np.zeros((max_concurrency,), np.int32)
        self._last_tok = np.zeros((max_concurrency,), np.int32)
        self._next_rid = 0

        # Two jits of the same paged step, one per shape the engine drives,
        # so a profiler trace tells the decode step from a prefill chunk by
        # its program name (jit_paged_decode_step, jit_paged_prefill_chunk).
        def paged_decode_step(p, t, c, bt, sl, act, li):
            return decode_step_paged(p, t, c, bt, sl, cfg, active=act,
                                     logit_index=li)

        def paged_prefill_chunk(*args):
            return paged_decode_step(*args)

        def paged_verify_step(p, t, c, bt, sl, act, nd):
            return verify_step_paged(p, t, c, bt, sl, cfg, n_draft=nd,
                                     active=act)

        self._decode_jit = jax.jit(paged_decode_step, donate_argnums=(2,))
        self._chunk_jit = jax.jit(paged_prefill_chunk, donate_argnums=(2,))
        self._decode_fn = self._paged_step
        self._prefill_fn = jax.jit(functools.partial(prefill, cfg=cfg))
        self._write_fn = jax.jit(write_prefill_prefix, donate_argnums=(0,))
        self._copy_fn = jax.jit(copy_page, donate_argnums=(0,))
        self._reset_scales_fn = jax.jit(reset_page_scales,
                                        donate_argnums=(0,))
        self._verify_fn = jax.jit(paged_verify_step, donate_argnums=(2,))

    def _paged_step(self, params, tokens, caches, bt, sl, act, li):
        """The paged model step: one token per slot runs the decode jit,
        several (a prefill chunk) the chunk jit.  Returns ``(logits,
        caches)``."""
        fn = self._decode_jit if tokens.shape[1] == 1 else self._chunk_jit
        return fn(params, tokens, caches, bt, sl, act, li)

    def _scope(self):
        """Mesh + activation-sharding context for every jitted model call
        (a no-op single-device).  Entered per call site, not stored: the
        logical-axis rules are read at trace time, so the first call under
        the scope bakes the sharding constraints into the compiled step."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.models.base import activation_sharding
        stack = contextlib.ExitStack()
        stack.enter_context(self.mesh)
        stack.enter_context(activation_sharding(self.mesh))
        return stack

    def _host(self, x, dtype=jnp.int32):
        """Host array -> device, replicated over the mesh when sharded
        (control tensors: tokens, block tables, lengths, masks)."""
        arr = jnp.asarray(x, dtype)
        if self._replicated is not None:
            arr = jax.device_put(arr, self._replicated)
        return arr

    @staticmethod
    def _tuned_plan(cfg: ArchConfig, max_seq_len: int,
                    quantized: bool = False):
        """The ``repro.tune`` paged plan for this architecture's KV-cache
        geometry under the resolved ``"attn"`` policy, or ``None`` when
        tuning is off."""
        from repro import tune
        from repro.core.context import resolve_policy
        pol = resolve_policy(None, "attn")
        if cfg.mla is not None:
            # MLA caches the compressed latent + rope key, one logical head.
            kvh = 1
            d = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
            dv = 0
        else:
            kvh, d = cfg.n_kv_heads, cfg.head_dim_
            dv = cfg.head_dim_
        return tune.paged_plan(max_seq_len, kvh, d, dv, policy=pol,
                               quantized=quantized)

    # -- submission ---------------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               rid: Optional[int] = None) -> int:
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        self.scheduler.submit(Request(rid=rid, prompt=list(prompt),
                                      max_new_tokens=max_new_tokens,
                                      eos_id=self.eos_id))
        return rid

    # -- one tick -----------------------------------------------------------

    def step(self) -> StepPlan:
        with self._scope(), _span("serving.step") as span:
            with _span("serving.schedule"):
                plan = self.scheduler.step()
            span.set_metadata(n_admit=len(plan.admit),
                              n_prefill=len(plan.prefill),
                              n_decode=len(plan.decode))
            self._step(plan)
        return plan

    def _step(self, plan: StepPlan) -> None:
        for rid, slot in plan.evict:
            self.block_table[slot] = NULL_PAGE
            self.seq_lens[slot] = 0
            if self.proposer is not None:
                self.proposer.release(rid)
        for rid, slot in plan.admit:
            with _span("serving.admit", rid=rid, slot=slot):
                self._admit(rid, slot)
        for chunk in plan.prefill:
            self._prefill(chunk)
        if plan.decode and self.spec is not None:
            self._spec_decode(plan)
        elif plan.decode:
            self._decode(plan)

    def _admit(self, rid: int, slot: int) -> None:
        """Install an admitted request's block-table row (and, with prefix
        caching or int8 pages, its COW clone and fresh page scales)."""
        st = self.scheduler.active[rid]
        if self.proposer is not None:
            self.proposer.register(rid, st.req.prompt)
        row = self.scheduler.block_row(rid)
        self.block_table[slot] = NULL_PAGE
        self.block_table[slot, :len(row)] = row
        if st.boundary_src is not None:
            # COW boundary: clone the cached page holding the span this
            # request diverges inside into its first private page; its
            # own tokens overwrite the clone from offset
            # cached_upto % page_size on.
            self.caches = self._copy_fn(
                self.caches, self._host(st.boundary_src),
                self._host(row[st.n_shared]))
        if self.quantized_kv:
            # recycled pages keep their stale scale (nothing is zeroed
            # on eviction) and scales only ever grow mid-residency —
            # zero the *fresh* pages' scales at admission so each
            # tenant quantizes against its own magnitudes.  Shared
            # prefix pages (and the COW boundary clone, which holds
            # live tokens at the source's scale) must keep theirs.
            keep = st.n_shared + (1 if st.boundary_src is not None else 0)
            fresh = list(row[keep:])
            fresh += [NULL_PAGE] * (self.npages_per_seq - len(fresh))
            self.caches = self._reset_scales_fn(self.caches,
                                                self._host(fresh))
        self.seq_lens[slot] = st.cached_upto

    def _prefill(self, chunk) -> None:
        """Run one prompt chunk; after the last one, sync on its first
        token."""
        sched = self.scheduler
        st = sched.active[chunk.rid]
        tokens = list(st.req.prompt[chunk.start:chunk.end])
        real = len(tokens)
        with _span("serving.prefill", rid=chunk.rid, start=chunk.start,
                   tokens=real):
            if sched.prefill_chunk is None and not self.prefix_cache:
                # single-shot: the standard prefill (same numerics as the
                # dense serve path), scattered into this request's pages
                logits, pf = self._prefill_fn(
                    self.params, {"tokens": self._host([tokens])})
                self.caches = self._write_fn(
                    self.caches, pf,
                    self._host(self.block_table[chunk.slot]),
                    self._host(chunk.slot))
            else:
                # chunked (or prefix-cached, which must be able to start
                # mid-prompt): the chunk rides the paged multi-token step.
                # The tail chunk is right-padded to prefill_chunk so every
                # chunk shares ONE compiled shape — unpadded, each distinct
                # final-chunk length re-traced the jitted step.  Padding is
                # causally inert for the real rows; pad K/V appends land
                # past the real positions and are overwritten (or
                # scratch-absorbed past the block row) before any read.
                if sched.prefill_chunk is not None \
                        and real < sched.prefill_chunk:
                    tokens = tokens + [0] * (sched.prefill_chunk - real)
                logits, self.caches = self._decode_fn(
                    self.params, self._host([tokens]),
                    self.caches,
                    self._host(self.block_table[chunk.slot][None]),
                    self._host(self.seq_lens[chunk.slot][None]), None,
                    self._host([real - 1]))
        self.seq_lens[chunk.slot] = chunk.end
        if not chunk.last:
            with _span("serving.record"):
                sched.record_prefill(chunk.rid, chunk.end)
            return
        # only the final chunk's logits are consumed (one host sync)
        with _span("serving.sync", kind="first_token", rid=chunk.rid):
            tok = int(jnp.argmax(logits[0]))
        with _span("serving.record"):
            sched.record_prefill(chunk.rid, chunk.end, first_token=tok)
            self._last_tok[chunk.slot] = tok
            if self.proposer is not None \
                    and not sched.active[chunk.rid].finished:
                # feed the first emitted token (unless it finished the
                # request outright — its state is about to be released)
                self.proposer.observe(chunk.rid, [tok])

    def _decode(self, plan: StepPlan) -> None:
        """One batched decode step over every slot."""
        with _span("serving.decode", lanes=len(plan.decode)):
            toks = self._host(self._last_tok[:, None])
            active = np.zeros((len(self.seq_lens),), bool)
            for _, slot in plan.decode:
                active[slot] = True
            logits, self.caches = self._decode_fn(
                self.params, toks, self.caches,
                self._host(self.block_table), self._host(self.seq_lens),
                self._host(active, jnp.bool_), None)
        with _span("serving.sync", kind="decode"):
            next_tok = np.asarray(jnp.argmax(logits, axis=-1))
        with _span("serving.record"):
            for rid, slot in plan.decode:
                self.seq_lens[slot] += 1
                tok = int(next_tok[slot])
                self.scheduler.record_decode(rid, tok)
                self._last_tok[slot] = tok

    def _spec_decode(self, plan: StepPlan) -> None:
        """One speculative verify tick over every decode-phase slot.

        Input row per slot: ``[last committed token, draft_1 .. draft_k]``
        right-padded past the slot's real draft count.  The draft budget
        is capped at ``max_new_tokens - generated - 1`` so a full accept
        (``budget + 1`` tokens) lands exactly on the request's reservation
        — ``record_decode_burst`` then only ever truncates on eos."""
        sched = self.scheduler
        k = self.spec.k
        b = len(self.seq_lens)
        toks = np.zeros((b, k + 1), np.int32)
        toks[:, 0] = self._last_tok
        n_draft = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        with _span("serving.propose"):
            for rid, slot in plan.decode:
                active[slot] = True
                st = sched.active[rid]
                budget = min(k, st.req.max_new_tokens - st.generated - 1)
                drafts = self.proposer.propose(rid, budget) \
                    if budget > 0 else []
                n_draft[slot] = len(drafts)
                toks[slot, 1:1 + len(drafts)] = drafts
        with _span("serving.decode", lanes=len(plan.decode)):
            targets, n_acc, self.caches = self._verify_fn(
                self.params, self._host(toks), self.caches,
                self._host(self.block_table), self._host(self.seq_lens),
                self._host(active, jnp.bool_), self._host(n_draft))
        with _span("serving.sync", kind="verify"):
            targets = np.asarray(targets)
            n_acc = np.asarray(n_acc)
        stats = self._spec_stats
        with _span("serving.record"):
            for rid, slot in plan.decode:
                n_out = int(n_acc[slot]) + 1
                out = [int(t) for t in targets[slot, :n_out]]
                committed = sched.record_decode_burst(rid, out)
                self.seq_lens[slot] += committed
                self._last_tok[slot] = out[committed - 1]
                if not sched.active[rid].finished:
                    self.proposer.observe(rid, out[:committed])
                stats.proposed += int(n_draft[slot])
                stats.accepted += n_out - 1
                stats.emitted += committed
        stats.ticks += 1

    @property
    def spec_stats(self):
        """``repro.spec.SpecStats`` counters, or ``None`` when the engine
        is not speculative."""
        return self._spec_stats

    def run(self, max_steps: int = 10_000) -> Dict[int, List[int]]:
        """Drive the step loop until every submitted request completed.
        Returns ``{rid: emitted tokens}``."""
        steps = 0
        while not self.scheduler.done:
            plan = self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} steps")
            if plan.idle and not self.scheduler.done:
                raise RuntimeError(
                    "scheduler idle with work pending (page/slot starvation: "
                    "a queued request can never be admitted)")
        return dict(self.scheduler.completed)
