"""Paged-vs-contiguous decode attention parity: the paged XLA twin must
match the dense decode path *exactly* per policy (it runs the same code on
the gathered pages), the Pallas kernel must stay within each policy's fp64
oracle bound, and one ``policy_scope("bf16x6_pallas")`` must flip paged
decode onto the fused kernel (site-reach acceptance)."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro import tcec
from repro.configs.base import ArchConfig, BlockSpec, MlaConfig
from repro.core.context import policy_scope
from repro.core.policy import get_policy
from repro.models import (init_params, prefill, decode_step,
                          init_decode_caches, decode_step_paged,
                          init_paged_decode_caches)
from repro.models.attention import decode_attention, mla_absorbed_attention
from repro.serving import (append_pages, gather_pages, pages_needed,
                           paged_decode_attention_pallas,
                           paged_decode_attention_xla,
                           paged_mla_decode_attention,
                           paged_prefill_attention, NULL_PAGE)
from repro.serving.paged_cache import lane_width, write_prefill_prefix

from oracles import attention_fp64, max_rel_err

POLICIES = ["fp32_vpu", "bf16x1", "bf16x3", "bf16x6"]
# max-rel-err ceilings vs the fp64 oracle (well-conditioned N(0,1) inputs),
# same ladder as tests/test_attention_policies.py.
TOL = {"fp32_vpu": 4e-6, "bf16x1": 5e-2, "bf16x3": 5e-4, "bf16x6": 4e-6}

B, PAGE, NPAGES, POOL = 2, 8, 3, 11
SV = PAGE * NPAGES
# nothing divides: request 0 ends mid-page, request 1 is shorter than two
# pages, and SV > both.
SEQ_LENS = np.asarray([21, 9], np.int32)


def _paged_case(rng, kvh, d, dv=None, tail3=False):
    """Random pool + a block table whose gather is a contiguous cache."""
    dv = dv or d
    tail = (d,) if tail3 else (kvh, d)
    tailv = (dv,) if tail3 else (kvh, dv)
    k_pages = rng.standard_normal((POOL, PAGE) + tail).astype(np.float32)
    v_pages = rng.standard_normal((POOL, PAGE) + tailv).astype(np.float32)
    bt = np.asarray([[3, 7, 1], [5, 2, 4]], np.int32)
    return jnp.asarray(k_pages), jnp.asarray(v_pages), jnp.asarray(bt)


# ---------------------------------------------------------------------------
# cache ops
# ---------------------------------------------------------------------------

def test_append_gather_roundtrip_across_page_boundary():
    rng = np.random.default_rng(0)
    pool = jnp.zeros((POOL, PAGE, 2, 4), jnp.float32)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    # request 0 appends 5 tokens starting at 6 -> spans pages 1 and 2
    new = jnp.asarray(rng.standard_normal((2, 5, 2, 4)).astype(np.float32))
    pool = append_pages(pool, new, bt, jnp.asarray([6, 0], np.int32))
    got = gather_pages(pool, bt)
    np.testing.assert_array_equal(np.asarray(got[0, 6:11]), np.asarray(new[0]))
    np.testing.assert_array_equal(np.asarray(got[1, 0:5]), np.asarray(new[1]))
    # untouched positions stay zero
    assert float(jnp.abs(got[0, :6]).max()) == 0.0


def test_append_past_block_row_redirects_to_scratch_not_last_page():
    """Regression: a logical position past the block-table row must go to
    the scratch page.  JAX's scatter clamp would otherwise silently alias
    the write onto the row's LAST physical page — which, under
    copy-on-write prefix sharing, may be a page another request reads."""
    rng = np.random.default_rng(9)
    pool = jnp.asarray(rng.standard_normal((POOL, PAGE, 1, 2)), jnp.float32)
    before = np.asarray(pool)
    bt = jnp.asarray([[3, 7]], np.int32)         # row holds 2 logical pages
    # append 4 tokens starting at 14: positions 14,15 hit page 7, 16,17
    # fall PAST the row (logical page 2 of a 2-page table)
    new = jnp.full((1, 4, 1, 2), 5.0, jnp.float32)
    out = np.asarray(append_pages(pool, new, bt, jnp.asarray([14], np.int32)))
    np.testing.assert_array_equal(out[7, 6:], np.asarray(new[0, :2]))
    np.testing.assert_array_equal(out[7, :6], before[7, :6])   # intact
    np.testing.assert_array_equal(out[3], before[3])           # untouched
    # overflow landed on the scratch page, nowhere else
    changed = [p for p in range(1, POOL)
               if not np.array_equal(out[p], before[p])]
    assert changed == [7]
    assert np.array_equal(out[NULL_PAGE, 0], np.asarray(new[0, 2]))


def test_append_prefix_past_block_row_redirects_to_scratch():
    from repro.serving.paged_cache import append_prefix_pages
    rng = np.random.default_rng(10)
    pool = jnp.asarray(rng.standard_normal((POOL, PAGE, 2)), jnp.float32)
    before = np.asarray(pool)
    row = jnp.asarray([4, 6], np.int32)          # 2 pages = 16 positions
    prefix = jnp.full((PAGE * 2 + 3, 2), 2.0, jnp.float32)
    out = np.asarray(append_prefix_pages(pool, prefix, row))
    np.testing.assert_array_equal(out[4], np.full((PAGE, 2), 2.0))
    np.testing.assert_array_equal(out[6], np.full((PAGE, 2), 2.0))
    changed = [p for p in range(1, POOL)
               if not np.array_equal(out[p], before[p])]
    assert changed == [4, 6]                     # overflow -> scratch only


def test_copy_page_clones_pool_leaves_only():
    """``copy_page`` (the COW boundary copy) clones src -> dst on every
    pool leaf across groups and passes per-slot state through untouched."""
    from repro.serving import copy_page
    rng = np.random.default_rng(11)
    tree = {"blk": {
        "k_pages": jnp.asarray(rng.standard_normal((2, POOL, PAGE, 1, 2)),
                               jnp.float32),
        "v_pages": jnp.asarray(rng.standard_normal((2, POOL, PAGE, 1, 2)),
                               jnp.float32),
        "state": jnp.asarray(rng.standard_normal((2, 3, 4)), jnp.float32),
    }}
    out = copy_page(tree, jnp.int32(3), jnp.int32(5))
    for key in ("k_pages", "v_pages"):
        np.testing.assert_array_equal(np.asarray(out["blk"][key][:, 5]),
                                      np.asarray(tree["blk"][key][:, 3]))
        np.testing.assert_array_equal(np.asarray(out["blk"][key][:, :3]),
                                      np.asarray(tree["blk"][key][:, :3]))
    np.testing.assert_array_equal(np.asarray(out["blk"]["state"]),
                                  np.asarray(tree["blk"]["state"]))


def test_idle_slot_append_lands_on_null_page():
    pool = jnp.zeros((POOL, PAGE, 1, 2), jnp.float32)
    bt = jnp.asarray([[NULL_PAGE, NULL_PAGE, NULL_PAGE], [1, 2, 3]], np.int32)
    new = jnp.ones((2, 1, 1, 2), jnp.float32)
    pool = append_pages(pool, new, bt, jnp.asarray([0, 0], np.int32))
    # the idle slot's write was absorbed by page 0; page 1 holds slot 1's
    np.testing.assert_array_equal(np.asarray(pool[1, 0]),
                                  np.ones((1, 2), np.float32))
    assert float(jnp.abs(pool[2:]).max()) == 0.0
    assert pages_needed(17, 8) == 3


# ---------------------------------------------------------------------------
# GQA decode parity: twin exact vs contiguous, kernel vs fp64 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("h,kvh,d", [(4, 4, 16), (4, 2, 16)])
def test_paged_twin_matches_contiguous_decode_exactly(policy, h, kvh, d):
    """The XLA twin gathers pages and runs the SAME decode_attention the
    dense path runs — parity is exact (bitwise for fp32_vpu and the split
    policies alike), GQA and MHA, non-dividing lengths."""
    rng = np.random.default_rng(h * 10 + kvh)
    q = jnp.asarray(rng.standard_normal((B, h, d)).astype(np.float32))
    k_pages, v_pages, bt = _paged_case(rng, kvh, d)
    sl = jnp.asarray(SEQ_LENS)
    out = paged_decode_attention_xla(q, k_pages, v_pages, bt, sl,
                                     policy=policy)
    k_dense = gather_pages(k_pages, bt)       # contiguous twin of the pages
    v_dense = gather_pages(v_pages, bt)
    ref = decode_attention(q[:, None], k_dense, v_dense, sl - 1,
                           policy=policy)[:, 0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("h,kvh,d", [(4, 4, 16), (8, 2, 16)])
def test_paged_kernel_and_twin_vs_fp64_oracle(policy, h, kvh, d):
    """Kernel (interpret mode) AND XLA twin stay inside each policy's
    accuracy band vs the fp64 oracle, per request length."""
    rng = np.random.default_rng(h + kvh + 7)
    q = jnp.asarray(rng.standard_normal((B, h, d)).astype(np.float32))
    k_pages, v_pages, bt = _paged_case(rng, kvh, d)
    sl = jnp.asarray(SEQ_LENS)
    out_k = np.asarray(paged_decode_attention_pallas(
        q, k_pages, v_pages, bt, sl, policy=policy, interpret=True))
    out_t = np.asarray(paged_decode_attention_xla(
        q, k_pages, v_pages, bt, sl, policy=policy))
    kd = np.asarray(gather_pages(k_pages, bt)).transpose(0, 2, 1, 3)
    vd = np.asarray(gather_pages(v_pages, bt)).transpose(0, 2, 1, 3)
    for i in range(B):
        ref = attention_fp64(np.asarray(q)[i:i + 1, :, None], kd[i:i + 1],
                             vd[i:i + 1], causal=False,
                             kv_len=int(SEQ_LENS[i]))[:, :, 0]
        assert max_rel_err(out_k[i:i + 1], ref) < TOL[policy], (policy, i)
        assert max_rel_err(out_t[i:i + 1], ref) < TOL[policy], (policy, i)


@pytest.mark.parametrize("policy", ["bf16x1", "bf16x6"])
def test_paged_kernel_matches_twin(policy):
    """Kernel and twin share one split schedule: bf16x6 agrees to fp32
    roundoff (online vs plain softmax accumulation order only)."""
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.standard_normal((B, 4, 16)).astype(np.float32))
    k_pages, v_pages, bt = _paged_case(rng, 2, 16)
    sl = jnp.asarray(SEQ_LENS)
    out_k = np.asarray(paged_decode_attention_pallas(
        q, k_pages, v_pages, bt, sl, policy=policy, interpret=True),
        np.float32)
    out_t = np.asarray(paged_decode_attention_xla(
        q, k_pages, v_pages, bt, sl, policy=policy), np.float32)
    tol = 2e-2 if policy == "bf16x1" else 1e-5
    np.testing.assert_allclose(out_k, out_t, rtol=tol, atol=tol)


def test_zero_length_request_emits_zeros():
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((B, 4, 16)).astype(np.float32))
    k_pages, v_pages, bt = _paged_case(rng, 2, 16)
    sl = jnp.asarray([0, 9], np.int32)
    for out in (
            paged_decode_attention_xla(q, k_pages, v_pages, bt, sl),
            paged_decode_attention_pallas(q, k_pages, v_pages, bt, sl,
                                          interpret=True)):
        assert float(jnp.abs(out[0]).max()) == 0.0
        assert float(jnp.abs(out[1]).max()) > 0.0


# ---------------------------------------------------------------------------
# MLA absorbed decode parity
# ---------------------------------------------------------------------------

def _mla_case(rng, h=4, lora=16, rope=8):
    q_c = rng.standard_normal((B, h, lora)).astype(np.float32)
    q_r = rng.standard_normal((B, h, rope)).astype(np.float32)
    c_pages = rng.standard_normal((POOL, PAGE, lora)).astype(np.float32)
    r_pages = rng.standard_normal((POOL, PAGE, rope)).astype(np.float32)
    bt = np.asarray([[3, 7, 1], [5, 2, 4]], np.int32)
    scale = 1.0 / np.sqrt(lora + rope)
    return (*map(jnp.asarray, (q_c, q_r, c_pages, r_pages, bt)), scale)


@pytest.mark.parametrize("policy", POLICIES)
def test_paged_mla_twin_matches_contiguous_exactly(policy):
    """Paged MLA decode calls the same ``mla_absorbed_attention`` core as
    the contiguous absorbed path — exact per policy."""
    rng = np.random.default_rng(11)
    q_c, q_r, c_pages, r_pages, bt, scale = _mla_case(rng)
    sl = jnp.asarray(SEQ_LENS)
    out = paged_mla_decode_attention(q_c, q_r, c_pages, r_pages, bt, sl,
                                     scale=scale, policy=policy)
    c = gather_pages(c_pages, bt)
    r = gather_pages(r_pages, bt)
    valid = jnp.arange(SV, dtype=jnp.int32)[None, None] < sl[:, None, None]
    ref = mla_absorbed_attention(q_c[:, None], q_r[:, None], c, r, valid,
                                 scale, policy)[:, 0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("policy", POLICIES)
def test_paged_mla_kernel_vs_fp64_oracle(policy):
    """The MLA instance of the kernel (kvh == 1, rope second operand) stays
    inside the policy band vs an independent fp64 reference."""
    if get_policy(policy).backend == "vpu":
        kpol = get_policy(policy)          # vpu never dispatches to pallas
    else:
        kpol = dataclasses.replace(get_policy(policy), kernel="pallas")
    rng = np.random.default_rng(13)
    q_c, q_r, c_pages, r_pages, bt, scale = _mla_case(rng)
    sl = jnp.asarray(SEQ_LENS)
    out = np.asarray(paged_mla_decode_attention(
        q_c, q_r, c_pages, r_pages, bt, sl, scale=scale, policy=kpol,
        interpret=True), np.float32)
    c = np.asarray(gather_pages(c_pages, bt), np.float64)
    r = np.asarray(gather_pages(r_pages, bt), np.float64)
    qc64 = np.asarray(q_c, np.float64)
    qr64 = np.asarray(q_r, np.float64)
    for i in range(B):
        n = int(SEQ_LENS[i])
        s = (qc64[i] @ c[i, :n].T + qr64[i] @ r[i, :n].T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = p @ c[i, :n]
        assert max_rel_err(out[i], ref) < TOL[policy], (policy, i)


# ---------------------------------------------------------------------------
# stacked pools: one layer of a (g, P, page, *tail) stack, read and written
# in place through its layer index
# ---------------------------------------------------------------------------

GROUPS = 3


def _stack(rng, tail, quantized):
    """A random pool stack ``(GROUPS, POOL, PAGE, *tail)`` and its scale
    sidecars ``(GROUPS, POOL)`` (``None`` unless ``quantized``)."""
    shape = (GROUPS, POOL, PAGE) + tail
    if not quantized:
        return jnp.asarray(rng.standard_normal(shape).astype(np.float32)), None
    pool = rng.integers(-127, 128, shape).astype(np.int8)
    scales = rng.uniform(0.01, 0.05, (GROUPS, POOL)).astype(np.float32)
    return jnp.asarray(pool), jnp.asarray(scales)


def _at(x, layer):
    return None if x is None else x[layer]


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("kind", ["gqa", "mla"])
@pytest.mark.parametrize("layer", [0, GROUPS - 1])
def test_kernel_reads_a_layer_of_a_stacked_pool_bitwise(layer, kind,
                                                        quantized):
    """The Pallas kernel on the whole stack, with the layer index
    prefetched beside the block table, gives bitwise the kernel's output
    on that layer's pool alone."""
    rng = np.random.default_rng(29 + layer)
    pol = dataclasses.replace(get_policy("bf16x6"), kernel="pallas")
    bt = jnp.asarray([[3, 7, 1], [5, 2, 4]], np.int32)
    sl = jnp.asarray(SEQ_LENS)
    if kind == "gqa":
        h, kvh, d = 4, 2, 16
        q = jnp.asarray(rng.standard_normal((B, h, d)).astype(np.float32))
        (kp, ks), (vp, vs) = (_stack(rng, (kvh, d), quantized)
                              for _ in range(2))

        def run(k, v, k_s, v_s, lay):
            return paged_decode_attention_pallas(
                q, k, v, bt, sl, policy=pol, interpret=True,
                k_scales=k_s, v_scales=v_s, layer=lay)
    else:
        q_c, q_r, *_unused, scale = _mla_case(rng)
        (kp, ks), (vp, vs) = (_stack(rng, (w,), quantized)
                              for w in (q_c.shape[-1], q_r.shape[-1]))

        def run(c, r, c_s, r_s, lay):
            return paged_mla_decode_attention(
                q_c, q_r, c, r, bt, sl, scale=scale, policy=pol,
                interpret=True, c_scales=c_s, r_scales=r_s, layer=lay)
    stacked = jax.jit(run)(kp, vp, ks, vs, jnp.int32(layer))
    alone = run(kp[layer], vp[layer], _at(ks, layer), _at(vs, layer), None)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(alone))


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_append_and_gather_one_layer_of_a_stacked_pool(quantized):
    """``append_pages`` into layer ``l`` of a stack writes what it writes
    into ``pool[l]`` alone and leaves every other layer bitwise as it was;
    ``gather_pages`` reads layer ``l`` as it reads ``pool[l]``."""
    rng = np.random.default_rng(31)
    layer = 1
    pool, scales = _stack(rng, (2, 4), quantized)
    bt = jnp.asarray([[3, 7, 1], [5, 2, 4]], np.int32)
    seq = jnp.asarray([6, 15], np.int32)              # crosses a page
    new = jnp.asarray(rng.standard_normal((B, 3, 2, 4)).astype(np.float32))
    if quantized:
        got, got_s = append_pages(pool, new, bt, seq, scales=scales,
                                  layer=jnp.int32(layer))
        want, want_s = append_pages(pool[layer], new, bt, seq,
                                    scales=scales[layer])
        np.testing.assert_array_equal(np.asarray(got_s[layer]),
                                      np.asarray(want_s))
        np.testing.assert_array_equal(
            np.delete(np.asarray(got_s), layer, 0),
            np.delete(np.asarray(scales), layer, 0))
    else:
        got = append_pages(pool, new, bt, seq, layer=jnp.int32(layer))
        want = append_pages(pool[layer], new, bt, seq)
        got_s, want_s = None, None
    np.testing.assert_array_equal(np.asarray(got[layer]), np.asarray(want))
    np.testing.assert_array_equal(np.delete(np.asarray(got), layer, 0),
                                  np.delete(np.asarray(pool), layer, 0))
    np.testing.assert_array_equal(
        np.asarray(gather_pages(got, bt, scales=got_s, layer=layer)),
        np.asarray(gather_pages(want, bt, scales=want_s)))


@pytest.mark.parametrize("quantized", [False, True], ids=["plain", "int8"])
def test_lane_padded_rows_read_back_as_written(quantized):
    """A pool whose rows are wider than the rows written into it (the
    engine's pools are ``lane_width`` wide): the append zero-pads each row,
    and the gather at the written width, the XLA twin and the kernel read
    back bitwise what they read from a pool of the written width."""
    rng = np.random.default_rng(37)
    kvh, d = 2, 16
    pol = dataclasses.replace(get_policy("bf16x6"), kernel="pallas")
    bt = jnp.asarray([[3, 7, 1], [5, 2, 4]], np.int32)
    seq = jnp.asarray([6, 15], np.int32)
    sl = seq + 3
    new = jnp.asarray(rng.standard_normal((B, 3, kvh, d)).astype(np.float32))
    q = jnp.asarray(rng.standard_normal((B, 4, d)).astype(np.float32))
    read = {}
    for width in (d, lane_width(d)):
        pool = jnp.zeros((POOL, PAGE, kvh, width),
                         jnp.int8 if quantized else jnp.float32)
        scales = None
        if quantized:
            pool, scales = append_pages(pool, new, bt, seq,
                                        scales=jnp.zeros((POOL,), jnp.float32))
        else:
            pool = append_pages(pool, new, bt, seq)
        assert not np.any(np.asarray(pool)[..., d:])
        read[width] = (
            gather_pages(pool, bt, scales=scales, width=d),
            paged_decode_attention_xla(q, pool, pool, bt, sl,
                                       policy="fp32_vpu", k_scales=scales,
                                       v_scales=scales),
            paged_decode_attention_pallas(q, pool, pool, bt, sl, policy=pol,
                                          interpret=True, k_scales=scales,
                                          v_scales=scales))
    for narrow, wide in zip(read[d], read[lane_width(d)]):
        np.testing.assert_array_equal(np.asarray(narrow), np.asarray(wide))


# ---------------------------------------------------------------------------
# chunked-prefill attention
# ---------------------------------------------------------------------------

def test_paged_prefill_attention_matches_causal_oracle():
    """A chunk's rows attend to the cache prefix + themselves causally —
    check against the fp64 oracle on the equivalent full causal problem."""
    rng = np.random.default_rng(17)
    h, kvh, d, chunk = 4, 2, 16, 6
    prefix = np.asarray([10, 3], np.int32)
    k_pages, v_pages, bt = _paged_case(rng, kvh, d)
    # overwrite pages so the virtual cache equals a known contiguous k/v
    kd = np.asarray(gather_pages(k_pages, bt))
    vd = np.asarray(gather_pages(v_pages, bt))
    q = jnp.asarray(rng.standard_normal((B, chunk, h, d)).astype(np.float32))
    row_pos = jnp.asarray(prefix)[:, None] + jnp.arange(chunk)[None]
    out = np.asarray(paged_prefill_attention(
        q, k_pages, v_pages, bt, row_pos, policy="fp32_vpu"))
    for i in range(B):
        n = int(prefix[i]) + chunk
        # fp64 reference: row t attends cols <= prefix + t
        q64 = np.asarray(q, np.float64)[i]                # (chunk, h, d)
        k64 = np.repeat(kd[i, :n].astype(np.float64), h // kvh, 1)
        v64 = np.repeat(vd[i, :n].astype(np.float64), h // kvh, 1)
        s = np.einsum("qhd,shd->hqs", q64, k64) / np.sqrt(d)
        mask = np.arange(n)[None] <= (int(prefix[i]) + np.arange(chunk))[:, None]
        s = np.where(mask[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        o = np.einsum("hqs,shd->qhd", p, v64)
        assert max_rel_err(out[i], o) < TOL["fp32_vpu"], i


# ---------------------------------------------------------------------------
# model-level: paged decode_step vs dense decode_step
# ---------------------------------------------------------------------------

def _tiny_cfg(mixer):
    mla = MlaConfig(kv_lora_rank=16, q_lora_rank=0, qk_nope_head_dim=8,
                    qk_rope_head_dim=4, v_head_dim=8) if mixer == "mla" \
        else None
    return ArchConfig(
        name=f"tiny-{mixer}", family="dense", n_layers=2, d_model=32,
        n_heads=4, n_kv_heads=2 if mixer == "attn" else 4, d_ff=64,
        vocab=128, pattern=(BlockSpec(mixer, "dense"),), mla=mla,
        remat="none")


def _paged_vs_dense_decode(mixer, policy, quantized=False):
    """Three decode steps of one prefilled request through
    ``decode_step_paged`` and through the dense ``decode_step``: logits
    equal exactly under fp32_vpu, to fp32 roundoff under bf16x6; int8
    pools stay within the quantized band, and do quantize."""
    cfg = _tiny_cfg(mixer)
    rng = jax.random.PRNGKey(0)
    params = init_params(rng, cfg)
    prompt = jax.random.randint(rng, (1, 11), 0, cfg.vocab)
    page, slots = 8, 2
    with policy_scope(policy):
        logits_p, pf = prefill(params, {"tokens": prompt}, cfg)
        # dense decode
        from repro.launch.serve import write_prefill_caches
        dense = write_prefill_caches(init_decode_caches(cfg, 1, 24), pf, cfg)
        # paged decode: same prefill scattered into pages
        pools = init_paged_decode_caches(cfg, slots, 9, page,
                                         quantized=quantized)
        row = jnp.asarray([2, 5, 7], np.int32)
        pools = write_prefill_prefix(pools, pf, row, jnp.int32(0))
        bt = jnp.full((slots, 3), NULL_PAGE, jnp.int32).at[0].set(row)
        tok_d = jnp.argmax(logits_p, -1)[:, None].astype(jnp.int32)
        tok_p = jnp.zeros((slots, 1), jnp.int32).at[0].set(tok_d[0])
        seq = jnp.zeros((slots,), jnp.int32).at[0].set(11)
        for i in range(3):
            ld, dense = decode_step(params, tok_d, dense, jnp.int32(11 + i),
                                    cfg)
            lp, pools = decode_step_paged(params, tok_p, pools, bt, seq, cfg)
            if quantized:
                rel = float(jnp.max(jnp.abs(ld[0] - lp[0]))
                            / jnp.max(jnp.abs(ld[0])))
                assert 0.0 < rel < 0.1, (i, rel)
            elif policy == "fp32_vpu":
                np.testing.assert_array_equal(np.asarray(ld[0]),
                                              np.asarray(lp[0]))
            else:
                np.testing.assert_allclose(np.asarray(ld[0]),
                                           np.asarray(lp[0]),
                                           rtol=1e-4, atol=1e-4)
            tok_d = jnp.argmax(ld, -1)[:, None].astype(jnp.int32)
            tok_p = tok_p.at[0].set(tok_d[0])
            seq = seq.at[0].add(1)


@pytest.mark.parametrize("mixer", ["attn", "mla"])
@pytest.mark.parametrize("policy", ["fp32_vpu", "bf16x6"])
def test_model_paged_decode_matches_dense_decode(mixer, policy):
    """decode_step_paged reproduces decode_step logits through a whole
    model: exactly under fp32_vpu, to fp32 roundoff under bf16x6."""
    _paged_vs_dense_decode(mixer, policy)


@pytest.mark.parametrize("mixer", ["attn", "mla"])
@pytest.mark.parametrize("policy", ["fp32_vpu", "bf16x6"])
def test_model_paged_decode_matches_dense_decode_int8_pools(mixer, policy):
    """The same through int8 pools with per-page scales: the stacked
    scale sidecars ride the layer scan beside their pools."""
    _paged_vs_dense_decode(mixer, policy, quantized=True)


@pytest.mark.parametrize("policy", ["fp32_vpu", "bf16x6"])
def test_logit_index_vector_matches_scalar_selection(policy):
    """Regression (spec satellite): ``decode_step_paged`` used to assume a
    single selected position per slot.  A ``(b, m)`` per-slot index vector
    must return ``(b, m, v)`` logits where row ``j`` equals the ``(b,)``
    scalar-index call selecting position ``j`` — the multi-position
    contract speculative verification scores through."""
    cfg = _tiny_cfg("attn")
    rng = jax.random.PRNGKey(3)
    params = init_params(rng, cfg)
    slots, page, s = 2, 8, 4
    pools = init_paged_decode_caches(cfg, slots, 9, page)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    seq = jnp.asarray([5, 3], np.int32)
    toks = jax.random.randint(jax.random.PRNGKey(4), (slots, s), 0, cfg.vocab)
    idx = jnp.asarray([[0, 2, 3], [1, 1, 2]], np.int32)
    with policy_scope(policy):
        lv, _ = decode_step_paged(params, toks, pools, bt, seq, cfg,
                                  logit_index=idx)
        assert lv.shape == (slots, idx.shape[1], cfg.vocab)
        for j in range(idx.shape[1]):
            ls, _ = decode_step_paged(params, toks, pools, bt, seq, cfg,
                                      logit_index=idx[:, j])
            np.testing.assert_array_equal(np.asarray(lv[:, j]),
                                          np.asarray(ls))
        # None still means "last position", shape (b, v)
        ln, _ = decode_step_paged(params, toks, pools, bt, seq, cfg)
        lk, _ = decode_step_paged(params, toks, pools, bt, seq, cfg,
                                  logit_index=jnp.full((slots,), s - 1,
                                                       jnp.int32))
        np.testing.assert_array_equal(np.asarray(ln), np.asarray(lk))


# ---------------------------------------------------------------------------
# site-reach acceptance: one scope flips paged decode onto the kernel
# ---------------------------------------------------------------------------

def test_policy_scope_pallas_reaches_paged_decode(monkeypatch):
    """Acceptance: ``policy_scope("bf16x6_pallas")`` (a) resolves at the
    attn site of paged decode — proven by trace_plans records — and
    (b) dispatches the fused paged kernel — proven by a spy on the kernel
    entry — and (c) changes the numerics vs the plain policy."""
    cfg = _tiny_cfg("attn")
    rng = jax.random.PRNGKey(1)
    params = init_params(rng, cfg)
    pools = init_paged_decode_caches(cfg, 2, 9, 8)
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], np.int32)
    seq = jnp.asarray([5, 3], np.int32)
    tok = jnp.asarray([[7], [9]], np.int32)

    from repro.serving import paged_attention as pa
    calls = []
    real = pa.paged_decode_attention_pallas

    def spy(*a, **kw):
        calls.append(kw.get("policy"))
        return real(*a, **kw)

    monkeypatch.setattr(pa, "paged_decode_attention_pallas", spy)
    pol = get_policy("bf16x6_pallas")
    with policy_scope("bf16x6_pallas"), tcec.trace_plans() as log:
        l6, _ = decode_step_paged(params, tok, pools, bt, seq, cfg)
    attn_recs = [r for r in log if r.site == "attn"]
    assert attn_recs and all(r.policy == pol for r in attn_recs)
    # the layer stack is scanned over groups: one trace per pattern position
    assert len(calls) == len(cfg.pattern) and all(p == pol for p in calls)

    with policy_scope("bf16x1"):
        l1, _ = decode_step_paged(params, tok, pools, bt, seq, cfg)
    assert np.any(np.asarray(l6) != np.asarray(l1))
