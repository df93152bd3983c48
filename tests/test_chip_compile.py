"""Real-width compiles of the main-path Pallas kernels for a described TPU
v5e, without the chip.

Interpret mode runs these kernels bit-faithfully on the CPU but never
shows what Mosaic refuses: block shapes that do not tile, VMEM overuse,
unsupported in-kernel layouts.  Each case lowers the kernel at
qwen2-0.5b widths (d=896, d_ff=4864, 14 heads over 2 KV heads, head dim
64) against one device of a described ``v5e:2x2`` topology, compiles it
with the TPU compiler, and checks that the kernel is in the program as a
``tpu_custom_call``.

The paged model step is compiled the same way at the serving cells'
widths, to show that it writes its page pools in place.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import contextlib
import dataclasses
import os
import re

import pytest
import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro import tcec
from repro.kernels.flash_attention import flash_attention
from repro.serving.paged_attention import paged_decode_attention_pallas

D_MODEL, D_FF = 896, 4864
HEADS, KV_HEADS, HEAD_DIM = 14, 2, 64
PAGE, NPAGES, POOL_PAGES, SLOTS = 16, 34, 137, 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no topology"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("policy", ["bf16x6_pallas", "int8x2_pallas"])
@pytest.mark.parametrize("rows", [37, 256])
def test_fused_tcec_matmul_compiles_at_ffn_width(one_chip, policy, rows):
    def fn(a, b):
        return tcec.matmul(a, b, site="ffn", policy=policy, interpret=False)
    text = _compiled_text(fn, one_chip, ((rows, D_MODEL), jnp.float32),
                          ((D_MODEL, D_FF), jnp.bfloat16))
    assert "tpu_custom_call" in text


def test_xla_split_residuals_survive_the_tpu_compiler(one_chip):
    """The XLA split path takes its residual words from ``reduce_precision``
    values; a ``f32(bf16(x))`` round trip may be folded to ``x`` by the
    compiler, which zeroes the residuals (bf16x6 then computes bf16x1)."""
    def fn(a, b):
        return tcec.matmul(a, b, site="ffn", policy="bf16x6")
    text = _compiled_text(fn, one_chip, ((37, D_MODEL), jnp.float32),
                          ((D_MODEL, D_MODEL), jnp.float32))
    assert "reduce-precision" in text


def test_flash_attention_compiles_for_gqa(one_chip):
    s = 512

    def fn(q, k, v):
        return flash_attention(q, k, v, causal=True, policy="bf16x6_pallas",
                               interpret=False)
    text = _compiled_text(
        fn, one_chip, ((1, HEADS, s, HEAD_DIM), jnp.bfloat16),
        ((1, KV_HEADS, s, HEAD_DIM), jnp.bfloat16),
        ((1, KV_HEADS, s, HEAD_DIM), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_paged_decode_compiles_for_gqa_pools(one_chip, quantized):
    pool = ((POOL_PAGES, PAGE, KV_HEADS, HEAD_DIM),
            jnp.int8 if quantized else jnp.bfloat16)
    shapes = [((SLOTS, HEADS, HEAD_DIM), jnp.float32), pool, pool,
              ((SLOTS, NPAGES), jnp.int32), ((SLOTS,), jnp.int32)]
    if quantized:
        shapes += [((POOL_PAGES,), jnp.float32)] * 2

    def fn(q, k_pages, v_pages, block_table, seq_lens, *scales):
        k_scales, v_scales = scales or (None, None)
        return paged_decode_attention_pallas(
            q, k_pages, v_pages, block_table, seq_lens,
            policy="bf16x6_pallas", interpret=False,
            k_scales=k_scales, v_scales=v_scales)
    text = _compiled_text(fn, one_chip, *shapes)
    assert "tpu_custom_call" in text


# -- the paged model step keeps its page pools in place ----------------------

STEP_LAYERS, STEP_SLOTS, STEP_POOL, STEP_ROWS, CHUNK = 2, 8, 513, 64, 256
_POOL_COPIES = {"copy", "copy-start", "copy-done", "dynamic-slice",
                "dynamic-update-slice", "slice", "slice-start", "slice-done"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = (.*?) ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def _pool_copies(text, pool_shape, groups):
    """Instructions of an optimised HLO module, fused computations
    included, that copy or slice a whole page pool: a ``_POOL_COPIES`` op
    whose result has the pool's shape ``(P, page, *tail)``, one layer of
    the stack ``(1, P, page, *tail)`` or the stack ``(g, P, page, *tail)``,
    in any layout and at any row width (the rows without their lane
    padding count too)."""
    shapes = {s[:-1] for s in (pool_shape, (1,) + pool_shape,
                               (groups,) + pool_shape)}
    found = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m is None or m.group(2) not in _POOL_COPIES:
            continue
        dims = {tuple(int(d) for d in s.split(",") if d)[:-1]
                for s in _SHAPE.findall(m.group(1))}
        if dims & shapes:
            found.append(line.strip()[:160])
    return found


def _step_config(name):
    from repro.configs import get_config
    if name == "qwen2-0.5b-fp32":
        cfg = get_config("qwen2-0.5b")
        replace = {"param_dtype": "float32"}
        policy = "bf16x6_pallas"
    else:
        cfg = get_config("internvl2-2b")
        replace = {"name": "internlm2-1.8b", "vision_tokens": 0,
                   "rope_theta": 1e6, "norm_eps": 1e-5}
        policy = None
    return dataclasses.replace(cfg, n_layers=STEP_LAYERS, **replace), policy


@pytest.mark.parametrize("config", ["qwen2-0.5b-fp32", "internlm2-1.8b"])
def test_pool_default_layout_is_row_major(one_chip, config):
    """Every page pool of the serving cells gets a row-major layout from
    the TPU by default, the layout the in-place append writes and the
    kernel reads, so no jit has to pin one (JAX's persistent compilation
    cache does not keep pinned layouts)."""
    from repro.models.model import paged_cache_specs
    cfg, _ = _step_config(config)
    pools = [a for a in jax.tree.leaves(
        paged_cache_specs(cfg, STEP_SLOTS, STEP_POOL, PAGE)) if a.ndim > 2]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in pools]
    formats = jax.jit(lambda *a: [x.at[0, 1].set(0) for x in a]).lower(
        *args).compile().input_formats[0]
    for a, f in zip(pools, formats):
        assert f.layout.major_to_minor == tuple(range(a.ndim)), (a.shape,
                                                                  f.layout)


@pytest.mark.parametrize("tokens", [1, CHUNK], ids=["decode", "chunk"])
@pytest.mark.parametrize("config", ["qwen2-0.5b-fp32", "internlm2-1.8b"])
def test_paged_step_writes_its_pools_in_place(one_chip, monkeypatch, config,
                                              tokens):
    """The donated paged step at the serving cells' widths: the pools ride
    the layer scan's carry in the TPU's default layout, which their
    lane-padded rows make row-major, so neither the scan nor the Pallas
    kernel's operand slices, copies or re-stacks a whole pool; the
    in-place scatter is the only write."""
    from repro.core.context import policy_scope
    from repro.models import decode_step_paged
    from repro.models.model import abstract_params, paged_cache_specs
    # Steer the kernels' interpret switch to the chip, as on a TPU host.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, policy = _step_config(config)
    b = STEP_SLOTS if tokens == 1 else 1
    specs = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        paged_cache_specs(cfg, STEP_SLOTS, STEP_POOL, PAGE))
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        abstract_params(cfg))
    i32 = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
           for s in ((b, tokens), (b, STEP_ROWS), (b,))]

    def step(p, t, c, bt, sl):
        return decode_step_paged(p, t, c, bt, sl, cfg)
    step = jax.jit(step, donate_argnums=(2,))
    with policy_scope(policy) if policy else contextlib.nullcontext():
        text = step.lower(params, i32[0], specs, i32[1],
                          i32[2]).compile().as_text()
    pool = jax.tree.leaves(specs)[0].shape
    assert _pool_copies(text, pool[1:], pool[0]) == []
    if policy:
        assert "tpu_custom_call" in text
