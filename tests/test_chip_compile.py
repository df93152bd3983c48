"""Real-width compiles of the main-path Pallas kernels for a described TPU
v5e, without the chip.

Interpret mode runs these kernels bit-faithfully on the CPU but never
shows what Mosaic refuses: block shapes that do not tile, VMEM overuse,
unsupported in-kernel layouts.  Each case lowers the kernel at
qwen2-0.5b widths (d=896, d_ff=4864, 14 heads over 2 KV heads, head dim
64) against one device of a described ``v5e:2x2`` topology, compiles it
with the TPU compiler, and checks that the kernel is in the program as a
``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import os

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
