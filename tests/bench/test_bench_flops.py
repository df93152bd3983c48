"""Work counts from shapes: parameter counts against the engine's own
parameter declarations for both configurations, FLOPs and kernel bounds
against hand arithmetic."""
import json

import numpy as np
import pytest

import bench_tiny  # noqa: F401
from bench import flops, spec
from bench import run as R


@pytest.mark.parametrize("name", ["qwen2-0.5b-fp32", "internlm2-1.8b"])
def test_param_count_matches_the_engine(name):
    import jax
    from repro.models.model import abstract_params
    config = json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())
    arch = flops.Arch.from_config(config["model"])
    tree = abstract_params(R.repro_config(config))
    leaves = jax.tree.leaves(tree)
    assert flops.param_count(arch) == sum(int(np.prod(x.shape)) for x in leaves)
    want = {"qwen2-0.5b-fp32": "float32", "internlm2-1.8b": "bfloat16"}[name]
    assert {str(x.dtype) for x in leaves} == {want}


def test_published_sizes():
    load = lambda n: flops.Arch.from_config(json.loads(
        (spec.BENCH / "configs" / f"{n}.json").read_text())["model"])
    q, i = load("qwen2-0.5b-fp32"), load("internlm2-1.8b")
    assert flops.param_count(q) == 494_032_768
    assert flops.param_count(i) == 1_889_146_880
    assert (q.head_dim, i.head_dim) == (64, 128)


ARCH = flops.Arch(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
                  vocab=10, tied=True, qkv_bias=False, rope_theta=1e4,
                  eps=1e-6, dtype="bfloat16")


def test_token_and_step_flops():
    weights = 2 * (8 * 8 + 8 * 4 + 8 * 4 + 8 * 8 + 8 * 16 * 2 + 16 * 8)
    assert flops.token_flops(ARCH, 5, False) == 2 * (weights + 4 * 2 * 4 * 5)
    assert flops.token_flops(ARCH, 5, True) == \
        2 * (weights + 4 * 2 * 4 * 5) + 2 * 8 * 10
    # a chunk of 3 tokens from position 4 attends over 5, 6 and 7 positions
    chunk = sum(flops.token_flops(ARCH, c, False) for c in (5, 6, 7)) \
        + 2 * 8 * 10
    assert flops.step_flops(ARCH, [(0, 3, 4, False)], []) == chunk
    assert flops.step_flops(ARCH, [], [(1, 9), (2, 3)]) == \
        flops.token_flops(ARCH, 9, True) + flops.token_flops(ARCH, 3, True)


def test_kernel_bounds():
    # 2*4*16*8 = 1024 FLOPs at 1e3/s vs (32+128+64)*2 = 448 bytes at 1e2/s
    assert flops.matmul_bound_s(4, 8, 16, 2, 1e3, 1e2) == pytest.approx(4.48)
    assert flops.matmul_bound_s(4, 8, 16, 2, 1e2, 1e3) == pytest.approx(10.24)
    # live K and V of contexts 3 and 5 (1 kv head of 4, bf16), plus q (bf16)
    # and the fp32 output of 3 rows of 2 heads
    nbytes = 2 * 8 * 1 * 4 * 2 + 3 * 2 * 4 * (2 + 4)
    assert flops.paged_decode_bound_s(ARCH, [3, 5], 3, 1.0) == nbytes
