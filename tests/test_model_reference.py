"""The plain float32 reference forward agrees with the model's prefill
under the fp32 policy: the two share only the parameter tree, so agreement
to fp32 roundoff checks the model's conventions (rope, norm gain, GQA head
grouping, tied head) against an independent implementation."""
import dataclasses

import numpy as np
import pytest
import jax

from repro.configs import get_config
from repro.core.context import policy_scope
from repro.models import init_params, prefill
from repro.models.reference import reference_logits

from oracles import max_rel_err


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "gemma-7b",
                                  "deepseek-coder-33b"])
def test_reference_matches_fp32_prefill(arch):
    cfg = dataclasses.replace(get_config(arch, reduced=True),
                              param_dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 13), 0, cfg.vocab)
    with policy_scope("fp32_vpu"):
        got, _ = prefill(params, {"tokens": tokens}, cfg)
    ref = reference_logits(params, tokens, cfg)
    assert max_rel_err(got, ref) < 1e-5


def test_reference_rejects_non_attention_patterns():
    cfg = get_config("jamba-1.5-large-398b", reduced=True)
    with pytest.raises(NotImplementedError):
        reference_logits({}, np.zeros((1, 4), np.int32), cfg)
