"""Cells at a size a CPU test run holds: the harness's own code paths, at
two layers of width 64 over a 512-token vocabulary.

``fp32`` mirrors ``qwen2-0.5b-fp32`` (fp32 weights, tied head, biases,
``bf16x6_pallas`` with the kernels in interpret mode, control ``bf16x3``);
``bf16`` mirrors ``internlm2-1.8b`` (bf16 weights, untied head, the default
XLA policies, control ``int8``).  Limits here are set from readings at this
size (see ``tests/bench/test_bench_correct.py``), not the cells' limits.
"""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec  # noqa: E402

_MODEL = {"hidden_size": 64, "intermediate_size": 128,
          "num_attention_heads": 4, "num_key_value_heads": 2,
          "num_hidden_layers": 2, "vocab_size": 512, "hidden_act": "silu",
          "rms_norm_eps": 1e-6, "rope_theta": 1e6}
_REPLACE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
            "d_ff": 128, "vocab": 512}

KINDS = {
    "fp32": dict(dtype="float32", tied=True, bias=True,
                 policy="bf16x6_pallas", control="bf16x3"),
    "bf16": dict(dtype="bfloat16", tied=False, bias=False, policy=None,
                 control="int8"),
}

MIXES = {
    "chat": {"arrival": "poisson",
             "prompt": {"median": 12, "sigma": 1.0, "min": 4, "max": 40},
             "output": {"median": 6, "sigma": 0.8, "min": 2, "max": 16},
             "max_seq_len": 64, "preroll_s": 1, "drain_s": 30},
    "batch": {"arrival": "offline",
              "prompt": {"median": 20, "sigma": 0.7, "min": 8, "max": 40},
              "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 20},
              "max_seq_len": 64},
}

# Readings at this size: the program's logit_err about 4e-7 (fp32) and
# 6e-3..9e-3 (bf16); see test_bench_correct.py for the controls'.
LIMITS = {"fp32": {"gap": 1e-4, "logit_err": 3e-6, "argmax_mismatch": 0},
          "bf16": {"gap": 0.1, "logit_err": 0.015, "argmax_mismatch": 0}}


def cell(kind: str, traffic: str, limits=None) -> spec.Cell:
    k = KINDS[kind]
    config = {
        "model": dict(_MODEL, tie_word_embeddings=k["tied"],
                      torch_dtype=k["dtype"], qkv_bias=k["bias"]),
        "engine": {"arch": "qwen2-0.5b",
                   "replace": dict(_REPLACE, param_dtype=k["dtype"],
                                   tie_embeddings=k["tied"],
                                   qkv_bias=k["bias"]),
                   "policy": k["policy"], "page_size": 8,
                   "prefill_chunk": 16},
        "reference": "dense_gqa", "control": k["control"]}
    params = {"slots": 4, "limits": dict(limits or LIMITS[kind])}
    if traffic == "chat":
        params["rate_per_s"] = 3.0
    else:
        params.update(queue=40, fill_completions=2)
    e2e = [{"name": n, "unit": u} for n, u in (
        ("ttft_p50_ms", "ms"), ("ttft_p90_ms", "ms"), ("itl_mean_ms", "ms"),
        ("output_tok_s", "tokens/s"), ("setup_s", "s"))]
    return spec.Cell(name=f"tiny-{kind}.{traffic}", chips=1,
                     config_name=f"tiny-{kind}", traffic=traffic,
                     config=config, mix=MIXES[traffic], params=params,
                     end_to_end=e2e, per_layer=[])
