# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness: one module per paper table/figure.

  Table 1 / §3  -> bf_table        (B/F ratios, staging-vs-matrix analysis)
  Fig. 3        -> ai_curves       (AI(n)=n/5 + crossovers)
  Fig. 4        -> householder     (fragment-from-rule vs staged)
  Fig. 5        -> givens          (map-generated rotation, embedded vs arg)
  Fig. 7        -> ai_curves       (TCEC staging roofline, 52 -> 104 TFlop/s)
  Fig. 8        -> tcec_accuracy   (measured: emulation matches fp32)
                   tcec_throughput (bounds + compiled HBM-traffic ratio)
  Fig. 10       -> attention_throughput (policy x (sq, skv, d) flash sweep)
  §4.4 policies -> policy_sweep    (every registered policy via policy_scope)
  §API (Code 4/5) -> einsum_frontend (fused-epilogue + fragment-operand
                   walltime vs the staged/unfused twins, saved-bytes claim)
  §Serving      -> serving_throughput (paged vs dense decode: tok/s and
                   cache-bytes-touched per step across policies; prefix
                   cache hit rates; speculative-decoding spec_ngram_* /
                   spec_draft_* accept-rate + tok/s speedup rows)
  §Roofline     -> roofline        (cluster table from dry-run artifacts)
  §Autotune     -> autotune        (repro.tune plan picks + predicted vs
                   measured walltime)

Every row prints as ``name,value,derived`` where timing rows use us_per_call
and analysis rows carry the derived quantity.  ``--json out.json``
additionally writes machine-readable records
``{"bench", "name", "shape", "policy", "metric", "value"}`` (shape/policy
parsed best-effort from the row key; null when a row has neither).
"""
import argparse
import json
import re
import sys
import time
import traceback

_SHAPE_RE = re.compile(r"(?:m(\d+)n(\d+)k(\d+))|(?:_s(\d+)(?:_|$))|"
                       r"(?:b(\d+)_s(\d+))")
_POLICY_RE = re.compile(
    r"(bf16x\d(?:_(?:pallas|staged))?|int8x\d(?:_pallas)?|fp32_vpu)")
# speculative-decoding rows (serving_throughput): spec_ngram_* /
# spec_draft_* accept-rate, tok/s and speedup rows carry the proposer.
_SPEC_RE = re.compile(r"spec_(ngram|draft)_")


def _row_record(bench: str, key: str, metric: str, value):
    shape = policy = proposer = None
    m = _SHAPE_RE.search(key)
    if m:
        groups = [g for g in m.groups() if g is not None]
        shape = "x".join(groups)
    p = _POLICY_RE.search(key)
    if p:
        policy = p.group(1)
    sp = _SPEC_RE.search(key)
    if sp:
        proposer = sp.group(1)
    rec = {"bench": bench, "name": key, "shape": shape, "policy": policy,
           "metric": metric, "value": value}
    if proposer is not None:
        rec["proposer"] = proposer
    return rec


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", metavar="OUT", default=None,
                    help="also write machine-readable results to this path")
    ap.add_argument("--only", default=None,
                    help="comma-separated module names to run (default: all)")
    args = ap.parse_args(argv)

    from repro.launch import use_compile_cache
    use_compile_cache()
    from benchmarks import (bf_table, ai_curves, householder, givens,
                            tcec_accuracy, tcec_throughput,
                            attention_throughput, policy_sweep,
                            einsum_frontend, serving_throughput, roofline,
                            autotune)
    modules = [
        ("bf_table", bf_table),
        ("ai_curves", ai_curves),
        ("householder", householder),
        ("givens", givens),
        ("tcec_accuracy", tcec_accuracy),
        ("tcec_throughput", tcec_throughput),
        ("attention_throughput", attention_throughput),
        ("policy_sweep", policy_sweep),
        ("einsum_frontend", einsum_frontend),
        ("serving_throughput", serving_throughput),
        ("roofline", roofline),
        ("autotune", autotune),
    ]
    if args.only:
        keep = set(args.only.split(","))
        modules = [(n, m) for n, m in modules if n in keep]
    failures = 0
    records = []
    print("name,us_per_call,derived")
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # noqa: BLE001
            traceback.print_exc()
            print(f"{name},ERROR,{type(e).__name__}")
            records.append(_row_record(name, "ERROR", "error",
                                       type(e).__name__))
            failures += 1
            continue
        dt_us = (time.perf_counter() - t0) * 1e6
        print(f"{name}.total,{dt_us:.1f},")
        records.append(_row_record(name, "total", "us_per_call", dt_us))
        for key, val in rows:
            if key.endswith("_us"):
                print(f"{name}.{key},{val:.2f},")
                records.append(_row_record(name, key, "us_per_call",
                                           float(val)))
            else:
                try:
                    shown = f"{val:.6g}"
                except (TypeError, ValueError):
                    shown = str(val)
                print(f"{name}.{key},,{shown}")
                records.append(_row_record(name, key, "derived", val))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1)
        print(f"# wrote {len(records)} records to {args.json}",
              file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == '__main__':
    main()
