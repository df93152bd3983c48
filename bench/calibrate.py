#!/usr/bin/env python3
"""Calibration runs behind the numbers in ``BENCHMARK.json`` and the cell
files; the benchmark's own runs never call this.  Each subcommand runs
its cells in one process, so compiled programs are shared.

    # knee sweep of a chat cell: backlog and tails at each rate
    python3 bench/calibrate.py sweep --workload W --rates 1,2,3 --seconds 20
    # the compared numbers of the program and of the cell's control
    python3 bench/calibrate.py readings --workload W --seeds 1,2,3 --seconds 15
    # a traced run whose trace is kept, with a summary of its event names
    python3 bench/calibrate.py trace --workload W --seconds 5 --out DIR
"""
from __future__ import annotations

import argparse
import copy
import json
import shutil
import sys
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[1])]

from bench import run as R  # noqa: E402
from bench import spec  # noqa: E402
from bench.drive import Clock  # noqa: E402


def _prepare(workload: str):
    cell = spec.load_cell(workload)
    device = R.require_device(cell.chips)
    from repro.launch import use_compile_cache
    use_compile_cache()
    return cell, device


def sweep(args) -> None:
    cell, device = _prepare(args.workload)
    if args.preroll is not None:
        cell.mix["preroll_s"] = args.preroll
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c.params["rate_per_s"] = rate
        res, numbers, _lim, run = R.run_cell(c, args.seed, args.seconds,
                                             False, Clock(), device,
                                             log=lambda s: print(s, flush=True))
        m = {k: v["value"] for k, v in res["metrics"].items()}
        print(json.dumps({"rate_per_s": rate, "backlog": run.backlog,
                          "attempted": res["attempted"],
                          "failed": res["failed"], **m,
                          "correct": res["correct"], **numbers}), flush=True)


def readings(args) -> None:
    cell, device = _prepare(args.workload)
    if args.preroll is not None:
        cell.mix["preroll_s"] = args.preroll
    if args.fill is not None:
        cell.params["fill_completions"] = args.fill
    control = cell.config["control"]
    for seed in (int(s) for s in args.seeds.split(",")):
        res, numbers, _lim, _run = R.run_cell(cell, seed, args.seconds,
                                              False, Clock(), device,
                                              control=control,
                                              log=lambda s: None)
        print(json.dumps({"seed": seed, "control": control,
                          "correct": res["correct"], **numbers,
                          "metrics": {k: v["value"]
                                      for k, v in res["metrics"].items()}}),
              flush=True)


def trace(args) -> None:
    cell, device = _prepare(args.workload)
    from bench import trace as tr
    out = Path(args.out)
    reduce = R._reduce_trace

    def keep(trace_dir):
        shutil.copytree(trace_dir, out, dirs_exist_ok=True)
        dev, host, n_dev = tr.load(str(trace_dir))
        names = {}
        for e in dev:
            names[e.name] = names.get(e.name, 0) + 1
        top = sorted(names.items(), key=lambda kv: -kv[1])[:60]
        print(json.dumps({"device_planes": n_dev, "device_events": len(dev),
                          "host_spans": len(host), "names": top}), flush=True)
        return reduce(trace_dir)

    R._reduce_trace = keep
    res, numbers, _lim, _run = R.run_cell(cell, args.seed, args.seconds, True,
                                          Clock(), device,
                                          log=lambda s: print(s, flush=True))
    print(json.dumps(res), flush=True)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("sweep", "readings", "trace"):
        p = sub.add_parser(name)
        p.add_argument("--workload", required=True)
        p.add_argument("--seconds", type=float, required=True)
        p.add_argument("--seed", type=int, default=1)
    sub.choices["sweep"].add_argument("--rates", required=True)
    sub.choices["sweep"].add_argument(
        "--preroll", type=float, default=None,
        help="a chat cell's preroll, longer than the cell's so that each "
             "rate's load has settled before its window")
    sub.choices["readings"].add_argument("--seeds", required=True)
    sub.choices["readings"].add_argument(
        "--preroll", type=float, default=None,
        help="a chat cell's preroll, shorter than the cell's to save chip "
             "time: the compared numbers do not depend on the backlog")
    sub.choices["readings"].add_argument(
        "--fill", type=int, default=None,
        help="a batch cell's fill_completions, for the same reason")
    sub.choices["trace"].add_argument("--out", required=True)
    args = ap.parse_args()
    {"sweep": sweep, "readings": readings, "trace": trace}[args.cmd](args)


if __name__ == "__main__":
    main()
