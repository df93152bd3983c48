#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the configuration's weights on the device from the seed,
builds ``PagedServingEngine`` with the cell's options, warms the two
programs the window drives (the ``(slots, 1)`` decode step and the
``(1, prefill_chunk)`` chunk step) through the engine itself, and brings
the load to steady state: a chat cell runs its arrivals for ``preroll_s``,
a batch cell runs its queue until ``fill_completions`` requests are done.
The window then measures for ``--seconds``.  Afterwards a sample of the
served requests is checked against the plain reference, and the last line
of standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics`` and ``device``.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics.  Without a TPU whose ``device_kind`` is in
``bench/peaks.json``, or with fewer chips than the cell asks for, it exits
non-zero before printing a result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# the benchmark's own package, and the system under test beside it
for _p in (str(ROOT / "src"), str(ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import check, flops, spec, traffic  # noqa: E402
from bench.drive import Clock, Drive  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"
# Logits rows kept per sampled request, spread over its output.
ROWS_PER_SAMPLE = 128
SAMPLE_RANDOM = 3
# Seconds past the window a batch cell waits for its sampled requests.
SAMPLE_WAIT_S = 60
WARM_RID = 1 << 40


class NoDevice(RuntimeError):
    """No accelerator this cell can be measured on."""


def require_device(chips: int) -> dict:
    """The device description, or ``NoDevice`` unless JAX sees at least
    ``chips`` TPU chips of a kind in the peaks table."""
    import jax
    devs = jax.devices()
    d = devs[0]
    table = spec.peaks()
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX platform is {d.platform!r}")
    if d.device_kind not in table:
        raise NoDevice(f"device kind {d.device_kind!r} is not in "
                       f"bench/peaks.json")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips, JAX sees {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def pct(xs, q) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ---------------------------------------------------------------------------
# The model under test
# ---------------------------------------------------------------------------

def repro_config(config: dict):
    """The engine's ``ArchConfig`` for a configuration file: the program's
    preset named under ``engine.arch``, with ``engine.replace`` applied."""
    from repro.configs import get_config
    e = config["engine"]
    return dataclasses.replace(get_config(e["arch"]), **e.get("replace", {}))


def policy_scope(config: dict):
    from repro.core.context import policy_scope as scope
    pol = config["engine"].get("policy")
    return scope(pol) if pol else contextlib.nullcontext()


class Capture:
    """Wraps the engine's jitted step: every call's logits are kept until
    the tick ends, then the rows of the sampled requests are copied out by
    one small jitted gather each (compiled at warm-up)."""

    def __init__(self, engine):
        import jax
        self.fn = engine._decode_fn
        engine._decode_fn = self
        self.calls: List = []
        self.take = jax.jit(lambda x, i: x[i])

    def __call__(self, params, tokens, *rest):
        import jax
        name = "engine.decode" if tokens.shape[1] == 1 else "engine.chunk"
        with jax.profiler.TraceAnnotation(name):
            logits, caches = self.fn(params, tokens, *rest)
        self.calls.append(logits)
        return logits, caches


class Sampler:
    """Keeps the engine's logits rows for the sampled requests."""

    def __init__(self, capture: Capture, drive: Drive):
        self.capture = capture
        self.drive = drive
        self.samples: Dict[int, check.Sample] = {}
        self.every: Dict[int, int] = {}

    def choose(self, rids) -> None:
        """Keep rows of ``rids`` from the next tick on, about
        ``ROWS_PER_SAMPLE`` spread over each one's output."""
        for r in rids:
            req = self.drive.by_rid[r]
            self.samples[r] = check.Sample(prompt=req.prompt, served=[])
            self.every[r] = max(1, -(-req.max_new // ROWS_PER_SAMPLE))

    def _keep(self, rid, logits, row, index):
        import jax.numpy as jnp
        s = self.samples[rid]
        if index % self.every[rid] == 0:
            s.rows.append(self.capture.take(logits, jnp.int32(row)))
            s.row_index.append(index)

    def __call__(self, plan) -> None:
        calls = self.capture.calls
        self.capture.calls = []
        for i, c in enumerate(plan.prefill):
            if c.last and c.rid in self.samples:
                self._keep(c.rid, calls[i], 0, 0)
        if plan.decode:
            logits = calls[len(plan.prefill)]
            for rid, slot in plan.decode:
                if rid in self.samples:
                    n = len(self.drive.token_times[rid]) - 1
                    self._keep(rid, logits, slot, n)


def build(cell: spec.Cell, seed: int):
    """Weights from the seed and the engine with the cell's options."""
    import jax
    from repro.models.model import abstract_params
    from repro.serving import PagedServingEngine
    from bench.weights import make_params
    cfg = repro_config(cell.config)
    arch = flops.Arch.from_config(cell.config["model"])
    params = make_params(abstract_params(cfg), arch.d, seed)
    jax.block_until_ready(params)
    e = cell.config["engine"]
    engine = PagedServingEngine(
        cfg, params, page_size=e["page_size"],
        max_concurrency=cell.params["slots"],
        max_seq_len=cell.mix["max_seq_len"],
        prefill_chunk=e["prefill_chunk"])
    return arch, params, engine


def warm(engine, capture: Capture, prefill_chunk: int, vocab: int) -> None:
    """Serve one request of two chunks and two tokens through the engine,
    and gather a row of each step's logits: every program and eager
    operation the window uses is compiled (or loaded) here."""
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    engine.submit(rng.integers(0, vocab, prefill_chunk + 1).tolist(), 3,
                  rid=WARM_RID)
    while not engine.scheduler.done:
        engine.step()
        for logits in capture.calls:
            capture.take(logits, jnp.int32(0)).block_until_ready()
        capture.calls = []


def pick_samples(reqs, lo: float, hi: float, seed: int) -> List[int]:
    """The sampled requests, drawn from the seed: among those due in
    ``[lo, hi]``, the longest (prompt and output) and ``SAMPLE_RANDOM``
    others."""
    cand = [r for r in reqs if lo <= r.arrival <= hi] \
        or [min((r for r in reqs if r.arrival >= lo), key=lambda r: r.arrival)]
    longest = max(cand, key=lambda r: (len(r.prompt) + r.max_new, -r.rid))
    rest = [r.rid for r in cand if r.rid != longest.rid]
    rng = np.random.default_rng([seed, 1])
    k = min(SAMPLE_RANDOM, len(rest))
    return [longest.rid] + sorted(rng.choice(rest, k, replace=False).tolist())


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """What the metric readers read."""
    cell: spec.Cell
    arch: flops.Arch
    peak: dict
    drive: Drive
    t0: float
    t1: float
    window_ticks: list
    in_window: List[int]
    trace: Optional[object] = None      # bench.trace.Reduced
    pallas_sites: Optional[set] = None
    backlog: tuple = ()


class CompileCounter:
    """Counts JAX traces and backend compiles (or persistent-cache loads)
    while ``armed``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _secs, **_kw):
        if self.armed and event in self.EVENTS:
            self.count += 1


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             clock: Clock, device: Optional[dict], control: Optional[str] = None,
             log=print):
    """Set up, warm, drive the window and check it.  Returns ``(result,
    numbers, limits, run)``: the result line's dict, the compared numbers,
    their limits, and what the metric readers read."""
    import jax
    from repro import tcec
    counter = CompileCounter()
    p, mix = cell.params, cell.mix
    e = cell.config["engine"]
    with policy_scope(cell.config), tcec.trace_plans() as plans:
        arch, params, engine = build(cell, seed)
        capture = Capture(engine)
        warm(engine, capture, e["prefill_chunk"], arch.vocab)
        pallas_sites = {r.site for r in plans if r.backend.startswith("pallas")}

        if mix["arrival"] == "poisson":
            pre, rate = mix["preroll_s"], p["rate_per_s"]
            segs = [traffic.segment(s, rate)
                    for s in (pre, seconds, mix["drain_s"])]
            reqs = traffic.schedule(mix, segs, seed, arch.vocab, rate)
            window_reqs = reqs[segs[0][0]:segs[0][0] + segs[1][0]]
        else:
            reqs = traffic.schedule(mix, [(p["queue"], 0.0)], seed,
                                    arch.vocab)
        origin = clock.now()
        drive = Drive(engine, reqs, clock, origin)
        sampler = Sampler(capture, drive)
        drive.on_tick = sampler

        if mix["arrival"] == "poisson":
            drive.run_until(origin + pre)
            # the window's own arrivals, due in its first half
            rids = pick_samples(window_reqs, pre, pre + seconds / 2, seed)
        else:
            need = p["fill_completions"]
            while sum(r in drive.by_rid for r in drive.completed()) < need:
                drive.tick()
            # the queue is served in order: the next ``slots`` requests are
            # those the window admits first
            nxt = len(drive.admitted_at)
            rids = pick_samples(reqs[nxt:nxt + p["slots"]], 0.0, 0.0, seed)
        sampler.choose(rids)
        t0 = clock.now()
        setup_s = t0 - T_START if device is not None else t0 - origin
        n_before = len(drive.ticks)
        backlog = [drive.backlog()]
        log(f"set-up done: {setup_s:.3f} s; {n_before} ticks before the "
            f"window; pallas sites {sorted(pallas_sites)}")

        counter.armed = True
        tracer = _trace_start() if trace else None
        if tracer is not None:
            with jax.profiler.TraceAnnotation("bench.window"):
                _window(drive, t0 + seconds)
                jax.block_until_ready(engine.caches)
        else:
            _window(drive, t0 + seconds)
        t1 = clock.now()
        backlog.append(drive.backlog())
        counter.armed = False
        reduced = _trace_stop(tracer) if tracer is not None else None
        window_ticks = drive.ticks[n_before:]

        if mix["arrival"] == "poisson":
            # the window's own segment: the same work for every seed.  Late
            # first tokens are late, not lost: wait for them
            in_window = [r.rid for r in window_reqs]
            drive.run_until(t1 + mix["drain_s"], done=lambda: all(
                drive.token_times.get(r) for r in in_window))
        else:
            # offline: the requests that emitted a token in the window
            in_window = sorted(rid for rid, times in drive.token_times.items()
                               if any(t0 < t <= t1 for t in times))
            drive.run_until(t1 + SAMPLE_WAIT_S, done=lambda: all(
                drive.token_times.get(r) for r in rids))
        late = drive.lateness()
        log(f"window: {seconds} s, {counter.count} compilations inside it; "
            f"generator lateness p50 {pct(late, 50) * 1e3:.3f} ms, max "
            f"{max(late) * 1e3:.3f} ms over {len(late)} submissions; "
            f"backlog {backlog[0]} at the window's start, {backlog[1]} at its "
            f"end")
        log(_window_summary(drive, window_ticks, in_window if mix["arrival"]
                            == "poisson" else [], mix["max_seq_len"]))
        dev = jax.devices()[0]
        stats = dev.memory_stats() or {}
        run = RunData(cell=cell, arch=arch, peak=spec.peaks().get(
            device["kind"] if device else "", {}), drive=drive, t0=t0, t1=t1,
            window_ticks=window_ticks, in_window=in_window, trace=reduced,
            pallas_sites=pallas_sites, backlog=tuple(backlog))

        failed = [r for r in in_window if not drive.token_times.get(r)]
        samples = []
        for r in rids:
            s = sampler.samples[r]
            s.served = drive.served(r)
            samples.append(s)
        # free the engine's pools before the reference runs
        engine.caches = None
        del engine, capture
        drive.engine = None
    ref = importlib.import_module(
        f"bench.references.{cell.config['reference']}")
    numbers = check.readings(params, samples, ref, arch,
                             pad_to=mix["max_seq_len"],
                             row_cap=ROWS_PER_SAMPLE, control=control)
    numbers["served_tokens"] = sum(len(s.served) for s in samples)
    limits = dict(p["limits"])
    ok = check.judge(numbers, limits) and not failed \
        and counter.count == 0 and all(s.served for s in samples)

    result = {"correct": bool(ok), "attempted": len(in_window),
              "failed": len(failed)}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = spec.metric_reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = _end_to_end(run, setup_s)
    result["metrics"] = metrics
    result["device"] = dict(device or {}, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)))
    if reduced is not None:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        top = sorted(reduced.op_time.items(), key=lambda kv: -kv[1])[:10]
        result["breakdown"] = {"device_ops": [[k, v] for k, v in top],
                               "idle_gaps": [[k, v] for k, v in reduced.gaps]}
    result["checks"] = {k: {"value": numbers.get(k), "limit": v}
                        for k, v in limits.items()}
    result["checks"]["compilations_in_window"] = {"value": counter.count,
                                                  "limit": 0}
    return result, numbers, limits, run


def _window(drive: Drive, t1: float) -> None:
    import jax
    while drive.clock.now() < t1:
        with jax.profiler.TraceAnnotation("bench.tick"):
            drive.tick()


def _trace_start():
    import jax
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    return TRACE_DIR


def _trace_stop(trace_dir):
    import jax
    jax.profiler.stop_trace()
    try:
        return _reduce_trace(trace_dir)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _reduce_trace(trace_dir):
    from bench import trace as tr
    device, host, _n = tr.load(str(trace_dir))
    return tr.reduce_events(device, host, tr.window_of(host))


def _ttft(d: Drive, in_window) -> List[float]:
    return [d.token_times[r][0] - d.due(r) for r in in_window
            if d.token_times.get(r)]


def _window_summary(d: Drive, ticks, in_window, max_seq_len: int) -> str:
    """A log line: TTFT percentiles over the window's arrivals, and how full
    the slots and the reserved KV rows were over the window's ticks."""
    ttft = _ttft(d, in_window)
    tt = " / ".join(f"{pct(ttft, q) * 1e3:.1f}" for q in (50, 70, 90)) \
        if ttft else "none"
    live = [sum(c for _r, c in t.decode)
            + sum(s + n for _r, n, s, _l in t.prefill) for t in ticks]
    busy = [len(t.decode) + len(t.prefill) for t in ticks]
    slots = d.slots
    return (f"ttft p50 / p70 / p90 ms: {tt} over {len(ttft)} arrivals; "
            f"mean over {len(ticks)} ticks: {np.mean(busy or [0]):.2f} of "
            f"{slots} slots in use, live KV tokens "
            f"{np.mean(live or [0]):.0f} of {slots * max_seq_len} reserved "
            f"({100 * np.mean(live or [0]) / (slots * max_seq_len):.1f}%)")


def _end_to_end(run: RunData, setup_s: float) -> Dict[str, dict]:
    d = run.drive
    out = {}
    names = {m["name"]: m["unit"] for m in run.cell.end_to_end}
    for name in names:
        # ttft_p<q>_ms: the q-th percentile over the window's arrivals
        q = re.fullmatch(r"ttft_p(\d+)_ms", name)
        if q:
            out[name] = pct(_ttft(d, run.in_window), int(q.group(1))) * 1e3
    if "itl_mean_ms" in names:
        gaps = [b - a for times in d.token_times.values()
                for a, b in zip(times, times[1:]) if run.t0 < b <= run.t1]
        out["itl_mean_ms"] = float(np.mean(gaps)) * 1e3
    if "output_tok_s" in names:
        n = sum(1 for times in d.token_times.values() for t in times
                if run.t0 < t <= run.t1)
        out["output_tok_s"] = n / (run.t1 - run.t0)
    out["setup_s"] = setup_s
    return {k: {"value": v, "unit": names[k]} for k, v in out.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    try:
        device = require_device(cell.chips)
    except NoDevice as err:
        print(f"bench: {err}", file=sys.stderr)
        return 2
    print(f"device: {device}", flush=True)
    from repro.launch import use_compile_cache
    use_compile_cache()
    result, numbers, limits, _ = run_cell(cell, args.seed, args.seconds,
                                       bool(args.trace), Clock(), device,
                                       log=lambda s: print(s, flush=True))
    print(f"compared: {json.dumps(numbers)}", flush=True)
    for line in check.report(numbers, limits):
        print(line, file=sys.stderr)
    print(f"compilations_in_window: {result['checks']['compilations_in_window']['value']} "
          f"(limit 0)", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
