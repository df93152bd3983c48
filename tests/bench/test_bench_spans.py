"""The reduction of the engine's spans and programs (``bench.spans``): idle
time by span, per-program device time and the per-layer numbers, on
synthetic events and on traces recorded on a TPU v5e chip."""
import gzip
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
from bench import spans as sp
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"
# kept apart: ``trace.load`` reads the newest recording under ``data``
DATA_CHAT = Path(__file__).resolve().parent / "data_chat"


def _S(name, t0, t1, **stats):
    return sp.Span(name, t0, t1, stats)


def _synthetic():
    """Two ticks in a 10 s window.  Tick 1 admits request 1 and runs its
    only chunk and a decode; tick 2 admits request 2, runs a chunk of it
    that is not its last, and decodes."""
    ops = [tr.Event("fusion", 1.0, 2.0), tr.Event("fusion", 2.5, 3.0),
           tr.Event("fusion", 5.0, 6.0), tr.Event("fusion", 6.5, 7.0)]
    modules = [tr.Event(sp.CHUNK, 1.0, 2.0), tr.Event(sp.DECODE, 2.5, 3.0),
               tr.Event(sp.CHUNK, 5.0, 6.0), tr.Event(sp.DECODE, 6.5, 7.0),
               tr.Event(sp.DECODE, 9.5, 10.5)]   # starts in the window
    host = [tr.Event("bench.window", 0.0, 10.0),
            tr.Event("bench.tick", 0.4, 3.8), tr.Event("bench.tick", 4.4, 8.0),
            tr.Event("engine.chunk", 0.95, 1.0)]
    spans = [
        _S("serving.step", 0.6, 3.4, n_admit=1, n_prefill=1, n_decode=1),
        _S("serving.schedule", 0.6, 0.7),
        _S("serving.admit", 0.7, 0.8, rid=1, slot=0),
        _S("serving.prefill", 0.8, 1.0, rid=1, start=0, tokens=7),
        _S("serving.sync", 1.0, 2.2, kind="first_token", rid=1),
        _S("serving.decode", 2.2, 2.4, lanes=1),
        _S("serving.sync", 2.4, 3.1, kind="decode"),
        _S("serving.record", 3.1, 3.3),
        _S("serving.step", 4.6, 7.2, n_admit=1, n_prefill=1, n_decode=1),
        _S("serving.schedule", 4.6, 4.7),
        _S("serving.admit", 4.7, 4.9, rid=2, slot=1),
        _S("serving.prefill", 4.9, 5.0, rid=2, start=0, tokens=16),
        _S("serving.decode", 5.0, 5.2, lanes=1),
        _S("serving.sync", 5.2, 7.1, kind="decode"),
    ]
    return sp.Trace(ops=ops, modules=modules, host=host, spans=spans)


def test_idle_is_named_by_the_innermost_engine_span():
    r = sp.reduce(_synthetic())
    assert r.window == (0.0, 10.0)
    assert r.busy_s == pytest.approx(3.0)
    idle = r.idle_by_span
    assert sum(idle.values()) == pytest.approx(7.0)
    # the harness's dispatch span gives way to the engine's around it
    assert idle["serving.prefill"] == pytest.approx(0.2 + 0.1)
    assert idle["serving.schedule"] == pytest.approx(0.1 + 0.1)
    assert idle["serving.admit"] == pytest.approx(0.1 + 0.2)
    assert idle["serving.sync"] == pytest.approx(0.2 + 0.1 + 0.1 + 0.5 + 0.1)
    assert idle["serving.decode"] == pytest.approx(0.2)
    assert idle["serving.record"] == pytest.approx(0.2)
    # host work of the step outside any phase span
    assert idle["serving.step"] == pytest.approx(0.1 + 0.1)
    assert idle["bench.tick"] == pytest.approx(0.2 + 0.4 + 0.2 + 0.8)
    assert idle["bench.window"] == pytest.approx(0.4 + 0.6 + 2.0)
    assert r.gaps[0] == ("bench.window", pytest.approx(3.0))
    assert [n for n, _ in r.gaps] == ["bench.window", "bench.window",
                                      "bench.tick", "serving.decode",
                                      "serving.sync"]


def test_program_time_and_step_means():
    r = sp.reduce(_synthetic())
    assert r.module_count == {sp.CHUNK: 2, sp.DECODE: 3}
    assert sp.step_ms(r, sp.CHUNK) == pytest.approx(1000.0)
    assert sp.step_ms(r, sp.DECODE) == pytest.approx(2000.0 / 3)
    assert sp.step_ms(r, sp.VERIFY) is None
    narrow = sp.reduce(_synthetic(), window=(2.2, 6.2))
    assert narrow.module_count == {sp.CHUNK: 1, sp.DECODE: 1}


def test_prefill_latency_counts_a_missing_first_token_to_the_window_end():
    r = sp.reduce(_synthetic())
    # request 1: 0.7 -> 2.2; request 2 has no first token: 4.7 -> 10
    lat = sorted([2.2 - 0.7, 10.0 - 4.7])
    expect = lat[0] + 0.9 * (lat[1] - lat[0])
    assert sp.prefill_latency_p90_ms(r) == pytest.approx(1e3 * expect)
    # only admissions in the window's first ``admit_s`` seconds count
    assert sp.prefill_latency_p90_ms(r, admit_s=1.0) == \
        pytest.approx(1e3 * 1.5)
    assert sp.prefill_latency_p90_ms(r, admit_s=0.5) is None


def test_host_time_per_tick_leaves_out_the_syncs():
    r = sp.reduce(_synthetic())
    host = [(3.4 - 0.6) - 1.2 - 0.7, (7.2 - 4.6) - 1.9]
    assert sp.host_ms_per_tick(r) == pytest.approx(1e3 * sum(host) / 2)
    # a step that the window cuts is left out
    cut = sp.reduce(_synthetic(), window=(0.0, 5.0))
    assert sp.host_ms_per_tick(cut) == pytest.approx(1e3 * host[0])


def test_a_trace_without_engine_spans_reads_none():
    t = _synthetic()
    bare = sp.Trace(ops=t.ops, modules=[], host=t.host, spans=[])
    r = sp.reduce(bare)
    assert sp.step_ms(r, sp.DECODE) is None
    assert sp.prefill_latency_p90_ms(r) is None
    assert sp.host_ms_per_tick(r) is None
    assert set(r.idle_by_span) == {"bench.tick", "bench.window",
                                   "engine.chunk"}
    # without the harness's window, the engine's ticks bound it
    alone = sp.Trace(ops=t.ops, modules=t.modules, host=[], spans=t.spans)
    assert alone.window() == (0.6, 7.2)
    with pytest.raises(ValueError):
        sp.reduce(sp.Trace([], [], [], []))


def test_module_names_drop_their_hash():
    assert sp.module_name("jit_paged_decode_step(8812930)") == \
        "jit_paged_decode_step"
    assert sp.module_name("jit__lambda") == "jit__lambda"


def test_recorded_chip_trace_programs():
    """The recording of ``test_bench_trace.test_recorded_chip_trace``, read
    for its programs: before the steps had names, the decode step and the
    prefill chunk were both ``jit__lambda``, and no engine span exists."""
    r = sp.reduce(sp.load(str(DATA)))
    assert r.window_s == pytest.approx(0.243363069)
    assert r.busy_s == pytest.approx(0.2334093769999999)
    assert r.module_count == {"jit__lambda": 7, "jit__argmax": 2,
                              "jit_convert_element_type": 6}
    assert sum(r.idle_by_span.values()) == pytest.approx(
        r.window_s - r.busy_s)
    assert all(name == "bench.tick" for name, _ in r.gaps)
    assert sp.step_ms(r, sp.DECODE) is None
    assert sp.host_ms_per_tick(r) is None


def test_recorded_chat_trace(tmp_path):
    """A 1 s traced window of ``qwen2-0.5b-fp32.chat`` on one TPU v5e chip
    (seed 3141000012), gzipped: three ticks, each two prefill chunks and
    one decode step over 8 lanes.  The third tick holds request 9's last
    chunk and its first-token sync; request 10, admitted in the first
    tick, has no first token by the window's end."""
    raw = gzip.decompress((DATA_CHAT / "chat-s3141000012.xplane.pb.gz")
                          .read_bytes())
    (tmp_path / "chat.xplane.pb").write_bytes(raw)
    t = sp.load(str(tmp_path))
    r = sp.reduce(t)
    assert r.window_s == pytest.approx(1.000737961)
    assert r.busy_s == pytest.approx(0.9848093550000011)
    assert r.module_count[sp.DECODE] == 3 and r.module_count[sp.CHUNK] == 6
    assert sp.step_ms(r, sp.DECODE) == pytest.approx(181.2126463333333)
    assert sp.step_ms(r, sp.CHUNK) == pytest.approx(73.50694816666666)
    assert sp.prefill_latency_p90_ms(r) == pytest.approx(1000.593601)
    assert sp.host_ms_per_tick(r) == pytest.approx(6.320556000000033)
    # the two programs are the device's work
    other = sum(v for k, v in r.module_time.items()
                if k not in (sp.DECODE, sp.CHUNK))
    assert r.module_time[sp.DECODE] + r.module_time[sp.CHUNK] == \
        pytest.approx(r.busy_s - other, rel=0.01)
    steps = [s for s in r.spans if s.name == "serving.step"]
    assert [(s.stats["n_prefill"], s.stats["n_decode"]) for s in steps] == \
        [(2, 8)] * 3
    first = [s.stats["rid"] for s in r.spans if s.name == "serving.sync"
             and s.stats["kind"] == "first_token"]
    assert first == [9]
    # the idle gaps: after each decode the host reads its tokens, and a
    # tick's first chunk waits for its inputs and dispatch
    assert [n for n, _ in r.gaps[:9]] == [
        "serving.sync", "serving.sync", "serving.sync", "serving.prefill",
        "serving.sync", "serving.prefill", "serving.prefill",
        "serving.prefill", "serving.prefill"]
    idle = r.window_s - r.busy_s
    assert sum(r.idle_by_span.values()) == pytest.approx(idle)
    named = sum(v for k, v in r.idle_by_span.items()
                if k.startswith("serving.") or k == "bench.tick")
    assert named >= 0.95 * idle
    # the harness's own reduction of the same recording is unchanged
    dev, host, n_dev = tr.load(str(tmp_path))
    old = tr.reduce_events(dev, host, tr.window_of(host))
    assert n_dev == 1 and old.busy_s == pytest.approx(r.busy_s)
    assert all(name == "bench.tick" for name, _ in old.gaps)
