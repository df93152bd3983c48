"""The trace reduction: interval union, idle share and idle-gap labels on
synthetic events, and on a small trace recorded on a TPU v5e chip."""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_counts_overlaps_once():
    assert tr.merge([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == [(0, 3), (5, 6)]
    assert tr.merge([(3, 4), (0, 1), (1, 2)]) == [(0, 2), (3, 4)]
    assert tr.merge([(2, 2), (3, 1)]) == []


def test_reduce_busy_idle_and_gaps():
    dev = [tr.Event("fusion.1", 0.5, 1.5), tr.Event("_paged_pallas", 1.0, 2.0),
           tr.Event("fusion.2", 3.0, 3.5), tr.Event("outside", 9.0, 11.0)]
    host = [tr.Event("bench.window", 0.0, 10.0),
            tr.Event("bench.tick", 0.2, 4.0),
            tr.Event("engine.decode", 2.1, 2.9)]
    r = tr.reduce_events(dev, host, tr.window_of(host))
    assert r.window_s == 10.0
    assert r.busy_s == pytest.approx(1.5 + 0.5 + 1.0)
    assert r.idle_share == pytest.approx(0.7)
    assert r.kernel_time("_paged_pallas") == (1.0, 1)
    assert r.op_time["outside"] == pytest.approx(1.0)   # clipped to 10 s
    gaps = dict((round(s, 6), name) for name, s in r.gaps)
    # 3.5..9 lies outside every tick; 2..3 inside the decode dispatch span
    assert gaps[5.5] == "bench.window"
    assert gaps[1.0] == "engine.decode"
    assert gaps[0.5] == "bench.tick"
    assert tr.op_name("%_paged_pallas.9 = f32[32] custom-call(x)") == \
        "_paged_pallas"
    assert tr.op_name("%cond.7.clone.6") == "cond"


def test_recorded_chip_trace():
    """A 0.25 s traced window of ``internlm2-1.8b.batch`` on one TPU v5e
    chip (seed 9); its run reported busy_s 0.2334093769999999 and
    window_s 0.243363069."""
    assert sorted(DATA.glob("*.xplane.pb")), "the recorded trace is missing"
    dev, host, n_dev = tr.load(str(DATA))
    assert n_dev == 1
    r = tr.reduce_events(dev, host, tr.window_of(host))
    assert r.window_s == pytest.approx(0.243363069)
    assert r.busy_s == pytest.approx(0.2334093769999999)
    # the KV pool updates of the window's ticks, counted on the chip
    assert r.kernel_time("dynamic-update-slice")[1] == 192
    top = max(r.op_time, key=r.op_time.get)
    assert top == "fusion" and not any(
        k.startswith(("while", "cond")) for k in r.op_time)
    assert all(name == "bench.tick" for name, _ in r.gaps)
