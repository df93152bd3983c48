"""The drive loop on the engine at a tiny size with a clock that advances
a fixed step per tick: the latency, gap and occupancy arithmetic."""
import pytest

import bench_tiny
from bench import run as R
from bench import spec
from bench.drive import Drive
from bench.traffic import Req

DT = 0.1


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def now(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


def _engine(clock):
    cell = bench_tiny.cell("bf16", "chat")
    _arch, _params, engine = R.build(cell, 0)
    step = engine.step

    def timed_step():
        plan = step()
        clock.t += DT
        return plan
    engine.step = timed_step
    return cell, engine


def test_ttft_gaps_and_occupancy():
    clock = FakeClock()
    cell, engine = _engine(clock)
    reqs = [Req(0, 0.0, list(range(1, 21)), 3), Req(1, 0.05, [7] * 5, 2)]
    d = Drive(engine, reqs, clock, origin=0.0)
    d.run_until(10.0, done=lambda: len(d.completed()) == 2)
    # tick 1: rid 0 chunk 0..16; tick 2: rid 0 chunk 16..20 and rid 1
    # prefill, both emit; tick 3: both decode; tick 4: rid 0 decodes
    assert [len(t.decode) for t in d.ticks] == [0, 0, 2, 1]
    assert d.token_times[0] == pytest.approx([0.2, 0.3, 0.4])
    assert d.token_times[1] == pytest.approx([0.2, 0.3])
    assert d.ticks[2].decode == ((0, 21), (1, 6))
    assert d.ticks[1].prefill == ((0, 4, 16, True), (1, 5, 0, True))
    assert d.served(0) == d.completed()[0] and len(d.served(0)) == 3

    run = R.RunData(cell=cell, arch=None, peak={}, drive=d, t0=0.0, t1=0.4,
                    window_ticks=d.ticks, in_window=[0, 1])
    e2e = R._end_to_end(run, setup_s=1.5)
    # ttft_p<q>_ms: first tokens at 0.2 s for arrivals at 0 and 0.05 s
    assert e2e["ttft_p50_ms"]["value"] == pytest.approx(175.0)
    assert e2e["ttft_p90_ms"]["value"] == pytest.approx(
        1e3 * (0.15 + 0.9 * (0.2 - 0.15)))
    assert e2e["itl_mean_ms"]["value"] == pytest.approx(100.0)
    assert e2e["output_tok_s"]["value"] == pytest.approx(5 / 0.4)
    assert e2e["setup_s"]["value"] == 1.5
    occ = spec.metric_reader("decode_occupancy.chat").read(run)
    assert occ == pytest.approx(100 * (2 / 4 + 1 / 4) / 2)
    wait = spec.metric_reader("queue_wait_p90_ms").read(run)
    assert wait == pytest.approx(1e3 * 0.9 * 0.05)
    assert d.lateness() == pytest.approx([0.0, 0.05])
