"""The traffic generator: deterministic per seed, lengths kept inside the
mix's clips, and the same work for every seed in another order."""
import bench_tiny
from bench import traffic

CHAT = bench_tiny.MIXES["chat"]
BIG = 2**31 + 12345
SEGS = [traffic.segment(s, 2.0) for s in (5.0, 12.5, 7.5)]


def test_same_seed_same_schedule():
    a = traffic.schedule(CHAT, SEGS, BIG, 512, rate=2.0)
    b = traffic.schedule(CHAT, SEGS, BIG, 512, rate=2.0)
    assert a == b
    c = traffic.schedule(CHAT, SEGS, BIG + 1, 512, rate=2.0)
    assert a != c


def test_clips_and_same_window_work_across_seeds():
    segs = [traffic.segment(s, 4.0) for s in (10.0, 50.0, 15.0)]
    assert [n for n, _ in segs] == [40, 200, 60]
    runs = [traffic.schedule(CHAT, segs, s, 512, rate=4.0)
            for s in (1, 2, BIG)]
    for reqs in runs:
        p = [len(r.prompt) for r in reqs]
        o = [r.max_new for r in reqs]
        assert min(p) >= 4 and max(p) <= 40
        assert min(o) >= 2 and max(o) <= 16
        assert all(0 <= t < 512 for r in reqs for t in r.prompt)
        assert reqs[0].arrival == 0.0
        assert all(b.arrival >= a.arrival for a, b in zip(reqs, reqs[1:]))
        window = reqs[40:240]
        assert window[0].arrival == 10.0
        assert all(10.0 <= r.arrival < 60.0 for r in window)
    # the window offers the same lengths for every seed, in another order
    sizes = [sorted((len(r.prompt), r.max_new) for r in reqs[40:240])
             for reqs in runs]
    assert sorted(x[0] for x in sizes[0]) == sorted(x[0] for x in sizes[2])
    assert sorted(x[1] for x in sizes[1]) == sorted(x[1] for x in sizes[2])
    assert [r.max_new for r in runs[0][40:240]] != \
        [r.max_new for r in runs[1][40:240]]


def test_lognormal_median_and_offline():
    q = traffic.lognormal_quantiles(101, 256, 1.0, 16, 2048)
    assert q[50] == 256 and q.min() >= 16 and q.max() <= 2048
    reqs = traffic.schedule(bench_tiny.MIXES["batch"], [(10, 0.0)], 3, 512)
    assert all(r.arrival == 0.0 for r in reqs)
