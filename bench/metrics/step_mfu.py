"""Model step: model FLOPs of the tokens the window's ticks processed, over
the wall time of those ticks at the chip's bf16 peak (host clock).  The
FLOPs count each contraction once (``bench.flops``), so under a corrected
policy the correction passes are overhead against this yardstick."""
from bench import flops


def read(run):
    ticks = run.window_ticks
    busy = sum(t.end - t.start for t in ticks)
    if not ticks or busy <= 0 or "bf16_flops" not in run.peak:
        return None
    work = sum(flops.step_flops(run.arch, t.prefill, t.decode) for t in ticks)
    return 100.0 * work / (busy * run.peak["bf16_flops"])
