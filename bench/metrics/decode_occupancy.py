"""Scheduler: mean share of the slots that decoded a token, over the
window's ticks that ran a decode step (from ``StepPlan.decode``)."""


def read(run):
    ticks = [t for t in run.window_ticks if t.decode]
    if not ticks:
        return None
    return 100.0 * sum(len(t.decode) / t.slots for t in ticks) / len(ticks)
