"""Scheduler: 90th percentile of the wait from a request's scheduled
arrival to the tick that admitted it to a slot, over the requests that
arrived in the window (harness host clock)."""
import numpy as np


def read(run):
    d = run.drive
    waits = [d.admitted_at[r] - d.due(r) for r in run.in_window
             if r in d.admitted_at]
    if not waits:
        return None
    return float(np.percentile(waits, 90)) * 1e3
