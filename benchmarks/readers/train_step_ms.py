"""Median time between the ends of successive training steps (host clock
around ``block_until_ready``; the device runs them back to back)."""
from .. import accounting


def read(ev):
    t = ev.train_done_times
    if len(t) < 2:
        return None
    return accounting.quantile([b - a for a, b in zip(t, t[1:])], 0.5) * 1e3
