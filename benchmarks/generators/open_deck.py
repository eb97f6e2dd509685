"""Open loop: requests are due on a schedule, whatever the system does.

The window's deck is dealt once, whole, to requests due inside the
window; the ramp before it deals a separate copy so the batch is at its
stationary occupancy when the window opens.  A request is timed from the
moment it was DUE, so a generator that runs late charges the wait to the
system's users, and how late it ran is reported.
"""
from __future__ import annotations

import threading
import time

from .. import decks
from .client import Record, follow_in_thread, join_all


def plan(traffic: dict, cell: dict, seed: int, seconds: float, vocab: int):
    """Every request of the run with its due time relative to the window's
    opening (ramp requests are negative).  Pure: no clock, no device."""
    rate = float(cell["rate_rps"])
    ramp_s = float(traffic["ramp_s"])
    # whole slots only, so every request is due before the window closes
    n_win = max(1, int(rate * seconds + 1e-9))
    n_ramp = int(rate * ramp_s + 1e-9)
    out = []
    win = decks.deal(decks.build_deck(traffic, n_win), seed,
                     decks.STREAM_ORDER)
    due = decks.arrivals(rate, n_win, seed, decks.STREAM_JITTER)
    for i, ((p, o), t) in enumerate(zip(win, due)):
        out.append(Record(i, "window", t,
                          decks.token_ids(seed, i, p, vocab), o))
    if n_ramp:
        ramp = decks.deal(decks.build_deck(traffic, n_ramp), seed,
                          decks.STREAM_RAMP_ORDER)
        due = decks.arrivals(rate, n_ramp, seed,
                             decks.STREAM_JITTER + 100)
        for i, ((p, o), t) in enumerate(zip(ramp, due)):
            out.append(Record(n_win + i, "ramp", t - ramp_s,
                              decks.token_ids(seed, n_win + i, p, vocab), o))
    out.sort(key=lambda r: r.due)
    return out


def run(system, traffic: dict, cell: dict, seed: int, seconds: float,
        vocab: int, on_window_open=None):
    """Drive the plan.  Returns (records, w0, w1): due times are rebased
    onto the monotonic clock, the window is [w0, w1)."""
    records = plan(traffic, cell, seed, seconds, vocab)
    abandon = threading.Event()
    t_start = time.monotonic() + 0.05
    w0 = t_start + float(traffic["ramp_s"])
    w1 = w0 + float(seconds)
    for r in records:
        r.due = w0 + r.due

    def dispatch():
        for r in records:
            wait = r.due - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            follow_in_thread(system, r, abandon)

    d = threading.Thread(target=dispatch, name="dispatcher", daemon=True)
    d.start()
    time.sleep(max(0.0, w0 - time.monotonic()))
    if on_window_open is not None:
        on_window_open(w0, w1)
    time.sleep(max(0.0, w1 - time.monotonic()))
    # follow every request due in the window to its first token
    deadline = w1 + float(traffic["drain_s"])
    d.join(max(0.0, deadline - time.monotonic()))
    window = [r for r in records if r.phase == "window"]
    while time.monotonic() < deadline and any(
            r.sent is None or (not r.token_times and r.error is None
                               and not r.finished) for r in window):
        time.sleep(0.01)
    abandon.set()
    join_all(records)
    return records, w0, w1
