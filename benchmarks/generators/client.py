"""The client's side of one request: submit, then stamp every streamed
token as it arrives.  One reader thread per request in flight, blocked on
the stream between tokens (no polling)."""
from __future__ import annotations

import queue
import threading
import time


class Record:
    __slots__ = ("index", "phase", "due", "sent", "prompt", "max_new",
                 "token_times", "tokens", "error", "finished",
                 "observed_until", "_thread")

    def __init__(self, index, phase, due, prompt, max_new):
        self.index, self.phase, self.due = index, phase, due
        self.prompt, self.max_new = prompt, int(max_new)
        self.sent = None
        self.token_times = []
        self.tokens = []
        self.error = None
        self.finished = False
        self.observed_until = None
        self._thread = None

    @property
    def prompt_len(self):
        return int(len(self.prompt))


def follow(system, rec: Record, abandon: threading.Event):
    """Submit ``rec`` and read its stream to the end (or until
    ``abandon``).  Runs on the caller's thread."""
    rec.sent = time.monotonic()
    try:
        req = system.submit(rec.prompt, rec.max_new)
    except Exception as e:                  # refused at admission
        rec.error = repr(e)
        rec.observed_until = time.monotonic()
        return
    while not abandon.is_set():
        try:
            for chunk in req.stream(timeout=0.25):
                now = time.monotonic()
                for tok in chunk:
                    rec.token_times.append(now)
                    rec.tokens.append(int(tok))
                if abandon.is_set():
                    break
            else:
                rec.finished = True
            break
        except queue.Empty:
            continue
        except Exception as e:              # the request failed in flight
            rec.error = repr(e)
            break
    rec.observed_until = time.monotonic()


def follow_in_thread(system, rec: Record, abandon: threading.Event):
    rec._thread = threading.Thread(target=follow, args=(system, rec, abandon),
                                   name=f"client-{rec.index}", daemon=True)
    rec._thread.start()


def join_all(records, timeout_s: float = 30.0):
    deadline = time.monotonic() + timeout_s
    for r in records:
        if r._thread is not None:
            r._thread.join(max(0.0, deadline - time.monotonic()))
            if r._thread.is_alive():
                raise RuntimeError(f"client thread {r.index} did not end")
