"""StepClock — one clock for a step, read where the work happens.

A scheduler iteration (``EngineCore._run_once_locked``) passes through
named phases.  The clock takes each boundary ONCE with ``time.monotonic()`` and uses the read
twice: the caller writes the durations into the step's StepLog record,
and the same call closes one ``jax.profiler.TraceAnnotation`` and opens
the next, so the phases land as host spans in the profiler's
``.xplane.pb`` beside the device's operations.  The spans are no-ops
without a profiler session; there is no switch.

``<name>.step`` is a ``StepTraceAnnotation`` carrying ``step_num``; the
phases are ``<name>.<phase>`` spans inside it.  A StepLog record's
``step`` is that ``step_num``, and its ``t_begin`` (monotonic) is the
start of the span: the join between ``/steps`` and a trace.

A part of a phase is timed by ``clock.child(name)``: a ``Span`` named
``<name>.<child>`` inside the running phase, whose seconds and count the
clock keeps per child name.  ``Span`` is also what times work that has no
clock of its own on the same two reads (the prefix cache's ``prefix.*``
spans), and ``GcWatch`` the interpreter's collections (``host.gc``).
"""
from __future__ import annotations

import gc
import time
from collections import deque

import jax

# the serving iteration's phases, in order (docs/OBSERVABILITY.md)
ENGINE_PHASES = ("admit", "pack", "launch", "wait", "emit")


class Span:
    """Two ``time.monotonic()`` reads around a block, each used twice: as
    ``seconds`` for whoever keeps the number, and as the ends of one
    ``TraceAnnotation`` called ``name`` (none where ``name`` is None: the
    block is timed all the same).  ``seconds`` is 0.0 until the block has
    ended; it is then also added, with one more to the count, to
    ``tally``, a ``[seconds, count]`` list, where one is given."""

    __slots__ = ("name", "seconds", "_t0", "_span", "_tally")

    def __init__(self, name=None, tally=None):
        self.name = name
        self.seconds = 0.0
        self._tally = tally

    def __enter__(self):
        self._span = (jax.profiler.TraceAnnotation(self.name)
                      if self.name is not None else None)
        self._t0 = time.monotonic()
        if self._span is not None:
            self._span.__enter__()
        return self

    def __exit__(self, *exc):
        self.seconds = time.monotonic() - self._t0
        if self._span is not None:
            self._span.__exit__(None, None, None)
        if self._tally is not None:
            self._tally[0] += self.seconds
            self._tally[1] += 1
        return False


class StepClock:
    """Boundaries of one step: ``t_begin``, then ``phase(name)`` at each
    boundary (returns the monotonic read it took), ``close()`` at the
    end.  ``durations(end)`` gives the seconds spent in each phase that
    was reached; a phase never entered is absent.  ``child(name)`` times
    a part of the running phase; ``child_seconds(name)`` and
    ``child_count(name)`` say what its parts took so far.  ``cpu_begin``
    is ``time.thread_time()`` of the thread that made the clock, read
    beside ``t_begin``."""

    __slots__ = ("name", "step_num", "t_begin", "cpu_begin", "starts",
                 "_step_span", "_phase_span", "_children")

    def __init__(self, name: str, step_num: int, first_phase: str):
        self.name = name
        self.step_num = int(step_num)
        self.starts = {}
        self._children = {}          # child name -> [seconds, count]
        self._phase_span = None
        self._step_span = jax.profiler.StepTraceAnnotation(
            name + ".step", step_num=self.step_num)
        self.t_begin = time.monotonic()
        # the calling thread's own CPU seconds so far (Linux:
        # CLOCK_THREAD_CPUTIME_ID), for what it used of the step's wall
        self.cpu_begin = time.thread_time()
        self._step_span.__enter__()
        self._open(first_phase, self.t_begin)

    def _open(self, phase: str, now: float):
        self.starts[phase] = now
        self._phase_span = jax.profiler.TraceAnnotation(
            self.name + "." + phase)
        self._phase_span.__enter__()

    def _close_phase(self):
        span, self._phase_span = self._phase_span, None
        if span is not None:
            span.__exit__(None, None, None)

    def phase(self, phase: str) -> float:
        """End the running phase and start ``phase``, on one read."""
        now = time.monotonic()
        self._close_phase()
        self._open(phase, now)
        return now

    def child(self, child: str) -> Span:
        """A ``Span`` over a part of the running phase (``with
        clock.child("release"):``), named ``<name>.<child>``; children may
        nest.  Its seconds and one more are added to what the clock keeps
        for ``child``.  A closed clock times without a span."""
        live = self._step_span is not None
        return Span(self.name + "." + child if live else None,
                    self._children.setdefault(child, [0.0, 0]))

    def child_seconds(self, child: str) -> float:
        return self._children.get(child, (0.0, 0))[0]

    def child_count(self, child: str) -> int:
        return self._children.get(child, (0.0, 0))[1]

    def durations(self, end: float) -> dict:
        """Seconds in each phase reached so far, the running one up to
        ``end``: together they tile ``t_begin`` .. ``end``."""
        names = list(self.starts)            # insertion order is time order
        out = {a: self.starts[b] - self.starts[a]
               for a, b in zip(names, names[1:])}
        out[names[-1]] = end - self.starts[names[-1]]
        return out

    def close(self):
        """End the running phase and the step span (idempotent)."""
        self._close_phase()
        span, self._step_span = self._step_span, None
        if span is not None:
            span.__exit__(None, None, None)


class GcWatch:
    """The interpreter's collections on the clock the steps are on.
    ``install()`` puts one callback on ``gc.callbacks``, ``remove()``
    takes it off.  A collection of generation 1 or 2, on whichever thread
    it runs (they cannot overlap: the collector does not re-enter), is a
    ``host.gc`` span and an interval ``began(t, generation)`` ..
    ``ended(t)`` on two reads of the clock; generation 0 runs every few
    hundred allocations and leaves the callback at its first comparison
    (``gc.get_stats()`` counts it).  ``book(t0, t1)`` hands out the
    seconds of those intervals that lie inside ``t0 .. t1`` and keeps
    what lies after ``t1`` for the next call, so callers whose intervals
    tile book every second once, to the interval in which it passed,
    whenever the callback ran."""

    __slots__ = ("_open", "_closed", "_carry", "_span", "_installed")

    def __init__(self):
        self._open = None           # (start, generation) of the running one
        # (start, stop, generation), unbooked; bounded, so that an engine
        # nobody asks anything of for days forgets its oldest
        self._closed = deque(maxlen=4096)
        self._carry = []            # those that reach past the last book
        self._span = None
        self._installed = False

    def install(self):
        if not self._installed:
            gc.callbacks.append(self._on_gc)
            self._installed = True

    def remove(self):
        if self._installed:
            self._installed = False
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info):
        if info["generation"] == 0:
            return
        if phase == "start":
            self._span = jax.profiler.TraceAnnotation("host.gc")
            self.began(time.monotonic(), info["generation"])
            self._span.__enter__()
        else:
            self.ended(time.monotonic())
            span, self._span = self._span, None
            if span is not None:
                span.__exit__(None, None, None)

    def began(self, t: float, generation: int):
        self._open = (t, generation)

    def ended(self, t: float):
        running, self._open = self._open, None
        if running is not None:     # began before the watch was installed
            self._closed.append((running[0], t, running[1]))

    def book(self, t0: float, t1: float):
        """``(seconds, gen2)``: the seconds of collections that passed
        inside ``t0 .. t1`` (one still running is counted up to ``t1``)
        and how many of generation 2 began there."""
        # the running one first: should it end on another thread before
        # the closed ones are drained it is among them, and counted once
        running = self._open
        spans, self._carry = self._carry, []
        while self._closed:
            spans.append(self._closed.popleft())
        if running is not None and not any(s[0] == running[0]
                                           for s in spans):
            spans.append((running[0], None, running[1]))
        seconds, gen2 = 0.0, 0
        for start, stop, generation in spans:
            if stop is not None and stop > t1:
                self._carry.append((start, stop, generation))
            if start >= t1:
                continue
            seconds += max(
                0.0, (t1 if stop is None else min(stop, t1)) - max(start, t0))
            gen2 += generation == 2 and start >= t0
        return seconds, gen2
