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
"""
from __future__ import annotations

import time

import jax

# the serving iteration's phases, in order (docs/OBSERVABILITY.md)
ENGINE_PHASES = ("admit", "pack", "launch", "wait", "emit")


class StepClock:
    """Boundaries of one step: ``t_begin``, then ``phase(name)`` at each
    boundary (returns the monotonic read it took), ``close()`` at the
    end.  ``durations(end)`` gives the seconds spent in each phase that
    was reached; a phase never entered is absent."""

    __slots__ = ("name", "step_num", "t_begin", "starts", "_step_span",
                 "_phase_span")

    def __init__(self, name: str, step_num: int, first_phase: str):
        self.name = name
        self.step_num = int(step_num)
        self.starts = {}
        self._phase_span = None
        self._step_span = jax.profiler.StepTraceAnnotation(
            name + ".step", step_num=self.step_num)
        self.t_begin = time.monotonic()
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
