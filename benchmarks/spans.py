"""Host spans the benchmark places around its own calls into the program,
written into the profiler's trace so that idle gaps on the device can be
attributed to what the host was doing.  No-ops when the run is not traced.
"""
from __future__ import annotations

import contextlib
import functools

# span name -> the gap label it gives (innermost span wins)
GAP_LABELS = {"bench.dispatch": "in_dispatch",
              "bench.run_once": "scheduler_host",
              "bench.train_step": "in_dispatch"}
OUTSIDE = "between_steps"


def span(name: str, traced: bool):
    if not traced:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


def wrap(fn, name: str):
    """``fn`` inside a span called ``name`` (traced runs only)."""
    import jax

    @functools.wraps(fn)
    def inner(*a, **kw):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **kw)

    return inner
