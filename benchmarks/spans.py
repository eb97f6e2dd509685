"""The program's own host spans that the reduction reads from the
profiler's trace, so that idle gaps on the device can be attributed to
what the host was doing: the interpreter's collections (``host.gc``), a
request's finish (``engine.release`` and the ``prefix.evict`` inside it),
the emit phase's row loop, the read-back's first half, the serving
iteration's phases (the StepClock's ``engine.step`` and ``engine.<phase>``
annotations) and the Fleet step's dispatch.  The benchmark wraps nothing
of the program's."""
from __future__ import annotations

# the spans whose names label a gap; innermost first: a child lies inside
# its phase and a phase inside its step, and a gap goes to the first span
# that covers it (``prefix.evict`` in ``engine.release`` in
# ``engine.emit_rows`` in ``engine.emit``; ``engine.ready`` in
# ``engine.wait``; a collection wherever it falls)
GAP_SPANS = ("host.gc", "prefix.evict", "engine.release",
             "engine.emit_rows", "engine.ready",
             "engine.admit", "engine.pack", "engine.launch", "engine.wait",
             "engine.emit", "engine.step", "fleet.train_step")
OUTSIDE = "between_steps"
