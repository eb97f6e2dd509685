"""The program's own host spans that the reduction reads from the
profiler's trace, so that idle gaps on the device can be attributed to
what the host was doing: the serving iteration's phases (the StepClock's
``engine.step`` and ``engine.<phase>`` annotations) and the Fleet step's
dispatch.  The benchmark wraps nothing of the program's."""
from __future__ import annotations

# the spans whose names label a gap; innermost first: a phase lies inside
# its step, and a gap goes to the first span that covers it
GAP_SPANS = ("engine.admit", "engine.pack", "engine.launch", "engine.wait",
             "engine.emit", "engine.step", "fleet.train_step")
OUTSIDE = "between_steps"
