"""What a run leaves for the metric readers: plain data, no program
objects.  A reader takes the fields it knows and returns ``None`` when
what it reads is not there."""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Evidence:
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    cell: Dict[str, Any]
    device_kind: str
    chips: int
    setup_s: float
    w0: float                       # window, monotonic clock
    w1: float
    records: List[Any] = dataclasses.field(default_factory=list)
    steps: List[dict] = dataclasses.field(default_factory=list)
    queue_waits: List[tuple] = dataclasses.field(default_factory=list)
    train_done_times: List[float] = dataclasses.field(default_factory=list)
    positions_per_step: int = 0
    compiles_in_window: Optional[int] = None
    # the allocator's peak of live buffers (weights, state, cache) and,
    # where the program's own memory analysis gives them, the temporaries
    # its compiled step holds while it runs, which that peak leaves out
    allocator_peak_bytes: Optional[int] = None
    program_temp_bytes: Optional[int] = None
    program: Optional[dict] = None      # flags and tuned choices, as run
    token_budget: Optional[int] = None
    max_batch: Optional[int] = None
    trace: Optional[dict] = None    # xplane.reduce(...) of the traced part

    @property
    def memory_peak_bytes(self) -> Optional[int]:
        """Peak bytes on the fullest chip: both parts, where known."""
        if self.allocator_peak_bytes is None:
            return None
        return self.allocator_peak_bytes + (self.program_temp_bytes or 0)


class CompileCounter:
    """Compilations between ``mark()`` and ``since_mark()``: the larger of
    the program's own CompileLog delta and JAX's own events (backend
    compiles and persistent-cache loads), so that a program that stops
    counting its own still cannot compile inside the window unseen."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self._jax = 0
        self._mark = (0, 0)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event in self.EVENTS:
            self._jax += 1

    def _now(self):
        from paddle_infer_tpu.observability.compilelog import \
            get_compile_log

        return get_compile_log().count(), self._jax

    def mark(self):
        self._mark = self._now()

    def since_mark(self) -> int:
        return max(b - a for a, b in zip(self._mark, self._now()))
