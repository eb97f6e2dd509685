"""Per-step LoRA adapter-slot side-channel.

The mixed-step executable (serving/programs.build_mixed_step) needs the
per-row adapter slot indices INSIDE the traced model forward without
threading a new argument through ``engine._model_step`` /
``functional_call``.  A thread-local context does it: the builder opens
an :func:`activate` context carrying the step's traced ``[b]`` int32
slot vector, and every ``LoRAServingLinear`` the forward hits gathers
its stacked A/B/scale pools by those indices.  The slots tensor is a
tracer of the SAME jit trace (the context only lives across one
``_model_step`` call on one thread), so no value ever crosses a trace
boundary.

Outside an active context (eager forwards, the offline engines,
training-style use of a converted model) the wrappers return the base
layer's output unchanged — the adapter plane is invisible unless the
mixed step turns it on.
"""
from __future__ import annotations

import threading

_TLS = threading.local()


def _raw(t):
    """Unwrap a core Tensor to its jax payload (the LoRA delta is plain
    jnp; the dispatcher hands the layer Tensors)."""
    return getattr(t, "_data", t)


class SlotContext:
    """One mixed step's adapter binding: ``slots`` is the traced [b]
    int32 per-row slot vector (slot 0 = identity/no-adapter)."""

    def __init__(self, slots):
        self.slots = slots


class activate:
    """Context manager installing a :class:`SlotContext` for the
    current thread; nests (the previous context is restored)."""

    def __init__(self, slots):
        self._slots = slots
        self._prev = None

    def __enter__(self) -> SlotContext:
        self._prev = getattr(_TLS, "active", None)
        _TLS.active = SlotContext(self._slots)
        return _TLS.active

    def __exit__(self, *exc):
        _TLS.active = self._prev
        return False


def current() -> SlotContext | None:
    return getattr(_TLS, "active", None)


def row_slots():
    """The active context's per-row slot vector, or None outside an
    activating context (wrappers then skip the LoRA delta entirely)."""
    c = current()
    return c.slots if c is not None else None
