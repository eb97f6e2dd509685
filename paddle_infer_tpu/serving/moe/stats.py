"""Per-step MoE routing stats side-channel.

The mixed-step executable (serving/programs.build_mixed_step) needs the
per-expert routed-token counts, the dropped-assignment count and the
gate aux loss OUT of the traced model forward without threading new
arguments through ``engine._model_step`` / ``functional_call``.  A
thread-local collector does it: the builder opens a :func:`collect`
context carrying the step's traced valid-slot mask, every
``ServingMoELayer`` the forward hits notes its stats tensors into the
active collector, and the builder drains the per-layer notes into three
extra program outputs.  Everything noted is a tracer of the SAME jit
trace (the context only lives across one ``_model_step`` call on one
thread), so no value ever crosses a trace boundary.

Outside a collecting context (eager forwards, training-style use of a
converted model) the layers fall back to an all-ones valid mask and the
notes go nowhere — the side-channel is invisible unless the mixed step
asks for it.
"""
from __future__ import annotations

import threading

_TLS = threading.local()


def _raw(t):
    """Unwrap a core Tensor to its jax payload (stats math is plain
    jnp; the dispatcher hands the layer Tensors)."""
    return getattr(t, "_data", t)


class MoEStatsCollector:
    """One mixed step's MoE note sink: ``valid`` is the traced [N] bool
    mask of real (non-pad) token slots; each MoE layer appends one
    (routed [E] i32, dropped i32, aux f32) triple."""

    def __init__(self, valid, max_valid=None):
        self.valid = valid
        # static bound on how many slots of ``valid`` can be set (the
        # mixed step's token budget, which is also the length of its
        # flat token axis); None = every slot may be
        self.max_valid = max_valid
        self.routed = []
        self.dropped = []
        self.aux = []
        self.dropless = []
        self.identity = []

    def note(self, routed, dropped, aux):
        self.routed.append(_raw(routed))
        self.dropped.append(_raw(dropped))
        self.aux.append(_raw(aux))

    def note_dropless(self, total, held, held_max, touched):
        """One dropless expert layer's counts over the valid slots:
        assignments made, assignments to experts held here, the largest
        count any held expert got, held experts with at least one."""
        self.dropless.append((total, held, held_max, touched))

    def note_identity(self, identity, real_max):
        """One dropless layer with identity experts: valid assignments to
        them, and the most computing experts one valid token chose."""
        self.identity.append((identity, real_max))

    def totals(self):
        """Sum the per-layer notes into the program's outputs.  Capacity
        layers give three: routed [E] i32 (kept expert assignments over
        valid slots, summed across layers), dropped i32 (capacity-overflow
        assignments over valid slots, summed across layers), aux f32
        (load-balancing loss, averaged across layers — a gauge, not a
        counter).  Dropless layers give four i32: assignments and held
        assignments summed over layers, the largest held-expert count of
        any layer, touched held experts summed over layers; layers with
        identity experts two more: the assignments to those summed over
        layers, and the most computing experts a token chose in any."""
        import jax.numpy as jnp

        if self.dropless:
            total, held, held_max, touched = zip(*self.dropless)
            i32 = lambda x: jnp.asarray(x).astype(jnp.int32)
            out = (i32(sum(total)), i32(sum(held)),
                   i32(jnp.max(jnp.stack(held_max))), i32(sum(touched)))
            if self.identity:
                identity, real_max = zip(*self.identity)
                out += (i32(sum(identity)),
                        i32(jnp.max(jnp.stack(real_max))))
            return out
        if not self.routed:
            raise RuntimeError(
                "moe_stats collection ran but no serving MoE layer "
                "noted stats — the model was not converted with "
                "prepare_moe_serving (or has no MoE FFN) and has no "
                "dropless expert layer")
        routed = self.routed[0]
        for r in self.routed[1:]:
            routed = routed + r
        dropped = self.dropped[0]
        for d in self.dropped[1:]:
            dropped = dropped + d
        aux = self.aux[0]
        for a in self.aux[1:]:
            aux = aux + a
        aux = aux / float(len(self.aux))
        return (routed.astype(jnp.int32), dropped.astype(jnp.int32),
                aux.astype(jnp.float32))


class collect:
    """Context manager installing a :class:`MoEStatsCollector` for the
    current thread; nests (the previous collector is restored)."""

    def __init__(self, valid, max_valid=None):
        self._valid = valid
        self._max_valid = max_valid
        self._prev = None

    def __enter__(self) -> MoEStatsCollector:
        self._prev = getattr(_TLS, "active", None)
        _TLS.active = MoEStatsCollector(self._valid, self._max_valid)
        return _TLS.active

    def __exit__(self, *exc):
        _TLS.active = self._prev
        return False


def current() -> MoEStatsCollector | None:
    return getattr(_TLS, "active", None)


def valid_mask():
    """The active collector's valid-slot mask, or None outside a
    collecting context (callers substitute all-ones)."""
    c = current()
    return c.valid if c is not None else None


def note(routed, dropped, aux):
    """Append one layer's stats to the active collector; no-op outside
    a collecting context."""
    c = current()
    if c is not None:
        c.note(routed, dropped, aux)
