"""Serving MoE layers and the in-place model conversion.

``ServingMoELayer`` wraps one MoE FFN (float ``MoELayer``, or the
quantized ``WeightOnlyMoELayer`` / ``Int8MoELayer`` deploy layers) and
routes its forward through the static-capacity serving ops
(``serving/moe/ops.py``).  The wrapped layer stays a proper sublayer,
so its parameters/buffers — ep dist_attrs included — flow through
``named_parameters`` / ``named_buffers`` and the engine's param
snapshot unchanged; only the forward dispatch differs.

``prepare_moe_serving`` converts a model in place (the analog of
``quantization.slim._swap``), ``moe_serving_info`` detects and
describes a model's MoE plane for validation/observability, and
``serving_capacity`` fixes the per-expert buffer size from deployment
config — the mixed step's ``token_budget`` token slots through the same
``_capacity`` formula the training fused path applies to its live
token count, so the converted routing is bitwise what the unconverted
model computes inside the mixed step.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ...core.dispatch import dispatch as D
from ...models.transformer_block import ParallelTransformerLayer
from ...nn.layer import Layer
from ...parallel.moe import MoELayer, _capacity
from ...quantization.moe import Int8MoELayer, WeightOnlyMoELayer
from . import stats as moe_stats

# make sure the serving ops are registered on import of this module
from . import ops as _ops  # noqa: F401

_MOE_KINDS = (MoELayer, WeightOnlyMoELayer, Int8MoELayer)


def _algo_of(layer) -> str:
    """Expert-arithmetic tag for the validation matrix / metrics:
    fp | weight_only_int8 | weight_only_int4 | int8_act."""
    if isinstance(layer, Int8MoELayer):
        return "int8_act"
    if isinstance(layer, WeightOnlyMoELayer):
        return layer.algo
    return "fp"


def _expert_bytes(layer) -> int:
    """HBM bytes of the stacked expert payloads (gate excluded — it is
    replicated, tiny, and not what ep shards)."""
    if isinstance(layer, (WeightOnlyMoELayer, Int8MoELayer)):
        names = ("qw1", "s1", "qw2", "s2", "b1", "b2")
        return sum(int(getattr(layer, n)._data.nbytes) for n in names)
    return sum(int(p._data.nbytes)
               for p in (layer.w1, layer.b1, layer.w2, layer.b2))


class ServingMoELayer(Layer):
    """One MoE FFN bound to a fixed serving capacity.

    ``inner`` is the wrapped layer (float or quantized); ``capacity``
    is the per-expert buffer width C — an int fixed at conversion, part
    of the mixed-step executable's config key.  Forward fetches the
    step's valid-slot mask from the stats side-channel (all-ones when
    none is active) and notes the routed/dropped/aux stats back."""

    def __init__(self, inner, capacity: int):
        super().__init__()
        if not isinstance(inner, _MOE_KINDS):
            raise TypeError(
                f"ServingMoELayer wraps a MoE FFN layer, got "
                f"{type(inner).__name__}")
        self.inner = inner
        self.capacity = int(capacity)
        if self.capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.num_experts = inner.num_experts
        self.gate_kind = inner.gate_kind
        self.top_k = inner.top_k
        self.capacity_factor = inner.capacity_factor
        self.l_aux = None

    def forward(self, x):
        v = moe_stats.valid_mask()
        if v is None:
            b, s = int(x.shape[0]), int(x.shape[1])
            v = jnp.ones((b * s,), jnp.bool_)
        inner = self.inner
        if isinstance(inner, Int8MoELayer):
            out, routed, dropped, aux = D(
                "serving_moe_int8", x, inner.gate_weight, inner.qw1,
                inner.s1, inner.b1, inner.qw2, inner.s2, inner.b2, v,
                inner.act_scale_in, inner.act_scale_hidden,
                gate=inner.gate_kind, top_k=inner.top_k,
                capacity=self.capacity, activation=inner.activation)
        elif isinstance(inner, WeightOnlyMoELayer):
            out, routed, dropped, aux = D(
                "serving_moe_weight_only", x, inner.gate_weight,
                inner.qw1, inner.s1, inner.b1, inner.qw2, inner.s2,
                inner.b2, v, gate=inner.gate_kind, top_k=inner.top_k,
                capacity=self.capacity, activation=inner.activation,
                algo=inner.algo)
        else:
            out, routed, dropped, aux = D(
                "serving_moe", x, inner.gate_weight, inner.w1, inner.b1,
                inner.w2, inner.b2, v, gate=inner.gate_kind,
                top_k=inner.top_k, capacity=self.capacity,
                activation=inner.activation)
        moe_stats.note(routed, dropped, aux)
        self.l_aux = aux
        return out

    def extra_repr(self):
        return (f"experts={self.num_experts}, gate={self.gate_kind}, "
                f"top_k={self.top_k}, capacity={self.capacity}, "
                f"algo={_algo_of(self.inner)}")


class MoETransformerLayer(ParallelTransformerLayer):
    """A serving transformer block whose MLP is the static-capacity
    ServingMoELayer from construction (``ParallelTransformerLayer``
    already swaps in ``MoELayer`` when ``num_experts > 1``; this wraps
    it for the mixed step).  Models loaded from checkpoints use
    :func:`prepare_moe_serving` instead — EngineCore calls it
    automatically."""

    def __init__(self, *args, serving_capacity: int, **kw):
        super().__init__(*args, **kw)
        if not isinstance(self.mlp, MoELayer):
            raise ValueError(
                "MoETransformerLayer needs num_experts > 1 (the dense "
                "MLP has no routing plane to bound)")
        self.mlp = ServingMoELayer(self.mlp, serving_capacity)


def _iter_moe_layers(model):
    """Yield the model's outermost MoE FFN layers (ServingMoELayer or
    unconverted) WITHOUT descending into converted wrappers — the
    wrapped inner layer is the same logical FFN, not a second one."""
    def visit(layer):
        for sub in layer._sub_layers.values():
            if sub is None:
                continue
            if isinstance(sub, (ServingMoELayer,) + _MOE_KINDS):
                yield sub
            else:
                yield from visit(sub)

    yield from visit(model)


def moe_serving_info(model) -> Optional[dict]:
    """Describe a model's MoE plane for validation and observability:
    ``{num_experts, top_k, gate, capacity_factor, algo, layers,
    expert_hbm_bytes}`` — or None for dense models.  Mixed expert
    counts across layers are rejected (the serving plane keys ONE
    (E, C) per deployment config)."""
    layers = list(_iter_moe_layers(model))
    if not layers:
        return None
    bare = [lay.inner if isinstance(lay, ServingMoELayer) else lay
            for lay in layers]
    counts = {lay.num_experts for lay in bare}
    if len(counts) != 1:
        from ..sharded import ShardedConfigError

        raise ShardedConfigError(
            f"MoE layers disagree on num_experts ({sorted(counts)}); "
            "the serving plane keys one (E, C) routing buffer shape "
            "per deployment config")
    algos = {_algo_of(lay) for lay in bare}
    if len(algos) != 1:
        from ..sharded import ShardedConfigError

        raise ShardedConfigError(
            f"MoE layers disagree on expert arithmetic ({sorted(algos)}); "
            "quantize all expert stacks with one algo")
    first = bare[0]
    return {
        "num_experts": int(first.num_experts),
        "top_k": int(first.top_k),
        "gate": first.gate_kind,
        "capacity_factor": float(first.capacity_factor),
        "algo": algos.pop(),
        "layers": len(bare),
        "expert_hbm_bytes": int(sum(_expert_bytes(b) for b in bare)),
    }


def serving_capacity(token_budget: int, info: dict) -> int:
    """The fixed per-expert buffer width for a deployment config: the
    training ``_capacity`` formula applied to the mixed step's static
    token count (the ``token_budget`` slots of its flat token axis, pad
    slots among them: they compete for capacity), so default-capacity
    serving routes bitwise-identically to the unconverted fused path."""
    return _capacity(int(token_budget), info["num_experts"],
                     info["capacity_factor"], info["top_k"])


def prepare_moe_serving(model, capacity: int) -> int:
    """Swap every MoE FFN in ``model`` (in place) for a
    :class:`ServingMoELayer` bound to ``capacity``.  Idempotent:
    already-converted layers are rebound to the new capacity instead of
    double-wrapped.  Returns the number of layers now serving."""
    n = 0

    def visit(layer):
        nonlocal n
        for name, sub in list(layer._sub_layers.items()):
            if sub is None:
                continue
            if isinstance(sub, ServingMoELayer):
                sub.capacity = int(capacity)
                n += 1
            elif isinstance(sub, _MOE_KINDS):
                setattr(layer, name, ServingMoELayer(sub, capacity))
                n += 1
            else:
                visit(sub)

    visit(model)
    return n
