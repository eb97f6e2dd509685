"""Serving LoRA wrapper layers and the in-place model conversion.

``LoRAServingLinear`` wraps one target projection (float
``ColumnParallelLinear`` / ``RowParallelLinear``, or the quantized
``WeightOnlyLinear`` deploy layer) and adds the batched ragged LoRA
delta ``y += scale[slot] * ((x @ A[slot]) @ B[slot])`` on top of the
wrapped forward.  The stacked pools are REGISTERED BUFFERS of fixed
shape ``[slots, d_in, r]`` / ``[slots, r, d_out]`` / ``[slots]``, so
they ride the engine's param snapshot into the jit'd step as plain
arguments: the AdapterCache swaps slot contents by rebinding the buffer
payload (``.at[slot].set``) and the executable never recompiles — slot
selection is per-row gather indices from the thread-local side-channel
(:mod:`.slots`), pure data under the one-executable invariant.

Slot 0 is the identity adapter: its A/B/scale rows stay all-zero
forever, so rows without an adapter ride the same gather at zero extra
control flow.  The wrapped layer stays a proper sublayer — its
parameters/buffers (mp dist_attrs included) flow through
``named_parameters`` / ``named_buffers`` unchanged; only the forward
gains the delta.

``prepare_lora_serving`` converts a model in place (the analog of
``serving/moe/layer.prepare_moe_serving``), ``lora_serving_info``
detects and describes a model's adapter plane for validation and
observability, and ``adapter_layer_spec`` extracts the
``{path: (d_in, d_out)}`` shape contract an ``AdapterStore`` validates
checkpoints against — it works on converted and unconverted models, so
the store can be built before the engine converts.
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ...core.tensor import Tensor
from ...nn.layer import Layer
from ...parallel.mp_layers import ColumnParallelLinear, RowParallelLinear
from ...quantization.weight_only import WeightOnlyLinear
from . import slots as lora_slots

# projection attribute names the conversion targets by default — the
# four linears of ParallelTransformerLayer (attention qkv/out, MLP
# fc1/fc2); weight-only conversion swaps them in place so the names
# survive quantization
DEFAULT_TARGETS = ("qkv_proj", "out_proj", "fc1", "fc2")


def _features_of(layer) -> tuple:
    """(d_in, d_out) of a linear-like target layer."""
    d_in = getattr(layer, "in_features", None)
    d_out = getattr(layer, "out_features", None)
    if d_in is None or d_out is None:
        w = getattr(layer, "weight", None)
        if w is None:
            raise TypeError(
                f"cannot infer (in, out) features of "
                f"{type(layer).__name__}")
        d_in, d_out = int(w.shape[0]), int(w.shape[1])
    return int(d_in), int(d_out)


def _target_kind(layer) -> Optional[str]:
    """TP orientation of a target layer for pool dist_attr stamping:
    ``column`` (output dim sharded on "mp"), ``row`` (reduction dim
    sharded), or None (replicated / unknown)."""
    if isinstance(layer, ColumnParallelLinear):
        return "column"
    if isinstance(layer, RowParallelLinear):
        return "row"
    if isinstance(layer, WeightOnlyLinear):
        # the quantized payload carries the source weight's dist_attr
        attr = getattr(layer.qweight, "dist_attr", None)
        if attr == (None, "mp"):
            return "column"
        if attr == ("mp", None):
            return "row"
    return None


def _is_linear_like(layer) -> bool:
    if not isinstance(layer, Layer) or isinstance(layer, LoRAServingLinear):
        return False
    try:
        _features_of(layer)
    except TypeError:
        return False
    return True


class LoRAServingLinear(Layer):
    """One target projection bound to a stacked adapter-slot pool.

    ``inner`` is the wrapped projection (float or weight-only int8 —
    the LoRA delta is always fp32 on top of the dequantized base
    matmul); ``slots``/``rank`` are deployment constants, part of the
    mixed-step executable's config key.  Forward fetches the step's
    per-row slot vector from the side-channel and is a pure pass-through
    when none is active."""

    def __init__(self, inner, slots: int, rank: int):
        super().__init__()
        if isinstance(inner, LoRAServingLinear):
            raise TypeError("LoRAServingLinear cannot wrap itself")
        if not _is_linear_like(inner):
            raise TypeError(
                f"LoRAServingLinear wraps a linear projection, got "
                f"{type(inner).__name__}")
        if int(slots) < 2:
            raise ValueError(
                f"adapter slots must be >= 2 (slot 0 is the reserved "
                f"identity), got {slots}")
        if int(rank) < 1:
            raise ValueError(f"rank must be >= 1, got {rank}")
        self.inner = inner
        self.slots = int(slots)
        self.rank = int(rank)
        d_in, d_out = _features_of(inner)
        self.in_features = d_in
        self.out_features = d_out
        self.register_buffer("lora_a", Tensor(
            jnp.zeros((self.slots, d_in, self.rank), jnp.float32)))
        self.register_buffer("lora_b", Tensor(
            jnp.zeros((self.slots, self.rank, d_out), jnp.float32)))
        self.register_buffer("lora_scale", Tensor(
            jnp.zeros((self.slots,), jnp.float32)))
        # TP sharding rides the pools exactly like the base weight: a
        # column-parallel target shards B's output dim (A replicated —
        # its r columns are the reduction no axis splits), a
        # row-parallel target shards A's input dim (B replicated, the
        # delta joins y before/under the same allreduce).  Scales are
        # tiny and replicated.
        kind = _target_kind(inner)
        if kind == "column":
            self.lora_b.dist_attr = (None, None, "mp")
        elif kind == "row":
            self.lora_a.dist_attr = (None, "mp", None)

    def forward(self, x):
        y = self.inner(x)
        rows = lora_slots.row_slots()
        if rows is None:
            return y
        raw = lora_slots._raw
        xd = raw(x)[0]                    # [T, d_in]: the flat token axis
        sl = raw(rows)                    # [T] int32, each token's row's
        ga = raw(self.lora_a)[sl]         # [T, d_in, r]
        gb = raw(self.lora_b)[sl]         # [T, r, d_out]
        gs = raw(self.lora_scale)[sl]     # [T]
        delta = jnp.einsum("td,tdr->tr", xd, ga)
        delta = jnp.einsum("tr,tro->to", delta, gb)
        return Tensor(raw(y) + (gs[:, None] * delta)[None])

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features}, "
                f"slots={self.slots}, rank={self.rank}, "
                f"base={type(self.inner).__name__}")


def lora_layers(model):
    """Yield ``(path, LoRAServingLinear)`` for every converted target
    projection, in traversal order — the stable per-layer key adapter
    checkpoints address factors by."""
    for path, sub in model.named_sublayers():
        if isinstance(sub, LoRAServingLinear):
            yield path, sub


def adapter_layer_spec(model, targets=DEFAULT_TARGETS) -> dict:
    """``{path: (d_in, d_out)}`` for every projection the conversion
    would target — the shape contract the AdapterStore validates tenant
    checkpoints against.  Works on unconverted models (pre-engine store
    construction) and converted ones (paths are identical: the wrapper
    sits at the target's original path)."""
    spec = {}
    for path, sub in model.named_sublayers():
        name = path.rsplit(".", 1)[-1]
        if isinstance(sub, LoRAServingLinear):
            spec[path] = (sub.in_features, sub.out_features)
        elif name in targets and _is_linear_like(sub) \
                and not path.endswith(".inner"):
            spec[path] = _features_of(sub)
    return spec


def lora_serving_info(model) -> Optional[dict]:
    """Describe a model's adapter plane for validation/observability:
    ``{slots, rank, layers, pool_hbm_bytes}`` — or None for unconverted
    models.  Mixed slots/rank across layers are rejected (the serving
    plane keys ONE (slots, rank) per deployment config)."""
    layers = [lay for _, lay in lora_layers(model)]
    if not layers:
        return None
    dims = {(lay.slots, lay.rank) for lay in layers}
    if len(dims) != 1:
        from ..sharded import ShardedConfigError

        raise ShardedConfigError(
            f"LoRA layers disagree on (slots, rank) ({sorted(dims)}); "
            "the serving plane keys one stacked-pool shape per "
            "deployment config")
    slots, rank = dims.pop()
    pool_bytes = sum(
        int(lay.lora_a._data.nbytes) + int(lay.lora_b._data.nbytes)
        + int(lay.lora_scale._data.nbytes) for lay in layers)
    return {"slots": int(slots), "rank": int(rank),
            "layers": len(layers), "pool_hbm_bytes": int(pool_bytes)}


def prepare_lora_serving(model, slots: int, rank: int,
                         targets=DEFAULT_TARGETS) -> int:
    """Wrap every target projection in ``model`` (in place) with a
    :class:`LoRAServingLinear` bound to ``(slots, rank)``.  Idempotent:
    already-converted layers are rebound to the new dims instead of
    double-wrapped (their pools reset to identity — the AdapterCache
    reloads residents).  Returns the number of projections serving."""
    n = 0

    def visit(layer):
        nonlocal n
        for name, sub in list(layer._sub_layers.items()):
            if sub is None:
                continue
            if isinstance(sub, LoRAServingLinear):
                if sub.slots != int(slots) or sub.rank != int(rank):
                    setattr(layer, name,
                            LoRAServingLinear(sub.inner, slots, rank))
                n += 1
            elif name in targets and _is_linear_like(sub):
                setattr(layer, name, LoRAServingLinear(sub, slots, rank))
                n += 1
            else:
                visit(sub)

    visit(model)
    return n
