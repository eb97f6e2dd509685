"""Dropless top-k expert layer for serving: scores over every published
expert, top-k, the valid assignments sorted by expert, one grouped
matmul over the experts HELD here (ops/pallas/grouped_matmul.py), a
weighted scatter-add back.  There is no capacity axis and no token is
ever dropped: cost follows the assignments made.

The layer is told which experts it holds (``held_first``,
``held_count``: this chip's share of an expert-parallel deployment).
The router keeps its published width and its top-k over all of it; the
layer computes ``sum_{i in T ∩ held} w_i E_i(x)`` with ``w`` normalised
over all ``k`` chosen.  What absent experts would add is left out — on
one chip the layer runs without its exchange.

Pad slots route nowhere: the mixed step hands the layer its flat token
axis (``N = token_budget`` slots, the rows' tokens end to end) and
threads the valid-slot mask ``arange(N) < sum(query_lens)`` and its
bound on valid slots (all ``N``) through the stats side-channel
(``serving/moe/stats.py``); the sort runs over ``N x k`` keys, the rows
buffer of the grouped matmul is sized from the bound times ``k``, so it
can never overflow, and the combine adds into ``[N, hidden]``.
Outside a collecting context every slot is valid and the buffer holds
every assignment.

Two ways to score and weigh (``route``): sigmoid scores renormalised
over the chosen (the DeepSeek-V3 family), and a softmax over all of the
router's outputs whose chosen scores are the weights as they are, times
the scale.  The second comes with IDENTITY experts (``identity_experts``:
router outputs ``n_published ..`` on, "zero-computation" experts whose
output is their input): an assignment to one never enters the sort's
live part, the rows buffer or the grouped matmul; a token's identity
weights are summed under its valid mask and ``that sum x the block's own
input`` is what the combine starts from instead of zeros.  They belong
to the token's home chip, whichever experts it holds.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...nn import initializer as I
from ...nn.layer import Layer
from ...ops.pallas.grouped_matmul import grouped_matmul
from . import stats as moe_stats

ROW_TILE = 128


def route(x, gate_weight, top_k: int, routed_scale: float,
          score_bias=None, scoring: str = "sigmoid",
          renormalise: bool = True):
    """Scores over all the router's outputs in float32, the ``top_k``
    largest (ties to the lower index), weighted and scaled.  x [N, h] ->
    (expert ids [N, k] int32, weights [N, k] float32).

    ``scoring`` "sigmoid" scores each output alone, "softmax" all of
    them together.  With ``renormalise`` the chosen scores are divided by
    their sum before the scale; without, a weight is its score times the
    scale, and a token's weights sum to less than the scale.

    ``score_bias`` [E] float32 (``topk_method: "noaux_tc"``, one group):
    the experts chosen are the ``top_k`` of ``scores + score_bias``; the
    bias decides WHICH experts and nothing else — their weights are the
    uncorrected scores."""
    logits = jnp.matmul(x.astype(jnp.float32),
                        gate_weight.astype(jnp.float32), precision="highest")
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    elif scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    else:
        raise ValueError(f"scoring={scoring!r}: \"sigmoid\" or \"softmax\"")
    if score_bias is None:
        top, ids = jax.lax.top_k(scores, top_k)
    else:
        _, ids = jax.lax.top_k(scores + score_bias.astype(jnp.float32),
                               top_k)
        top = jnp.take_along_axis(scores, ids, axis=-1)
    if renormalise:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), top * routed_scale


def identity_weights(ids, w, valid, first_identity: int):
    """What each token's chosen IDENTITY experts (router outputs
    ``first_identity`` on) weigh together, over valid slots: one masked
    sum a token.  -> (weights [N] float32, chosen [N, k] bool)."""
    chosen = (ids >= first_identity) & valid[:, None]
    return jnp.sum(jnp.where(chosen, w, 0.0), axis=-1), chosen


def dropless_experts(x, ids, w, valid, w_gate, w_up, w_down, held_first,
                     max_valid: Optional[int] = None, base=None):
    """The held experts' part of the layer.

    x [N, h]; ids / w [N, k] from :func:`route`; valid [N] bool; the
    stacked SwiGLU experts ``w_gate`` / ``w_up`` [E, h, f] and ``w_down``
    [E, f, h] are experts ``held_first .. held_first + E - 1``.
    ``base`` [N, h] float32, where given, is what the combine adds the
    experts' rows into instead of zeros (the identity experts' term).
    Returns ``(y [N, h], counts [E] int32)``: the weighted sum over each
    token's chosen held experts, and how many valid assignments each
    held expert received."""
    n, k = ids.shape
    e = w_gate.shape[0]
    # the grouped matmul's Pallas calls stay outside the scope (the TPU
    # compiler names a Mosaic call after its innermost scope)
    with jax.named_scope("moe_experts"):
        local = ids - held_first
        mine = (local >= 0) & (local < e) & valid[:, None]
        key = jnp.where(mine, local, e).reshape(-1)            # [N*k]
        order = jnp.argsort(key, stable=True)
        cap = n * k if max_valid is None else min(n, int(max_valid)) * k
        if cap > ROW_TILE:
            cap = -(-cap // ROW_TILE) * ROW_TILE
        order = order[:min(cap, n * k)]
        if order.shape[0] < cap:
            order = jnp.pad(order, (0, cap - order.shape[0]))
        counts = jnp.sum((key[:, None] == jnp.arange(e)[None, :]), axis=0,
                         dtype=jnp.int32)
        token = order // k
        rows = x[token]                                        # [cap, h]
    gate = grouped_matmul(rows, w_gate, counts)
    up = grouped_matmul(rows, w_up, counts)
    with jax.named_scope("moe_experts"):
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(x.dtype)
    out = grouped_matmul(hidden, w_down, counts)
    with jax.named_scope("moe_experts"):
        # held valid assignments sort first: exactly sum(counts) rows
        live = jnp.arange(cap) < jnp.sum(counts)
        weight = jnp.where(live, w.reshape(-1)[order], 0.0)
        if base is None:
            base = jnp.zeros((n, x.shape[1]), jnp.float32)
        y = base.at[
            jnp.where(live, token, n)].add(
                out.astype(jnp.float32) * weight[:, None], mode="drop")
    return y.astype(x.dtype), counts


class DroplessMoE(Layer):
    """Router over ``n_published`` experts + the stacked SwiGLU experts
    ``held_first .. held_first + held_count - 1``; with ``score_bias`` a
    score-correction bias a router output beside the router.  With
    ``identity_experts`` the router has that many outputs more, behind
    the published experts', and an assignment to one adds its weight
    times the layer's input (module docstring); ``scoring`` and
    ``renormalise`` are :func:`route`'s."""

    def __init__(self, hidden: int, ffn_hidden: int, n_published: int,
                 top_k: int, held_first: int = 0,
                 held_count: Optional[int] = None,
                 routed_scale: float = 1.0, init_std: float = 0.02,
                 score_bias: bool = False, identity_experts: int = 0,
                 scoring: str = "sigmoid", renormalise: bool = True):
        super().__init__()
        if scoring not in ("sigmoid", "softmax"):
            raise ValueError(
                f"scoring={scoring!r}: \"sigmoid\" or \"softmax\"")
        held_count = n_published if held_count is None else held_count
        if not (0 <= held_first
                and held_first + held_count <= n_published):
            raise ValueError(
                f"held experts {held_first}..{held_first + held_count - 1}"
                f" lie outside the {n_published} published")
        self.num_experts = int(n_published)
        self.held_first, self.held_count = int(held_first), int(held_count)
        self.top_k = int(top_k)
        self.routed_scale = float(routed_scale)
        self.identity_experts = int(identity_experts)
        self.scoring, self.renormalise = scoring, bool(renormalise)
        outputs = self.num_experts + self.identity_experts
        init = I.Normal(0.0, init_std)
        self.gate_weight = self.create_parameter(
            (hidden, outputs), default_initializer=init)
        self.w_gate = self.create_parameter(
            (held_count, hidden, ffn_hidden), default_initializer=init)
        self.w_up = self.create_parameter(
            (held_count, hidden, ffn_hidden), default_initializer=init)
        self.w_down = self.create_parameter(
            (held_count, ffn_hidden, hidden), default_initializer=init)
        # the score-correction bias of ``noaux_tc`` routing: float32
        # whatever the model is served in
        self.e_score_correction_bias = self.create_parameter(
            (outputs,), dtype="float32",
            default_initializer=I.Constant(0.0)) if score_bias else None

    def forward(self, x):
        b, s, h = x.shape
        xf = x._data.reshape(b * s, h)
        col = moe_stats.current()
        valid = col.valid if col is not None \
            else jnp.ones((b * s,), jnp.bool_)
        with jax.named_scope("moe_router"):
            bias = self.e_score_correction_bias
            ids, w = route(xf, self.gate_weight._data, self.top_k,
                           self.routed_scale,
                           None if bias is None else bias._data,
                           self.scoring, self.renormalise)
        base = chosen_identity = None
        if self.identity_experts:
            with jax.named_scope("moe_identity"):
                w_id, chosen_identity = identity_weights(
                    ids, w, valid, self.num_experts)
                base = w_id[:, None] * xf.astype(jnp.float32)
        y, counts = dropless_experts(
            xf, ids, w, valid, self.w_gate._data, self.w_up._data,
            self.w_down._data, self.held_first,
            None if col is None else col.max_valid, base)
        if col is not None:
            with jax.named_scope("moe_router"):
                col.note_dropless(
                    jnp.sum(valid.astype(jnp.int32)) * self.top_k,
                    jnp.sum(counts), jnp.max(counts),
                    jnp.sum((counts > 0).astype(jnp.int32)))
                if chosen_identity is not None:
                    a_token = jnp.sum(chosen_identity, axis=-1,
                                      dtype=jnp.int32)
                    col.note_identity(
                        jnp.sum(a_token),
                        jnp.max(jnp.where(valid, self.top_k - a_token, 0)))
        return Tensor(y.reshape(b, s, h))

    def extra_repr(self):
        return (f"published={self.num_experts}, held={self.held_first}+"
                f"{self.held_count}, top_k={self.top_k}"
                + (f", identity={self.identity_experts}"
                   if self.identity_experts else ""))


def dropless_moe_info(model) -> Optional[dict]:
    """``{num_experts, held_first, held_count, top_k, layers,
    identity_experts}`` of a model's dropless expert layers, or None when
    it has none."""
    layers = [sub for _, sub in model.named_sublayers()
              if isinstance(sub, DroplessMoE)]
    if not layers:
        return None
    first = layers[0]
    return {"num_experts": first.num_experts,
            "held_first": first.held_first,
            "held_count": first.held_count, "top_k": first.top_k,
            "layers": len(layers),
            "identity_experts": first.identity_experts}
