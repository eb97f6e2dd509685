"""Serving MoE ops: the fused-MoE formulation with a STATIC capacity.

The training fused path (``parallel/moe.py``) derives its capacity from
the live token count inside the trace — fine there (every training step
has the same [b, s]), fatal for serving if anything shape-valued ever
depended on batch composition.  These ops take ``capacity`` as an
explicit attribute fixed by deployment config
(``serving.moe.serving_capacity``: token_budget token slots), so
the dispatch/combine buffers are ``[E, C]``-shaped once per config and
routing changes DATA, never shapes.  In the ragged mixed step the token
count is itself static, the ``token_budget`` slots of the step's flat
token axis, so with the default capacity the routing numerics are
bitwise what the training fused path computes — conversion changes
nothing in the stream.

Three variants mirror the fused-MoE matrix (float / weight-only int8
and int4 / int8-activation), each returning the routed/dropped/aux
stats the serving plane surfaces: capacity overflow must be observable,
not silent.  Stats are masked to the step's VALID token slots (the
``valid`` operand — pad slots still compete for capacity exactly as in
the unconverted model, they just don't count).  The int8-activation
variant quantizes the dispatched expert buffer BEFORE the "ep" pin, so
the GSPMD all-to-all genuinely moves int8 bytes (quantization is
elementwise — numerically identical to pinning first).

No internal jit: inside the mixed step these trace into the one serving
executable; eager calls run op-by-op (parity tests, calibration).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...core.dispatch import register_op
from ...parallel.moe import (_GATES, _combine_out, _expert_ffn, _pin_ep,
                             naive_gate)
from ...quantization.moe import _moe_weight_dequantize


def _requested_k(gate: str, top_k: int) -> int:
    """Expert-slot assignments each token requests — what the drop
    count is measured against."""
    return {"switch": 1, "gshard": 2}.get(gate, top_k)


def _serving_dispatch(x, gate_w, valid, gate, top_k, capacity):
    """Gate + fixed-capacity dispatch: returns (combine [N, E, C],
    expert_in [E, C, d] — NOT yet ep-pinned, aux, routed [E] i32,
    dropped i32).  Same gate functions and einsum formulation as the
    training fused path; only the capacity source differs."""
    b, s, d = x.shape
    n = b * s
    xt = x.reshape(n, d)
    logits = xt.astype(jnp.float32) @ gate_w.astype(jnp.float32)
    cap = int(capacity)
    if gate == "naive":
        combine, dispatch, aux = naive_gate(logits, cap, top_k=top_k)
    else:
        combine, dispatch, aux = _GATES[gate](logits, cap)
    expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(x.dtype), xt)
    v = valid.reshape(n).astype(jnp.int32)
    kept = jnp.sum(dispatch.astype(jnp.int32), axis=2)        # [N, E]
    routed = jnp.sum(kept * v[:, None], axis=0).astype(jnp.int32)
    k = _requested_k(gate, top_k)
    dropped = jnp.sum(
        (k - jnp.sum(kept, axis=1)) * v).astype(jnp.int32)
    return combine, expert_in, aux, routed, dropped


@register_op("serving_moe", jit=False)
def _serving_moe(x, gate_w, w1, b1, w2, b2, valid, gate="gshard",
                 top_k=2, capacity=4, activation="gelu"):
    """Float serving MoE: x [b, s, d] → (out [b, s, d], routed [E],
    dropped, aux)."""
    combine, expert_in, aux, routed, dropped = _serving_dispatch(
        x, gate_w, valid, gate, top_k, capacity)
    out_e = _expert_ffn(_pin_ep(expert_in), w1, b1, w2, b2, activation)
    return (_combine_out(x, combine, out_e), routed, dropped,
            aux.astype(jnp.float32))


@register_op("serving_moe_weight_only", jit=False)
def _serving_moe_weight_only(x, gate_w, qw1, s1, b1, qw2, s2, b2, valid,
                             gate="gshard", top_k=2, capacity=4,
                             activation="gelu", algo="weight_only_int8"):
    """Weight-only serving MoE: int8/int4 expert payloads, dequant fused
    into the expert-einsum operand feed (quantization/moe.py numerics)."""
    combine, expert_in, aux, routed, dropped = _serving_dispatch(
        x, gate_w, valid, gate, top_k, capacity)
    w1 = _moe_weight_dequantize(qw1, s1, algo, x.dtype)
    w2 = _moe_weight_dequantize(qw2, s2, algo, x.dtype)
    out_e = _expert_ffn(_pin_ep(expert_in), w1, b1, w2, b2, activation)
    return (_combine_out(x, combine, out_e), routed, dropped,
            aux.astype(jnp.float32))


@register_op("serving_moe_int8", jit=False)
def _serving_moe_int8(x, gate_w, qw1, s1, b1, qw2, s2, b2, valid,
                      act_scale_in, act_scale_hidden, gate="gshard",
                      top_k=2, capacity=4, activation="gelu"):
    """Int8-activation serving MoE: both expert einsums int8×int8 with
    int32 accumulators (quantization/moe._fused_moe_int8_impl numerics);
    the dispatched buffer is quantized before the ep pin so the
    dispatch all-to-all moves 1-byte payloads."""
    combine, expert_in, aux, routed, dropped = _serving_dispatch(
        x, gate_w, valid, gate, top_k, capacity)
    a_in = jnp.asarray(act_scale_in, jnp.float32)
    a_h = jnp.asarray(act_scale_hidden, jnp.float32)

    def q_act(a, scale):
        return jnp.clip(jnp.round(a.astype(jnp.float32) / scale),
                        -127, 127).astype(jnp.int8)

    xq = _pin_ep(q_act(expert_in, a_in))
    acc1 = jnp.einsum("ecd,edf->ecf", xq, qw1,
                      preferred_element_type=jnp.int32)
    y1 = acc1.astype(jnp.float32) * (s1[:, None, :] * a_in)
    act = getattr(jax.nn, activation)
    h = act(y1 + b1[:, None, :].astype(jnp.float32))
    hq = q_act(h, a_h)
    acc2 = jnp.einsum("ecf,efd->ecd", hq, qw2,
                      preferred_element_type=jnp.int32)
    out_e = acc2.astype(jnp.float32) * (s2[:, None, :] * a_h)
    out_e = (out_e + b2[:, None, :].astype(jnp.float32)).astype(x.dtype)
    return (_combine_out(x, combine, out_e), routed, dropped,
            aux.astype(jnp.float32))
