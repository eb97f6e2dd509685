"""Plain reference for the LongCat-Flash decoder (``meituan-longcat/
LongCat-Flash-Omni`` config.json, the language model; ``model_type:
longcat_flash``).  Straightforward ``jax.numpy`` in float32 with matmul
precision "highest"; EXPANDED attention (per-head keys and values from
``W_kvb``), no cache, no kernels, no batching, its own top-k (a sort);
one sequence at a time, layer by layer so that only one layer's float32
weights are alive at once (the held experts stay in the served type and
are made float32 one at a time), attention in blocks of ``QUERY_BLOCK``
queries.  The RMS norm and the SwiGLU are ``reference/axk1.py``'s and the
plain rotary embedding and the tie-breaking top-k ``reference/glm5.py``'s
(the same arithmetic; nothing of the program's).

One layer, ``x`` the residual stream, ``N`` an RMSNorm (weights one)::

    u  = x + MLA_a(N(x))
    y  = N(u)
    s  = Experts(y)                  # read here, added at the end
    v  = u + MLP_a(y)
    w  = v + MLA_b(N(v))
    z  = N(w)
    x' = w + MLP_b(z) + s

``MLA``: ``c^Q = sqrt(h / q_rank) N(W_qa h)``, ``q = W_qb c^Q`` -> heads x
(nope ‖ rope lanes, the latter rotated); ``[c^KV ‖ k^R] = W_kva h``,
``c^KV`` normed AND THEN scaled by ``sqrt(h / kv_rank)``, ``k^R`` rotated
and not scaled; keys ``[W^K c^KV ‖ k^R]``, values ``W^V c^KV``, scale
``(nope + rope)^-1/2``, causal softmax, ``W_o``.  ``MLP``: SwiGLU.
``Experts(y)``: ``p = softmax(W_r y)`` over published + identity outputs;
``T`` = the ``moe_topk`` largest of ``p + b`` (ties to the lower index);
``g_i = routed_scaling_factor x p_i`` for ``i`` in ``T`` (uncorrected, not
renormalised); ``sum_{i in T held here} g_i E_i(y) + (sum_{i in T, i >=
published} g_i) y``.

Departures from the published description, all stated in the
configuration file too:

- The chip's share: the router scores all its outputs, but only experts
  ``experts_held_first .. + n_routed_experts - 1`` exist here; what the
  absent ones would add is left out.  The identity experts are the
  token's home chip's and are all here.  The vocabulary is the slice of
  ``vocab_size`` rows.
- ``hidden_act`` silu, no router bias, untied head; rotary lanes paired
  as the family's latent-attention code pairs them (interleaved,
  de-interleaved before the rotate-half rotation, queries and keys
  alike); the score-correction bias is a buffer of the family's code.
- The audio and vision towers and the codec decoder are not built.

``precision`` is one of ``lowp``'s (the lower ones are the controls of
``correct``) or one of ``FAULTS``: float32 with the expert block altered,
the faults the limits must catch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_longcat
from .axk1 import QUERY_BLOCK, _rms_norm, _swiglu
from .glm5 import _best, _rope
from .lowp import matmul, rounder

# "no_identity": the identity experts' term left out; "renormalised": the
# chosen weights divided by their sum; "experts_read_z": the expert block
# reads the SECOND sub-layer's post-attention norm
FAULTS = ("no_identity", "renormalised", "experts_read_z")


def _attention(x, w, cfg, r):
    t, h = x.shape
    heads = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    q_scale = (h / qr) ** 0.5 if cfg["mla_scale_q_lora"] else 1.0
    kv_scale = (h / kr) ** 0.5 if cfg["mla_scale_kv_lora"] else 1.0
    c_q = _rms_norm(matmul(x, w["w_qa"], r), eps) * q_scale
    q = matmul(c_q, w["w_qb"], r).reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckv = matmul(x, w["w_kva"], r)
    c_kv = _rms_norm(ckv[:, :kr], eps) * kv_scale
    k_pe = _rope(ckv[:, kr:], theta)                         # [T, rope]
    kv = matmul(c_kv, w["w_kvb"], r).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (t, heads, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    keys = jnp.arange(t)

    def block(args):
        qb, pos = args                    # [Q, H, d], [Q]
        s = jnp.einsum("qhd,khd->hqk", r(qb), r(k),
                       precision="highest") * scale
        s = jnp.where(keys[None, None, :] <= pos[None, :, None], s,
                      -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", r(p), r(v), precision="highest")

    nb = t // QUERY_BLOCK
    o = jax.lax.map(block, (q.reshape(nb, QUERY_BLOCK, heads, nope + rope),
                            keys.reshape(nb, QUERY_BLOCK)))
    return matmul(o.reshape(t, heads * vd), w["w_o"], r)


def _experts(y, w, cfg, r, fault):
    """The held experts' and the identity experts' parts of the block."""
    published = int(cfg["n_routed_experts_published"])
    first = int(cfg["experts_held_first"])
    p = jax.nn.softmax(matmul(y, w["router"], r), axis=-1)   # [T, outputs]
    chosen = _best(p + w["e_bias"], jnp.ones(p.shape, bool),
                   cfg["moe_topk"])
    g = jnp.where(chosen, p, 0.0)
    if fault == "renormalised":
        g = g / jnp.sum(g, axis=-1, keepdims=True)
    g = g * cfg["routed_scaling_factor"]

    def add_expert(out, expert):
        # the expert's matrices arrive in the served type and are made
        # float32 here, one expert at a time
        j, gate, up, down = (expert[0],) + tuple(
            m.astype(jnp.float32) for m in expert[1:])
        gj = jax.lax.dynamic_index_in_dim(g, first + j, axis=1)  # [T, 1]
        return out + gj * _swiglu(y, gate, up, down, r), None

    identity = jnp.sum(g[:, published:], axis=-1, keepdims=True) * y
    if fault == "no_identity":
        identity = jnp.zeros_like(y)
    n = w["e_gate"].shape[0]
    return jax.lax.scan(add_expert, identity, (
        jnp.arange(n), w["e_gate"], w["e_up"], w["e_down"]))[0]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, cfg_items, precision):
    cfg = dict(cfg_items)
    fault = precision if precision in FAULTS else None
    r = rounder("float32" if fault else precision)
    w = {k: v if k in ("e_gate", "e_up", "e_down")
         else v.astype(jnp.float32) for k, v in w.items()}
    eps = float(cfg["rms_norm_eps"])
    for j in range(weights_longcat.SUB_LAYERS):
        sub = lambda prefix: {k[len(prefix):]: v for k, v in w.items()
                              if k.startswith(prefix)}
        x = x + _attention(_rms_norm(x, eps), sub(f"a{j}_"), cfg, r)
        y = _rms_norm(x, eps)
        if j == (1 if fault == "experts_read_z" else 0):
            shortcut = _experts(y, w, cfg, r, fault)
        m = sub(f"m{j}_")
        x = x + _swiglu(y, m["gate"], m["up"], m["down"], r)
    return x + shortcut


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, lm_head, eps, precision):
    r = rounder("float32" if precision in FAULTS else precision)
    return matmul(_rms_norm(x[rows], eps), lm_head.astype(jnp.float32), r)


_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "q_lora_rank", "kv_lora_rank", "rms_norm_eps",
         "rope_theta", "mla_scale_q_lora", "mla_scale_kv_lora", "moe_topk",
         "routed_scaling_factor", "n_routed_experts_published")


def logits_at(cfg: dict, layer_weights, outer, tokens, rows,
              precision: str = "float32"):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence;
    the sequence is padded at its end to a multiple of the query block,
    or of the configuration's ``check.reference_pad_to`` where it states
    one so that every request of a run has one shape (under a causal mask
    padding changes nothing before it)."""
    tokens = np.asarray(tokens, np.int32)
    pad = int(cfg.get("check", {}).get("reference_pad_to", QUERY_BLOCK))
    assert pad % QUERY_BLOCK == 0, pad
    t = -(-len(tokens) // pad) * pad
    ids = np.zeros((t,), np.int32)
    ids[:len(tokens)] = tokens
    items = tuple((k, cfg[k]) for k in _KEYS) + (
        ("experts_held_first", int(cfg.get("experts_held_first", 0))),)
    x = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_layers"]):
        x = _layer(x, layer_weights(i), items, precision)
    return _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                 outer["lm_head"], float(cfg["rms_norm_eps"]), precision)


def served_logits(cfg: dict, seed: int, tokens, rows,
                  precision: str = "float32"):
    """The contract of a served reference (``reference/__init__.py``)."""
    dtype = cfg["torch_dtype"]
    return logits_at(
        cfg, lambda i: weights_longcat.layer_weights(cfg, seed, i, dtype),
        weights_longcat.outer_weights(cfg, seed, dtype), tokens, rows,
        precision)
