"""Plain reference for the GLM-5 decoder (``zai-org/GLM-5`` config.json,
``model_type: glm_moe_dsa``): pre-RMSNorm blocks of multi-head latent
attention that reads, for each query, only the ``index_topk`` cached
tokens a learned indexer scores highest (DeepSeek Sparse Attention, as
published with DeepSeek-V3.2-Exp: the "dsa" of the model type, whose
``index_*`` keys are that design's), and — after ``first_k_dense_replace``
SwiGLU layers — routed SwiGLU experts chosen top-k of a bias-corrected
sigmoid score (``noaux_tc``, one group) beside one shared expert; untied
head.  Straightforward ``jax.numpy`` in float32 with matmul precision
"highest"; EXPANDED attention (per-head keys and values from ``W_kvb``),
no cache, no kernels, no batching, its own top-k (a sort); the RMS norm
and the SwiGLU are ``reference/axk1.py``'s (the same family's); one sequence
at a time, layer by layer so that only one layer's float32 weights are
alive at once, and index scores and attention in blocks of
``QUERY_BLOCK`` queries so that 12.7k positions fit.

For token ``t`` with ``h_t`` the layer's normed input and ``c^Q_t`` the
normed query latent (both the attention's own):

    q^I_{t,j} = (W^I_q c^Q_t)_j  (j < index_n_heads, index_head_dim wide)
    k^I_s     = LayerNorm(W^I_k h_s)      one key a token for all heads
    rotary on the first qk_rope_head_dim lanes of both
    w_t       = W^I_w h_t / sqrt(index_n_heads x index_head_dim)
    I_{t,s}   = sum_j w_{t,j} relu(q^I_{t,j} . k^I_s)
    S_t       = the min(index_topk, t + 1) tokens s <= t of largest I
    o_{t,h}   = sum_{s in S_t} softmax_{S_t}(q_{t,h} . k_{s,h} * scale) v_{s,h}

Departures from the published description, all stated in the
configuration file too:

- The deployment rotates index queries and keys by one orthogonal
  (Hadamard) matrix and stores the keys in fp8.  The rotation cannot
  change a dot product and is left out; the fp8 is a deployment's
  precision, and this configuration states bfloat16.
- The index key's LayerNorm has a weight and a bias and eps 1e-6.
- Ties at the ``index_topk``-th place go to the earlier token.
- The chip's share: the router scores all ``n_routed_experts_published``
  experts and normalises over its chosen, but only experts
  ``experts_held_first .. + n_routed_experts - 1`` exist here; what the
  absent ones would add is left out.  The vocabulary is the slice of
  ``vocab_size`` rows.
- Rotary lanes: each interleaved pair ``(x[2i], x[2i+1])`` is
  de-interleaved to ``(x'[i], x'[i + d/2])`` and then rotated in the
  rotate-half form, queries and keys alike (attention's and the
  indexer's), so scores are those of the interleaved convention.
- ``num_nextn_predict_layers`` is carried and not built: the main
  model's logits do not depend on it.

``precision`` is one of ``lowp``'s (the lower ones are the controls of
``correct``) or one of ``FAULTS``: float32 with the selection altered,
the two faults the limits must catch (``control.py --precision
no_selection+random_selection`` reads them on the chip beside fp8).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_glm5
from .axk1 import QUERY_BLOCK, _rms_norm, _swiglu
from .lowp import matmul, rounder

INDEX_LN_EPS = 1e-6

# "no_selection": attention over every cached token; "random_selection":
# a random set of the same size in the indexer's place
FAULTS = ("no_selection", "random_selection")


def _layer_norm(x, weight, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * weight + bias


def _rope(x, theta):
    """x [T, ..., d], positions 0..T-1 along the first axis; plain rotary
    embedding, no scaling."""
    t, d = x.shape[0], x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def _best(scores, valid, k):
    """bool [Q, T]: the ``min(k, valid count)`` largest valid scores of
    each row, ties at the last place to the earlier token."""
    k = min(int(k), scores.shape[1])
    kth = jnp.sort(scores, axis=-1)[:, -k][:, None]
    above = (scores > kth) & valid
    tied = (scores == kth) & valid
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return above | (tied & (jnp.cumsum(tied, axis=-1) <= room))


def _attention(x, w, cfg, r, fault):
    t = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    hi, di, topk = (cfg["index_n_heads"], cfg["index_head_dim"],
                    cfg["index_topk"])
    c_q = _rms_norm(matmul(x, w["w_qa"], r), eps)
    q = matmul(c_q, w["w_qb"], r).reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    ckv = matmul(x, w["w_kva"], r)
    c_kv = _rms_norm(ckv[:, :rank], eps)
    k_pe = _rope(ckv[:, rank:], theta)                       # [T, rope]
    kv = matmul(c_kv, w["w_kvb"], r).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (t, heads, rope))], -1)
    v = kv[..., nope:]
    scale = (nope + rope) ** -0.5
    # the indexer
    qi = matmul(c_q, w["idx_wq"], r).reshape(t, hi, di)
    qi = jnp.concatenate([_rope(qi[..., :rope], theta), qi[..., rope:]], -1)
    ki = _layer_norm(matmul(x, w["idx_wk"], r), w["idx_norm_w"],
                     w["idx_norm_b"], INDEX_LN_EPS)
    ki = jnp.concatenate([_rope(ki[:, :rope], theta), ki[:, rope:]], -1)
    wi = matmul(x, w["idx_ww"], r) * (hi ** -0.5 * di ** -0.5)  # [T, Hi]
    keys = jnp.arange(t)

    def block(args):
        qb, qib, wib, pos = args          # [Q, H, d], [Q, Hi, di], [Q, Hi], [Q]
        valid = keys[None, :] <= pos[:, None]                # [Q, T]
        if fault == "no_selection":
            chosen = valid
        else:
            if fault == "random_selection":
                idx = jax.random.uniform(
                    jax.random.fold_in(jax.random.PRNGKey(5), pos[0]),
                    valid.shape)
            else:
                idx = jnp.einsum("qjd,kd->qjk", r(qib), r(ki),
                                 precision="highest")
                idx = jnp.sum(jnp.maximum(idx, 0.0) * wib[:, :, None],
                              axis=1)
            chosen = _best(jnp.where(valid, idx, -jnp.inf), valid, topk)
        s = jnp.einsum("qhd,khd->hqk", r(qb), r(k),
                       precision="highest") * scale
        p = jax.nn.softmax(jnp.where(chosen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", r(p), r(v), precision="highest")

    nb = t // QUERY_BLOCK
    o = jax.lax.map(block, (
        q.reshape(nb, QUERY_BLOCK, heads, nope + rope),
        qi.reshape(nb, QUERY_BLOCK, hi, di),
        wi.reshape(nb, QUERY_BLOCK, hi), keys.reshape(nb, QUERY_BLOCK)))
    return matmul(o.reshape(t, heads * vd), w["w_o"], r)


def _experts(y, w, cfg, r):
    """Bias-corrected choice over all published experts, uncorrected
    weights; the chosen experts held here, plus the shared expert."""
    k = cfg["num_experts_per_tok"]
    first = int(cfg["experts_held_first"])
    s = jax.nn.sigmoid(matmul(y, w["router"], r))            # [T, published]
    _, idx = jax.lax.top_k(s + w["e_bias"], k)
    top = jnp.take_along_axis(s, idx, axis=-1)
    wts = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]

    def add_expert(out, expert):
        # the expert's matrices arrive in the served type and are made
        # float32 here, one expert at a time
        j, gate, up, down = (expert[0],) + tuple(
            m.astype(jnp.float32) for m in expert[1:])
        wj = jnp.sum(jnp.where(idx == first + j, wts, 0.0), axis=-1)
        return out + wj[:, None] * _swiglu(y, gate, up, down, r), None

    # one held expert after the other, every token through each
    shared = _swiglu(y, w["s_gate"], w["s_up"], w["s_down"], r)
    n = w["e_gate"].shape[0]
    return jax.lax.scan(add_expert, shared, (
        jnp.arange(n), w["e_gate"], w["e_up"], w["e_down"]))[0]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, cfg_items, precision):
    cfg = dict(cfg_items)
    fault = precision if precision in FAULTS else None
    r = rounder("float32" if fault else precision)
    w = {k: v if k in ("e_gate", "e_up", "e_down")
         else v.astype(jnp.float32) for k, v in w.items()}
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(_rms_norm(x, eps), w, cfg, r, fault)
    y = _rms_norm(x, eps)
    if "router" in w:
        return x + _experts(y, w, cfg, r)
    return x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"], r)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, lm_head, eps, precision):
    r = rounder("float32" if precision in FAULTS else precision)
    return matmul(_rms_norm(x[rows], eps), lm_head.astype(jnp.float32), r)


_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "kv_lora_rank", "rms_norm_eps",
         "num_experts_per_tok", "routed_scaling_factor", "index_n_heads",
         "index_head_dim", "index_topk")


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
        ("experts_held_first", int(cfg.get("experts_held_first", 0))),
        ("rope_theta", float(cfg["rope_parameters"]["rope_theta"])))
    x = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(i), items, precision)
    return _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                 outer["lm_head"], float(cfg["rms_norm_eps"]), precision)


def served_logits(cfg: dict, seed: int, tokens, rows,
                  precision: str = "float32"):
    """The contract of a served reference (``reference/__init__.py``)."""
    dtype = cfg["torch_dtype"]
    return logits_at(
        cfg, lambda i: weights_glm5.layer_weights(cfg, seed, i, dtype),
        weights_glm5.outer_weights(cfg, seed, dtype), tokens, rows,
        precision)
