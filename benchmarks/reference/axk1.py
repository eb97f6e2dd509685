"""Plain reference for the A.X-K1 decoder (``skt/A.X-K1`` config.json,
``model_type: axk1``; the DeepSeek-V3 family's published modelling code
for the equations): pre-RMSNorm blocks of multi-head latent attention and
— after ``first_k_dense_replace`` SwiGLU layers — 192 routed SwiGLU
experts chosen top-8 by sigmoid score beside one shared expert, untied
head.  Straightforward ``jax.numpy`` in float32 with matmul precision
"highest"; EXPANDED attention only (per-head keys and values from
``W_kvb``), no cache, no kernels, no batching; one sequence at a time,
layer by layer so that only one layer's float32 weights are alive at
once, and the queries in blocks so that the scores fit.

Departures from the published description, all stated in the
configuration file too:

- ``topk_method: "none"`` beside ``n_group`` / ``topk_group`` is read as
  plain top-8 over all 192 sigmoid scores, no group restriction and no
  score-correction bias; ``n_group`` and ``topk_group`` are unused.
- The chip's share: the router scores all ``n_routed_experts_published``
  experts and normalises over its 8 chosen, but only experts
  ``experts_held_first .. + n_routed_experts - 1`` exist here; what the
  absent ones would add is left out and the partial result goes on.
  The vocabulary is the slice of ``vocab_size`` rows.
- Rotary lanes: the published code de-interleaves each pair
  ``(x[2i], x[2i+1])`` to ``(x'[i], x'[i + d/2])`` and then applies the
  rotate-half rotation, for queries and keys alike; this file does the
  same, so the rotated vectors are in the de-interleaved order (scores
  are those of the interleaved convention, since both sides agree).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_axk1
from .lowp import matmul, rounder

QUERY_BLOCK = 256


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim, theta, sc):
    """Inverse frequencies of the ``dim`` rotary lanes under YaRN: the
    original ``theta^(-2i/dim)`` below the ``beta_fast`` correction dim,
    divided by ``factor`` above the ``beta_slow`` one, a linear ramp
    between."""
    i = np.arange(dim // 2, dtype=np.float64)
    extra = theta ** (-2.0 * i / dim)
    inter = extra / sc["factor"]

    def dim_of(rotations):
        return dim * math.log(sc["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(dim_of(sc["beta_fast"])), 0)
    high = min(math.ceil(dim_of(sc["beta_slow"])), dim - 1)
    if low == high:
        high = high + 0.001
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    return inter * ramp + extra * (1.0 - ramp)


def softmax_scale(cfg):
    sc = cfg["rope_scaling"]
    m = yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 \
        * m * m


def _rope(x, inv_freq, mscale):
    """x [T, ..., d], positions 0..T-1 along the first axis."""
    t, d = x.shape[0], x.shape[-1]
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = ang.reshape((t,) + (1,) * (x.ndim - 2) + (d // 2,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1) * mscale
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1) * mscale
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


def _attention(x, w, cfg, r):
    t = x.shape[0]
    heads = cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], float(cfg["rms_norm_eps"])
    sc = cfg["rope_scaling"]
    inv = jnp.asarray(yarn_inv_freq(rope, float(cfg["rope_theta"]), sc),
                      jnp.float32)
    ms = yarn_mscale(sc["factor"], sc["mscale"]) \
        / yarn_mscale(sc["factor"], sc["mscale_all_dim"])
    q = matmul(_rms_norm(matmul(x, w["w_qa"], r), eps), w["w_qb"], r)
    q = q.reshape(t, heads, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], inv, ms)], -1)
    ckv = matmul(x, w["w_kva"], r)
    c_kv = _rms_norm(ckv[:, :rank], eps)
    k_pe = _rope(ckv[:, rank:], inv, ms)                     # [T, rope]
    kv = matmul(c_kv, w["w_kvb"], r).reshape(t, heads, nope + vd)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
        k_pe[:, None, :], (t, heads, rope))], -1)
    v = kv[..., nope:]
    scale = softmax_scale(cfg)
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


def _swiglu(y, gate, up, down, r):
    return matmul(jax.nn.silu(matmul(y, gate, r)) * matmul(y, up, r),
                  down, r)


def _experts(y, w, cfg, r):
    """sum over the chosen experts held here of w_i E_i(y), plus the
    shared expert."""
    k = cfg["num_experts_per_tok"]
    first = int(cfg.get("experts_held_first", 0))
    g = jax.nn.sigmoid(matmul(y, w["router"], r))            # [T, published]
    top, idx = jax.lax.top_k(g, k)
    wts = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]
    out = _swiglu(y, w["s_gate"], w["s_up"], w["s_down"], r)
    for j in range(w["e_gate"].shape[0]):
        wj = jnp.sum(jnp.where(idx == first + j, wts, 0.0), axis=-1)
        out = out + wj[:, None] * _swiglu(y, w["e_gate"][j], w["e_up"][j],
                                          w["e_down"][j], r)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, cfg_items, precision):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    r = rounder(precision)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(cfg["rms_norm_eps"])
    x = x + _attention(_rms_norm(x, eps), w, cfg, r)
    y = _rms_norm(x, eps)
    if "router" in w:
        return x + _experts(y, w, cfg, r)
    return x + _swiglu(y, w["w_gate"], w["w_up"], w["w_down"], r)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, lm_head, eps, precision):
    r = rounder(precision)
    return matmul(_rms_norm(x[rows], eps), lm_head.astype(jnp.float32), r)


_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
         "num_experts_per_tok", "routed_scaling_factor",
         "experts_held_first")


def logits_at(cfg: dict, layer_weights, outer, tokens, rows,
              precision: str = "float32"):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence;
    the sequence is padded at its end to a multiple of the query block
    (under a causal mask padding changes nothing before it)."""
    tokens = np.asarray(tokens, np.int32)
    t = -(-len(tokens) // QUERY_BLOCK) * QUERY_BLOCK
    ids = np.zeros((t,), np.int32)
    ids[:len(tokens)] = tokens
    items = tuple((k, cfg.get(k, 0)) for k in _KEYS) + (
        ("rope_scaling", tuple(sorted(
            (k, v) for k, v in cfg["rope_scaling"].items()
            if not isinstance(v, str)))),)
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
        cfg, lambda i: weights_axk1.layer_weights(cfg, seed, i, dtype),
        weights_axk1.outer_weights(cfg, seed, dtype), tokens, rows,
        precision)
