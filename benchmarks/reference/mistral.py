"""Plain reference for the Mistral-7B-v0.1 decoder (Jiang et al. 2023;
``mistralai/Mistral-7B-v0.1`` config.json): pre-RMSNorm blocks, rotary
embedding in the rotate-half convention, grouped-query causal attention,
SwiGLU feed-forward, untied head.  Straightforward ``jax.numpy`` in
float32 with matmul precision "highest"; no cache, no kernels, no
batching; one sequence at a time, layer by layer so that only one layer's
float32 weights are alive at once.

Departure from the published model: the 4096-token sliding window is not
applied, because no sequence here is longer than it (the configuration
file's ``max_model_len`` is 2048); with a shorter window this function
would be wrong and says so.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights
from .lowp import matmul, rounder


def _rms_norm(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [T, H, D], positions 0..T-1, rotate-half."""
    t, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7))
def _layer(x, w, hq, hkv, d, eps, theta, precision):
    r = rounder(precision)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    t = x.shape[0]
    qkv = matmul(_rms_norm(x, eps), w["wqkv"], r)
    q = _rope(qkv[:, :hq * d].reshape(t, hq, d), theta)
    k = _rope(qkv[:, hq * d:(hq + hkv) * d].reshape(t, hkv, d), theta)
    v = qkv[:, (hq + hkv) * d:].reshape(t, hkv, d)
    rep = hq // hkv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("qhd,khd->hqk", r(q), r(k), precision="highest") \
        / np.sqrt(d)
    causal = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hqk,khd->qhd", r(p), r(v), precision="highest")
    x = x + matmul(a.reshape(t, hq * d), w["wo"], r)
    y = _rms_norm(x, eps)
    ff = jax.nn.silu(matmul(y, w["w_gate"], r)) * matmul(y, w["w_up"], r)
    return x + matmul(ff, w["w_down"], r)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, lm_head, eps, precision):
    r = rounder(precision)
    return matmul(_rms_norm(x[rows], eps), lm_head.astype(jnp.float32), r)


def logits_at(cfg: dict, layer_weights, outer, tokens, rows,
              precision: str = "float32", pad_to: int = 128):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence.

    ``layer_weights(i)`` gives layer i's matrices (weights.llama_layer_weights
    in the served type: the model *is* those rounded values); ``outer`` has
    ``embed`` and ``lm_head``.  The sequence is padded at its end to a
    multiple of ``pad_to`` — under a causal mask padding changes nothing
    before it — so few shapes compile.
    """
    window = cfg.get("sliding_window")
    tokens = np.asarray(tokens, np.int32)
    if window is not None and len(tokens) > window:
        raise ValueError(
            f"sequence of {len(tokens)} exceeds the sliding window "
            f"{window}: this reference does not apply the window")
    t = -(-len(tokens) // pad_to) * pad_to
    ids = np.zeros((t,), np.int32)
    ids[:len(tokens)] = tokens
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    x = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(i), hq, hkv, d,
                   float(cfg["rms_norm_eps"]), float(cfg["rope_theta"]),
                   precision)
    return _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                 outer["lm_head"], float(cfg["rms_norm_eps"]), precision)


def served_logits(cfg: dict, seed: int, tokens, rows,
                  precision: str = "float32"):
    """The contract of a served reference (``reference/__init__.py``):
    float32 logits [len(rows), vocab] of the model whose weights are the
    family's seeded ones in the served type, one layer's float32 copy
    alive at a time."""
    dtype = cfg["torch_dtype"]
    return logits_at(
        cfg, lambda i: weights.llama_layer_weights(cfg, seed, i, dtype),
        weights.llama_outer_weights(cfg, seed, dtype), tokens, rows,
        precision)
