"""Plain reference for the Xing4.0 decoder (``XingChen-AGI/Xing4.0-29B-A4B``
config.json, ``model_type: xing4_0``): ``hc_mult`` residual streams mixed
by manifold-constrained hyper-connections (mHC, arXiv 2512.24880) around
multi-head latent attention and — after ``first_k_dense_replace`` SwiGLU
layers — routed SwiGLU experts chosen top-k of a bias-corrected sigmoid
score (``noaux_tc``, one group) beside one shared expert; untied head.
Straightforward ``jax.numpy`` in float32 with matmul precision
"highest"; the EXPANDED attention of ``reference/axk1.py`` (the same
family's: per-head keys and values, YaRN, no cache, no kernels), a plain
loop for Sinkhorn-Knopp; one sequence at a time, layer by layer so that
only one layer's float32 weights are alive at once.

Per token, with ``x in R^{n x C}`` (``n = hc_mult``), for each of a
layer's two sub-layers ``F`` (attention behind ``input_layernorm``, FFN
behind ``post_attention_layernorm``), each with its own ``Phi, alpha, b``:

    f^ = vec(x) / sqrt(mean(vec(x)^2) + rms_norm_eps);   z = f^ Phi
    H_pre = sigmoid(alpha_pre z[:n] + b_pre)
    H_post = 2 sigmoid(alpha_post z[n:2n] + b_post)
    M = exp(clamp(alpha_res mat(z[2n:]) + b_res));  hc_sinkhorn_iters times:
        M <- M / (colsum(M) + hc_eps);  M <- M / (rowsum(M) + hc_eps)
    x'[i] = sum_j M[i, j] x[j] + H_post[i] F(sum_j H_pre[j] x[j])

Streams start as ``n`` copies of the embedding; the head reads
``RMSNorm(sum_i x[i])``.  Router: ``s = sigmoid(y W_r)``; the experts are
the top-k of ``s + b_e``; their weights ``s[chosen] / sum s[chosen] x
routed_scaling_factor`` from the uncorrected ``s``.

What the source's config does not fix is stated in the configuration
file's ``assumed`` (replicated start, summed read-out, column step before
row step, where each epsilon sits).  ``num_nextn_predict_layers`` is
carried and not built: the main model's logits do not depend on it.

``precision`` is one of ``lowp``'s (the lower ones are the controls of
``correct``) or one of ``FAULTS``: float32 with one piece of the
mathematics altered, the faults the limits must catch
(``control.py --precision sinkhorn_1+maps_bf16`` reads them on the chip
beside fp8; tests/test_hyper_connections.py holds each at a small size).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights_xing4
from .axk1 import QUERY_BLOCK, _attention, _rms_norm, _swiglu
from .lowp import matmul, rounder

# sequences are padded to whole multiples of this many positions: three of
# the attention's query blocks hold the longest sequence the cell's check
# samples (a prompt of 512 and 255 served tokens), so every request of a
# run has ONE shape and a layer kind compiles once
PAD_TO = 3 * QUERY_BLOCK

FAULTS = ("sinkhorn_1", "no_row_step", "post_x1", "no_bias",
          "bias_in_weights", "maps_bf16")


def _maps(z, alpha, bias, cfg, fault):
    """z [T, n^2 + 2n] -> H_pre [T, n], H_post [T, n], H_res [T, n, n]."""
    n = int(cfg["hc_mult"])
    eps = float(cfg["hc_eps"])
    iters = 1 if fault == "sinkhorn_1" else int(cfg["hc_sinkhorn_iters"])
    low = (lambda v: v.astype(jnp.bfloat16)) if fault == "maps_bf16" \
        else (lambda v: v)
    z, alpha, bias = low(z), low(alpha), low(bias)
    h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + bias[:n])
    h_post = jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
    if fault != "post_x1":
        h_post = 2.0 * h_post
    a = alpha[2] * z[:, 2 * n:] + bias[2 * n:]
    m = jnp.exp(jnp.clip(a, float(cfg["mhc_h_res_clamp_min"]),
                         float(cfg["mhc_h_res_clamp_max"]))
                ).reshape(-1, n, n)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + low(jnp.float32(eps)))
        if fault != "no_row_step":
            m = m / (jnp.sum(m, axis=2, keepdims=True)
                     + low(jnp.float32(eps)))
    f32 = lambda v: v.astype(jnp.float32)
    return f32(h_pre), f32(h_post), f32(m)


def _hyper(x, w, sub, fn, cfg, r, fault):
    """One hyper-connected sub-layer.  x [T, n, C] -> [T, n, C]."""
    t, n, c = x.shape
    f = x.reshape(t, n * c)
    fhat = f * jax.lax.rsqrt(jnp.mean(f * f, axis=-1, keepdims=True)
                             + float(cfg["rms_norm_eps"]))
    z = matmul(fhat, w[sub + "_phi"], r)
    h_pre, h_post, h_res = _maps(z, w[sub + "_alpha"], w[sub + "_bias"],
                                 cfg, fault)
    u = jnp.einsum("tj,tjc->tc", h_pre, x, precision="highest")
    y = fn(u)
    return (jnp.einsum("tij,tjc->tic", h_res, x, precision="highest")
            + h_post[:, :, None] * y[:, None, :])


def _experts(y, w, cfg, r, fault):
    """Bias-corrected choice, uncorrected weights; all experts held."""
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(matmul(y, w["router"], r))            # [T, E]
    corrected = s if fault == "no_bias" else s + w["e_bias"]
    top_c, idx = jax.lax.top_k(corrected, k)
    top = top_c if fault == "bias_in_weights" \
        else jnp.take_along_axis(s, idx, axis=-1)
    wts = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20) \
        * cfg["routed_scaling_factor"]

    def add_expert(out, expert):
        j, gate, up, down = expert
        wj = jnp.sum(jnp.where(idx == j, wts, 0.0), axis=-1)
        return out + wj[:, None] * _swiglu(y, gate, up, down, r), None

    # one expert after the other, every token through every expert (a
    # scan and not 64 unrolled copies: the reference compiles in seconds)
    shared = _swiglu(y, w["s_gate"], w["s_up"], w["s_down"], r)
    n = w["e_gate"].shape[0]
    return jax.lax.scan(add_expert, shared, (
        jnp.arange(n), w["e_gate"], w["e_up"], w["e_down"]))[0]


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, w, cfg_items, precision):
    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    fault = precision if precision in FAULTS else None
    r = rounder("float32" if fault else precision)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    eps = float(cfg["rms_norm_eps"])
    x = _hyper(x, w, "hc_attn",
               lambda u: _attention(_rms_norm(u, eps), w, cfg, r), cfg, r,
               fault)
    if "router" in w:
        ffn = lambda u: _experts(_rms_norm(u, eps), w, cfg, r, fault)
    else:
        ffn = lambda u: _swiglu(_rms_norm(u, eps), w["w_gate"], w["w_up"],
                                w["w_down"], r)
    return _hyper(x, w, "hc_ffn", ffn, cfg, r, fault)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, rows, lm_head, eps, precision):
    r = rounder("float32" if precision in FAULTS else precision)
    h = jnp.sum(x[rows], axis=1)
    return matmul(_rms_norm(h, eps), lm_head.astype(jnp.float32), r)


_KEYS = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "kv_lora_rank", "rms_norm_eps", "rope_theta",
         "num_experts_per_tok", "routed_scaling_factor", "hc_mult",
         "hc_sinkhorn_iters", "hc_eps", "mhc_h_res_clamp_min",
         "mhc_h_res_clamp_max")


def logits_at(cfg: dict, layer_weights, outer, tokens, rows,
              precision: str = "float32"):
    """Logits [len(rows), vocab] at positions ``rows`` of one sequence;
    the sequence is padded at its end to a multiple of ``PAD_TO`` (under
    a causal mask padding changes nothing before it)."""
    tokens = np.asarray(tokens, np.int32)
    t = -(-len(tokens) // PAD_TO) * PAD_TO
    ids = np.zeros((t,), np.int32)
    ids[:len(tokens)] = tokens
    items = tuple((k, cfg[k]) for k in _KEYS) + (
        ("rope_scaling", tuple(sorted(
            (k, v) for k, v in cfg["rope_scaling"].items()
            if not isinstance(v, str)))),)
    x = outer["embed"][jnp.asarray(ids)].astype(jnp.float32)
    x = jnp.broadcast_to(x[:, None, :],
                         (t, int(cfg["hc_mult"]), x.shape[-1]))
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, layer_weights(i), items, precision)
    return _head(x, jnp.asarray(np.asarray(rows, np.int32)),
                 outer["lm_head"], float(cfg["rms_norm_eps"]), precision)


def served_logits(cfg: dict, seed: int, tokens, rows,
                  precision: str = "float32"):
    """The contract of a served reference (``reference/__init__.py``)."""
    dtype = cfg["torch_dtype"]
    return logits_at(
        cfg, lambda i: weights_xing4.layer_weights(cfg, seed, i, dtype),
        weights_xing4.outer_weights(cfg, seed, dtype), tokens, rows,
        precision)
