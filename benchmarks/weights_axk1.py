"""Seeded weights of the latent-attention + shared-expert MoE decoder
(``reference: axk1``), made by the benchmark on the device: every matrix
from ``weights.seed_key(seed, stream)`` in ONE jitted call, in the type
the model is served in.  The system adapter and the plain reference are
both handed these and take nothing from each other.

Names are the benchmark's own ([in, out] matrices):

  attention   w_qa [h, q_rank]  w_qb [q_rank, H*(nope+rope)]
              w_kva [h, kv_rank+rope]  w_kvb [kv_rank, H*(nope+v)]
              w_o [H*v, h]
  dense FFN   w_gate, w_up [h, ffn]  w_down [ffn, h]
  expert FFN  router [h, published]  e_gate, e_up [held, h, f]
              e_down [held, f, h]  s_gate, s_up [h, f*shared]
              s_down [f*shared, h]
  outer       embed [vocab, h]  lm_head [h, vocab]

``held`` is the configuration's ``n_routed_experts`` (the experts this
chip holds, ``experts_held_first`` on) and ``published`` its
``n_routed_experts_published``: held expert ``j`` is drawn from the
stream of published expert ``experts_held_first + j``, so another share
of the same deployment draws the same experts.  Norm weights are one and
are not stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .weights import INIT_STD, seed_key

LAYER_STREAM, OUTER_STREAM = 11, 12


def is_dense(cfg: dict, layer: int) -> bool:
    return layer < int(cfg["first_k_dense_replace"])


def layer_shapes(cfg: dict, layer: int) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    out = {"w_qa": (h, cfg["q_lora_rank"]),
           "w_qb": (cfg["q_lora_rank"], heads * (nope + rope)),
           "w_kva": (h, cfg["kv_lora_rank"] + rope),
           "w_kvb": (cfg["kv_lora_rank"], heads * (nope + cfg["v_head_dim"])),
           "w_o": (heads * cfg["v_head_dim"], h)}
    if is_dense(cfg, layer):
        ffn = cfg["intermediate_size"]
        out.update(w_gate=(h, ffn), w_up=(h, ffn), w_down=(ffn, h))
        return out
    f, held = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    fs = f * cfg["n_shared_experts"]
    out.update(router=(h, cfg.get("n_routed_experts_published")
                       or cfg["n_routed_experts"]),
               e_gate=(held, h, f), e_up=(held, h, f), e_down=(held, f, h),
               s_gate=(h, fs), s_up=(h, fs), s_down=(fs, h))
    return out


def _normal(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * INIT_STD
            ).astype(dtype)


def _layer(key, shapes, first_expert, dtype):
    """One layer's matrices.  A stacked expert matrix is drawn expert by
    expert from the stream of its PUBLISHED index."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.startswith("e_"):
            out[name] = jnp.stack([
                _normal(jax.random.fold_in(k, first_expert + j), shape[1:],
                        dtype) for j in range(shape[0])])
        else:
            out[name] = _normal(k, shape, dtype)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(key, layer, shapes, first_expert, dtype):
    return _layer(jax.random.fold_in(key, layer), dict(shapes),
                  first_expert, jnp.dtype(dtype))


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    shapes = tuple(sorted(layer_shapes(cfg, layer).items()))
    return _layer_jit(seed_key(seed, LAYER_STREAM), layer, shapes,
                      int(cfg.get("experts_held_first", 0)),
                      jnp.dtype(dtype).name)


def _outer(key, vocab, hidden, dtype):
    dt = jnp.dtype(dtype)
    return {"embed": _normal(jax.random.fold_in(key, 0), (vocab, hidden), dt),
            "lm_head": _normal(jax.random.fold_in(key, 1), (hidden, vocab),
                               dt)}


_outer_jit = jax.jit(_outer, static_argnums=(1, 2, 3))


def outer_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Embedding [vocab, hidden] and untied head [hidden, vocab], over the
    configuration's slice of the vocabulary."""
    return _outer_jit(seed_key(seed, OUTER_STREAM), cfg["vocab_size"],
                      cfg["hidden_size"], jnp.dtype(dtype).name)


def all_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every matrix of the model in ONE jitted call, in the served type:
    ``{"layers": [per-layer dict], "embed", "lm_head"}`` — value for value
    what ``layer_weights`` and ``outer_weights`` give."""
    layers = int(cfg["num_hidden_layers"])
    shapes = [layer_shapes(cfg, i) for i in range(layers)]
    first = int(cfg.get("experts_held_first", 0))
    dt = jnp.dtype(dtype)

    def make(k_layers, k_outer):
        out = {"layers": [_layer(jax.random.fold_in(k_layers, i), shapes[i],
                                 first, dt) for i in range(layers)]}
        out.update(_outer(k_outer, cfg["vocab_size"], cfg["hidden_size"],
                          dt.name))
        return out

    return jax.jit(make)(seed_key(seed, LAYER_STREAM),
                         seed_key(seed, OUTER_STREAM))
