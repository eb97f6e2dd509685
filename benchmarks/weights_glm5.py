"""Seeded weights of the latent-attention + shared-expert MoE decoder
whose attention reads a learned indexer's selection (``reference: glm5``;
``model_type: glm_moe_dsa``), made by the benchmark on the device: every
array from ``weights.seed_key(seed, stream)``, one jitted call a layer, in
the type the model is served in.  The system adapter and the plain
reference are both handed these and take nothing from each other.

The matrices of attention, dense FFN, experts and the outer pair have
``weights_axk1``'s names and shapes (``qk_nope_head_dim`` 192 and
``v_head_dim`` 256 differ here: ``w_kvb`` is [kv_rank, H*(nope+v)], ``w_o``
[H*v, h]).  Added here, per layer:

  indexer   idx_wq [q_rank, Hi*di]   idx_wk [h, di]   idx_ww [h, Hi]
            idx_norm_w, idx_norm_b [di]      (the key's LayerNorm)
  router    e_bias [published]   normal(0, 0.1), float32  (expert layers:
                                 ``e_score_correction_bias``)

How they are drawn, and why (the configuration's ``assumed`` says the
same).  With every matrix ``normal(0, 0.02)`` a head's attention logits
over 4k-12k cached tokens have a standard deviation of 0.8: the softmax
is nearly flat, a layer's output is the mean of thousands of values,
and neither leaving the selection out nor choosing another set of the
same size moves a logit by more than bfloat16's own rounding: the check
could not see the mechanism this configuration is here for.  So the
query up-projection ``w_qb`` is drawn ``QUERY_GAIN`` times wider: logits
of standard deviation about 3, a softmax that puts most of its weight on
some tens of tokens, as a trained model's does.  Whether THOSE tokens are
among the 2,048 the indexer chose then decides the layer's output, and
both controls read far outside the limits (PERF.md section 2).  The
indexer's matrices stay ``normal(0, 0.02)`` (its scores' order does not
depend on their scale); its LayerNorm's weight is ``1 + normal(0, 0.1)``
and its bias ``normal(0, 0.1)``, so that a weight or bias left out shows.
Other norm weights are one and are not stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights_axk1
from .weights import INIT_STD, seed_key
from .weights_axk1 import LAYER_STREAM, is_dense, outer_weights  # noqa: F401

QUERY_GAIN = 4.0
ROUTER_BIAS_STD = NORM_STD = 0.1


def extra_shapes(cfg: dict, layer: int) -> dict:
    """The arrays this family adds to ``weights_axk1.layer_shapes``."""
    h, hi, di = (cfg["hidden_size"], cfg["index_n_heads"],
                 cfg["index_head_dim"])
    out = {"idx_wq": (cfg["q_lora_rank"], hi * di), "idx_wk": (h, di),
           "idx_ww": (h, hi), "idx_norm_w": (di,), "idx_norm_b": (di,)}
    if not is_dense(cfg, layer):
        out["e_bias"] = (cfg.get("n_routed_experts_published")
                         or cfg["n_routed_experts"],)
    return out


def layer_shapes(cfg: dict, layer: int) -> dict:
    return dict(weights_axk1.layer_shapes(cfg, layer),
                **extra_shapes(cfg, layer))


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(key, shapes, first_expert, dtype):
    """One layer's arrays from its key.  A stacked expert matrix is drawn
    expert by expert from the stream of its PUBLISHED index, in one
    batched draw, so another share of the deployment draws the same
    experts."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name == "e_bias":
            out[name] = _normal(k, shape, ROUTER_BIAS_STD, jnp.float32)
        elif name == "idx_norm_w":
            out[name] = (1.0 + _normal(k, shape, NORM_STD, jnp.float32)
                         ).astype(dtype)
        elif name == "idx_norm_b":
            out[name] = _normal(k, shape, NORM_STD, dtype)
        elif name.startswith("e_"):
            out[name] = jax.vmap(lambda j: _normal(
                jax.random.fold_in(k, first_expert + j), shape[1:],
                INIT_STD, dtype))(jnp.arange(shape[0]))
        else:
            std = INIT_STD * (QUERY_GAIN if name == "w_qb" else 1.0)
            out[name] = _normal(k, shape, std, dtype)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(key, layer, shapes, first_expert, dtype):
    # ``layer`` is traced: one compile for the dense layers' shapes and
    # one for the expert layers', whatever the depth
    return _layer(jax.random.fold_in(key, layer), dict(shapes),
                  first_expert, jnp.dtype(dtype))


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    return _layer_jit(seed_key(seed, LAYER_STREAM), layer,
                      tuple(sorted(layer_shapes(cfg, layer).items())),
                      int(cfg.get("experts_held_first", 0)),
                      jnp.dtype(dtype).name)


def all_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every array of the model in the served type: ``{"layers":
    [per-layer dict], "embed", "lm_head"}``, by the very calls
    ``layer_weights`` and ``outer_weights`` make, which the reference's
    own calls then find compiled."""
    out = {"layers": [layer_weights(cfg, seed, i, dtype)
                      for i in range(int(cfg["num_hidden_layers"]))]}
    out.update(outer_weights(cfg, seed, dtype))
    return out
