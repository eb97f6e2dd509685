"""Seeded weights of the decoder whose layer holds two latent attentions,
two dense SwiGLU blocks and one shortcut expert block (``reference:
longcat``; ``model_type: longcat_flash``), made by the benchmark on the
device: every array from ``weights.seed_key(seed, stream)``, one jitted
call a layer, in the type the model is served in.  The system adapter and
the plain reference are both handed these and take nothing from each
other.

Names are the benchmark's own ([in, out] matrices), sub-layer ``j`` in
``0, 1``:

  attention   a{j}_w_qa [h, q_rank]   a{j}_w_qb [q_rank, H*(nope+rope)]
              a{j}_w_kva [h, kv_rank+rope]   a{j}_w_kvb [kv_rank, H*(nope+v)]
              a{j}_w_o [H*v, h]
  dense FFN   m{j}_gate, m{j}_up [h, ffn]   m{j}_down [ffn, h]
  experts     router [h, published + identity]   e_bias [published +
              identity] float32   e_gate, e_up [held, h, f]
              e_down [held, f, h]
  outer       embed [vocab, h]   lm_head [h, vocab]

``held`` is the configuration's ``n_routed_experts`` (``experts_held_first``
on) and ``published`` its ``n_routed_experts_published``; held expert ``j``
is drawn from the stream of published expert ``experts_held_first + j``,
so another share of the same deployment draws the same experts.  The
identity experts have no weights.

Every matrix is ``normal(0, 0.02)``.  The score-correction bias is seeded
as ``weights_glm5`` seeds its own, at THIS router's scale: a softmax over
768 outputs has a mean score of 1/768 = 0.0013 and, with logits of
standard deviation 1.57, a twelfth-largest near 0.012; ``normal(0,
0.002)`` moves a choice near the twelfth place and leaves the routing the
token's (``normal(0, 0.1)`` would hand every token the bias's own top 12).
Norm weights are one and are not stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .weights import INIT_STD, seed_key
from .weights_axk1 import outer_weights  # noqa: F401  (embed, lm_head)

LAYER_STREAM = 11
ROUTER_BIAS_STD = 0.002
SUB_LAYERS = 2


def router_outputs(cfg: dict) -> int:
    return int(cfg["n_routed_experts_published"]) + int(cfg["zero_expert_num"])


def layer_shapes(cfg: dict) -> dict:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    qr, kr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    ffn, f = cfg["ffn_hidden_size"], cfg["expert_ffn_hidden_size"]
    held, out = cfg["n_routed_experts"], {}
    for j in range(SUB_LAYERS):
        out.update({f"a{j}_w_qa": (h, qr),
                    f"a{j}_w_qb": (qr, heads * (nope + rope)),
                    f"a{j}_w_kva": (h, kr + rope),
                    f"a{j}_w_kvb": (kr, heads * (nope + vd)),
                    f"a{j}_w_o": (heads * vd, h),
                    f"m{j}_gate": (h, ffn), f"m{j}_up": (h, ffn),
                    f"m{j}_down": (ffn, h)})
    out.update(router=(h, router_outputs(cfg)),
               e_bias=(router_outputs(cfg),), e_gate=(held, h, f),
               e_up=(held, h, f), e_down=(held, f, h))
    return out


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(key, shapes, first_expert, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name == "e_bias":
            out[name] = _normal(k, shape, ROUTER_BIAS_STD, jnp.float32)
        elif name.startswith("e_"):
            out[name] = jax.vmap(lambda j: _normal(
                jax.random.fold_in(k, first_expert + j), shape[1:],
                INIT_STD, dtype))(jnp.arange(shape[0]))
        else:
            out[name] = _normal(k, shape, INIT_STD, dtype)
    return out


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer_jit(key, layer, shapes, first_expert, dtype):
    # ``layer`` is traced: one compile whatever the depth
    return _layer(jax.random.fold_in(key, layer), dict(shapes),
                  first_expert, jnp.dtype(dtype))


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    return _layer_jit(seed_key(seed, LAYER_STREAM), layer,
                      tuple(sorted(layer_shapes(cfg).items())),
                      int(cfg.get("experts_held_first", 0)),
                      jnp.dtype(dtype).name)


def all_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every array of the model in the served type: ``{"layers":
    [per-layer dict], "embed", "lm_head"}``, by the very calls
    ``layer_weights`` and ``outer_weights`` make."""
    out = {"layers": [layer_weights(cfg, seed, i, dtype)
                      for i in range(int(cfg["num_layers"]))]}
    out.update(outer_weights(cfg, seed, dtype))
    return out
