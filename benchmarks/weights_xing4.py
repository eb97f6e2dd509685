"""Seeded weights of the latent-attention + shared-expert MoE decoder with
hyper-connected residual streams and bias-corrected routing (``reference:
xing4``), made by the benchmark on the device: every array from
``weights.seed_key(seed, stream)``, one jitted call a layer, in the type
the model is served in.  The system adapter and the plain reference are both
handed these and take nothing from each other.

The matrices of attention, dense FFN, experts and the outer pair have
``weights_axk1``'s names and shapes (the family is the same; only the
widths differ), and the outer pair is drawn by its code.  Added here, per
layer:

  residual maps  hc_attn_phi, hc_ffn_phi [n*h, n^2 + 2n]   normal(0, 0.02),
                                                           served type
                 hc_attn_alpha, hc_ffn_alpha [3]            ones, float32
                 hc_attn_bias, hc_ffn_bias [n^2 + 2n]       normal(0, 1),
                                                           float32
  router         e_bias [published]   normal(0, 0.1), float32  (expert
                                      layers: ``e_score_correction_bias``)

Why those biases (the configuration's ``assumed`` says the same): with
``Phi ~ normal(0, 0.02)`` over 14,336 unit-RMS inputs ``z`` has a standard
deviation of 2.4, so the maps differ from token to token whatever the
bias; ``b ~ normal(0, 1)`` on top puts every sub-layer's ``H_res`` far
from the identity and from the uniform matrix, and ``b_e ~ normal(0,
0.1)`` against sigmoid scores whose 4th and 5th largest of 64 lie some
hundredths apart changes the experts chosen for most tokens
(tests/benchmarks/test_bench_xing4_cpu.py measures both).  Norm weights
are one and are not stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import weights_axk1
from .weights import INIT_STD, seed_key
from .weights_axk1 import LAYER_STREAM, is_dense, outer_weights  # noqa: F401

MAP_BIAS_STD, ROUTER_BIAS_STD = 1.0, 0.1


def extra_shapes(cfg: dict, layer: int) -> dict:
    """The arrays this family adds to ``weights_axk1.layer_shapes``."""
    n, h = int(cfg["hc_mult"]), cfg["hidden_size"]
    w = n * n + 2 * n
    out = {}
    for sub in ("hc_attn", "hc_ffn"):
        out.update({f"{sub}_phi": (n * h, w), f"{sub}_alpha": (3,),
                    f"{sub}_bias": (w,)})
    if not is_dense(cfg, layer):
        out["e_bias"] = (cfg["n_routed_experts"],)
    return out


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _layer(key, shapes, dtype):
    """One layer's arrays from its key.  A stacked expert matrix is drawn
    expert by expert from the stream of its index, in ONE batched draw
    (64 x 3 separate draws a layer take the compiler a minute a layer)."""
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_alpha"):
            out[name] = jnp.ones(shape, jnp.float32)
        elif name.endswith("_bias"):
            std = ROUTER_BIAS_STD if name == "e_bias" else MAP_BIAS_STD
            out[name] = _normal(k, shape, std, jnp.float32)
        elif name.startswith("e_"):
            out[name] = jax.vmap(lambda j: _normal(
                jax.random.fold_in(k, j), shape[1:], INIT_STD, dtype))(
                    jnp.arange(shape[0]))
        else:
            out[name] = _normal(k, shape, INIT_STD, dtype)
    return out


def layer_shapes(cfg: dict, layer: int) -> dict:
    return dict(weights_axk1.layer_shapes(cfg, layer),
                **extra_shapes(cfg, layer))


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(key, layer, shapes, dtype):
    # ``layer`` is traced: one compile for the dense layers' shapes and
    # one for the expert layers', whatever the depth
    return _layer(jax.random.fold_in(key, layer), dict(shapes),
                  jnp.dtype(dtype))


def layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    return _layer_jit(seed_key(seed, LAYER_STREAM), layer,
                      tuple(sorted(layer_shapes(cfg, layer).items())),
                      jnp.dtype(dtype).name)


def all_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Every array of the model in the served type: ``{"layers":
    [per-layer dict], "embed", "lm_head"}``, by the very calls
    ``layer_weights`` and ``outer_weights`` make — a jitted call a layer,
    compiled once for the dense layers' shapes and once for the expert
    layers' (all 64 x 3 x 5 expert matrices in one program take the
    compiler minutes), which the reference's own calls then find
    compiled."""
    out = {"layers": [layer_weights(cfg, seed, i, dtype)
                      for i in range(int(cfg["num_hidden_layers"]))]}
    out.update(outer_weights(cfg, seed, dtype))
    return out
