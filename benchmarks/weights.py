"""Seeded weights, made by the benchmark on the device.

The program under test and the plain references are both handed these:
neither takes anything the other has made.  Names are the benchmark's
own; each system adapter maps them onto the program's parameters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

INIT_STD = 0.02          # both sources' published initializer_range


def seed_key(seed: int, stream: int = 0):
    """A key from any whole-number seed (the driver's exceed 2**31)."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    k = jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(k, stream)


# ------------------------------------------------------------------ llama

def _llama_shapes(cfg: dict):
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ffn = cfg["intermediate_size"]
    return {"wqkv": (h, (hq + 2 * hkv) * d), "wo": (hq * d, h),
            "w_gate": (h, ffn), "w_up": (h, ffn), "w_down": (ffn, h)}


def _llama_layer(key, shapes, dtype):
    out = {}
    for i, (name, shape) in enumerate(sorted(shapes.items())):
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shape,
                                       jnp.float32) * INIT_STD).astype(dtype)
    return out


def llama_layer_weights(cfg: dict, seed: int, layer: int, dtype=jnp.bfloat16):
    """One layer's matrices ([in, out]; wqkv is [Wq | Wk | Wv] over its
    columns).  Norm weights are ones and are not stored."""
    shapes = tuple(sorted(_llama_shapes(cfg).items()))
    return _llama_layer_jit(seed_key(seed, 1), layer, shapes,
                            jnp.dtype(dtype).name)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _llama_layer_jit(key, layer, shapes, dtype):
    return _llama_layer(jax.random.fold_in(key, layer), dict(shapes),
                        jnp.dtype(dtype))


def llama_outer_weights(cfg: dict, seed: int, dtype=jnp.bfloat16):
    """Embedding [vocab, hidden] and untied head [hidden, vocab]."""
    return _llama_outer_jit(seed_key(seed, 2), cfg["vocab_size"],
                            cfg["hidden_size"], jnp.dtype(dtype).name)


def _llama_outer(key, vocab, hidden, dtype):
    dt = jnp.dtype(dtype)
    return {"embed": (jax.random.normal(jax.random.fold_in(key, 0),
                                        (vocab, hidden), jnp.float32)
                      * INIT_STD).astype(dt),
            "lm_head": (jax.random.normal(jax.random.fold_in(key, 1),
                                          (hidden, vocab), jnp.float32)
                        * INIT_STD).astype(dt)}


_llama_outer_jit = jax.jit(_llama_outer, static_argnums=(1, 2, 3))


def llama_all_weights(cfg: dict, seed: int, dtype=jnp.bfloat16,
                      shardings=None):
    """Every matrix of the model in ONE jitted call, in the served type.
    ``shardings``: optional {name: sharding} applied per layer matrix and
    to embed / lm_head, so a sharded model is never whole on one chip."""
    shapes = tuple(sorted(_llama_shapes(cfg).items()))
    layers = cfg["num_hidden_layers"]
    dt = jnp.dtype(dtype)

    def make(k_layers, k_outer):
        out = {"layers": [
            _llama_layer(jax.random.fold_in(k_layers, i), dict(shapes), dt)
            for i in range(layers)]}
        out.update(_llama_outer(
            k_outer, cfg["vocab_size"], cfg["hidden_size"], dt.name))
        return out

    out_sh = None
    if shardings is not None:
        layer_sh = {n: shardings[n] for n, _ in shapes}
        out_sh = {"layers": [layer_sh] * layers,
                  "embed": shardings["embed"],
                  "lm_head": shardings["lm_head"]}
    fn = jax.jit(make, out_shardings=out_sh)
    return fn(seed_key(seed, 1), seed_key(seed, 2))


# ------------------------------------------------------------------ ernie

def ernie_shapes(cfg: dict, seq: int) -> dict:
    """name -> (shape, init) with init in {"normal", "zeros", "ones"}."""
    h, ffn, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    out = {"word_emb": ((v, h), "normal"), "pos_emb": ((int(cfg["max_position_embeddings"]), h), "normal"),
           "type_emb": ((cfg["type_vocab_size"], h), "normal"),
           "emb_ln_w": ((h,), "ones"), "emb_ln_b": ((h,), "zeros"),
           "pool_w": ((h, h), "normal"), "pool_b": ((h,), "zeros"),
           "mlm_w": ((h, h), "normal"), "mlm_b": ((h,), "zeros"),
           "mlm_ln_w": ((h,), "ones"), "mlm_ln_b": ((h,), "zeros"),
           "dec_b": ((v,), "zeros"),
           "nsp_w": ((h, 2), "normal"), "nsp_b": ((2,), "zeros")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        out.update({
            p + "wqkv": ((h, 3 * h), "normal"), p + "bqkv": ((3 * h,), "zeros"),
            p + "wo": ((h, h), "normal"), p + "bo": ((h,), "zeros"),
            p + "w1": ((h, ffn), "normal"), p + "b1": ((ffn,), "zeros"),
            p + "w2": ((ffn, h), "normal"), p + "b2": ((h,), "zeros"),
            p + "ln1_w": ((h,), "ones"), p + "ln1_b": ((h,), "zeros"),
            p + "ln2_w": ((h,), "ones"), p + "ln2_b": ((h,), "zeros")})
    return out


def ernie_weights(cfg: dict, seq: int, seed: int) -> dict:
    """All of ERNIE's float32 parameters in one jitted call."""
    shapes = ernie_shapes(cfg, seq)
    names = sorted(shapes)

    def make(key):
        out = {}
        for i, n in enumerate(names):
            shape, init = shapes[n]
            if init == "normal":
                out[n] = jax.random.normal(jax.random.fold_in(key, i), shape,
                                           jnp.float32) * INIT_STD
            else:
                out[n] = jnp.full(shape, 1.0 if init == "ones" else 0.0,
                                  jnp.float32)
        return out

    return jax.jit(make)(seed_key(seed, 3))
