"""Plain reference for the ERNIE-3.0-base pretraining step (Sun et al.
2021; the standard BERT-style post-LN encoder of PaddleNLP's
``ernie-3.0-base-zh``): embeddings (word + position + type 0) -> LayerNorm
-> dropout; 12 post-LN blocks (self-attention over segment ids with
probability dropout, exact-erf GELU feed-forward with activation
dropout); tanh pooler; MLM head tied to the word embedding; NSP head;
loss = masked-mean MLM cross entropy + mean NSP cross entropy; AdamW with
decoupled decay on every parameter.

Straightforward ``jax.numpy`` in float32, matmul precision "highest", no
kernels.  Dropout masks are this file's own draws: the program's masks
are its own business, so the two agree in distribution, not elementwise —
which is why the comparison is between norms, never of differences.
Rows are processed in blocks and their gradients summed (the loss is a
sum over rows divided by batch-wide counts), so float32 activations fit
beside nothing else.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .. import weights
from .lowp import matmul, rounder


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _drop(x, key, p):
    if p <= 0.0:
        return x
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0)


def _ce(logits, labels):
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return lse - picked


def block_loss_sum(params, ids, mask, labels, nsp_labels, key, cfg,
                   precision):
    """(sum of MLM losses over valid tokens, sum of NSP losses over rows)
    of one block of rows."""
    cfg = dict(cfg)
    r = rounder(precision)
    heads = cfg["num_attention_heads"]
    h = cfg["hidden_size"]
    d = h // heads
    eps = cfg["layer_norm_eps"]
    pd, pa = cfg["hidden_dropout_prob"], cfg["attention_probs_dropout_prob"]
    b, s = ids.shape
    keys = iter(jax.random.split(key, 2 + 4 * cfg["num_hidden_layers"]))
    x = params["word_emb"][ids] + params["pos_emb"][None, :s] \
        + params["type_emb"][0]
    x = _drop(_ln(x, params["emb_ln_w"], params["emb_ln_b"], eps),
              next(keys), pd)
    same = mask[:, :, None] == mask[:, None, :]           # [b, q, k]
    for i in range(cfg["num_hidden_layers"]):
        p = f"l{i}."
        qkv = matmul(x, params[p + "wqkv"], r) + params[p + "bqkv"]
        q, k, v = (qkv[..., j * h:(j + 1) * h].reshape(b, s, heads, d)
                   for j in range(3))
        sc = jnp.einsum("bqhd,bkhd->bhqk", r(q), r(k),
                        precision="highest") / np.sqrt(d)
        pr = jax.nn.softmax(jnp.where(same[:, None], sc, -jnp.inf), -1)
        pr = _drop(pr, next(keys), pa)
        a = jnp.einsum("bhqk,bkhd->bqhd", r(pr), r(v), precision="highest")
        a = matmul(a.reshape(b, s, h), params[p + "wo"], r) + params[p + "bo"]
        x = _ln(x + _drop(a, next(keys), pd), params[p + "ln1_w"],
                params[p + "ln1_b"], eps)
        f = jax.nn.gelu(matmul(x, params[p + "w1"], r) + params[p + "b1"],
                        approximate=False)
        f = matmul(_drop(f, next(keys), pd), params[p + "w2"], r) \
            + params[p + "b2"]
        x = _ln(x + _drop(f, next(keys), pd), params[p + "ln2_w"],
                params[p + "ln2_b"], eps)
    pooled = jnp.tanh(matmul(x[:, 0], params["pool_w"], r) + params["pool_b"])
    t = jax.nn.gelu(matmul(x, params["mlm_w"], r) + params["mlm_b"],
                    approximate=False)
    t = _ln(t, params["mlm_ln_w"], params["mlm_ln_b"], eps)
    logits = matmul(t, params["word_emb"].T, r) + params["dec_b"]
    valid = labels != -100
    mlm = jnp.sum(jnp.where(valid, _ce(logits, jnp.where(valid, labels, 0)),
                            0.0))
    nsp_logits = matmul(pooled, params["nsp_w"], r) + params["nsp_b"]
    return mlm, jnp.sum(_ce(nsp_logits, nsp_labels))


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _block_grad(params, ids, mask, labels, nsp_labels, key, cfg, precision,
                scales):
    n_valid, n_rows = scales

    def f(p):
        mlm, nsp = block_loss_sum(p, ids, mask, labels, nsp_labels, key,
                                  cfg, precision)
        return mlm / (n_valid + 1e-6) + nsp / n_rows

    return jax.value_and_grad(f)(params)


def loss_and_grads(params, batch, key, cfg, precision="float32",
                   row_block=8):
    """The step's loss and its gradients for one batch
    (ids, mask, labels, nsp_labels as numpy int32)."""
    ids, mask, labels, nsp = (np.asarray(a) for a in batch)
    n_valid = float((labels != -100).sum())
    rows = ids.shape[0]
    cfg_t = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))))
    loss, grads = 0.0, None
    for j, lo in enumerate(range(0, rows, row_block)):
        sl = slice(lo, lo + row_block)
        l, g = _block_grad(params, ids[sl], mask[sl], labels[sl], nsp[sl],
                           jax.random.fold_in(key, j), cfg_t, precision,
                           (n_valid, float(rows)))
        loss = loss + l
        grads = g if grads is None else jax.tree_util.tree_map(
            jnp.add, grads, g)
    return loss, grads


@jax.jit
def _adamw(params, grads, m, v, t, lr, b1, b2, eps, wd):
    def one(p, g, m_, v_):
        m2 = b1 * m_ + (1 - b1) * g
        v2 = b2 * v_ + (1 - b2) * g * g
        step = lr * (m2 / (1 - b1 ** t)) / (jnp.sqrt(v2 / (1 - b2 ** t))
                                            + eps)
        return p - step - lr * wd * p, m2, v2

    out = {n: one(params[n], grads[n], m[n], v[n]) for n in params}
    return ({n: o[0] for n, o in out.items()},
            {n: o[1] for n, o in out.items()},
            {n: o[2] for n, o in out.items()})


def follow_steps(cfg, opt, params, batches, key, precision="float32",
                 row_block=8):
    """Follow the first ``len(batches)`` steps.  Returns each step's loss,
    the first gradient's norm per leaf and, per leaf, the norm of the
    parameters' change over all the steps."""
    p0 = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_norms = [], None
    for t, batch in enumerate(batches, start=1):
        loss, grads = loss_and_grads(params, batch,
                                     jax.random.fold_in(key, t), cfg,
                                     precision, row_block)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {n: float(jnp.linalg.norm(g.ravel()))
                          for n, g in grads.items()}
        params, m, v = _adamw(params, grads, m, v, float(t),
                              float(opt["learning_rate"]), float(opt["beta1"]),
                              float(opt["beta2"]), float(opt["epsilon"]),
                              float(opt["weight_decay"]))
    delta = {n: float(jnp.linalg.norm((params[n] - p0[n]).ravel()))
             for n in params}
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


def first_steps(cfg: dict, seed: int, batches, n: int = 3,
                precision: str = "float32", mask_stream: int = 9) -> dict:
    """The contract of a training reference (``reference/__init__.py``):
    the first ``n`` steps from the family's seeded weights, the batches
    cycled as the window cycles them, dropout masks drawn from
    ``mask_stream``."""
    return follow_steps(
        cfg, cfg["optimizer"],
        weights.ernie_weights(cfg, int(cfg["seq_len"]), seed),
        [batches[t % len(batches)] for t in range(n)],
        weights.seed_key(seed, mask_stream), precision,
        int(cfg["check"].get("row_block", 8)))


def matrix_leaves(cfg: dict) -> list:
    """The leaves with two dimensions: sums over enough elements for a
    norm to be steady under another draw of the dropout masks."""
    shapes = weights.ernie_shapes(cfg, int(cfg["seq_len"]))
    return sorted(n for n, (shape, _) in shapes.items() if len(shape) == 2)
