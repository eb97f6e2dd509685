"""Decoder with multi-head LATENT attention and sigmoid top-k experts
beside a shared expert (the DeepSeek-V3-style family; ``model_type``
``axk1`` among them).  Config keys keep their published names.

Attention (every layer).  ``c_q = RMS(x W_qa)``; ``q = c_q W_qb`` -> heads
x (``qk_nope_head_dim`` no-position ‖ ``qk_rope_head_dim`` rotary).
``[c_kv ‖ k_pe] = x W_kva``; ``c_kv = RMS(c_kv)``; ``k_pe = rope(k_pe)``,
one vector shared by all heads.  ``[k_nope ‖ v] = c_kv W_kvb``.  What is
cached is ``[c_kv ‖ k_pe]`` after the norm and the rotation: one row of
``kv_lora_rank + qk_rope_head_dim`` numbers a token a layer
(inference/cache_layout.py ``latent``).

Two forms of the same attention:

* expanded (no cache: the eager forward) — keys and values per head from
  ``W_kvb``, causal softmax over the sequence;
* absorbed (the serving path) — ``q_lat = q_nope W_kvb^K[h]^T``, scores
  against the cached rows, ``o = (softmax · c_kv) W_kvb^V[h]``
  (ops/pallas/latent_attention.py), so per-head keys and values are
  never materialised.

Rotary embedding is YaRN as the family's published code has it: inverse
frequencies blended between interpolated and original by a linear ramp
between the two correction dims; interleaved lane pairs are
de-interleaved before the rotate-half rotation (queries and keys alike,
so scores are those of the interleaved convention); the softmax scale
carries ``yarn_mscale(factor, mscale_all_dim)`` squared.

FFN.  The first ``first_k_dense_replace`` layers are a SwiGLU MLP
(``LlamaMLP``); the others route each token over all published experts
by sigmoid score, take the top ``num_experts_per_tok``, renormalise and
scale by ``routed_scaling_factor`` (serving/moe/dropless.py), and add a
shared expert.  ``n_routed_experts`` is the number of experts HELD here
(``experts_held_first`` on); ``n_routed_experts_published`` the router's
width.  ``topk_method`` "none" is read as no group restriction and no
score-correction bias; "noaux_tc" with ``n_group == topk_group == 1``
(the group step keeps everything) adds the score-correction bias that
decides which experts are chosen and not their weights; grouped routing
is refused.

Residual path.  ``hc_mult = n > 1`` (``model_type`` ``xing4_0``) carries
``n`` residual streams ``[.., n, hidden]`` mixed by manifold-constrained
hyper-connections (nn/hyper_connections.py): the embedding fans out to
``n`` equal streams, each layer's two sub-layers read a learned mix of
them and write back through a doubly-stochastic carry, and the final
norm reads their sum.  With ``hc_mult`` absent or 1 the residual is the
plain ``x + F(norm(x))`` and the program built is the same as before the
key existed.  ``num_nextn_predict_layers`` (a multi-token-prediction
module behind the last layer) is carried and not built: the main model's
logits do not depend on it.

Indexer.  ``index_topk`` (``model_type`` ``glm_moe_dsa``; the sparse
attention published with DeepSeek-V3.2-Exp) gives every layer a learned
indexer beside its attention: ``index_n_heads`` queries of
``index_head_dim`` from the normed query latent (``wq_b``), ONE key a
token for all of them from the layer's normed input (``wk`` and a
LayerNorm with weight and bias), the layer's rotary embedding on the
first ``qk_rope_head_dim`` lanes of both, and a weight a head from the
input (``weights_proj``).  A query's score for a cached token is ``sum_j
w_j relu(q_j . k) / sqrt(heads x dim)``; attention reads only the
``index_topk`` best-scored tokens at or before the query, ties to the
earlier token (ops/pallas/sparse_latent_attention.py), so with
``position < index_topk`` the layer is the dense one.  The index keys
are cached beside the latent rows, in a second pool under the same
block table (inference/cache_layout.py).  A model without the key
builds none of this and compiles what it compiled.  The deployment's
Hadamard rotation of index queries and keys (which no dot product sees)
and their fp8 storage are not built.  ``rope_parameters`` (plain rotary:
``rope_theta`` and no scaling) is read beside ``rope_scaling``;
``qk_nope_head_dim`` and ``v_head_dim`` may differ.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn.hyper_connections import (HyperConnection, expand_streams,
                                    merge_streams)
from ..nn.layer import Layer
from ..nn.layers_common import LayerList, RMSNorm
from ..parallel.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding)
from .llama import LlamaMLP
from .pretrained import PretrainedMixin
from .transformer_block import take_head_rows


class LatentMoEConfig:
    def __init__(self, vocab_size=163840, hidden_size=7168,
                 num_hidden_layers=61, num_attention_heads=64,
                 q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 intermediate_size=18432, moe_intermediate_size=2048,
                 first_k_dense_replace=1, n_routed_experts=192,
                 n_routed_experts_published=None, experts_held_first=0,
                 n_shared_experts=1, num_experts_per_tok=8,
                 routed_scaling_factor=2.5, scoring_func="sigmoid",
                 norm_topk_prob=True, topk_method="none",
                 max_position_embeddings=131072, rms_norm_eps=1e-6,
                 rope_theta=10000.0, rope_scaling=None,
                 initializer_range=0.02, n_group=None, topk_group=None,
                 hc_mult=1, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30.0, mhc_h_res_clamp_max=30.0,
                 rope_parameters=None, index_topk=None, index_n_heads=None,
                 index_head_dim=None, indexer_rope_interleave=True,
                 **extra):
        if scoring_func != "sigmoid" or not norm_topk_prob:
            raise NotImplementedError(
                "this family's expert layer scores by sigmoid and "
                "renormalises the chosen; got scoring_func="
                f"{scoring_func!r}, norm_topk_prob={norm_topk_prob!r}. "
                "serving.moe.dropless.DroplessMoE takes scoring="
                "\"softmax\" and renormalise=False; the decoder built on "
                "them is models.longcat_flash.LongcatFlashForCausalLM")
        if topk_method not in ("none", "noaux_tc"):
            raise NotImplementedError(
                f"topk_method={topk_method!r}: only plain top-k over all "
                "sigmoid scores (\"none\") and the score-correction bias "
                "of one group (\"noaux_tc\") are built")
        if topk_method == "noaux_tc" and not (
                (n_group or 1) == 1 and (topk_group or 1) == 1):
            raise NotImplementedError(
                f"topk_method='noaux_tc' with n_group={n_group!r}, "
                f"topk_group={topk_group!r}: grouped routing is not built; "
                "the score-correction bias is, for n_group == topk_group "
                "== 1")
        if rope_parameters:
            kind = rope_parameters.get("rope_type", "default")
            if kind != "default":
                raise NotImplementedError(
                    f"rope_parameters.rope_type={kind!r}: plain rotary "
                    "embedding (\"default\") is read from rope_parameters; "
                    "YaRN is stated through rope_scaling")
            rope_theta = rope_parameters.get("rope_theta", rope_theta)
        if index_topk and not (index_n_heads and index_head_dim):
            raise ValueError(
                "index_topk needs index_n_heads and index_head_dim: the "
                "indexer's own widths")
        if index_topk and not indexer_rope_interleave:
            raise NotImplementedError(
                "indexer_rope_interleave=False: the indexer's rotary "
                "lanes are paired as the attention's are (interleaved)")
        if index_topk and int(hc_mult or 1) > 1:
            raise NotImplementedError(
                "an indexer beside hyper-connected residual streams is "
                "not built")
        if index_topk and index_head_dim < qk_rope_head_dim:
            raise ValueError(
                f"index_head_dim={index_head_dim} is narrower than the "
                f"{qk_rope_head_dim} rotary lanes it carries")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank = q_lora_rank
        self.kv_lora_rank = kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.first_k_dense_replace = first_k_dense_replace
        self.n_routed_experts = n_routed_experts
        self.n_routed_experts_published = (n_routed_experts_published
                                           or n_routed_experts)
        self.experts_held_first = experts_held_first
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.routed_scaling_factor = routed_scaling_factor
        self.scoring_func = scoring_func
        self.norm_topk_prob = norm_topk_prob
        self.topk_method = topk_method
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = rope_scaling
        self.rope_parameters = rope_parameters
        self.index_topk = int(index_topk or 0)
        self.index_n_heads = int(index_n_heads or 0)
        self.index_head_dim = int(index_head_dim or 0)
        self.indexer_rope_interleave = indexer_rope_interleave
        self.initializer_range = initializer_range
        self.n_group, self.topk_group = n_group, topk_group
        self.hc_mult = int(hc_mult or 1)
        self.hc_sinkhorn_iters = hc_sinkhorn_iters
        self.hc_eps = hc_eps
        self.mhc_h_res_clamp_min = mhc_h_res_clamp_min
        self.mhc_h_res_clamp_max = mhc_h_res_clamp_max
        for k, v in extra.items():
            setattr(self, k, v)


# -------------------------------------------------------------------- yarn

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling):
    """The ``dim / 2`` inverse frequencies (a Python list of floats)."""
    base = [theta ** (-2.0 * i / dim) for i in range(dim // 2)]
    if not scaling:
        return base
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(correction_dim(float(scaling["beta_slow"]))),
               dim - 1)
    if low == high:
        high += 0.001
    out = []
    for i, f in enumerate(base):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        # ramp 0: the original frequency; ramp 1: interpolated
        out.append(f * (1.0 - ramp) + f / factor * ramp)
    return out


def attention_scale(cfg) -> float:
    scale = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    sc = cfg.rope_scaling
    if sc and sc.get("mscale_all_dim"):
        scale *= yarn_mscale(float(sc["factor"]),
                             float(sc["mscale_all_dim"])) ** 2
    return scale


def _rope(x, positions, inv_freq, mscale):
    """x [..., d] with interleaved lane pairs, positions broadcastable to
    x's leading dims: de-interleave, then rotate-half."""
    d = x.shape[-1]
    x = x.astype(jnp.float32)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1) * mscale
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1) * mscale
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


# --------------------------------------------------------------- attention

class Indexer(Layer):
    """The learned index of one layer: which cached tokens a query's
    attention reads.  Parameter names are the published module's."""

    LN_EPS = 1e-6

    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        from ..nn.layers_common import LayerNorm

        self.heads, self.dim = cfg.index_n_heads, cfg.index_head_dim
        self.rope_dim, self.topk = cfg.qk_rope_head_dim, cfg.index_topk
        lin = lambda i, o: ColumnParallelLinear(i, o, has_bias=False,
                                                gather_output=True)
        self.wq_b = lin(cfg.q_lora_rank, self.heads * self.dim)
        self.wk = lin(cfg.hidden_size, self.dim)
        self.k_norm = LayerNorm(self.dim, epsilon=self.LN_EPS)
        self.weights_proj = lin(cfg.hidden_size, self.heads)
        # the published constants: positive, so no order depends on them
        self.scale = self.heads ** -0.5 * self.dim ** -0.5

    def _rotate(self, v, positions, rope):
        """Rotary embedding on the first ``rope_dim`` lanes."""
        inv_freq, mscale = rope
        r = _rope(v[..., :self.rope_dim], positions, inv_freq, mscale)
        return jnp.concatenate([r.astype(v.dtype), v[..., self.rope_dim:]],
                               axis=-1)

    def queries(self, x, c_q, positions, rope):
        """-> q_idx [b, s, Hi, dim] (rotated), w_idx [b, s, Hi] float32
        with the constants folded in."""
        b, s = x.shape[0], x.shape[1]
        q = self.wq_b(c_q)._data.reshape(b, s, self.heads, self.dim)
        q = self._rotate(q, positions[:, :, None], rope)
        w = self.weights_proj(x)._data.astype(jnp.float32) * self.scale
        return q, w

    def keys(self, x, positions, rope):
        """-> k_idx [b, s, dim] (normed, rotated), as cached."""
        return self._rotate(self.k_norm(self.wk(x))._data, positions, rope)


class LatentAttention(Layer):
    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        h, heads = cfg.hidden_size, cfg.num_attention_heads
        self.heads = heads
        self.nope, self.rope_dim = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        self.v_dim, self.rank = cfg.v_head_dim, cfg.kv_lora_rank
        lin = lambda i, o: ColumnParallelLinear(i, o, has_bias=False,
                                                gather_output=True)
        self.q_a_proj = lin(h, cfg.q_lora_rank)
        self.q_a_layernorm = RMSNorm(cfg.q_lora_rank,
                                     epsilon=cfg.rms_norm_eps)
        self.q_b_proj = lin(cfg.q_lora_rank,
                            heads * (self.nope + self.rope_dim))
        self.kv_a_proj_with_mqa = lin(h, self.rank + self.rope_dim)
        self.kv_a_layernorm = RMSNorm(self.rank, epsilon=cfg.rms_norm_eps)
        self.kv_b_proj = lin(self.rank, heads * (self.nope + self.v_dim))
        self.o_proj = RowParallelLinear(heads * self.v_dim, h,
                                        has_bias=False)
        self.scale = attention_scale(cfg)
        sc = cfg.rope_scaling
        self.inv_freq = jnp.asarray(
            yarn_inv_freq(self.rope_dim, float(cfg.rope_theta), sc),
            jnp.float32)
        self.rope_mscale = 1.0 if not sc else (
            yarn_mscale(float(sc["factor"]), float(sc.get("mscale", 1)))
            / yarn_mscale(float(sc["factor"]),
                          float(sc.get("mscale_all_dim", 0))))
        self.indexer = Indexer(cfg) if cfg.index_topk else None
        # ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: a latent times
        # sqrt(hidden / its rank) behind its norm (the rotary key is not
        # scaled); None where the config has no such key
        scaled = lambda key, rank: (
            (h / rank) ** 0.5 if getattr(cfg, key, False) else None)
        self.q_latent_scale = scaled("mla_scale_q_lora", cfg.q_lora_rank)
        self.kv_latent_scale = scaled("mla_scale_kv_lora", self.rank)

    def _query_latent(self, x):
        c_q = self.q_a_layernorm(self.q_a_proj(x))
        if self.q_latent_scale is not None:
            c_q = Tensor(c_q._data * self.q_latent_scale)
        return c_q

    def _queries(self, c_q, positions):
        """The normed query latent -> q_nope [b, s, H, nope], q_pe
        [b, s, H, rope] (rotated)."""
        b, s = c_q.shape[0], c_q.shape[1]
        q = self.q_b_proj(c_q)._data
        # keep the head split out of the projection: over the mixed
        # step's few flat tokens the TPU compiler otherwise computes the
        # product head-major and transposes the whole weight every step
        q = jax.lax.optimization_barrier(q)
        q = q.reshape(b, s, self.heads, self.nope + self.rope_dim)
        q_pe = _rope(q[..., self.nope:], positions[:, :, None],
                     self.inv_freq, self.rope_mscale).astype(q.dtype)
        return q[..., :self.nope], q_pe

    def _absorbed_queries(self, c_q, positions, wk):
        """-> [b, s, H, rank + rope]: queries in the latent space (the
        no-position part through ``W^K``) ‖ their rotated position part."""
        q_nope, q_pe = self._queries(c_q, positions)
        q_lat = jnp.einsum("bshd,chd->bshc", q_nope, wk,
                           preferred_element_type=jnp.float32)
        return jnp.concatenate([q_lat.astype(q_pe.dtype), q_pe], -1)

    def _latent_rows(self, x, positions):
        """-> [b, s, rank + rope]: the normed latent ‖ the rotated key
        position part, as cached."""
        ckv = self.kv_a_proj_with_mqa(x)._data
        c_kv = self.kv_a_layernorm(Tensor(ckv[..., :self.rank]))._data
        if self.kv_latent_scale is not None:
            c_kv = c_kv * self.kv_latent_scale
        k_pe = _rope(ckv[..., self.rank:], positions, self.inv_freq,
                     self.rope_mscale).astype(ckv.dtype)
        return jnp.concatenate([c_kv, k_pe], axis=-1)

    def _w_kvb(self):
        """(W^K [rank, H, nope], W^V [rank, H, v])."""
        w = self.kv_b_proj.weight._data.reshape(
            self.rank, self.heads, self.nope + self.v_dim)
        return w[..., :self.nope], w[..., self.nope:]

    def forward(self, x, cache=None, position_ids=None):
        b, s = x.shape[0], x.shape[1]
        if position_ids is None:
            base = 0 if cache is None else cache[2]._data[:, None]
            positions = base + jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32)[None], (b, s))
        else:
            positions = position_ids._data
        if cache is None:
            return self._forward_expanded(x, positions)
        from ..ops.pallas import latent_attention as LA
        from ..ops.pallas.ragged_paged_attention import (ragged_rows,
                                                         rows_from_flat)

        # the mixed step's flat token axis: x [1, T, hidden], rows end to
        # end; tables, ctx and qlens stay per row
        if self.indexer is not None:
            return self._forward_selected(x, positions, cache)
        pages, tables, ctx, qlens, scratch = cache
        wk, wv = self._w_kvb()
        with jax.named_scope("mla_q_proj"):
            q_abs = self._absorbed_queries(self._query_latent(x), positions,
                                           wk)
        with jax.named_scope("mla_kv_proj"):
            rows = self._latent_rows(x, positions)
        with jax.named_scope("latent_write"):
            starts = ragged_rows(qlens._data, s)[0]
            pool = LA.write_latent_pages(
                pages._data, tables._data,
                rows_from_flat(rows[0], starts, s), ctx._data, qlens._data)
        # scoped "latent_attention" inside (its Pallas call keeps its name)
        o_lat = LA.latent_ragged_attention(
            q_abs[0], pool, tables._data, ctx._data, qlens._data,
            self.scale, self.rank)[None]
        return self._project_out(x, o_lat, wv), (
            Tensor(pool), tables, Tensor(ctx._data + qlens._data), qlens,
            scratch)

    def _project_out(self, x, o_lat, wv):
        b, s = x.shape[0], x.shape[1]
        with jax.named_scope("attn_out"):
            o = jnp.einsum("bshc,chd->bshd", o_lat, wv,
                           preferred_element_type=jnp.float32)
            o = Tensor(o.astype(x._data.dtype).reshape(
                b, s, self.heads * self.v_dim))
            return self.o_proj(o)

    def _forward_selected(self, x, positions, cache):
        """The serving path of a layer with an indexer: the cache tuple
        carries the index-key pool behind the latent one, both are
        written, and attention reads each query's selection."""
        from ..ops.pallas import latent_attention as LA
        from ..ops.pallas import sparse_latent_attention as SA
        from ..ops.pallas.ragged_paged_attention import (ragged_rows,
                                                         rows_from_flat)

        s = x.shape[1]
        pages, index_pages, tables, ctx, qlens, scratch = cache
        wk, wv = self._w_kvb()
        rope = (self.inv_freq, self.rope_mscale)
        with jax.named_scope("mla_q_proj"):
            c_q = self._query_latent(x)
            q_abs = self._absorbed_queries(c_q, positions, wk)
        with jax.named_scope("dsa_index_proj"):
            q_idx, w_idx = self.indexer.queries(x, c_q, positions, rope)
            k_idx = self.indexer.keys(x, positions, rope)
        with jax.named_scope("mla_kv_proj"):
            rows = self._latent_rows(x, positions)
        with jax.named_scope("latent_write"):
            starts = ragged_rows(qlens._data, s)[0]
            pool = LA.write_latent_pages(
                pages._data, tables._data,
                rows_from_flat(rows[0], starts, s), ctx._data, qlens._data)
            index_pool = LA.write_latent_pages(
                index_pages._data, tables._data,
                rows_from_flat(k_idx[0], starts, s), ctx._data, qlens._data)
        o_lat = SA.dsa_ragged_attention(
            q_abs[0], q_idx[0], w_idx[0], pool, index_pool, tables._data,
            ctx._data, qlens._data, self.scale, self.rank,
            self.indexer.topk)[None]
        return self._project_out(x, o_lat, wv), (
            Tensor(pool), Tensor(index_pool), tables,
            Tensor(ctx._data + qlens._data), qlens, scratch)

    def _forward_expanded(self, x, positions):
        """Causal self-attention over the sequence with per-head keys and
        values (no cache); with an indexer, each query over its selection
        (a dense mask: the eager forward is for small sizes)."""
        b, s = x.shape[0], x.shape[1]
        c_q = self._query_latent(x)
        q_nope, q_pe = self._queries(c_q, positions)
        rows = self._latent_rows(x, positions)
        kv = self.kv_b_proj(Tensor(rows[..., :self.rank]))._data.reshape(
            b, s, self.heads, self.nope + self.v_dim)
        k_pe = jnp.broadcast_to(rows[:, :, None, self.rank:],
                                (b, s, self.heads, self.rope_dim))
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate([kv[..., :self.nope], k_pe], -1)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * self.scale
        keep = positions[:, :, None] >= positions[:, None, :]    # [b, q, k]
        if self.indexer is not None:
            from ..ops.pallas.sparse_latent_attention import selection_mask

            rope = (self.inv_freq, self.rope_mscale)
            q_idx, w_idx = self.indexer.queries(x, c_q, positions, rope)
            k_idx = self.indexer.keys(x, positions, rope)
            isc = jnp.einsum("bqhd,bkd->bqhk", q_idx, k_idx,
                             preferred_element_type=jnp.float32)
            isc = jnp.sum(jnp.maximum(isc, 0.0) * w_idx[..., None], axis=2)
            isc = jnp.where(keep, isc, -jnp.inf)
            keep = jax.vmap(lambda a, v: selection_mask(
                a, v, self.indexer.topk))(isc, keep)
        p = jax.nn.softmax(jnp.where(keep[:, None], sc, -1e30), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(kv.dtype),
                       kv[..., self.nope:],
                       preferred_element_type=jnp.float32)
        o = Tensor(o.astype(x._data.dtype).reshape(
            b, s, self.heads * self.v_dim))
        return self.o_proj(o)


# --------------------------------------------------------------------- ffn

class SharedExpertMoE(Layer):
    """Routed experts held here + the shared expert(s), summed."""

    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        from ..serving.moe.dropless import DroplessMoE

        self.experts = DroplessMoE(
            cfg.hidden_size, cfg.moe_intermediate_size,
            n_published=cfg.n_routed_experts_published,
            top_k=cfg.num_experts_per_tok,
            held_first=cfg.experts_held_first,
            held_count=cfg.n_routed_experts,
            routed_scale=cfg.routed_scaling_factor,
            init_std=cfg.initializer_range,
            score_bias=cfg.topk_method == "noaux_tc")
        self.shared_experts = LlamaMLP(
            cfg.hidden_size,
            cfg.moe_intermediate_size * cfg.n_shared_experts)

    def forward(self, x):
        routed = self.experts(x)
        with jax.named_scope("moe_shared"):
            return routed + self.shared_experts(x)


class LatentMoEDecoderLayer(Layer):
    def __init__(self, cfg: LatentMoEConfig, index: int):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size,
                                       epsilon=cfg.rms_norm_eps)
        self.self_attn = LatentAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                epsilon=cfg.rms_norm_eps)
        self.dense = index < cfg.first_k_dense_replace
        self.mlp = (LlamaMLP(cfg.hidden_size, cfg.intermediate_size)
                    if self.dense else SharedExpertMoE(cfg))
        # hc_mult streams: one hyper-connection a sub-layer
        self.hc_attn = self.hc_ffn = None
        if cfg.hc_mult > 1:
            hc = lambda: HyperConnection(
                cfg.hidden_size, cfg.hc_mult, cfg.hc_sinkhorn_iters,
                cfg.hc_eps, cfg.rms_norm_eps, cfg.mhc_h_res_clamp_min,
                cfg.mhc_h_res_clamp_max, cfg.initializer_range)
            self.hc_attn, self.hc_ffn = hc(), hc()

    def forward(self, x, cache=None, position_ids=None):
        if self.hc_attn is not None:
            return self._forward_streams(x, cache, position_ids)
        h = self.self_attn(self.input_layernorm(x), cache=cache,
                           position_ids=position_ids)
        if cache is not None:
            h, new_cache = h
        x = x + h
        y = self.post_attention_layernorm(x)
        if self.dense:
            with jax.named_scope("ffn"):
                x = x + self.mlp(y)
        else:
            x = x + self.mlp(y)
        return (x, new_cache) if cache is not None else x

    def _forward_streams(self, x, cache, position_ids):
        """x [b, s, hc_mult, hidden]: each sub-layer reads a mix of the
        streams and writes back through its carry."""
        u, carry = self.hc_attn.read(x)
        h = self.self_attn(self.input_layernorm(u), cache=cache,
                           position_ids=position_ids)
        if cache is not None:
            h, new_cache = h
        x = self.hc_attn.write(carry, h)
        u, carry = self.hc_ffn.read(x)
        y = self.post_attention_layernorm(u)
        if self.dense:
            with jax.named_scope("ffn"):
                y = self.mlp(y)
        else:
            y = self.mlp(y)
        x = self.hc_ffn.write(carry, y)
        return (x, new_cache) if cache is not None else x


class LatentMoEModel(Layer):
    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        self.layers = LayerList([LatentMoEDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                head_rows=None):
        x = self.embed_tokens(input_ids)
        streams = self.config.hc_mult
        if streams > 1:
            x = Tensor(expand_streams(x._data, streams))
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, cache=caches[i], position_ids=position_ids)
                new_caches.append(c)
            else:
                x = layer(x, position_ids=position_ids)
        x = take_head_rows(x, head_rows)
        if streams > 1:
            x = Tensor(merge_streams(x._data))
        x = self.norm(x)
        return (x, new_caches) if caches is not None else x


class LatentMoEForCausalLM(PretrainedMixin, Layer):
    """Untied head.  Served through ``serving.EngineCore``'s mixed step:
    each layer's cache is the ``latent`` kind, a five-element tuple
    ``(pages [P, page, lanes], tables, context_lens, query_lens,
    scratch_page)`` per layer; a layer with an indexer carries its
    index-key pool ``[P, page, index_lanes]`` right behind ``pages``."""

    config_class = LatentMoEConfig

    def __init__(self, config: LatentMoEConfig):
        super().__init__()
        self.model = LatentMoEModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False)
        self.config = config

    def cache_layout(self):
        from ..inference.cache_layout import LayerCache

        cfg = self.config
        return [LayerCache.latent(
            cfg.kv_lora_rank + cfg.qk_rope_head_dim,
            index_width=cfg.index_head_dim if cfg.index_topk else 0)
                ] * cfg.num_hidden_layers

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None, head_rows=None):
        if attention_mask is not None:
            raise NotImplementedError(
                "the latent-attention decoder takes right-padded rows "
                "with per-row lengths, not an additive pad mask")
        out = self.model(input_ids, position_ids=position_ids,
                         caches=caches, head_rows=head_rows)
        with jax.named_scope("lm_head_sample"):
            if caches is not None:
                x, new_caches = out
                return self.lm_head(x), new_caches
            return self.lm_head(out)
