"""Decoder whose layer holds TWO latent-attention sub-layers, two dense
SwiGLU blocks and one SHORTCUT expert block (``model_type``
``longcat_flash``).  Config keys keep their published names
(``num_layers``, ``ffn_hidden_size``, ``expert_ffn_hidden_size``,
``moe_topk``, ``zero_expert_num``).

One layer, ``x`` the residual stream and ``N`` an RMSNorm with its own
weight::

    u  = x + MLA_a(N_a,in(x))
    y  = N_a,post(u)
    s  = Experts(y)              # the shortcut: read here, added at the end
    v  = u + MLP_a(y)
    w  = v + MLA_b(N_b,in(v))
    z  = N_b,post(w)
    x' = w + MLP_b(z) + s

so a deployment may run the experts' exchange across chips while a whole
attention and a dense block compute; on one chip the block runs where
``y`` is live and its result waits.  Parameter names are the published
module's (``self_attn.0``, ``mlps.1``, ``input_layernorm.0`` ...).

Attention is models/latent_moe.py's ``LatentAttention`` (same cached
row, same kernels) with both latents scaled behind their norms:
``mla_scale_q_lora`` multiplies the normed query latent by
``sqrt(hidden / q_lora_rank)``, ``mla_scale_kv_lora`` the normed
key/value latent by ``sqrt(hidden / kv_lora_rank)``; the rotary key is
not scaled.  The cached row is the SCALED latent ‖ the rotated key, so
the absorbed products are as they were.  Plain rotary embedding
(``rope_theta``), interleaved lane pairs.

Experts.  ``p = softmax(W_r y)`` over ``n_routed_experts_published +
zero_expert_num`` outputs in float32; the ``moe_topk`` largest of ``p +
e_score_correction_bias`` are chosen; a chosen output weighs
``routed_scaling_factor x p`` (the uncorrected score, NOT renormalised).
Outputs ``n_routed_experts_published ..`` are identity experts: their
assignments add ``(sum of their weights) x y`` and compute nothing
(serving/moe/dropless.py).  ``n_routed_experts`` is the number of
experts HELD here (``experts_held_first`` on), as in latent_moe.py.

Cache.  Each attention sub-layer has its own latent pool: the layout has
``2 x num_layers`` entries (inference/cache_layout.py), sub-layer ``j``
of layer ``i`` at ``2 i + j``, all under one block table.
"""
from __future__ import annotations

import jax

from ..nn.layer import Layer
from ..nn.layers_common import LayerList, RMSNorm
from ..parallel.mp_layers import (ColumnParallelLinear,
                                  VocabParallelEmbedding)
from .latent_moe import LatentAttention
from .llama import LlamaMLP
from .pretrained import PretrainedMixin
from .transformer_block import take_head_rows

SUB_LAYERS = 2


class LongcatFlashConfig:
    def __init__(self, vocab_size=131072, hidden_size=6144,
                 ffn_hidden_size=12288, expert_ffn_hidden_size=2048,
                 num_layers=28, num_attention_heads=64, q_lora_rank=1536,
                 kv_lora_rank=512, qk_nope_head_dim=128,
                 qk_rope_head_dim=64, v_head_dim=128,
                 mla_scale_q_lora=True, mla_scale_kv_lora=True,
                 n_routed_experts=512, n_routed_experts_published=None,
                 experts_held_first=0, zero_expert_num=256,
                 zero_expert_type="identity", moe_topk=12,
                 routed_scaling_factor=6.0, attention_method="MLA",
                 attention_bias=False, router_bias=False,
                 hidden_act="silu", tie_word_embeddings=False,
                 max_position_embeddings=131072, rms_norm_eps=1e-5,
                 rope_theta=10000000.0, rope_scaling=None,
                 initializer_range=0.02, **extra):
        if zero_expert_num and zero_expert_type != "identity":
            raise NotImplementedError(
                f"zero_expert_type={zero_expert_type!r}: the "
                "zero-computation experts built are \"identity\" (an "
                "assignment adds its weight times the block's input)")
        refused = {"attention_method": (attention_method, "MLA"),
                   "attention_bias": (bool(attention_bias), False),
                   "router_bias": (bool(router_bias), False),
                   "hidden_act": (hidden_act, "silu"),
                   "tie_word_embeddings": (bool(tie_word_embeddings), False),
                   "rope_scaling": (rope_scaling or None, None)}
        for key, (got, built) in refused.items():
            if got != built:
                raise NotImplementedError(
                    f"{key}={got!r}: this decoder is built for "
                    f"{key}={built!r}")
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.ffn_hidden_size = ffn_hidden_size
        self.expert_ffn_hidden_size = expert_ffn_hidden_size
        self.num_layers = int(num_layers)
        self.num_attention_heads = num_attention_heads
        self.q_lora_rank, self.kv_lora_rank = q_lora_rank, kv_lora_rank
        self.qk_nope_head_dim = qk_nope_head_dim
        self.qk_rope_head_dim = qk_rope_head_dim
        self.v_head_dim = v_head_dim
        self.mla_scale_q_lora = bool(mla_scale_q_lora)
        self.mla_scale_kv_lora = bool(mla_scale_kv_lora)
        self.n_routed_experts = n_routed_experts
        self.n_routed_experts_published = (n_routed_experts_published
                                           or n_routed_experts)
        self.experts_held_first = experts_held_first
        self.zero_expert_num = int(zero_expert_num or 0)
        self.zero_expert_type = zero_expert_type
        self.moe_topk = moe_topk
        self.routed_scaling_factor = routed_scaling_factor
        self.attention_method = attention_method
        self.attention_bias, self.router_bias = False, False
        self.hidden_act = hidden_act
        self.tie_word_embeddings = False
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.rope_scaling = None
        self.index_topk = 0           # LatentAttention: no indexer
        self.initializer_range = initializer_range
        for k, v in extra.items():
            setattr(self, k, v)


class LongcatFlashDecoderLayer(Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        from ..serving.moe.dropless import DroplessMoE

        norm = lambda: RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)
        subs = range(SUB_LAYERS)
        self.input_layernorm = LayerList([norm() for _ in subs])
        self.self_attn = LayerList([LatentAttention(cfg) for _ in subs])
        self.post_attention_layernorm = LayerList([norm() for _ in subs])
        self.mlps = LayerList([
            LlamaMLP(cfg.hidden_size, cfg.ffn_hidden_size) for _ in subs])
        self.mlp = DroplessMoE(
            cfg.hidden_size, cfg.expert_ffn_hidden_size,
            n_published=cfg.n_routed_experts_published,
            top_k=cfg.moe_topk, held_first=cfg.experts_held_first,
            held_count=cfg.n_routed_experts,
            routed_scale=cfg.routed_scaling_factor,
            init_std=cfg.initializer_range, score_bias=True,
            identity_experts=cfg.zero_expert_num, scoring="softmax",
            renormalise=False)

    def forward(self, x, caches=None, position_ids=None):
        """``caches``: the two sub-layers' cache tuples, or None."""
        new_caches = []
        for j in range(SUB_LAYERS):
            h = self.self_attn[j](
                self.input_layernorm[j](x), position_ids=position_ids,
                cache=None if caches is None else caches[j])
            if caches is not None:
                h, c = h
                new_caches.append(c)
            x = x + h
            y = self.post_attention_layernorm[j](x)
            if j == 0:
                shortcut = self.mlp(y)
            with jax.named_scope("ffn"):
                x = x + self.mlps[j](y)
        x = x + shortcut
        return (x, new_caches) if caches is not None else x


class LongcatFlashModel(Layer):
    def __init__(self, cfg: LongcatFlashConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = VocabParallelEmbedding(cfg.vocab_size,
                                                   cfg.hidden_size)
        self.layers = LayerList([LongcatFlashDecoderLayer(cfg)
                                 for _ in range(cfg.num_layers)])
        self.norm = RMSNorm(cfg.hidden_size, epsilon=cfg.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                head_rows=None):
        x = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, position_ids=position_ids, caches=caches[
                    SUB_LAYERS * i:SUB_LAYERS * (i + 1)])
                new_caches.extend(c)
            else:
                x = layer(x, position_ids=position_ids)
        x = self.norm(take_head_rows(x, head_rows))
        return (x, new_caches) if caches is not None else x


class LongcatFlashForCausalLM(PretrainedMixin, Layer):
    """Untied head.  Served through ``serving.EngineCore``'s mixed step
    as models/latent_moe.py's decoder is: a ``latent`` cache entry a
    sub-layer, ``(pages, tables, context_lens, query_lens,
    scratch_page)`` each."""

    config_class = LongcatFlashConfig

    def __init__(self, config: LongcatFlashConfig):
        super().__init__()
        self.model = LongcatFlashModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False)
        self.config = config

    def cache_layout(self):
        from ..inference.cache_layout import LayerCache

        cfg = self.config
        width = cfg.kv_lora_rank + cfg.qk_rope_head_dim
        return [LayerCache.latent(width, part=j)
                for _ in range(cfg.num_layers) for j in range(SUB_LAYERS)]

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
