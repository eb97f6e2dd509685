"""LLaMA-family decoder (BASELINE.md milestone #5: LLaMA-7B generation
with paged-KV Pallas attention).

Reference bar: the fork serves LLaMA through fused_multi_transformer with
rotary embeddings and CacheKV decode
(paddle/fluid/operators/fused/fused_multi_transformer_op.cc:103 cache
semantics; phi fused_rope kernel for the rotary application).

TPU-first: built from the shared tensor-parallel blocks —
ParallelSelfAttention with in-block RoPE (cache-position-aware: decode
steps rotate by the per-row page cursor, so one compiled program serves
every step) and optional GQA, RMSNorm (fused rms_norm op, ops/math.py),
SwiGLU MLP as Column→(silu·mul)→Row so the mp sharding needs no
collective inside the FFN.  Serves on both generation engines (static KV
and paged-KV Pallas decode) and under a serving mesh.
"""
from __future__ import annotations

import jax

from ..core.dispatch import dispatch as D
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers_common import LayerList, RMSNorm
from ..parallel.mp_layers import (ColumnParallelLinear, RowParallelLinear,
                                  VocabParallelEmbedding)
from .pretrained import PretrainedMixin
from .transformer_block import ParallelSelfAttention, take_head_rows

LLAMA_PRESETS = {
    # (hidden, layers, heads, kv_heads, ffn, vocab, max_pos, theta)
    "llama-7b": dict(hidden_size=4096, num_hidden_layers=32,
                     num_attention_heads=32, num_key_value_heads=32,
                     intermediate_size=11008, vocab_size=32000,
                     max_position_embeddings=4096, rope_theta=10000.0),
    "llama-13b": dict(hidden_size=5120, num_hidden_layers=40,
                      num_attention_heads=40, num_key_value_heads=40,
                      intermediate_size=13824, vocab_size=32000,
                      max_position_embeddings=4096, rope_theta=10000.0),
    "llama2-70b": dict(hidden_size=8192, num_hidden_layers=80,
                       num_attention_heads=64, num_key_value_heads=8,
                       intermediate_size=28672, vocab_size=32000,
                       max_position_embeddings=4096, rope_theta=10000.0),
    "llama3-8b": dict(hidden_size=4096, num_hidden_layers=32,
                      num_attention_heads=32, num_key_value_heads=8,
                      intermediate_size=14336, vocab_size=128256,
                      max_position_embeddings=8192, rope_theta=500000.0),
}


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096,
                 num_hidden_layers=32, num_attention_heads=32,
                 num_key_value_heads=None, intermediate_size=11008,
                 max_position_embeddings=4096, rms_norm_eps=1e-6,
                 rope_theta=10000.0, initializer_range=0.02, **extra):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.num_key_value_heads = num_key_value_heads or num_attention_heads
        self.intermediate_size = intermediate_size
        self.max_position_embeddings = max_position_embeddings
        self.rms_norm_eps = rms_norm_eps
        self.rope_theta = rope_theta
        self.initializer_range = initializer_range
        for k, v in extra.items():
            setattr(self, k, v)

    @classmethod
    def from_preset(cls, name: str, **overrides) -> "LlamaConfig":
        cfg = dict(LLAMA_PRESETS[name])
        cfg.update(overrides)
        return cls(**cfg)


class LlamaMLP(Layer):
    """SwiGLU FFN: down(silu(gate(x)) * up(x)) — gate/up column-sharded,
    down row-sharded (Megatron split: the elementwise silu·mul happens on
    the sharded ffn dim, no collective until the down projection)."""

    def __init__(self, hidden, ffn_hidden):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(hidden, ffn_hidden,
                                              has_bias=False,
                                              gather_output=False)
        self.up_proj = ColumnParallelLinear(hidden, ffn_hidden,
                                            has_bias=False,
                                            gather_output=False)
        self.down_proj = RowParallelLinear(ffn_hidden, hidden,
                                           has_bias=False,
                                           input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(D("multiply", F.silu(self.gate_proj(x)),
                                self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    """Pre-RMSNorm decoder block with rotary attention."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = ParallelSelfAttention(
            config.hidden_size, config.num_attention_heads, dropout=0.0,
            causal=True, rope_theta=config.rope_theta,
            num_kv_heads=config.num_key_value_heads)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config.hidden_size, config.intermediate_size)

    def forward(self, x, attn_mask=None, cache=None, position_ids=None):
        h = self.self_attn(self.input_layernorm(x), attn_mask=attn_mask,
                           cache=cache, position_ids=position_ids)
        if cache is not None:
            h, new_cache = h
        x = x + h
        with jax.named_scope("ffn"):
            x = x + self.mlp(self.post_attention_layernorm(x))
        if cache is not None:
            return x, new_cache
        return x


class LlamaModel(Layer):
    """Backbone: vocab-sharded embedding, N rotary decoder blocks, final
    RMSNorm.  No learned position table — positions enter only through
    RoPE inside attention (derived from the cache kind, so the engines'
    position_ids plumbing is optional)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                   config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size,
                            epsilon=config.rms_norm_eps)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None, head_rows=None):
        x = self.embed_tokens(input_ids)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, attn_mask=attention_mask, cache=caches[i],
                             position_ids=position_ids)
                new_caches.append(c)
            else:
                x = layer(x, attn_mask=attention_mask,
                          position_ids=position_ids)
        x = self.norm(take_head_rows(x, head_rows))
        if caches is not None:
            return x, new_caches
        return x


class LlamaForCausalLM(PretrainedMixin, Layer):
    """Untied LM head (LLaMA keeps lm_head separate from the embedding),
    column-sharded over the vocab so mp serving splits the logits."""

    config_class = LlamaConfig

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size,
                                            config.vocab_size,
                                            has_bias=False)
        self.config = config

    def generate(self, input_ids, generation_config=None,
                 attention_mask=None, **kwargs):
        from ..inference.generation import (GenerationConfig,
                                            PagedGenerationEngine)

        if getattr(self, "_gen_engine", None) is None:
            self._gen_engine = PagedGenerationEngine(self)
        if generation_config is None:
            generation_config = GenerationConfig(**kwargs) if kwargs \
                else None
        elif kwargs:
            import dataclasses

            generation_config = dataclasses.replace(generation_config,
                                                    **kwargs)
        return self._gen_engine.generate(input_ids, generation_config,
                                         attention_mask=attention_mask)

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None, head_rows=None):
        out = self.llama(input_ids, position_ids=position_ids,
                         attention_mask=attention_mask, caches=caches,
                         head_rows=head_rows)
        with jax.named_scope("lm_head_sample"):
            if caches is not None:
                x, new_caches = out
                return self.lm_head(x), new_caches
            return self.lm_head(out)


def llama_lm_loss(logits, labels, ignore_index=-100):
    """Shifted next-token cross entropy (reference PaddleNLP
    LlamaPretrainingCriterion)."""
    from .losses import masked_lm_loss

    s = logits.shape[1]
    shift_logits = D("slice", logits, axes=(1,), starts=(0,), ends=(s - 1,))
    shift_labels = D("slice", labels, axes=(1,), starts=(1,), ends=(s,))
    return masked_lm_loss(shift_logits, shift_labels,
                          ignore_index=ignore_index)
