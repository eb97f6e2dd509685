"""GPT-family causal decoder — the serving-side flagship (the model shape the
fork's fused_multi_transformer decoder path exists for:
paddle/fluid/operators/fused/fused_multi_transformer_op.cu — per-layer
attention with CacheKV append + masked decode).

TPU-first: pre-LN ParallelTransformerLayer blocks with causal sdpa; decode
uses a static-shape KV cache written with dynamic_update_slice inside one
compiled step (inference/generation.py) instead of the reference's in-kernel
cache append.
"""
from __future__ import annotations

from ..core.dispatch import dispatch as D
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers_common import Dropout, Embedding, LayerList, LayerNorm
from ..parallel.mp_layers import VocabParallelEmbedding
from .pretrained import PretrainedMixin
from .transformer_block import ParallelTransformerLayer, take_head_rows

GPT_PRESETS = {
    "gpt2-small": dict(hidden_size=768, num_hidden_layers=12,
                       num_attention_heads=12, intermediate_size=3072,
                       vocab_size=50304, max_position_embeddings=1024),
    "gpt2-medium": dict(hidden_size=1024, num_hidden_layers=24,
                        num_attention_heads=16, intermediate_size=4096,
                        vocab_size=50304, max_position_embeddings=1024),
    "gpt2-large": dict(hidden_size=1280, num_hidden_layers=36,
                       num_attention_heads=20, intermediate_size=5120,
                       vocab_size=50304, max_position_embeddings=1024),
    "gpt3-1.3b": dict(hidden_size=2048, num_hidden_layers=24,
                      num_attention_heads=32, intermediate_size=8192,
                      vocab_size=50304, max_position_embeddings=2048),
    "gpt3-6.7b": dict(hidden_size=4096, num_hidden_layers=32,
                      num_attention_heads=32, intermediate_size=16384,
                      vocab_size=50304, max_position_embeddings=2048),
    "llama-7b": dict(hidden_size=4096, num_hidden_layers=32,
                     num_attention_heads=32, intermediate_size=11008,
                     vocab_size=32000, max_position_embeddings=4096,
                     hidden_act="silu"),
}


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=3072, hidden_act="gelu",
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 max_position_embeddings=1024, initializer_range=0.02,
                 layer_norm_eps=1e-5, **extra):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.max_position_embeddings = max_position_embeddings
        self.initializer_range = initializer_range
        self.layer_norm_eps = layer_norm_eps
        for k, v in extra.items():
            setattr(self, k, v)

    @classmethod
    def from_preset(cls, name: str, **overrides) -> "GPTConfig":
        cfg = dict(GPT_PRESETS[name])
        cfg.update(overrides)
        return cls(**cfg)


class GPTModel(Layer):
    """Backbone: word+pos embeddings, N pre-LN causal blocks, final LN."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.dropout = Dropout(config.hidden_dropout_prob)
        num_experts = getattr(config, "num_experts", 1)
        self.layers = LayerList([
            ParallelTransformerLayer(
                config.hidden_size, config.num_attention_heads,
                config.intermediate_size,
                dropout=config.hidden_dropout_prob,
                attn_dropout=config.attention_probs_dropout_prob,
                activation=config.hidden_act, normalize_before=True,
                causal=True, layer_norm_eps=config.layer_norm_eps,
                num_experts=num_experts,
                moe_gate=getattr(config, "moe_gate", "gshard"),
                moe_top_k=getattr(config, "moe_top_k", 2),
                moe_capacity_factor=getattr(config, "moe_capacity_factor",
                                            2.0))
            for _ in range(config.num_hidden_layers)])
        self.final_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_eps)

    def moe_aux_loss(self):
        """Sum of the per-layer MoE load-balance losses from the last
        forward (0 for dense models).  Valid in the same step that produced
        it — read it while building the loss; aux values left over from an
        earlier compiled program (e.g. a generate() call) are stale tracers
        and are skipped."""
        import jax

        from ..parallel.moe import MoELayer

        total = None
        for layer in self.layers:
            mlp = layer.mlp
            if isinstance(mlp, MoELayer) and mlp.l_aux is not None:
                try:
                    val = mlp.l_aux + 0.0   # touch: raises if stale
                except jax.errors.UnexpectedTracerError:
                    continue
                total = val if total is None else total + val
        if total is None:
            from ..core.tensor import Tensor
            import jax.numpy as jnp

            total = Tensor(jnp.zeros((), jnp.float32))
        return total

    def forward(self, input_ids, position_ids=None, attention_mask=None,
                caches=None, head_rows=None):
        b, s = input_ids.shape[0], input_ids.shape[1]
        x = self.word_embeddings(input_ids)
        if position_ids is None:
            import jax.numpy as jnp

            if caches and len(caches[0]) == 4:
                # paged cache: per-row positions [b] from the page cursor
                pos_rows = caches[0][3]
                arange = Tensor(jnp.arange(s, dtype=jnp.int32))
                position_ids = D("unsqueeze", pos_rows, axis=1) + arange
                pos = self.position_embeddings(position_ids)  # [b, s, H]
            else:
                if caches and len(caches[0]) == 3:
                    # static-cache decode: positions continue after the
                    # traced write index (inference/generation.py loop)
                    past = caches[0][2]
                    arange = Tensor(jnp.arange(s, dtype=jnp.int32))
                    position_ids = arange + past
                else:
                    # growing cache: positions continue after the cached
                    # prefix (cache [b, s_past, h, d], static under trace)
                    past = caches[0][0].shape[1] if caches else 0
                    position_ids = Tensor(
                        jnp.arange(past, past + s, dtype=jnp.int32))
                pos = D("unsqueeze", self.position_embeddings(position_ids),
                        axis=0)
        else:
            pos = self.position_embeddings(position_ids)
        x = self.dropout(x + pos)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, attn_mask=attention_mask, cache=caches[i])
                new_caches.append(c)
            else:
                x = layer(x, attn_mask=attention_mask)
        x = self.final_norm(take_head_rows(x, head_rows))
        if caches is not None:
            return x, new_caches
        return x


class GPTForCausalLM(PretrainedMixin, Layer):
    """LM head tied to the word embedding (vocab-sharded logits)."""

    config_class = GPTConfig

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def generate(self, input_ids, generation_config=None, attention_mask=None,
                 **kwargs):
        """Compiled KV-cache generation (inference/generation.py); the
        engine is built once and cached on the model."""
        from ..inference.generation import GenerationConfig, GenerationEngine

        if getattr(self, "_gen_engine", None) is None:
            self._gen_engine = GenerationEngine(self)
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
        if caches is not None:
            hidden, new_caches = self.gpt(input_ids, position_ids,
                                          attention_mask, caches,
                                          head_rows=head_rows)
        else:
            hidden = self.gpt(input_ids, position_ids, attention_mask)
        logits = D("matmul", hidden, self.gpt.word_embeddings.weight,
                   transpose_y=True)
        spec = ("data",) + (None,) * (logits.ndim - 2) + ("mp",)
        logits = D("sharding_constraint", logits, spec=spec)
        if caches is not None:
            return logits, new_caches
        return logits


def gpt_lm_loss(logits, labels, ignore_index=-100):
    """Shifted causal-LM loss: predict token t+1 from prefix ≤ t."""
    from .losses import masked_lm_loss

    s = logits.shape[1]
    shift_logits = D("slice", logits, axes=(1,), starts=(0,), ends=(s - 1,))
    shift_labels = D("slice", labels, axes=(1,), starts=(1,), ends=(s,))
    return masked_lm_loss(shift_logits, shift_labels,
                          ignore_index=ignore_index)
