"""ERNIE/BERT-family encoder models — the framework's flagship train target
(BASELINE.md north star: ERNIE-3.0-base trained + served on TPU).

Reference architecture surface: the fork serves these through
`fused_multi_transformer_encoder_pass` graph fusion
(paddle/fluid/framework/ir/fused_multi_transformer_encoder_pass) over
standard paddle.nn.TransformerEncoder graphs; the Python-side model zoo
lives outside the reference repo (PaddleNLP), so the layer composition here
follows the standard ERNIE 3.0 configuration.

TPU-first: built from ParallelTransformerLayer blocks (TP specs dormant on
one chip), no data-dependent Python control flow, static shapes — the whole
forward traces into one XLA program for fleet/jit/inference.
"""
from __future__ import annotations

import math

import jax
import numpy as np

from ..core.dispatch import dispatch as D
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn.layer import Layer
from ..nn.layers_common import Dropout, LayerList, LayerNorm, Linear
from ..nn.layers_common import Embedding
from ..parallel.mp_layers import (ParallelCrossEntropy,
                                  VocabParallelEmbedding)
from .pretrained import PretrainedMixin
from .transformer_block import ParallelTransformerLayer

ERNIE_PRESETS = {
    # ERNIE 3.0 / BERT size ladder
    "ernie-3.0-nano": dict(hidden_size=312, num_hidden_layers=4,
                           num_attention_heads=12, intermediate_size=1248),
    "ernie-3.0-micro": dict(hidden_size=384, num_hidden_layers=4,
                            num_attention_heads=12, intermediate_size=1536),
    "ernie-3.0-mini": dict(hidden_size=384, num_hidden_layers=6,
                           num_attention_heads=12, intermediate_size=1536),
    "ernie-3.0-medium": dict(hidden_size=768, num_hidden_layers=6,
                             num_attention_heads=12, intermediate_size=3072),
    "ernie-3.0-base": dict(hidden_size=768, num_hidden_layers=12,
                           num_attention_heads=12, intermediate_size=3072),
    "ernie-3.0-xbase": dict(hidden_size=1024, num_hidden_layers=20,
                            num_attention_heads=16, intermediate_size=4096),
    "bert-base": dict(hidden_size=768, num_hidden_layers=12,
                      num_attention_heads=12, intermediate_size=3072,
                      vocab_size=30522),
    "bert-large": dict(hidden_size=1024, num_hidden_layers=24,
                       num_attention_heads=16, intermediate_size=4096,
                       vocab_size=30522),
    # BASELINE.md milestone #4 ladder (ERNIE-3.5 10B on v5p via TP+ZeRO;
    # the 1.3b rung is the largest size the CPU host can build for the
    # measured-scaling study, tools/scale_study.py -> docs/SCALE.md)
    "ernie-1.3b": dict(hidden_size=2048, num_hidden_layers=24,
                       num_attention_heads=32, intermediate_size=8192,
                       vocab_size=50176, max_position_embeddings=2048),
    "ernie-3.5-10b": dict(hidden_size=4096, num_hidden_layers=48,
                          num_attention_heads=32,
                          intermediate_size=16384, vocab_size=50176,
                          max_position_embeddings=2048),
}


class ErnieConfig:
    def __init__(self, vocab_size=40000, hidden_size=768,
                 num_hidden_layers=12, num_attention_heads=12,
                 intermediate_size=3072, hidden_act="gelu",
                 hidden_dropout_prob=0.1, attention_probs_dropout_prob=0.1,
                 max_position_embeddings=2048, type_vocab_size=4,
                 initializer_range=0.02, pad_token_id=0,
                 layer_norm_eps=1e-12, **extra):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.num_attention_heads = num_attention_heads
        self.intermediate_size = intermediate_size
        self.hidden_act = hidden_act
        self.hidden_dropout_prob = hidden_dropout_prob
        self.attention_probs_dropout_prob = attention_probs_dropout_prob
        self.max_position_embeddings = max_position_embeddings
        self.type_vocab_size = type_vocab_size
        self.initializer_range = initializer_range
        self.pad_token_id = pad_token_id
        self.layer_norm_eps = layer_norm_eps
        for k, v in extra.items():
            setattr(self, k, v)

    @classmethod
    def from_preset(cls, name: str, **overrides) -> "ErnieConfig":
        cfg = dict(ERNIE_PRESETS[name])
        cfg.update(overrides)
        return cls(**cfg)


class ErnieEmbeddings(Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.word_embeddings = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.token_type_embeddings = Embedding(
            config.type_vocab_size, config.hidden_size)
        self.layer_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_eps)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        b, s = input_ids.shape[0], input_ids.shape[1]
        emb = self.word_embeddings(input_ids)
        if position_ids is None:
            import jax.numpy as jnp

            position_ids = Tensor(jnp.arange(s, dtype=jnp.int32))
            pos = self.position_embeddings(position_ids)
            pos = D("unsqueeze", pos, axis=0)
        else:
            pos = self.position_embeddings(position_ids)
        emb = emb + pos
        if token_type_ids is None:
            tok = self.token_type_embeddings.weight[0]
        else:
            tok = self.token_type_embeddings(token_type_ids)
        emb = emb + tok
        return self.dropout(self.layer_norm(emb))


class ErniePooler(Layer):
    def __init__(self, hidden_size):
        super().__init__()
        self.dense = Linear(hidden_size, hidden_size)

    def forward(self, hidden_states):
        first = D("slice", hidden_states, axes=(1,), starts=(0,), ends=(1,))
        first = D("squeeze", first, axis=1)
        return F.tanh(self.dense(first))


class ErnieModel(Layer):
    """Backbone: embeddings + N parallel transformer layers + pooler."""

    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.config = config
        self.embeddings = ErnieEmbeddings(config)
        self.layers = LayerList([
            ParallelTransformerLayer(
                config.hidden_size, config.num_attention_heads,
                config.intermediate_size,
                dropout=config.hidden_dropout_prob,
                attn_dropout=config.attention_probs_dropout_prob,
                activation=config.hidden_act, normalize_before=False,
                layer_norm_eps=config.layer_norm_eps)
            for _ in range(config.num_hidden_layers)])
        self.pooler = ErniePooler(config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        segment_ids = None
        if attention_mask is not None and attention_mask.ndim == 2:
            # [b, s] padding mask → segment ids (1 = real, 0 = pad; attend
            # iff equal), which keeps the Pallas flash kernels engaged —
            # a dense additive mask would force the O(s^2) XLA path
            segment_ids = D("cast", attention_mask, dtype="int32")
            attention_mask = None
        with jax.named_scope("embed"):
            x = self.embeddings(input_ids, token_type_ids, position_ids)
        for layer in self.layers:
            x = layer(x, attn_mask=attention_mask, segment_ids=segment_ids)
        pooled = self.pooler(x)
        return x, pooled


class ErnieMLMHead(Layer):
    """Transform + vocab projection tied to the word embedding
    (standard MLM head; logits sharded over "mp" like the embedding)."""

    def __init__(self, config: ErnieConfig, embedding_weights):
        super().__init__()
        self.transform = Linear(config.hidden_size, config.hidden_size)
        self.activation = getattr(F, config.hidden_act)
        self.layer_norm = LayerNorm(config.hidden_size,
                                    epsilon=config.layer_norm_eps)
        self._tied_weight = embedding_weights   # [vocab, hidden], mp-sharded
        from ..core.tensor import Parameter
        from ..nn import initializer as I

        self.decoder_bias = Parameter(
            I.Constant(0.0)((config.vocab_size,), "float32"))
        self.decoder_bias.dist_attr = ("mp",)

    def forward(self, hidden_states):
        with jax.named_scope("mlm_head_loss"):
            x = self.layer_norm(
                self.activation(self.transform(hidden_states)))
            logits = D("matmul", x, self._tied_weight, transpose_y=True)
            logits = logits + self.decoder_bias
            spec = ("data",) + (None,) * (logits.ndim - 2) + ("mp",)
            return D("sharding_constraint", logits, spec=spec)


class ErnieForMaskedLM(PretrainedMixin, Layer):
    config_class = ErnieConfig

    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.config = config
        self.ernie = ErnieModel(config)
        self.cls = ErnieMLMHead(config,
                                self.ernie.embeddings.word_embeddings.weight)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        seq, _ = self.ernie(input_ids, token_type_ids, position_ids,
                            attention_mask)
        return self.cls(seq)


class ErnieForPretraining(PretrainedMixin, Layer):
    """MLM + next-sentence/sop heads (BERT-style pretraining objective)."""

    config_class = ErnieConfig

    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.config = config
        self.ernie = ErnieModel(config)
        self.cls = ErnieMLMHead(config,
                                self.ernie.embeddings.word_embeddings.weight)
        self.nsp = Linear(config.hidden_size, 2)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        seq, pooled = self.ernie(input_ids, token_type_ids, position_ids,
                                 attention_mask)
        return self.cls(seq), self.nsp(pooled)


class ErnieForSequenceClassification(PretrainedMixin, Layer):
    config_class = ErnieConfig

    def __init__(self, config: ErnieConfig, num_classes=None):
        super().__init__()
        # num_classes rides on the config so from_pretrained round-trips
        # the head shape (the mixin rebuilds as cls(config))
        if num_classes is not None:
            config.num_classes = num_classes
        n_cls = getattr(config, "num_classes", 2)
        self.config = config
        self.ernie = ErnieModel(config)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.classifier = Linear(config.hidden_size, n_cls)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        _, pooled = self.ernie(input_ids, token_type_ids, position_ids,
                               attention_mask)
        return self.classifier(self.dropout(pooled))


def ernie_pretrain_loss(mlm_logits, nsp_logits, mlm_labels, nsp_labels,
                        ignore_index=-100):
    """Summed MLM + NSP loss with label masking (mean over valid tokens)."""
    from .losses import masked_lm_loss

    with jax.named_scope("mlm_head_loss"):
        mlm_loss = masked_lm_loss(mlm_logits, mlm_labels,
                                  ignore_index=ignore_index)
        nsp_loss = F.cross_entropy(nsp_logits, nsp_labels,
                                   reduction="mean")
        return mlm_loss + nsp_loss
