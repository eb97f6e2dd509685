"""Operations and bytes the attention of one serving step needs over a
paged K/V cache, from shapes alone: the attention terms of
``costs.llama_step_cost`` on their own (QK^T and PV over the query-key
pairs the step must compute; K and V of every cached and every new token
of the step's rows read once, at the published KV width)."""
from __future__ import annotations


def paged_attention_cost(cfg: dict, new_tokens: int, context_tokens: int,
                         kv_bytes_per_token_layer: int,
                         resident_tokens: int) -> dict:
    """Arguments as ``costs.llama_step_cost`` names them."""
    layers = cfg["num_hidden_layers"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    flops = 4 * cfg["num_attention_heads"] * d * context_tokens * layers
    nbytes = layers * kv_bytes_per_token_layer * (resident_tokens
                                                  + new_tokens)
    return {"flops": float(flops), "bytes": float(nbytes)}
