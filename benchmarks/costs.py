"""Operations and bytes a step needs, computed from shapes alone.

These are the algorithm's needs, not the program's doings: padding,
recomputation and copies the program adds count as waste against the
roofline, not as work.
"""
from __future__ import annotations


def _llama_matmul_params_per_layer(cfg: dict) -> int:
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ffn = cfg["intermediate_size"]
    return h * (hq + 2 * hkv) * d + hq * d * h + 3 * h * ffn


def llama_weight_bytes(cfg: dict, bytes_per_param: int = 2) -> int:
    """Bytes of the weights one step has to read: every layer, the final
    head, and nothing of the embedding table but the rows looked up."""
    per_layer = _llama_matmul_params_per_layer(cfg) + 2 * cfg["hidden_size"]
    return bytes_per_param * (cfg["num_hidden_layers"] * per_layer
                              + cfg["hidden_size"] * cfg["vocab_size"])


def llama_step_cost(cfg: dict, new_tokens: int, sampled_rows: int,
                    context_tokens: int, kv_bytes_per_token_layer: int,
                    resident_tokens: int) -> dict:
    """One serving step over ``new_tokens`` real query tokens.

    ``context_tokens``: sum over the query tokens of the keys each attends
    to.  ``resident_tokens``: cached tokens of the rows in the step, read
    once each.  ``sampled_rows``: rows whose last position goes through
    the head.  ``kv_bytes_per_token_layer``: K and V of one token in one
    layer at the published KV width (num_key_value_heads), in the served
    type.
    """
    layers = cfg["num_hidden_layers"]
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    hq = cfg["num_attention_heads"]
    flops = 2 * new_tokens * layers * _llama_matmul_params_per_layer(cfg)
    flops += 4 * hq * d * context_tokens * layers       # QK^T and PV
    flops += 2 * sampled_rows * h * cfg["vocab_size"]
    nbytes = llama_weight_bytes(cfg)
    nbytes += layers * kv_bytes_per_token_layer * (resident_tokens
                                                   + new_tokens)
    return {"flops": float(flops), "bytes": float(nbytes)}


def least_seconds(cost: dict, peaks: dict) -> dict:
    """The roofline's least time and which side bounds it."""
    t_c = cost["flops"] / peaks["bf16_flops"]
    t_m = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}


def ernie_train_flops_per_position(cfg: dict, seq: int) -> float:
    """Forward + backward operations per position of the pretraining step,
    nothing recomputed: 6 x the matmul weights a position passes through
    (12 layers, MLM transform, tied decoder, no embedding look-ups) plus
    attention's QK^T and PV at this sequence length (x3 for backward)."""
    h, ffn = cfg["hidden_size"], cfg["intermediate_size"]
    layers = cfg["num_hidden_layers"]
    per_layer = 4 * h * h + 2 * h * ffn
    weights = layers * per_layer + h * h + h * cfg["vocab_size"]
    attention = layers * 4 * seq * h        # fwd: 2*seq*h for QK^T, PV each
    return 6.0 * weights + 3.0 * attention
