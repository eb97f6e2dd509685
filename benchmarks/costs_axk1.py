"""Operations and bytes of the latent-attention + shared-expert MoE
decoder (``reference: axk1``), computed from shapes alone: the
algorithm's needs, not the program's doings.  Padding, recomputation and
copies the program adds count as waste against the roofline.

Counted at the chip's share the configuration states: ``n_routed_experts``
experts held of ``n_routed_experts_published`` routed over, the
vocabulary's slice.
"""
from __future__ import annotations

BYTES = 2           # bf16, as served


def _dims(cfg: dict):
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    return h, heads, nope, rope, vd, cfg["q_lora_rank"], cfg["kv_lora_rank"]


def attention_params(cfg: dict) -> int:
    """W_qa, W_qb, W_kva, W_kvb, W_o of one layer."""
    h, heads, nope, rope, vd, qr, kr = _dims(cfg)
    return (h * qr + qr * heads * (nope + rope) + h * (kr + rope)
            + kr * heads * (nope + vd) + heads * vd * h)


def expert_params(cfg: dict) -> int:
    """One SwiGLU expert (routed or shared)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * (cfg.get("n_routed_experts_published")
                                 or cfg["n_routed_experts"])


def layer_counts(cfg: dict):
    dense = int(cfg["first_k_dense_replace"])
    return dense, int(cfg["num_hidden_layers"]) - dense


def latent_row_bytes(cfg: dict) -> int:
    """What one token caches in one layer: the latent and the rotated
    position part."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BYTES


def fixed_params_per_token(cfg: dict) -> int:
    """Matrix parameters every token passes through, whatever it is
    routed to: attention in every layer, the dense FFNs, and per expert
    layer the router and the shared expert(s)."""
    dense, moe = layer_counts(cfg)
    return ((dense + moe) * attention_params(cfg)
            + dense * dense_ffn_params(cfg)
            + moe * (router_params(cfg)
                     + cfg["n_shared_experts"] * expert_params(cfg)))


def latent_attention_cost(cfg: dict, attended_keys: int, queries: int,
                          resident_tokens: int) -> dict:
    """ONE layer's attention in the absorbed form: per query-key pair and
    head, ``kv_lora_rank + rope`` multiply-adds for the score and
    ``kv_lora_rank`` for the value; every cached row of the rows in the
    step read once, the absorbed queries read and the latent outputs
    written."""
    _, heads, _, rope, _, _, kr = _dims(cfg)
    flops = 2 * heads * ((kr + rope) + kr) * attended_keys
    nbytes = (resident_tokens * latent_row_bytes(cfg)
              + queries * heads * ((kr + rope) + kr) * BYTES)
    return {"flops": float(flops), "bytes": float(nbytes)}


def grouped_matmul_cost(cfg: dict, assignments_held: int,
                        experts_touched: int) -> dict:
    """The held routed experts' three matrices over a step, all expert
    layers together: operations follow the assignments to held experts,
    bytes the experts touched (each read once) and the assigned rows in
    and out."""
    h, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    flops = 2 * assignments_held * expert_params(cfg)
    nbytes = (experts_touched * expert_params(cfg)
              + assignments_held * (2 * h + 3 * f)) * BYTES
    return {"flops": float(flops), "bytes": float(nbytes)}


def step_cost(cfg: dict, new_tokens: int, sampled_rows: int,
              attended_keys: int, resident_tokens: int,
              assignments_held: int, experts_touched: int) -> dict:
    """One serving step over ``new_tokens`` real query tokens.

    ``attended_keys``: sum over the query tokens of the keys each attends
    to.  ``resident_tokens``: cached tokens of the rows in the step.
    ``sampled_rows``: rows whose last position goes through the head.
    ``assignments_held`` / ``experts_touched``: summed over the expert
    layers.  Weights read = the non-routed matrices, the held experts
    touched, the head; nothing of the embedding but the rows looked up.
    """
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    layers = int(cfg["num_hidden_layers"])
    attn = latent_attention_cost(cfg, attended_keys, new_tokens,
                                 resident_tokens + new_tokens)
    gmm = grouped_matmul_cost(cfg, assignments_held, experts_touched)
    flops = (2 * new_tokens * fixed_params_per_token(cfg)
             + layers * attn["flops"] + gmm["flops"]
             + 2 * sampled_rows * h * vocab)
    nbytes = ((fixed_params_per_token(cfg) + h * vocab) * BYTES
              + layers * attn["bytes"] + gmm["bytes"])
    return {"flops": float(flops), "bytes": float(nbytes)}
