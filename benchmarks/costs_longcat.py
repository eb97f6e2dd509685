"""Operations and bytes of the decoder whose layer holds two latent
attentions, two dense SwiGLU blocks and one shortcut expert block with
identity experts (``reference: longcat``), computed from shapes alone: the
algorithm's needs, not the program's doings.  The latent attention's terms
are ``costs_axk1``'s at this configuration's widths (the same cached row,
the same absorbed form); the rest is counted here under the source's own
keys.  **An assignment to an identity expert costs no bytes and no
operations**: its output is its input.

Counted at the chip's share the configuration states:
``n_routed_experts`` experts held of ``n_routed_experts_published``, the
router over all its outputs (published + ``zero_expert_num``), the
vocabulary's slice.  A layer has ``SUB_LAYERS`` attention sub-layers,
each with a cache of its own.
"""
from __future__ import annotations

from .costs_axk1 import (BYTES, attention_params,  # noqa: F401
                         latent_attention_cost, latent_row_bytes)

SUB_LAYERS = 2


def expert_params(cfg: dict) -> int:
    """One routed SwiGLU expert."""
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def dense_ffn_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def router_params(cfg: dict) -> int:
    return cfg["hidden_size"] * (cfg["n_routed_experts_published"]
                                 + cfg["zero_expert_num"])


def cache_layers(cfg: dict) -> int:
    return SUB_LAYERS * int(cfg["num_layers"])


def fixed_params_per_token(cfg: dict) -> int:
    """Matrix parameters every token passes through, whatever it is
    routed to: per layer two attentions, two dense blocks, the router."""
    return int(cfg["num_layers"]) * (
        SUB_LAYERS * (attention_params(cfg) + dense_ffn_params(cfg))
        + router_params(cfg))


def total_params(cfg: dict) -> int:
    """Every matrix parameter of the configuration as the file states it
    (experts held, vocabulary slice)."""
    return (fixed_params_per_token(cfg)
            + int(cfg["num_layers"]) * cfg["n_routed_experts"]
            * expert_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def grouped_matmul_cost(cfg: dict, assignments_held: int,
                        experts_touched: int) -> dict:
    """The held routed experts' three matrices over a step, all expert
    blocks together: operations follow the assignments to held experts,
    bytes the experts touched (each read once) and the assigned rows in
    and out.  Identity assignments are in neither count."""
    h, f = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    flops = 2 * assignments_held * expert_params(cfg)
    nbytes = (experts_touched * expert_params(cfg)
              + assignments_held * (2 * h + 3 * f)) * BYTES
    return {"flops": float(flops), "bytes": float(nbytes)}


def step_cost(cfg: dict, new_tokens: int, sampled_rows: int,
              attended_keys: int, resident_tokens: int,
              assignments_held: int, experts_touched: int) -> dict:
    """One serving step over ``new_tokens`` real query tokens.
    ``attended_keys`` and ``resident_tokens`` are ONE cache layer's (the
    StepLog's); ``assignments_held`` / ``experts_touched`` are summed over
    the expert blocks.  Weights read = the non-routed matrices, the held
    experts touched, the head; nothing of the embedding but the rows
    looked up."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    attn = latent_attention_cost(cfg, attended_keys, new_tokens,
                                 resident_tokens + new_tokens)
    gmm = grouped_matmul_cost(cfg, assignments_held, experts_touched)
    fixed = fixed_params_per_token(cfg)
    flops = (2 * new_tokens * fixed + cache_layers(cfg) * attn["flops"]
             + gmm["flops"] + 2 * sampled_rows * h * vocab)
    nbytes = ((fixed + h * vocab) * BYTES
              + cache_layers(cfg) * attn["bytes"] + gmm["bytes"])
    return {"flops": float(flops), "bytes": float(nbytes)}
