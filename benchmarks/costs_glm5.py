"""Operations and bytes of the latent-attention + shared-expert MoE
decoder whose attention reads a learned indexer's selection
(``reference: glm5``), computed from shapes alone: the algorithm's needs,
not the program's doings.  Projections, experts, router, dense FFN and
head are ``costs_axk1``'s terms at this configuration's widths; added
here are the indexer's three matrices, the index scores over every
cached token a query may read, and attention over the selection only.

A key is one query-key pair.  The program gathers the chosen rows and
then reads them again, pads a row to 640 lanes and sorts a whole window
to choose: all of that is waste against these counts, so no share built
on them can pass 100 %.
"""
from __future__ import annotations

from . import costs_axk1
from .costs_axk1 import BYTES


def indexer_params(cfg: dict) -> int:
    """W^I_q, W^I_k, W^I_w of one layer (the key's LayerNorm has
    2 x index_head_dim numbers more, not counted)."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return (cfg["q_lora_rank"] * hi * di + cfg["hidden_size"] * di
            + cfg["hidden_size"] * hi)


def index_key_bytes(cfg: dict) -> int:
    """What one token caches for the indexer in one layer."""
    return cfg["index_head_dim"] * BYTES


def index_scores_cost(cfg: dict, scored_keys: int) -> dict:
    """ONE layer's index scores: per scored key, one product of
    index_head_dim a head (the ReLU and the heads' weighted sum are two
    operations a head more, not counted); each scored key's 128 numbers
    read once a query that scores it, as a decode row must."""
    hi, di = cfg["index_n_heads"], cfg["index_head_dim"]
    return {"flops": float(2 * hi * di * scored_keys),
            "bytes": float(scored_keys * index_key_bytes(cfg))}


def sparse_attention_cost(cfg: dict, selected_keys: int) -> dict:
    """ONE layer's attention over selected keys in the absorbed form: per
    key and head ``kv_lora_rank + rope`` multiply-adds for the score and
    ``kv_lora_rank`` for the value; each selected key's cached row read
    once."""
    heads = cfg["num_attention_heads"]
    kr, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return {"flops": float(2 * heads * ((kr + rope) + kr) * selected_keys),
            "bytes": float(selected_keys * costs_axk1.latent_row_bytes(cfg))}


def fixed_params_per_token(cfg: dict) -> int:
    return (costs_axk1.fixed_params_per_token(cfg)
            + int(cfg["num_hidden_layers"]) * indexer_params(cfg))


def total_params(cfg: dict) -> int:
    """Every matrix parameter of the configuration as the file states it
    (experts held, vocabulary slice)."""
    _, moe = costs_axk1.layer_counts(cfg)
    return (fixed_params_per_token(cfg)
            + moe * cfg["n_routed_experts"] * costs_axk1.expert_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def step_cost(cfg: dict, new_tokens: int, sampled_rows: int,
              scored_keys: int, selected_keys: int,
              decode_scored_keys: int, decode_selected_keys: int,
              resident_tokens: int, assignments_held: int,
              experts_touched: int) -> dict:
    """One serving step over ``new_tokens`` real query tokens.  The
    counts are one layer's (StepLog ``index_*``).  Bytes of the caches: a
    decode row reads every index key it scores and every latent row it
    selects; a chunk row's queries share what they read, so its cached
    tokens' index keys are read once and of its latent rows no more than
    its queries select together, and no more than there are."""
    h, vocab = cfg["hidden_size"], cfg["vocab_size"]
    layers = int(cfg["num_hidden_layers"])
    idx = index_scores_cost(cfg, scored_keys)
    att = sparse_attention_cost(cfg, selected_keys)
    gmm = costs_axk1.grouped_matmul_cost(cfg, assignments_held,
                                         experts_touched)
    chunk_resident = max(resident_tokens - decode_scored_keys, 0)
    chunk_rows_read = min(chunk_resident,
                          selected_keys - decode_selected_keys)
    cache_bytes = (
        (decode_scored_keys + chunk_resident) * index_key_bytes(cfg)
        + (decode_selected_keys + chunk_rows_read)
        * costs_axk1.latent_row_bytes(cfg)
        # what the step's own tokens write
        + new_tokens * (index_key_bytes(cfg)
                        + costs_axk1.latent_row_bytes(cfg)))
    fixed = fixed_params_per_token(cfg)
    flops = (2 * new_tokens * fixed
             + layers * (idx["flops"] + att["flops"]) + gmm["flops"]
             + 2 * sampled_rows * h * vocab)
    nbytes = ((fixed + h * vocab) * BYTES + layers * cache_bytes
              + gmm["bytes"])
    return {"flops": float(flops), "bytes": float(nbytes)}
