"""Operations and bytes of the latent-attention + shared-expert MoE
decoder with hyper-connected residual streams (``reference: xing4``),
computed from shapes alone: the algorithm's needs, not the program's
doings.  Attention, experts, router, dense FFN and head are
``costs_axk1``'s terms at this configuration's widths (all 64 experts
held, the whole vocabulary); added here is what the ``hc_mult`` residual
streams cost: per sub-layer (two a layer) the projection onto the maps'
``n^2 + 2n`` inputs, the maps themselves (``mhc_maps``: Sinkhorn-Knopp's
rounds counted operation by operation) and the streams' traffic.
"""
from __future__ import annotations

from . import costs_axk1
from .costs_axk1 import BYTES

MAP_BYTES = 4       # the maps' inputs and outputs are float32


def _n_w(cfg: dict):
    n = int(cfg["hc_mult"])
    return n, n * n + 2 * n


def mhc_proj_params(cfg: dict) -> int:
    """Phi of one sub-layer: [n * hidden, n^2 + 2n]."""
    n, w = _n_w(cfg)
    return n * cfg["hidden_size"] * w


def mhc_maps_cost(cfg: dict, tokens: int) -> dict:
    """ONE sub-layer's three maps over ``tokens`` tokens: the affine
    ``alpha z + b`` (2 a number), 2n sigmoids (4 operations each: negate,
    exp, add, divide), n^2 clamps and exps (3), then per Sinkhorn round a
    column and a row normalisation, each n(n - 1) adds, n adds of the
    epsilon and n^2 divisions.  Bytes: z in and the maps out in float32,
    alpha and b once."""
    n, w = _n_w(cfg)
    rounds = int(cfg["hc_sinkhorn_iters"])
    per_token = (2 * w + 4 * 2 * n + 3 * n * n
                 + rounds * 2 * (n * (n - 1) + n + n * n))
    return {"flops": float(tokens * per_token),
            "bytes": float((tokens * 2 * w + 2 * w) * MAP_BYTES)}


def residual_stream_cost(cfg: dict, tokens: int) -> dict:
    """ONE sub-layer's residual path without its maps: the projection
    (2 n hidden w a token, Phi read once a step), the read mix (2 n
    hidden), the write mix (2 n^2 hidden + 2 n hidden); the streams read
    once and written once (n hidden each), the sub-layer's input written
    and its output read (hidden each), in the served type."""
    n, w = _n_w(cfg)
    h = cfg["hidden_size"]
    flops = tokens * (2 * n * h * w + 2 * n * h + 2 * n * n * h + 2 * n * h)
    nbytes = (mhc_proj_params(cfg) + tokens * (2 * n + 2) * h) * BYTES
    return {"flops": float(flops), "bytes": float(nbytes)}


def residual_stream_bytes_per_token(cfg: dict) -> int:
    """The streams of one token as stored: n x hidden in the served type."""
    return int(cfg["hc_mult"]) * cfg["hidden_size"] * BYTES


def sublayers(cfg: dict) -> int:
    return 2 * int(cfg["num_hidden_layers"])


def step_cost(cfg: dict, new_tokens: int, sampled_rows: int,
              attended_keys: int, resident_tokens: int,
              assignments_held: int, experts_touched: int) -> dict:
    """One serving step over ``new_tokens`` real query tokens:
    ``costs_axk1.step_cost``'s terms, plus the residual path of every
    sub-layer."""
    base = costs_axk1.step_cost(cfg, new_tokens, sampled_rows, attended_keys,
                                resident_tokens, assignments_held,
                                experts_touched)
    maps = mhc_maps_cost(cfg, new_tokens)
    streams = residual_stream_cost(cfg, new_tokens)
    k = sublayers(cfg)
    return {"flops": base["flops"] + k * (maps["flops"] + streams["flops"]),
            "bytes": base["bytes"] + k * (maps["bytes"] + streams["bytes"])}
