"""ServingMesh config, validation, and engine construction.

The mesh layout reuses ``parallel.topology.create_hybrid_mesh`` so the
serving axes carry the same names the training stack uses ("mp" for the
tensor-parallel head/column/row splits, "dp" for batch replica groups)
and every existing ``sharding_constraint`` / ``axis_if_divides`` site in
the model and paged kernel picks them up unmodified.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Sequence


class ShardedConfigError(ValueError):
    """A ServingMesh / feature combination that cannot serve correctly.
    Raised at configuration time with an actionable message — never from
    inside the step loop."""


@dataclass(frozen=True)
class ServingMesh:
    """Topology of the sharded serving plane.

    ``mp``: tensor-parallel degree — attention heads and MLP
    column/row splits sharded over this axis, KV page pools sharded on
    the head dim, one all-reduce per row-parallel matmul.
    ``dp_replicas``: data-parallel replica groups — batch rows split
    across replicas, weights replicated across them.
    ``quantized_allreduce``: ``"int8"`` switches the mp all-reduces to
    the blockwise-int8 wire format (EQuARX); approximate logits, so it
    is rejected together with features whose invariants need exact
    arithmetic (speculation's acceptance rule, prefix-cache warm/cold
    stream identity).
    ``ep``: expert-parallel degree — a MoE model's stacked expert
    parameters ([E, ...], dist_attr ("ep", ...)) shard their expert dim
    over this axis, and the serving MoE ops' sharding constraints make
    GSPMD emit the dispatch/combine all-to-alls inside the step
    program.  The axis reuses the training stack's "ep" name, so every
    existing constraint composes unmodified.
    """

    mp: int = 1
    dp_replicas: int = 1
    quantized_allreduce: Optional[str] = None
    ep: int = 1

    @property
    def n_devices(self) -> int:
        return int(self.mp) * int(self.dp_replicas) * int(self.ep)

    def describe(self) -> str:
        parts = [f"mp={self.mp}"]
        if self.dp_replicas > 1:
            parts.append(f"dp={self.dp_replicas}")
        if self.ep > 1:
            parts.append(f"ep={self.ep}")
        if self.quantized_allreduce:
            parts.append(f"quantized_allreduce={self.quantized_allreduce}")
        return "ServingMesh(" + ", ".join(parts) + ")"

    def build(self, devices: Optional[Sequence] = None):
        """The hybrid mesh for this config (axes [pp, dp, sharding, sep,
        ep, mp]; only dp/ep/mp exceed 1 here)."""
        from ...parallel.topology import create_hybrid_mesh

        return create_hybrid_mesh(dp=self.dp_replicas, mp=self.mp,
                                  ep=self.ep, devices=devices)


def validate_kv_quant_combo(kv_dtype: Optional[str], *,
                            speculate: bool = False,
                            enable_prefix_cache: bool = False,
                            spec_accept_threshold: Optional[float] = None):
    """The KV-cache-quantization feature matrix, one rule per row.

    * ``kv_dtype=None`` — fp pool, everything allowed (trivially).
    * ``"int8"`` + prefix cache — ALLOWED: pages quantize at write time
      under the slot-0 scale protocol, so a warm (suffix-only) prefill
      reads exactly the bytes a cold prefill wrote and the warm/cold
      stream identity holds *within the quantized domain*.
    * ``"int8"`` + speculation — ALLOWED: the verify lane's target
      logits are computed in the same quantized domain the decode lane
      would have used, so greedy acceptance stays self-consistent and
      the emitted stream equals quantized-domain target-only decoding.
    * ``"int4"`` + speculation — REJECTED unless an explicit
      ``spec_accept_threshold`` is set: 4-bit dequant error is large
      enough to flip near-tie argmax comparisons in the verify lane,
      so the operator must opt in with a margin below which drafts are
      rejected outright.
    """
    if kv_dtype not in (None, "int8", "int4"):
        raise ShardedConfigError(
            f"unsupported kv_dtype={kv_dtype!r}; expected 'int8' or "
            "'int4' (or None for the fp KV pool)")
    if spec_accept_threshold is not None:
        t = float(spec_accept_threshold)
        if not 0.0 < t < 1.0:
            raise ShardedConfigError(
                f"spec_accept_threshold={spec_accept_threshold!r} out "
                "of range: expected a margin in (0, 1)")
    if kv_dtype == "int4" and speculate and spec_accept_threshold is None:
        raise ShardedConfigError(
            "kv_dtype='int4' is incompatible with speculative decoding "
            "unless spec_accept_threshold is set: 4-bit KV dequant "
            "error can flip near-tie verify-lane acceptance "
            "comparisons — set an explicit acceptance margin (e.g. "
            "spec_accept_threshold=0.1) or serve with kv_dtype='int8'")


def validate_moe_quant_combo(moe_quant: Optional[str], *,
                             speculate: bool = False,
                             spec_accept_threshold: Optional[float] = None):
    """The quantized-expert feature matrix (the MoE analog of
    :func:`validate_kv_quant_combo`).

    * ``moe_quant=None`` / ``"fp"`` — float experts, everything allowed.
    * ``"weight_only_int8"`` / ``"weight_only_int4"`` + speculation —
      ALLOWED: weight-only dequant is deterministic per checkpoint, so
      the verify lane's target logits live in the same (quantized-
      weight) domain the decode lane would have used; greedy acceptance
      stays self-consistent.
    * ``"int8_act"`` + speculation — REJECTED unless an explicit
      ``spec_accept_threshold`` is set: activation quantization error
      depends on the routed batch contents, so draft-lane and verify-
      lane logits for the same token can disagree enough to flip
      near-tie acceptance comparisons — the operator must opt in with a
      rejection margin.
    """
    if moe_quant not in (None, "fp", "weight_only_int8",
                         "weight_only_int4", "int8_act"):
        raise ShardedConfigError(
            f"unsupported moe_quant={moe_quant!r}; expected "
            "'weight_only_int8', 'weight_only_int4' or 'int8_act' (or "
            "None for float experts)")
    if moe_quant == "int8_act" and speculate \
            and spec_accept_threshold is None:
        raise ShardedConfigError(
            "int8-activation experts are incompatible with speculative "
            "decoding unless spec_accept_threshold is set: activation "
            "quantization error varies with routed batch contents, so "
            "verify-lane logits can flip near-tie acceptance "
            "comparisons — set an explicit acceptance margin (e.g. "
            "spec_accept_threshold=0.1) or serve weight-only experts")


def validate_cache_layout(cache_layout, *, mp: int = 1,
                          kv_dtype: Optional[str] = None,
                          speculate: bool = False,
                          kv_host_pages: int = 0, handoff: bool = False):
    """What a ``latent`` cache layer (inference/cache_layout.py: one
    ``[P, page, lanes]`` pool, no head axis, no V pool — or, with an
    ``index_width``, that pool and the indexer's ``[P, page, 128]`` keys
    beside it; or two such pools a decoder layer, one an attention
    sub-layer, stated as two entries) cannot do yet — refused here, at
    start-up, one mechanism a sentence.  Silent for layouts of ``kv``
    layers only."""
    if not cache_layout or all(c.kind != "latent" for c in cache_layout):
        return
    # the pair of a layer with an indexer: two pools of UNEQUAL shape
    pair = any(c.index_width for c in cache_layout if c.kind == "latent")
    # a decoder layer of two attention sub-layers: a latent pool each
    twin = any(c.part for c in cache_layout if c.kind == "latent")
    either = ", in either of a decoder layer's two pools" if twin else ""
    if mp > 1:
        raise ShardedConfigError(
            f"mp={mp} splits the page pool over its head axis; a latent "
            "cache layer has no head axis to split" + (
                ", and its index keys are one vector a token for every "
                "index head" if pair else "") + either
            + " — serve it with mp=1")
    if kv_dtype is not None:
        raise ShardedConfigError(
            f"kv_dtype={kv_dtype!r} scales each page per head; a latent "
            "cache layer has no heads to scale over" + (
                ", and its index-key pool is stored in the served type"
                if pair else "") + either
            + " — serve it with full-precision pages")
    if speculate:
        raise ShardedConfigError(
            "speculative decoding verifies drafts through the per-head "
            "decode kernel's verify lanes; the latent decode kernel" + (
                "s (index scores, sparse decode) have" if pair else " has")
            + " none" + (", for either of a decoder layer's two attention "
                         "sub-layers" if twin else "") + " — drop speculate")
    one = ("a latent pool and an index-key pool of another width" if pair
           else "two latent pools, one an attention sub-layer, that are "
           "no key and value of each other" if twin else "one pool")
    if int(kv_host_pages) > 0:
        raise ShardedConfigError(
            "the host KV tier parks and resumes a row through its key "
            "and value pools, a pair of equal shape; a latent cache "
            f"layer has {one} — drop kv_host_pages")
    if handoff:
        raise ShardedConfigError(
            "KV handoff between replicas serialises a row's key and "
            "value pools, a pair of equal shape; a latent cache layer "
            f"has {one} — serve it without a dedicated prefill role")


def validate_serving_config(cfg: ServingMesh, *, speculate: bool = False,
                            enable_prefix_cache: bool = False,
                            max_batch: Optional[int] = None,
                            num_heads: Optional[int] = None,
                            available_devices: Optional[int] = None,
                            kv_dtype: Optional[str] = None,
                            spec_accept_threshold: Optional[float] = None,
                            num_experts: Optional[int] = None,
                            moe_quant: Optional[str] = None,
                            cache_layout=None, kv_host_pages: int = 0,
                            handoff: bool = False):
    """Raise :class:`ShardedConfigError` for combos that would serve
    incorrectly or crash mid-step; silent on valid configs."""
    validate_cache_layout(cache_layout, mp=cfg.mp, kv_dtype=kv_dtype,
                          speculate=speculate, kv_host_pages=kv_host_pages,
                          handoff=handoff)
    validate_kv_quant_combo(kv_dtype, speculate=speculate,
                            enable_prefix_cache=enable_prefix_cache,
                            spec_accept_threshold=spec_accept_threshold)
    validate_moe_quant_combo(moe_quant, speculate=speculate,
                             spec_accept_threshold=spec_accept_threshold)
    if cfg.mp < 1 or cfg.dp_replicas < 1 or cfg.ep < 1:
        raise ShardedConfigError(
            f"mesh degrees must be >= 1, got mp={cfg.mp} "
            f"dp_replicas={cfg.dp_replicas} ep={cfg.ep}")
    if cfg.ep > 1:
        if num_experts is None:
            raise ShardedConfigError(
                f"ep={cfg.ep} needs a MoE model: no stacked expert "
                "parameters to shard over the ep axis — drop --ep or "
                "serve a model with num_experts > 1")
        if num_experts % cfg.ep:
            raise ShardedConfigError(
                f"ep={cfg.ep} does not divide num_experts="
                f"{num_experts}: the stacked expert dim must split "
                "evenly over the ep axis — pick an ep degree that "
                "divides the expert count")
    q = cfg.quantized_allreduce
    if q not in (None, "int8"):
        raise ShardedConfigError(
            f"unsupported quantized_allreduce={q!r}; expected 'int8' "
            "(or None for exact fp all-reduces)")
    if q and cfg.mp <= 1:
        raise ShardedConfigError(
            "quantized_allreduce only applies to the mp partial-sum "
            f"all-reduces; mp={cfg.mp} has none — raise --mp or drop "
            "--quantized_allreduce")
    if q and speculate:
        raise ShardedConfigError(
            "quantized_allreduce is incompatible with speculative "
            "decoding: the verify lane's acceptance rule assumes exact "
            "target logits, and quantized wire error would silently "
            "shift acceptance decisions — drop --speculate or serve "
            "with exact all-reduces")
    if q and enable_prefix_cache:
        raise ShardedConfigError(
            "quantized_allreduce is incompatible with prefix caching: "
            "warm (suffix-only) and cold (full-prompt) prefills "
            "quantize over different block boundaries, so a cache hit "
            "would change the token stream — drop --prefix_cache or "
            "serve with exact all-reduces")
    if available_devices is not None and cfg.n_devices > available_devices:
        raise ShardedConfigError(
            f"{cfg.describe()} needs {cfg.n_devices} devices but only "
            f"{available_devices} are visible (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N for a "
            "CPU dryrun)")
    if max_batch is not None and cfg.dp_replicas > 1 \
            and max_batch % cfg.dp_replicas:
        raise ShardedConfigError(
            f"max_batch={max_batch} does not divide across "
            f"dp_replicas={cfg.dp_replicas}; the batch dim must split "
            "evenly over the replica groups")
    if num_heads is not None and cfg.mp > 1 and num_heads % cfg.mp:
        raise ShardedConfigError(
            f"mp={cfg.mp} does not divide num_attention_heads="
            f"{num_heads}: attention heads and the KV page pool cannot "
            "shard — pick an mp degree that divides the head count")


def build_sharded_engine(model, cfg: ServingMesh, *, page_size: int = 16,
                         num_pages: Optional[int] = None,
                         prompt_bucket: int = 64, cache_dtype=None,
                         kv_dtype: Optional[str] = None,
                         devices: Optional[Sequence] = None):
    """A ``PagedGenerationEngine`` serving over ``cfg``'s mesh.

    Validation here covers only what the engine itself needs (device
    count, head divisibility); EngineCore re-validates against its own
    feature flags when the engine is handed to it with
    ``serving_mesh=cfg``."""
    import jax

    from ...inference.generation import PagedGenerationEngine
    from ..moe import moe_serving_info

    avail = len(list(devices) if devices is not None else jax.devices())
    moe = moe_serving_info(model)
    from ...inference.cache_layout import layout_of

    validate_serving_config(
        cfg, num_heads=model.config.num_attention_heads,
        available_devices=avail, kv_dtype=kv_dtype,
        cache_layout=layout_of(model),
        num_experts=moe["num_experts"] if moe else None,
        moe_quant=moe["algo"] if moe else None)
    mesh = cfg.build(devices) if cfg.n_devices > 1 else None
    return PagedGenerationEngine(
        model, page_size=page_size, num_pages=num_pages,
        prompt_bucket=prompt_bucket, cache_dtype=cache_dtype, mesh=mesh,
        kv_dtype=kv_dtype,
        quantized_allreduce=cfg.quantized_allreduce)


def sharding_snapshot(engine) -> Optional[dict]:
    """The ``sharding`` section of the serving metrics snapshot: the
    engine's placement report plus the global collective-bytes ledger.
    None when the engine serves single-device (section omitted)."""
    report = getattr(engine, "shard_report", lambda: None)()
    if report is None:
        return None
    from ...parallel.collective import LEDGER

    out = dict(report)
    out["collectives"] = LEDGER.snapshot()
    return out
