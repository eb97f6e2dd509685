"""Sharded serving plane: tensor-parallel EngineCore over a pjit mesh.

The single-device serving stack (EngineCore's ragged mixed step, KV
block pool, prefix cache, speculation) composes with the ``parallel/``
mesh machinery here: a :class:`ServingMesh` describes the topology (mp
tensor-parallel degree, optional dp replica groups, quantized-allreduce
wire format), :func:`build_sharded_engine` stands up a
``PagedGenerationEngine`` over the matching hybrid mesh — TP weights
placed by their ``mp_layers`` dist_attrs via ``serving_param_spec``, KV
page pools head-sharded, block tables replicated — and
:func:`validate_serving_config` rejects feature combinations that would
break the plane's invariants *before* the engine starts instead of
crashing mid-step.

Everything downstream (chunked prefill, prefix-cache CoW, supervisor
replay, speculative verify rows) runs unchanged: the mixed-step
executable is one SPMD program, so the host-side scheduler never learns
the mesh exists.  Token streams are bitwise-identical to single-device
because the math is the same — GSPMD only changes where the operands
live — except under ``quantized_allreduce``, which trades bounded logit
error for ~4x fewer mp interconnect bytes (see
``parallel.collective.quantization_error_bound``).
"""
from .mesh import (ServingMesh, ShardedConfigError, build_sharded_engine,
                   sharding_snapshot, validate_cache_layout,
                   validate_kv_quant_combo, validate_moe_quant_combo,
                   validate_serving_config)

__all__ = [
    "ServingMesh",
    "ShardedConfigError",
    "build_sharded_engine",
    "sharding_snapshot",
    "validate_cache_layout",
    "validate_kv_quant_combo",
    "validate_moe_quant_combo",
    "validate_serving_config",
]
