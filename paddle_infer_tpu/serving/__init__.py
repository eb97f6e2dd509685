"""Continuous-batching serving engine — the orchestration layer between
the paged-KV machinery (``PagedGenerationEngine``,
``ops/pallas/paged_attention.py``) and an HTTP front end.

This is the gap PAPERS.md "Ragged Paged Attention" identifies between a
paged attention *kernel* and a serving *engine*: the kernel gives you
per-row page tables and device-resident pools; somebody still has to
decide, every step, which requests occupy which KV slots.

Layer map:

  ``RequestQueue``    admission control — depth-bounded FIFO with
                      per-request deadlines; overload answers with a
                      graceful rejection (HTTP 429/504) instead of OOM.
  ``EngineCore``      the scheduler: each iteration admits queued
                      requests into free KV-block slots (staging KV
                      only), runs ONE mixed step — prompt chunks and
                      decode tokens — for every active row, evicts
                      finished rows and immediately backfills their
                      slots — no
                      stop-the-world between request generations.
  ``ServingMetrics``  queue depth, batch occupancy, TTFT, inter-token
                      latency p50/p99, tokens/s, rejection counts —
                      exposed by ``tools/serve.py`` as ``GET /metrics``.
  ``resilience``      fault tolerance: deterministic fault injection
                      (``FaultPlane``), supervised retry/replay recovery
                      (``EngineSupervisor``) and the HEALTHY/DEGRADED/
                      DRAINING/DOWN health state machine driving
                      ``/healthz``/``/readyz`` and load shedding.
  ``sharded``         the tensor-parallel serving plane: ``ServingMesh``
                      (mp × dp × ep topology + quantized-allreduce wire
                      format), ``build_sharded_engine`` and the
                      config validation EngineCore re-runs against its
                      feature flags (docs/SERVING.md "Sharded serving").
  ``moe``             the expert-parallel MoE plane: static-capacity
                      serving MoE layers (float or quantized experts),
                      in-place conversion (``prepare_moe_serving``) and
                      the thread-local stats side-channel feeding the
                      mixed step's routed/dropped/aux outputs
                      (docs/SERVING.md "MoE serving").
  ``fleet``           the disaggregated tier: ``FleetRouter`` over N
                      replicas with prefill/decode/mixed roles,
                      prefix-affinity dispatch (``PrefixCache.peek``),
                      cross-replica KV page handoff and elastic role
                      flips (docs/SERVING.md "Disaggregated serving").
  ``sched``           SLO-aware scheduling: ``StepPlanner`` (cost-model
                      per-step chunk planning calibrated by the steplog
                      fit) and pluggable admission policies — ``fifo``
                      (bitwise-compat default) and ``slack`` (EDF over
                      predicted completion with predictive shedding);
                      docs/SERVING.md "SLO-aware scheduling".
  ``adapters``        multi-LoRA tenancy: paged host ``AdapterStore``,
                      device-resident slot-LRU ``AdapterCache`` with
                      pin refcounts, and the in-place conversion
                      (``prepare_lora_serving``) adding per-row ragged
                      LoRA gathers inside the one mixed-step executable
                      (docs/SERVING.md "Multi-LoRA serving").
  ``structured``      constrained decoding: JSON-schema / regex / JSON
                      grammars compiled host-side to token-level FSMs
                      (``GrammarCache``) whose per-row states thread
                      through the one mixed-step executable as DATA —
                      a ``[batch, vocab]`` additive mask, never a shape
                      (docs/SERVING.md "Constrained decoding").

Requests with per-request sampling configs share one decode executable:
temperature/top-k/top-p/eos ride as *per-row arrays* (serving/programs),
so admitting a new request never recompiles the hot loop.
"""

from .metrics import ServingMetrics
from .request import (DeadlineExceededError, GrammarError,
                      GrammarIncompleteError, HandoffError, LoadShedError,
                      QuarantinedError, QueueFullError, RejectedError,
                      Request, RequestQueue, RequestState,
                      effective_salt)
from .structured import (CompiledGrammar, GrammarCache, compile_grammar,
                         conforms, decode_text, default_vocab,
                         grammar_digest, validate_spec)
from .adapters import (AdapterCache, AdapterError, AdapterStore,
                       LoRAServingLinear, UnknownAdapterError,
                       adapter_layer_spec, lora_serving_info,
                       make_random_adapter, prepare_lora_serving)
from .engine_core import EngineCore
from .resilience import (EngineSupervisor, FaultPlane, FaultSpec,
                         HealthMonitor, HealthState)
from .sharded import (ServingMesh, ShardedConfigError,
                      build_sharded_engine, validate_kv_quant_combo,
                      validate_moe_quant_combo, validate_serving_config)
from .moe import (MoETransformerLayer, ServingMoELayer, moe_serving_info,
                  prepare_moe_serving, serving_capacity)
from .fleet import (ElasticRolePolicy, FleetRouter, ReplicaHandle,
                    ReplicaRole, parse_fleet_roles)
from .sched import (AdmissionPolicy, FifoPolicy, SlackPolicy,
                    StepPlanner, make_policy)

__all__ = [
    "AdapterCache",
    "AdapterError",
    "AdapterStore",
    "LoRAServingLinear",
    "UnknownAdapterError",
    "adapter_layer_spec",
    "effective_salt",
    "lora_serving_info",
    "make_random_adapter",
    "prepare_lora_serving",
    "AdmissionPolicy",
    "FifoPolicy",
    "SlackPolicy",
    "StepPlanner",
    "make_policy",
    "ElasticRolePolicy",
    "FleetRouter",
    "HandoffError",
    "ReplicaHandle",
    "ReplicaRole",
    "parse_fleet_roles",
    "ServingMesh",
    "ShardedConfigError",
    "build_sharded_engine",
    "validate_kv_quant_combo",
    "validate_moe_quant_combo",
    "validate_serving_config",
    "MoETransformerLayer",
    "ServingMoELayer",
    "moe_serving_info",
    "prepare_moe_serving",
    "serving_capacity",
    "CompiledGrammar",
    "GrammarCache",
    "GrammarError",
    "GrammarIncompleteError",
    "compile_grammar",
    "conforms",
    "decode_text",
    "default_vocab",
    "grammar_digest",
    "validate_spec",
    "EngineCore",
    "Request",
    "RequestQueue",
    "RequestState",
    "ServingMetrics",
    "RejectedError",
    "QueueFullError",
    "DeadlineExceededError",
    "QuarantinedError",
    "LoadShedError",
    "EngineSupervisor",
    "FaultPlane",
    "FaultSpec",
    "HealthMonitor",
    "HealthState",
]
