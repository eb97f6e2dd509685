"""MoE expert-parallel serving plane.

Serves MoE decoders through the SAME ragged mixed step that serves
dense models (docs/SERVING.md "MoE serving"):

  ``ServingMoELayer``     one MoE FFN (float or quantized experts)
                          routed through static-capacity serving ops —
                          gate → fixed [E, C] dispatch → batched expert
                          einsum → combine; routing changes data, never
                          shapes, so the mixed-step executable stays
                          keyed only on deployment config.
  ``prepare_moe_serving`` in-place model conversion (EngineCore runs it
                          automatically before its param snapshot).
  ``moe_serving_info``    detection + description of a model's MoE
                          plane (validation matrix, metrics).
  ``serving_capacity``    the per-expert buffer width from deployment
                          config (the token_budget slots through the
                          training capacity formula — default-capacity
                          serving is bitwise the unconverted stream).
  ``stats``               the thread-local side-channel carrying
                          per-step routed/dropped/aux out of the traced
                          forward into mixed-step outputs.

Expert parallelism rides the existing machinery end to end: expert
stacks keep their ``("ep", ...)`` dist_attrs, ``ServingMesh(ep=N)``
grows the hybrid mesh's "ep" axis, ``serving_param_spec`` places the
stacks, and the ops' ``_pin_ep`` sharding constraints make GSPMD emit
the dispatch/combine all-to-alls inside the one step program.
"""
from .layer import (MoETransformerLayer, ServingMoELayer,
                    moe_serving_info, prepare_moe_serving,
                    serving_capacity)

__all__ = [
    "MoETransformerLayer",
    "ServingMoELayer",
    "moe_serving_info",
    "prepare_moe_serving",
    "serving_capacity",
]
