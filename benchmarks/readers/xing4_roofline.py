"""Roofline shares of the hyper-connected latent-attention + MoE decoder
over the traced part of the window (``costs_xing4.py`` for operations and
bytes, ``peaks.py`` for the chip):

- ``step``: the whole step, the residual streams' traffic counted,
  against the seconds the device was busy;
- ``mhc_maps``: the maps of every sub-layer (two a layer) against the
  seconds of the operations whose key holds ``kernel`` (``mhc_maps``).
  The kernel works on a few KB a launch and is bound by its launch, so
  this share reads low by nature: ``kernel_ms_per_step`` beside it says
  what the launches cost a step.

(The latent decode kernel's and the grouped matmul's shares are
``axk1_roofline``'s, at this configuration's widths.)  None where the
StepLog lacks the counters, where the trace holds no such operation, or
for a run that was not traced."""
from .. import costs, costs_xing4, peaks
from .axk1_roofline import FIELDS, _kernel_seconds
from .steplog_stat import serving_steps


def read(ev, what, kernel=None):
    tr = ev.trace
    if not tr or not tr["busy_s"] or "hc_mult" not in ev.config:
        return None
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not steps or any(f not in s for s in steps for f in FIELDS):
        return None
    cfg = ev.config
    if what == "step":
        seconds = tr["busy_s"]
        step_costs = [costs_xing4.step_cost(
            cfg, s["decode_rows"] + s["prefill_chunk_tokens"],
            s["emitted_tokens"], s["attended_keys"], s["resident_tokens"],
            s["moe_assignments_held"], s["moe_experts_touched"])
            for s in steps]
    elif what == "mhc_maps":
        seconds = _kernel_seconds(tr, kernel)
        k = costs_xing4.sublayers(cfg)
        step_costs = [{name: k * v for name, v in costs_xing4.mhc_maps_cost(
            cfg, s["decode_rows"] + s["prefill_chunk_tokens"]).items()}
            for s in steps]
    else:
        raise ValueError(f"unknown share {what!r}")
    if not seconds:
        return None
    pk = peaks.peaks_for(ev.device_kind)
    least = sum(costs.least_seconds(c, pk)["seconds"] for c in step_costs)
    return 100.0 * least / seconds
