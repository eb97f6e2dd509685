"""Roofline shares of the latent-attention + MoE decoder's step over the
traced part of the window: the least time the chip could take for what
the steps REALLY had to do (operations and bytes from shapes,
``costs_axk1.py``; peaks from ``peaks.py``), summed over the traced
steps, over device seconds from the trace.

``what``:

- ``step``: the whole step against the seconds the device was busy;
- ``latent_decode``: the decode rows' attention in every layer against
  the seconds of the operations whose key holds ``kernel``
  (``latent_paged_decode``);
- ``grouped_matmul``: the held routed experts' three matrices in every
  expert layer against the seconds of ``kernel`` (``moe_grouped_matmul``).

The counts are the step's own StepLog fields; None where the records
lack them (a program without these counters), where the trace holds no
such operation, or for a run that was not traced."""
from .. import costs, costs_axk1, peaks
from .steplog_stat import serving_steps

FIELDS = ("attended_keys", "resident_tokens", "decode_keys",
          "moe_assignments_held", "moe_experts_touched")


def _kernel_seconds(tr, kernel):
    return sum(v for k, v in tr["op_seconds"].items()
               if kernel in k)


def read(ev, what, kernel=None):
    tr = ev.trace
    if not tr or not tr["busy_s"]:
        return None
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not steps or any(f not in s for s in steps for f in FIELDS):
        return None
    cfg = ev.config
    layers = int(cfg["num_hidden_layers"])
    if what == "step":
        seconds = tr["busy_s"]
        step_costs = [costs_axk1.step_cost(
            cfg, s["decode_rows"] + s["prefill_chunk_tokens"],
            s["emitted_tokens"], s["attended_keys"], s["resident_tokens"],
            s["moe_assignments_held"], s["moe_experts_touched"])
            for s in steps]
    elif what == "latent_decode":
        seconds = _kernel_seconds(tr, kernel)
        step_costs = [{k: layers * v for k, v in
                       costs_axk1.latent_attention_cost(
                           cfg, s["decode_keys"], s["decode_rows"],
                           s["decode_keys"]).items()} for s in steps]
    elif what == "grouped_matmul":
        seconds = _kernel_seconds(tr, kernel)
        step_costs = [costs_axk1.grouped_matmul_cost(
            cfg, s["moe_assignments_held"], s["moe_experts_touched"])
            for s in steps]
    else:
        raise ValueError(f"unknown share {what!r}")
    if not seconds:
        return None
    pk = peaks.peaks_for(ev.device_kind)
    least = sum(costs.least_seconds(c, pk)["seconds"] for c in step_costs)
    return 100.0 * least / seconds
