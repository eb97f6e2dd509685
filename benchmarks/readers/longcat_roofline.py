"""Roofline shares and identity-expert readings of the decoder whose layer
holds two latent attentions and one shortcut expert block
(``costs_longcat.py`` for operations and bytes, ``peaks.py`` for the chip;
the counts are the step's own StepLog fields).

``what``:

- ``step``: the whole step against the seconds the device was busy;
- ``latent_decode``: the decode rows' attention in every attention
  sub-layer against the seconds of the operations whose key holds
  ``kernel`` (``latent_paged_decode``);
- ``grouped_matmul``: the held routed experts' three matrices in every
  expert block against the seconds of ``kernel``
  (``moe_grouped_matmul``);
- ``identity_ms``: device milliseconds a traced step spends under the
  program's ``moe_identity`` scope: the seconds of every operation whose
  key holds one of ``kernels`` (the scope's XLA operations keep their own
  instruction names, so they are named by opcode and shape), over the
  traced steps.

None where the configuration has no identity experts, where the StepLog
lacks the counters (a program without them), where the trace holds no
such operation, or for a run that was not traced."""
from .. import costs, costs_longcat, peaks
from .steplog_stat import serving_steps

FIELDS = ("attended_keys", "resident_tokens", "decode_keys",
          "moe_assignments_held", "moe_experts_touched",
          "moe_assignments_identity")


def _seconds(tr, kernels):
    return sum(v for k, v in tr["op_seconds"].items()
               if any(name in k for name in kernels))


def read(ev, what, kernel=None, kernels=()):
    tr = ev.trace
    if not tr or not tr["busy_s"] or not ev.config.get("zero_expert_num"):
        return None
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not steps or any(f not in s for s in steps for f in FIELDS):
        return None
    cfg = ev.config
    if what == "identity_ms":
        seconds = _seconds(tr, kernels)
        return 1e3 * seconds / len(steps) if seconds else None
    if what == "step":
        seconds = tr["busy_s"]
        step_costs = [costs_longcat.step_cost(
            cfg, s["decode_rows"] + s["prefill_chunk_tokens"],
            s["emitted_tokens"], s["attended_keys"], s["resident_tokens"],
            s["moe_assignments_held"], s["moe_experts_touched"])
            for s in steps]
    elif what == "latent_decode":
        seconds = _seconds(tr, (kernel,))
        layers = costs_longcat.cache_layers(cfg)
        step_costs = [{k: layers * v for k, v in
                       costs_longcat.latent_attention_cost(
                           cfg, s["decode_keys"], s["decode_rows"],
                           s["decode_keys"]).items()} for s in steps]
    elif what == "grouped_matmul":
        seconds = _seconds(tr, (kernel,))
        step_costs = [costs_longcat.grouped_matmul_cost(
            cfg, s["moe_assignments_held"], s["moe_experts_touched"])
            for s in steps]
    else:
        raise ValueError(f"unknown reading {what!r}")
    if not seconds:
        return None
    pk = peaks.peaks_for(ev.device_kind)
    least = sum(costs.least_seconds(c, pk)["seconds"] for c in step_costs)
    return 100.0 * least / seconds
