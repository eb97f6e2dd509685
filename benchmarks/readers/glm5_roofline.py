"""Roofline shares and selection costs of the latent-attention + MoE
decoder whose attention reads a learned indexer's selection, over the
traced part of the window (``costs_glm5.py`` for operations and bytes,
``peaks.py`` for the chip; the counts are the StepLog's ``index_*``
fields, one layer's, times the layers).

``what``:

- ``step``: the whole step against the seconds the device was busy;
- ``index_scores``: the decode rows' index scores in every layer against
  the seconds of the operations whose key holds ``kernel``
  (``dsa_index_scores``);
- ``sparse_decode``: the decode rows' attention over their selections
  against the seconds of ``kernel`` (``dsa_sparse_decode``);
- ``select_ms``: device milliseconds a traced step spends choosing and
  gathering: the seconds of every operation whose key holds one of
  ``kernels`` (the sorts of ``lax.top_k`` and the gathers under the
  program's ``dsa_select`` scope keep their own instruction names, so
  they are named by opcode and shape), over the traced steps.

None where the StepLog lacks the counters (a program without an
indexer), where the trace holds no such operation, or for a run that was
not traced."""
from .. import costs, costs_glm5, peaks
from .steplog_stat import serving_steps

FIELDS = ("index_scored_keys", "index_selected_keys",
          "index_decode_scored_keys", "index_decode_selected_keys",
          "resident_tokens", "moe_assignments_held", "moe_experts_touched")


def _seconds(tr, kernels):
    return sum(v for k, v in tr["op_seconds"].items()
               if any(name in k for name in kernels))


def read(ev, what, kernel=None, kernels=()):
    tr = ev.trace
    if not tr or not tr["busy_s"] or "index_topk" not in ev.config:
        return None
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not steps or any(f not in s for s in steps for f in FIELDS) \
            or not any(s["index_scored_keys"] for s in steps):
        return None
    cfg = ev.config
    layers = int(cfg["num_hidden_layers"])
    if what == "select_ms":
        seconds = _seconds(tr, kernels)
        return 1e3 * seconds / len(steps) if seconds else None
    if what == "step":
        seconds = tr["busy_s"]
        step_costs = [costs_glm5.step_cost(
            cfg, s["decode_rows"] + s["prefill_chunk_tokens"],
            s["emitted_tokens"], s["index_scored_keys"],
            s["index_selected_keys"], s["index_decode_scored_keys"],
            s["index_decode_selected_keys"], s["resident_tokens"],
            s["moe_assignments_held"], s["moe_experts_touched"])
            for s in steps]
    elif what in ("index_scores", "sparse_decode"):
        seconds = _seconds(tr, (kernel,))
        cost = (costs_glm5.index_scores_cost if what == "index_scores"
                else costs_glm5.sparse_attention_cost)
        field = ("index_decode_scored_keys" if what == "index_scores"
                 else "index_decode_selected_keys")
        step_costs = [{k: layers * v for k, v in cost(cfg, s[field]).items()}
                      for s in steps]
    else:
        raise ValueError(f"unknown share {what!r}")
    if not seconds:
        return None
    pk = peaks.peaks_for(ev.device_kind)
    least = sum(costs.least_seconds(c, pk)["seconds"] for c in step_costs)
    return 100.0 * least / seconds
