"""The paged-attention kernel's share of its roofline over the traced
part of the window: the least time the chip could take for the attention
the traced steps REALLY had to do (``costs_paged_attention.py``: the
packer's own counters ``attended_keys`` and ``resident_tokens``, K and V
at the published KV width; peaks from ``peaks.py``), summed over the
steps, over the device seconds of the operations whose key holds
``kernel``.

None for a run that was not traced, where the records lack the counters,
and where the trace holds no such operation (a program that serves its
attention some other way)."""
from .. import costs, costs_paged_attention, peaks
from .steplog_stat import serving_steps

FIELDS = ("attended_keys", "resident_tokens")


def read(ev, kernel):
    tr = ev.trace
    if not tr or not tr["busy_s"]:
        return None
    seconds = sum(v for k, v in tr["op_seconds"].items()
                  if kernel in k)
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not seconds or not steps or any(f not in s for s in steps
                                       for f in FIELDS):
        return None
    cfg = ev.config
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    kv = 2 * cfg["num_key_value_heads"] * d * 2       # K and V, bf16
    pk = peaks.peaks_for(ev.device_kind)
    least = sum(costs.least_seconds(
        costs_paged_attention.paged_attention_cost(
            cfg, s["decode_rows"] + s["prefill_chunk_tokens"],
            s["attended_keys"], kv, s["resident_tokens"]), pk)["seconds"]
        for s in steps)
    return 100.0 * least / seconds
