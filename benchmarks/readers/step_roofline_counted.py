"""The step program's share of its roofline over the traced part of the
window: the least time the chip could take for each step's REAL tokens
(operations and bytes from shapes, costs.py; peaks from peaks.py), summed,
over the seconds the device was busy.  The counts are the step's own: the
packer records, per step, the query-key pairs its attention must compute
(``attended_keys``) and the cached tokens it reads (``resident_tokens``).
None where the records have no such fields or the run was not traced."""
from .. import costs, peaks
from .steplog_stat import serving_steps

FIELDS = ("attended_keys", "resident_tokens")


def step_costs(ev, steps):
    cfg = ev.config
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    kv = 2 * cfg["num_key_value_heads"] * d * 2       # K and V, bf16
    return [costs.llama_step_cost(
        cfg, s["decode_rows"] + s["prefill_chunk_tokens"],
        s["emitted_tokens"], s["attended_keys"], kv, s["resident_tokens"])
        for s in steps]


def read(ev, what="share"):
    tr = ev.trace
    if not tr or not tr["busy_s"]:
        return None
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not steps or any(f not in s for s in steps for f in FIELDS):
        return None
    pk = peaks.peaks_for(ev.device_kind)
    least = [costs.least_seconds(c, pk) for c in step_costs(ev, steps)]
    if what == "memory_bound_steps":
        return sum(1 for x in least if x["bound"] == "memory") / len(least)
    return 100.0 * sum(x["seconds"] for x in least) / tr["busy_s"]
