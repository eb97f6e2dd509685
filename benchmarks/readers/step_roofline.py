"""The step program's share of its roofline over the traced part of the
window: the least time the chip could take for each step's REAL tokens and
the cached tokens of the rows in it (operations and bytes from shapes,
costs.py; peaks from peaks.py), summed, over the seconds the device was
busy.  ``bound`` says which side of the roofline set the least time in
most steps."""
from .. import costs, peaks
from .steplog_stat import serving_steps


def _resident_tokens(ev, t):
    """Cached tokens of the requests in flight at ``t``, from the client's
    records: prompt progress linear between send and first token, then
    one more per streamed token."""
    n = 0.0
    for r in ev.records:
        if r.sent is None or r.sent > t or r.observed_until is None:
            continue
        if r.token_times and (r.finished and r.token_times[-1] < t):
            continue
        if r.observed_until < t:
            continue
        if not r.token_times or t < r.token_times[0]:
            end = r.token_times[0] if r.token_times else r.observed_until
            n += r.prompt_len * min(1.0, (t - r.sent)
                                    / max(end - r.sent, 1e-9))
        else:
            n += r.prompt_len + sum(1 for x in r.token_times if x <= t)
    return n


def step_costs(ev, steps):
    cfg = ev.config
    d = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    kv = 2 * cfg["num_key_value_heads"] * d * 2       # K and V, bf16
    out = []
    for s in steps:
        new = s["decode_rows"] + s["prefill_chunk_tokens"]
        resident = _resident_tokens(ev, s["t"])
        rows = max(s["active_rows"], 1)
        # every new token attends to its own row's cache: the mean row's
        context = new * resident / rows
        out.append(costs.llama_step_cost(
            cfg, new, s["emitted_tokens"], context, kv, resident))
    return out


def read(ev, what="share"):
    tr = ev.trace
    if not tr or not tr.get("busy_s"):
        return None
    steps = [s for s in serving_steps(ev) if tr["t0"] <= s["t"] < tr["t1"]]
    if not steps:
        return None
    pk = peaks.peaks_for(ev.device_kind)
    least = [costs.least_seconds(c, pk) for c in step_costs(ev, steps)]
    if what == "memory_bound_steps":
        return sum(1 for x in least if x["bound"] == "memory") / len(least)
    return 100.0 * sum(x["seconds"] for x in least) / tr["busy_s"]
