"""Mean or median of one steplog field over the serving steps of the
window (kinds mixed / decode / prefill: one launch of the step program
each)."""
from .. import accounting

STEP_KINDS = ("mixed", "decode", "prefill")


def serving_steps(ev):
    return [s for s in ev.steps
            if s["kind"] in STEP_KINDS and not s["failed"]]


def read(ev, field, stat, scale=1.0):
    xs = [float(s[field]) for s in serving_steps(ev)]
    if not xs:
        return None
    if stat == "mean":
        return sum(xs) / len(xs) * scale
    if stat == "p50":
        return accounting.quantile(xs, 0.5) * scale
    raise ValueError(f"unknown stat {stat!r}")
