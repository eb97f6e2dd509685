"""Mean, median or largest, over the serving steps of the window, of one
or several steplog fields summed per record: the phases of a scheduler
iteration (``gap_s``, ``admit_s``, ``pack_s``, ``launch_s``, ``wait_s``,
``host_s``) and the counts taken at the same boundaries.  A program whose
records lack a field (the phases are newer than the benchmark) gives
None, and the metric is left out of the line."""
from .. import accounting
from .steplog_stat import serving_steps


def per_step(ev, fields):
    """The fields' sum for each serving step, or None where any record
    lacks one of them."""
    steps = serving_steps(ev)
    if not steps or any(f not in s for s in steps for f in fields):
        return None
    return [sum(float(s[f]) for f in fields) for s in steps]


def read(ev, fields, stat, scale=1.0):
    xs = per_step(ev, fields)
    if xs is None:
        return None
    if stat == "mean":
        return sum(xs) / len(xs) * scale
    if stat == "p50":
        return accounting.quantile(xs, 0.5) * scale
    if stat == "max":
        return max(xs) * scale
    raise ValueError(f"unknown stat {stat!r}")
