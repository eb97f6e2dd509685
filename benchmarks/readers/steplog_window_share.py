"""One steplog field of seconds, summed over the serving steps of the
window, as a share of the window's own seconds (``scale`` 100 for per
cent): how much of the wall the loop spent in what the field times.  None
where a record lacks the field or the window has no length."""
from .steplog_phase import per_step


def read(ev, field, scale=100.0):
    xs = per_step(ev, [field])
    window = ev.w1 - ev.w0
    if xs is None or window <= 0:
        return None
    return scale * sum(xs) / window
