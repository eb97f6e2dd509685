"""A quantile (linear interpolation) of a client-side series.

``ttft_due``: first streamed token minus DUE time, requests due in the
window.  ``itl``: gaps between streamed tokens, all requests pooled, later token inside the
window.  ``lateness``: send time minus due time, the generator's own.
"""
from .. import accounting


def series(ev, name):
    w0, w1 = ev.w0, ev.w1
    if name == "ttft_due":
        return [r.token_times[0] - r.due for r in ev.records
                if r.phase == "window" and r.token_times]
    if name == "itl":
        return accounting.gaps_in_window(
            (r.token_times for r in ev.records), w0, w1)
    if name == "lateness":
        return [r.sent - r.due for r in ev.records
                if r.phase == "window" and r.sent is not None]
    raise ValueError(f"unknown series {name!r}")


def read(ev, series_name, q, scale=1.0):
    xs = series(ev, series_name)
    if not xs:
        return None
    return accounting.quantile(xs, q) * scale
