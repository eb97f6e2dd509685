"""Of the gaps between streamed tokens that the window's steps made, the
share made by a step whose ``field`` is above 0: over the serving steps
with a decode row, each weighted by its ``decode_rows`` (a step hands one
token, so one gap, to each of them).  ``chunk_step_gap_share`` is this
over ``prefill_chunk_tokens``; over ``finished_rows`` it says on which
side of a quantile of the gaps the steps that released a request lie.
None where no step of the window carried a decode row, or a record lacks
the field."""
from .steplog_stat import serving_steps


def read(ev, field):
    steps = serving_steps(ev)
    if any(field not in s for s in steps):
        return None
    rows = sum(s["decode_rows"] for s in steps)
    if not rows:
        return None
    marked = sum(s["decode_rows"] for s in steps if s[field] > 0)
    return 100.0 * marked / rows
