"""Of the gaps between streamed tokens that the window's steps made, the
share made by a step that also carried a chunk of some prompt: over the
serving steps with a decode row, each weighted by its ``decode_rows`` (a
step hands one token, so one gap, to each of them), the share whose
``prefill_chunk_tokens`` is above 0.

It says on which side of a quantile of the gaps the chunk steps lie: a
step that holds a chunk is longer than one that holds decode rows alone,
so where this share is near 5 % ``itl_p95_ms`` sits on the border between
the two kinds of gap and reads either by the seed.  None where no step
of the window carried a decode row."""
from .steplog_stat import serving_steps


def read(ev):
    rows = chunked = 0
    for s in serving_steps(ev):
        rows += s["decode_rows"]
        if s["prefill_chunk_tokens"] > 0:
            chunked += s["decode_rows"]
    if not rows:
        return None
    return 100.0 * chunked / rows
