"""Share of the step program's [max_batch, token_budget] token slots that
carried no real token: 1 - (decode rows + prompt-chunk tokens) / slots."""
from .steplog_stat import serving_steps


def read(ev):
    steps = serving_steps(ev)
    if not steps or not ev.max_batch or not ev.token_budget:
        return None
    real = sum(s["decode_rows"] + s["prefill_chunk_tokens"] for s in steps)
    return 100.0 * (1.0 - real / (len(steps) * ev.max_batch
                                  * ev.token_budget))
