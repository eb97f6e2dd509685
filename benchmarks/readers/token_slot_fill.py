"""Share of the step program's flat token axis that carried a real token:
(decode rows + prompt-chunk tokens + draft tokens) / token slots, summed
over the window's serving steps.  ``token_slots`` is the StepLog's count of
the slots the program ran (its token budget); a program whose records
carry none (it ran a slot array of another shape) has nothing to read."""
from .steplog_stat import serving_steps


def read(ev):
    steps = serving_steps(ev)
    slots = sum(int(s.get("token_slots", 0)) for s in steps)
    if not slots:
        return None
    real = sum(s["decode_rows"] + s["prefill_chunk_tokens"]
               + s.get("draft_tokens", 0) for s in steps)
    return 100.0 * real / slots
