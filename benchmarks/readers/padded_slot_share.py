"""Share of the step program's flat token axis that carried no real
token: 100 less ``token_slot_fill`` (the StepLog's own ``token_slots``,
not ``max_batch x token_budget``, which the program has not run since the
token-wise layers went onto one flat axis).  Kept for ``.chat`` alone:
``tests/test_latent_moe.py`` pins that entry's place in BENCHMARK.json."""
from . import token_slot_fill


def read(ev):
    fill = token_slot_fill.read(ev)
    return None if fill is None else 100.0 - fill
