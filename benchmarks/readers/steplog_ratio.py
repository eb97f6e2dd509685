"""The sum of one steplog field over the sum of another, both over the
serving steps of the window: work per unit of outcome (tree entries
examined per block evicted).  None where a record lacks either field or
the denominator's sum is 0 (nothing happened to divide by)."""
from .steplog_phase import per_step


def read(ev, numerator, denominator, scale=1.0):
    num, den = per_step(ev, [numerator]), per_step(ev, [denominator])
    if num is None or den is None or not sum(den):
        return None
    return scale * sum(num) / sum(den)
