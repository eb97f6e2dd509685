"""Mean of one steplog field over the window's serving steps whose
``where`` field is above 0: a count that only such steps carry (the
latent decode kernel's grid over the steps with a decode row), which a
mean over every step would dilute by the window's mix of step kinds.
None where a record lacks either field, where no step qualifies, or where
the field reads 0 on every one of them (the program ran nothing that the
field counts)."""
from .steplog_stat import serving_steps


def read(ev, field, where):
    steps = serving_steps(ev)
    if any(field not in s or where not in s for s in steps):
        return None
    xs = [float(s[field]) for s in steps if s[where] > 0]
    if not xs or not any(xs):
        return None
    return sum(xs) / len(xs)
