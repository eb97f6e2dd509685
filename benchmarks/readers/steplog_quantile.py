"""A quantile (linear interpolation) of one steplog field over the
serving steps of the window.  None where the records lack the field."""
from .. import accounting
from .steplog_stat import serving_steps


def read(ev, field, q, scale=1.0):
    steps = serving_steps(ev)
    if not steps or any(field not in s for s in steps):
        return None
    return accounting.quantile([float(s[field]) for s in steps],
                               float(q)) * scale
