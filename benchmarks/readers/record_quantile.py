"""A quantile (linear interpolation) of one field over the window's
steplog records of one kind, whatever the kind: the ``evict`` record a
finished request leaves, a ``page_copy``.  None where the window holds no
record of that kind, or one that lacks the field."""
from .. import accounting


def read(ev, kind, field, q, scale=1.0):
    records = [s for s in ev.steps if s["kind"] == kind]
    if not records or any(field not in s for s in records):
        return None
    return accounting.quantile([float(s[field]) for s in records],
                               float(q)) * scale
