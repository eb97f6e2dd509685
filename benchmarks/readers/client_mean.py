"""The mean of a client-side series (the series of client_quantile)."""
from .client_quantile import series


def read(ev, series_name, scale=1.0):
    xs = series(ev, series_name)
    if not xs:
        return None
    return sum(xs) / len(xs) * scale
