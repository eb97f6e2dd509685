"""Mean length of the program's ``queue_wait`` spans that began inside
the window (request trace, server side)."""


def read(ev, scale=1.0):
    xs = [b - a for a, b in ev.queue_waits if ev.w0 <= a < ev.w1]
    if not xs:
        return None
    return sum(xs) / len(xs) * scale
