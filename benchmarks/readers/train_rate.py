"""Positions (batch x sequence, padding included) per second over all
the steps of the window and all its time, the last step's end included."""


def read(ev):
    if not ev.train_done_times:
        return None
    return (len(ev.train_done_times) * ev.positions_per_step
            / (ev.w1 - ev.w0))
