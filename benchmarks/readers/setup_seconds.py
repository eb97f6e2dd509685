"""Process start to window open: build, seeded weights, warm-up of the
cell's own shapes, ramp."""


def read(ev):
    return ev.setup_s
