"""Compilations inside the window: the larger of the program's own
CompileLog delta and JAX's own count.  Must read 0."""


def read(ev):
    return ev.compiles_in_window
