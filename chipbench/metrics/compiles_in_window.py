"""Compilations inside the measured window (JAX monitoring events): a
program that was not warmed up compiles in the window and stalls it."""


def read(data):
    t0, t1 = data.window
    return float(len(data.served.rec.compiles_between(t0, t1)))
