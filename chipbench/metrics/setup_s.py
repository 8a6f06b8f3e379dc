"""Seconds from process start to the first timed request: imports,
weights, the pool, warming every shape the mix uses, compiling in a
run whose cache is cold."""


def read(rec):
    return rec.setup_s
