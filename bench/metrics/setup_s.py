"""setup_s: process start to the opening of the measured window: imports,
weights and inputs, compilation or compile-cache reads, solving or
plan-store reads, warm-up (host clock)."""


def read(run):
    return run.setup_s
