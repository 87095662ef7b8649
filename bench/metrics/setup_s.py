"""Set-up time: process start to the window's start (weights from the seed,
engine, warm-up serve, compiles or compile-cache loads)."""


def read(run):
    return run.setup_s
