"""Programs compiled or loaded from the compile cache that ended inside
the window (there should be none)."""


def read(run):
    return float(sum(1 for t, _ in run.compiles if 0 <= t < run.seconds))
