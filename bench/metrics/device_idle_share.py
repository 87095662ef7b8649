"""Share of the traced window, in percent, in which no operation ran on the
device (one minus the union of op intervals over the window)."""


def read(run):
    if not run.trace or run.trace["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - run.trace["busy_ns"] / run.trace["window_ns"])
