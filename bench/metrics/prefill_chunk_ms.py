"""Device milliseconds per execution of the prefill-chunk program, from the
profiler trace of the window."""


def read(run):
    if not run.trace:
        return None
    n, ns = run.trace["modules"].get(run.programs["chunk"], (0, 0))
    return ns / n / 1e6 if n else None
