"""90th percentile of first token minus due time over every request due in
the window; a request with no first token by the window's end counts at
its wait so far."""
from bench import window


def read(run):
    return window.pct(window.ttft_samples(run.reqs, run.seconds), 90)
