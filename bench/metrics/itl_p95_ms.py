"""95th percentile of every gap between successive tokens of one request
whose later token was committed in the window, in milliseconds."""
from bench import window


def read(run):
    v = window.pct(window.token_gaps(run.reqs, run.seconds), 95)
    return None if v is None else v * 1e3
