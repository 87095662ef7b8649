"""Output tokens committed in the window over the window's seconds."""
from bench import window


def read(run):
    return window.tokens_in_window(run.reqs, run.seconds) / run.seconds
