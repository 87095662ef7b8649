"""Tokens committed in the window per pool decode pass started in it: the
mean number of slots a pass serves."""
from bench import window


def read(run):
    passes = len(window.calls_in_window(run.decode_calls, run.seconds))
    if passes == 0:
        return None
    return window.tokens_in_window(run.reqs, run.seconds) / passes
