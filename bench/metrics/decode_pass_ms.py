"""Window milliseconds per pool decode pass started in the window: the
serve loop's whole pass, host work and prefill chunks included."""
from bench import window


def read(run):
    passes = len(window.calls_in_window(run.decode_calls, run.seconds))
    return None if passes == 0 else run.seconds * 1e3 / passes
