"""Live K/V as a share of the KV pool, in percent: the tokens written in
the active slots when each decode pass of the window starts (the pool
step's ``lengths``), averaged over the passes, over the pool's capacity
(``kv_pages`` x ``page_size`` tokens).  Reserved pages that hold nothing
yet do not count."""
import numpy as np

from bench import window


def read(run):
    calls = [c for c in window.calls_in_window(run.decode_calls, run.seconds)
             if c[2] is not None]
    if not calls:
        return None
    cap = run.engine["kv_pages"] * run.engine["page_size"]
    return 100.0 * float(np.mean([c[2].sum() for c in calls])) / cap
