"""Decode attention's share of its roofline, in percent: the least time its
work needs at live lengths (the larger of flops over peak bf16 FLOP/s and
bytes over HBM bandwidth, from ``bench/shapes.py``), per decode pass, over
the device time per pass of the ops under the ``R.attn`` scope of the pool
decode step.  The scope holds the q/k/v/o projections, the K/V write and
the attention itself, and so does the work counted."""
import numpy as np

from bench import shapes, window
from bench.peaks import least_time_s


def read(run):
    if not run.trace or run.peaks is None:
        return None
    mod = run.programs["decode"]
    n_exec = run.trace["modules"].get(mod, (0, 0))[0]
    attn_ns = run.trace["ops"].get((mod, "attn"), 0)
    calls = [c for c in window.calls_in_window(run.decode_calls, run.seconds)
             if c[2] is not None and len(c[2])]
    if not n_exec or not attn_ns or not calls:
        return None
    least = np.mean([least_time_s(*shapes.attn_decode_work(
        run.cfg, c[2].tolist()), run.peaks)[0] for c in calls])
    return 100.0 * least / (attn_ns / n_exec / 1e9)
