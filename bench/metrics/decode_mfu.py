"""The pool decode step's share of the chip's peak, in percent: the flops
a decode pass needs at live lengths and actual occupancy (from
``bench/shapes.py``), averaged over the window's passes, over the device
time per execution of the decode-step program (from the profiler trace)
and peak bf16 FLOP/s."""
import numpy as np

from bench import shapes, window


def read(run):
    if not run.trace or run.peaks is None:
        return None
    n_exec, ns = run.trace["modules"].get(run.programs["decode"], (0, 0))
    calls = [c for c in window.calls_in_window(run.decode_calls, run.seconds)
             if c[2] is not None]
    if not n_exec or not calls:
        return None
    flops = np.mean([shapes.decode_pass_work(run.cfg, c[2].tolist())[0]
                     for c in calls])
    return 100.0 * flops / (ns / n_exec / 1e9) / run.peaks["bf16_flops"]
