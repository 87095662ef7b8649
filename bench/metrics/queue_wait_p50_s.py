"""Median admission wait (the program's ``t_admit`` minus the due time)
over every request due in the window; one not admitted by the window's end
counts at its wait so far."""
from bench import window


def read(run):
    return window.pct(window.queue_wait_samples(run.reqs, run.seconds), 50)
