"""Arithmetic of the measured window, on the harness's own clock.

Times are seconds from ``serve()`` entry.  The window is [0, seconds).
Counted are the requests due inside it and the tokens committed inside it.
A request with no first token by the window's end enters the time-to-first-
token sample at its wait so far, so that a stall cannot hide; the same
holds for the admission wait.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Req:
    rid: int
    due: float                      # arrival, seconds from serve() entry
    prompt_len: int
    max_new: int
    times: list                     # commit time of each served token
    t_admit: Optional[float] = None  # the program's admission time
    state: str = ""
    error: str = ""


def pct(values, q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation); None for no values."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def arrived(reqs: list, seconds: float) -> list:
    return [r for r in reqs if r.due < seconds]


def ttft_samples(reqs: list, seconds: float) -> list:
    out = []
    for r in arrived(reqs, seconds):
        first = r.times[0] if r.times else seconds
        out.append(min(first, seconds) - r.due)
    return out


def queue_wait_samples(reqs: list, seconds: float) -> list:
    out = []
    for r in arrived(reqs, seconds):
        if r.t_admit is not None and r.t_admit < seconds:
            out.append(r.t_admit - r.due)
        else:
            out.append(seconds - r.due)
    return out


def token_gaps(reqs: list, seconds: float) -> list:
    """Every gap between successive tokens of one request whose later
    token was committed in the window."""
    out = []
    for r in reqs:
        t = np.asarray([x for x in r.times if x < seconds])
        if t.size > 1:
            out.extend(np.diff(t).tolist())
    return out


def tokens_in_window(reqs: list, seconds: float) -> int:
    return sum(sum(1 for x in r.times if 0 <= x < seconds) for r in reqs)


def calls_in_window(calls: list, seconds: float) -> list:
    return [c for c in calls if 0 <= c[0] < seconds]
