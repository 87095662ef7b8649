"""The program's own spans in a profiler trace, and what they say.

The serve loop (``repro.serve.engine``) records its phases as profiler
spans (``serve.run`` > ``serve.loop`` > ``serve.pass`` > ``serve.admit``,
``serve.chunk_dispatch``, ``serve.headroom``, ``serve.step_dispatch``,
``serve.step_wait``, ``serve.commit``, ``serve.tap``, ``serve.idle``) and
each garbage collection as ``python.gc``, with counters as the spans'
metadata.  They sit in the same trace as the device's ops, on its clock.
:func:`extract` reads them; the rest reduces them: the host's time
between one decode step's tokens and the next step's dispatch, the
prefill time of admitted requests, the program's pass and live-token
counts, and which phase the host was in during each device idle gap.
Times are nanoseconds on the trace's clock unless a name says otherwise.
"""
from __future__ import annotations

import numpy as np

from bench import window as W

#: Spans that hold phases rather than being one.
NESTING = ("serve.run", "serve.loop", "serve.pass")
WAIT = "serve.step_wait"
#: The label of an idle gap the host spent waiting for the device.
DEVICE_LABEL = "between device ops (host in serve.step_wait)"


def extract(path: str) -> list:
    """``[name, start_ns, end_ns, counters]`` of every ``serve.*`` and
    ``python.gc`` host event of one ``.xplane.pb``, by start."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("serve.") or ev.name == "python.gc":
                    out.append([ev.name, ev.start_ns, ev.end_ns,
                                dict(ev.stats)])
    out.sort(key=lambda s: (s[1], -s[2]))
    return out


def named(spans: list, name: str) -> list:
    return [s for s in spans if s[0] == name]


def host_gap_ms(spans: list, win: tuple):
    """Median, over the decode steps whose dispatch ends in ``win``, of
    the time from the end of the previous step's ``serve.step_wait`` to
    the end of this step's ``serve.step_dispatch``, clamped at 0 (a loop
    that dispatches the next step before it fetches the last reads 0).
    The k-th wait pairs with the (k+1)-th dispatch, once waits that
    precede the first dispatch are dropped."""
    disp = named(spans, "serve.step_dispatch")
    if not disp:
        return None
    waits = [w for w in named(spans, WAIT) if w[1] >= disp[0][1]]
    gaps = [max(d[2] - w[2], 0) for w, d in zip(waits, disp[1:])
            if win[0] <= d[2] < win[1]]
    return float(np.median(gaps)) / 1e6 if gaps else None


def prefill_samples(reqs, seconds: float) -> list:
    """``t_prefilled - t_admit`` (program clock) of every request admitted
    in ``[0, seconds)``; one not yet prefilled at the window's end counts
    at its time so far."""
    out = []
    for r in reqs:
        if r.t_admit is None or r.t_admit >= seconds:
            continue
        done = r.t_prefilled
        if done is None or done < r.t_admit:    # a re-admission's prefill
            done = seconds
        out.append(min(done, seconds) - r.t_admit)
    return out


def prefill_p90_s(reqs, seconds: float):
    return W.pct(prefill_samples(reqs, seconds), 90)


def stepped_passes(spans: list, win: tuple) -> list:
    """The counters of each ``serve.pass`` starting in ``win`` that ran a
    decode step."""
    return [s[3] for s in named(spans, "serve.pass")
            if win[0] <= s[1] < win[1] and "step" in s[3]]


def live_tokens(spans: list, win: tuple) -> list:
    """``live_tokens`` of each decode step dispatched in ``win``: the sum
    of the step's ``lengths`` over the slots that step."""
    return [s[3]["live_tokens"] for s in named(spans, "serve.step_dispatch")
            if win[0] <= s[1] < win[1]]


def kv_live_share(spans: list, win: tuple, engine: dict):
    """``kv_live_share`` from the program's counter: mean live tokens per
    step over the pool's tokens, in percent."""
    got = live_tokens(spans, win)
    if not got:
        return None
    cap = engine["kv_pages"] * engine["page_size"]
    return 100.0 * float(np.mean(got)) / cap


def _depth(name: str) -> int:
    """How deep a span nests: run, loop, pass, a phase, a collection."""
    return NESTING.index(name) if name in NESTING else (
        4 if name == "python.gc" else 3)


def labeller(spans: list):
    """``label(start_ns, end_ns)``: the span name whose spans cover most
    of the interval — of the names that cover more than half of it, the
    innermost; else the name that covers the most — with
    :data:`DEVICE_LABEL` for ``serve.step_wait``; None where no program
    span overlaps."""
    by_name = {}
    for name, s, e, _ in spans:
        by_name.setdefault(name, []).append((s, e))
    by_name = {n: np.asarray(v, np.int64).T for n, v in by_name.items()}

    def label(a, b):
        cover = {n: int(np.clip(np.minimum(e, b) - np.maximum(s, a), 0,
                                None).sum())
                 for n, (s, e) in by_name.items()}
        cover = {n: c for n, c in cover.items() if c > 0}
        if not cover:
            return None
        most = [n for n, c in cover.items() if 2 * c > b - a]
        name = (max(most, key=_depth) if most
                else max(cover, key=lambda n: (cover[n], _depth(n))))
        return DEVICE_LABEL if name == WAIT else name
    return label


def _union(intervals) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def covered_ns(gaps: list, intervals) -> int:
    """How much of ``gaps`` (disjoint ``(start, end)``) the union of
    ``intervals`` covers: F(end) - F(start) summed over the gaps, where
    F(x) is the union's measure below x."""
    u = np.asarray(_union(intervals), np.int64).reshape(-1, 2)
    g = np.asarray(gaps, np.int64).reshape(-1, 2)
    if not len(u) or not len(g):
        return 0
    size = u[:, 1] - u[:, 0]
    before = np.concatenate([[0], np.cumsum(size)])

    def measure_below(x):
        i = np.maximum(np.searchsorted(u[:, 0], x, side="right") - 1, 0)
        return before[i] + np.clip(x - u[i, 0], 0, size[i])
    return int((measure_below(g[:, 1]) - measure_below(g[:, 0])).sum())


def idle_account(gaps: list, spans: list) -> dict:
    """Device idle time (``gaps``: ``(start, end)`` in ns) against the
    program's spans: the share under any phase span (every span but
    :data:`NESTING`) and under a ``serve.pass``, and seconds under each
    span name (a ``python.gc`` inside a phase counts in both)."""
    idle = sum(b - a for a, b in gaps)
    if idle <= 0:
        return {"idle_s": 0.0}
    phases = [(s[1], s[2]) for s in spans if s[0] not in NESTING]
    by_name = {}
    for name in sorted({s[0] for s in spans}):
        by_name[name] = covered_ns(
            gaps, [(s[1], s[2]) for s in spans if s[0] == name]) / 1e9
    return {"idle_s": idle / 1e9,
            "under_phase_share": 100.0 * covered_ns(gaps, phases) / idle,
            "under_pass_share": 100.0 * covered_ns(
                gaps, [(s[1], s[2]) for s in named(spans, "serve.pass")])
            / idle,
            "idle_s_by_span": by_name}


def phase_ms_per_step(spans: list, win: tuple) -> dict:
    """Host milliseconds per decode step spent in each phase span that
    starts in ``win``, and the pass's own time outside its phases."""
    n = len(stepped_passes(spans, win))
    if not n:
        return {}
    inside = [s for s in spans if win[0] <= s[1] < win[1]]
    out = {}
    for s in inside:
        if s[0] not in NESTING:
            out[s[0]] = out.get(s[0], 0) + (s[2] - s[1])
    phases = sum(v for k, v in out.items() if k != "python.gc")
    passes = sum(s[2] - s[1] for s in inside if s[0] == "serve.pass")
    out["serve.pass (self)"] = passes - phases
    return {k: v / 1e6 / n for k, v in sorted(out.items())}
