"""Profiler trace to metrics.

:func:`extract` reads a JAX profiler ``.xplane.pb`` into plain records (the
form that ``bench/tests/data`` keeps a small recorded trace in);
:func:`reduce` turns them into device busy time, time per program, time per
``R.<region>`` scope and the idle gaps, inside one window of the trace's
clock.  Both are the yardstick: later changes to the program cannot alter
them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

_HLO_LINE = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name="([^"]*)"')


def hlo_scopes(hlo_text: str) -> dict:
    """instruction name -> op_name metadata, from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_LINE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")


def instruction_name(event_name: str) -> str:
    """An op event's HLO instruction name: a v5e trace names each op event
    by its whole instruction (``%fusion.7 = f32[..] fusion(..), ..``)."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def scope_of(op_name: str) -> str:
    """The ``R.`` regions of an op's name path, without layer indices:
    ``jit(step)/R.layer3/R.attn/dot`` -> ``attn``."""
    parts = [p[2:] for p in op_name.split("/") if p.startswith("R.")]
    parts = [p for p in parts if not re.fullmatch(r"layer\d+", p)]
    return "/".join(parts) if parts else "other"


def module_name(name: str) -> str:
    """``jit_step(12)`` -> ``jit_step``."""
    return name.split("(")[0].strip()


def latest_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def extract(path: str, host_prefix: str = "bench.") -> dict:
    """Plain records of one trace: device modules and ops per device plane,
    and host spans whose name starts with ``host_prefix``.  Times are
    nanoseconds on the trace's clock."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                lname = line.name.lower()
                if "module" in lname:
                    kind = "modules"
                elif lname in ("xla ops", "ops"):
                    kind = "ops"
                else:
                    continue
                for ev in line.events:
                    name = (instruction_name(ev.name) if kind == "ops"
                            else ev.name)
                    rec = [name, ev.start_ns, ev.end_ns]
                    if kind == "ops":
                        st = dict(ev.stats)
                        path = st.get("tf_op") or next(
                            (v for v in st.values()
                             if isinstance(v, str) and "R." in v), "")
                        rec.append(str(st.get("hlo_module", "")))
                        rec.append(str(path))
                    dev[kind].append(rec)
            if dev["modules"] or dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(host_prefix):
                        out["host"].append([ev.name, ev.start_ns, ev.end_ns])
    return out


def _union(intervals: list) -> list:
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def window_of(rec: dict, span: str, seconds: float) -> tuple:
    """[start, start + seconds) on the trace clock, from the start of the
    first host span named ``span``."""
    starts = [s for n, s, _ in rec["host"] if n == span]
    if not starts:
        raise ValueError(f"no host span {span!r} in the trace")
    t0 = min(starts)
    return t0, t0 + seconds * 1e9


def reduce(rec: dict, window: tuple, scopes: dict | None = None,
           label=None, n_gaps: int = 10) -> dict:
    """Per device plane, inside ``window`` (ns): busy ns (union of op
    intervals, else of module intervals), executions and device ns per
    module (those starting in the window), op ns per (module, scope), and
    the ``n_gaps`` longest idle gaps labelled by ``label(start_ns, end_ns)``.
    ``scopes`` maps module -> {op name -> op_name path} where the trace's
    op events carry no path of their own; an op that names no module is
    given the module whose execution encloses it.  Values are averaged
    over the device planes."""
    a, b = window
    scopes = scopes or {}
    per_dev = []
    for dev in rec["devices"].values():
        src = dev["ops"] or dev["modules"]
        spans = [(max(s, a), min(e, b)) for _, s, e, *_ in src
                 if e > a and s < b]
        busy = _union(spans)
        busy_ns = sum(e - s for s, e in busy)
        mods = defaultdict(lambda: [0, 0])
        for name, s, e in dev["modules"]:
            if a <= s < b:
                m = mods[module_name(name)]
                m[0] += 1
                m[1] += e - s
        ops = defaultdict(int)
        execs = sorted((s, e, module_name(n)) for n, s, e in dev["modules"])
        starts = [x[0] for x in execs]
        for name, s, e, mod, path in dev["ops"]:
            if a <= s < b:
                if not mod:
                    i = bisect.bisect_right(starts, s) - 1
                    if i >= 0 and s < execs[i][1]:
                        mod = execs[i][2]
                mod = module_name(mod)
                path = path or scopes.get(mod, {}).get(name, "")
                ops[(mod, scope_of(path))] += e - s
        gaps = []
        prev = a
        for s, e in busy + [[b, b]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        per_dev.append({"busy_ns": busy_ns, "modules": dict(mods),
                        "ops": dict(ops), "gaps": gaps[:n_gaps]})
    if not per_dev:
        return {}
    n = len(per_dev)
    first = per_dev[0]
    return {
        "window_ns": b - a,
        "busy_ns": sum(d["busy_ns"] for d in per_dev) / n,
        "modules": first["modules"],
        "ops": first["ops"],
        "gaps": [[label(s, e) if label else "idle", (e - s) / 1e9]
                 for s, e in first["gaps"]],
    }


def host_label(rec: dict, names: dict):
    """A gap labeller: the host span (by ``names``: span -> label) that
    overlaps the gap most, else None."""
    spans = [(n, s, e) for n, s, e in rec["host"] if n in names]

    def label(gs, ge):
        best, over = None, 0
        for n, s, e in spans:
            o = min(e, ge) - max(s, gs)
            if o > over:
                best, over = names[n], o
        return best
    return label
