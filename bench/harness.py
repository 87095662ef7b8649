"""One run of one cell: set-up, the measured window, the metrics and the
check against the plain reference.

A cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); ``bench/cells/<cell>.json`` holds its
engine settings, the mix's per-cell parameters and the comparison's limit.
Each metric is read by ``bench/metrics/<metric>.py``.  All are found by
name, so a cell, a mix, a configuration or a metric is added by adding
files and entries.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Optional

import numpy as np

from bench import devtrace, traffic, window
from bench import weights as W

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
ABORT_PREFIX = "engine aborted: WindowClosed"
FAILED_STATES = ("failed", "expired", "rejected")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict               # configuration file
    mix: dict               # traffic mix, with the cell's overrides
    engine: dict            # engine settings
    check: dict             # {"compare_requests", "limit"}
    metrics: dict           # name -> {"unit", "kind": e2e|layer}


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    spec = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg = _json(root / "bench" / "configs" / f"{w['config']}.json")
    mix = _json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    own = _json(root / "bench" / "cells" / f"{name}.json")
    mix.update(own.get("traffic", {}))
    metrics = {m["name"]: {"unit": m["unit"], "kind": "e2e"}
               for m in spec["end_to_end"] if _applies(m, name)}
    metrics.update({m["name"]: {"unit": m["unit"], "kind": "layer"}
                    for m in spec["per_layer"] if _applies(m, name)})
    return Cell(name=name, chips=w["chips"], cfg=cfg, mix=mix,
                engine=own["engine"], check=own["check"], metrics=metrics)


def reader(name: str) -> Callable:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class WindowClosed(Exception):
    """Raised into the serve loop by the first token committed after the
    window's end: the harness's own bound on the drain."""


class Clock:
    t0 = 0.0
    close = float("inf")


class Stamped(list):
    """A request's output list that stamps each committed token."""

    def __init__(self, clock: Clock):
        super().__init__()
        self.clock = clock
        self.times: list = []

    def append(self, tok):
        t = time.perf_counter()
        if t >= self.clock.close:
            raise WindowClosed()
        self.times.append(t - self.clock.t0)
        super().append(tok)


class CompileLog:
    """Programs built by the backend, compiled or loaded from the
    persistent cache (JAX reports both under one event): when each ended
    and how long it took."""

    def __init__(self):
        import jax
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self.events.append((time.perf_counter(), duration))


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    seconds: float
    setup_s: float
    cfg: dict
    engine: dict                    # the cell's engine settings
    reqs: list                      # window.Req
    decode_calls: list              # (start, end, lengths of active slots)
    compiles: list                  # (time, seconds), window-relative
    peaks: Optional[dict]
    programs: dict                  # role -> module name
    trace: Optional[dict] = None    # devtrace.reduce(...)


def check_device(chips: int):
    """The device list, or SystemExit when JAX finds no accelerator or
    fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform == "cpu":
        raise SystemExit(f"no accelerator: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


def warmup_specs(engine: dict) -> list:
    """Two short requests whose prompts take a full and a padded chunk, so
    that the pool decode step and the one chunk width both run."""
    c = engine["prefill_chunk"]
    n = min(c + c // 2 + 1, engine["max_len"] - 8)
    rng = np.random.default_rng(0)
    return [traffic.Spec(rid=i, arrival_s=0.0,
                         prompt=rng.integers(0, 100, n, dtype=np.int32),
                         max_new_tokens=4) for i in range(2)]


def compare(cell: Cell, kd, reqs: list, seed: int,
            gaps_fn: Optional[Callable] = None) -> dict:
    """The widest gap by which a served token's logit lies below the plain
    reference's best, over a sample of the served requests drawn from the
    seed with the longest in it."""
    from bench.reference import dense
    gaps_fn = gaps_fn or dense.served_gaps
    served = [r for r in reqs if len(r.out_tokens) > 0]
    n = cell.check["compare_requests"]
    if not served:
        return {"requests": 0, "tokens": 0, "widest_gap": None}
    longest = max(served, key=lambda r: (len(r.out_tokens), -r.rid))
    rest = [r for r in served if r is not longest]
    pick = traffic.rng_for(seed, 5).permutation(len(rest))[:n - 1]
    sample = [longest] + [rest[i] for i in sorted(pick)]
    seqs = [np.concatenate([r.prompt, np.asarray(list(r.out_tokens),
                                                 np.int32)]) for r in sample]
    gaps = gaps_fn(cell.cfg, kd, seqs, [r.prompt.size for r in sample])
    return {"requests": len(sample),
            "tokens": int(sum(g.size for g in gaps)),
            "widest_gap": float(max(float(np.max(g)) for g in gaps))}


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, *, require_chip: bool = True,
             gaps_fn: Optional[Callable] = None,
             on_engine: Optional[Callable] = None,
             on_trace: Optional[Callable] = None) -> dict:
    """One run; returns the result line's object.  ``require_chip=False``
    and ``on_engine`` (called with the warmed engine, before the harness
    wraps its programs) serve the tests; ``on_trace`` (called with the
    trace's directory, the run and the programs' texts in a traced run)
    serves ``bench/record_trace.py``."""
    import jax
    from bench import system
    from bench.peaks import peaks_for
    devs = check_device(cell.chips) if require_chip else jax.devices()
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if require_chip else None
    clog = CompileLog()

    # -- set-up: weights from the seed, the engine, one warm-up serve -------
    kd = W.key_data(seed)
    params = system.program_params(cell.cfg, kd)
    engine = system.build_engine(cell.cfg, params, cell.engine)
    del params
    engine.serve(system.requests(warmup_specs(cell.engine), lambda _: []))
    n_setup_compiles = len(clog.events)
    clock = Clock()
    t0_ref = [0.0]
    decode_calls, chunk_calls = [], []
    if on_engine is not None:
        on_engine(engine)
    system.instrument(engine, decode_calls, chunk_calls, t0_ref, traced)
    specs = traffic.generate(cell.mix, seed, seconds, cell.cfg["vocab_size"])
    reqs = system.requests(specs, lambda _: Stamped(clock))
    modules = system.module_texts(engine) if traced else {}
    programs = {role: name for role, (name, _) in modules.items()}
    log_dir = None
    if traced:
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start

    # -- the window ----------------------------------------------------------
    finished_early = False
    with jax.profiler.TraceAnnotation("bench.window"):
        clock.t0 = t0_ref[0] = time.perf_counter()
        clock.close = clock.t0 + seconds
        try:
            engine.serve(reqs)
            finished_early = True
        except WindowClosed:
            pass
    t_end = time.perf_counter() - clock.t0
    if traced:
        jax.profiler.stop_trace()
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    system.check_instrumented(engine, decode_calls)

    # -- what the readers read; then the program's state is freed -----------
    if traced:
        decode_calls[:] = [(s, e, np.asarray(ln)[np.asarray(act)])
                           for s, e, (ln, act) in decode_calls]
    rec = [window.Req(rid=r.rid, due=r.arrival_s, prompt_len=r.prompt.size,
                      max_new=r.max_new_tokens, times=r.out_tokens.times,
                      t_admit=r.t_admit, state=r.state.value, error=r.error)
           for r in reqs]
    run = Run(seconds=seconds, setup_s=setup_s, cfg=cell.cfg,
              engine=cell.engine, reqs=rec,
              decode_calls=decode_calls,
              compiles=[(t - clock.t0, d) for t, d in clog.events[
                  n_setup_compiles:]],
              peaks=peaks, programs=programs)
    breakdown = None
    if traced:
        run.trace, breakdown = _read_trace(log_dir, run, modules)
        if on_trace is not None:
            on_trace(log_dir, run, modules)
        shutil.rmtree(log_dir, ignore_errors=True)
    del engine
    gc.collect()

    metrics = {}
    for name, m in cell.metrics.items():
        if (m["kind"] == "layer") != traced:
            continue
        v = reader(name)(run)
        if v is None:
            # every metric the cell declares has something to read here
            raise RuntimeError(f"metric {name!r} read nothing in this run")
        metrics[name] = {"value": v, "unit": m["unit"]}

    # -- the check against the plain reference -------------------------------
    cmp = compare(cell, kd, reqs, seed, gaps_fn)
    limit = cell.check["limit"]
    correct = (cmp["widest_gap"] is not None and cmp["widest_gap"] <= limit)
    in_window = window.arrived(rec, seconds)
    failed = [r for r in in_window if r.state in FAILED_STATES
              and not r.error.startswith(ABORT_PREFIX)]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if traced and run.trace:
        device["busy_s"] = run.trace["busy_ns"] / 1e9
        device["window_s"] = run.trace["window_ns"] / 1e9
    out = {"correct": bool(correct), "attempted": len(in_window),
           "failed": len(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["notes"] = {"setup_compiles": n_setup_compiles,
                    "window_compiles": len(run.compiles),
                    "served_to_end": finished_early, "serve_s": t_end,
                    "compared_requests": cmp["requests"],
                    "compared_tokens": cmp["tokens"]}
    out["check"] = {"logit_gap": {"value": cmp["widest_gap"],
                                  "limit": limit}}
    return out


def _read_trace(log_dir: str, run: Run, modules: dict):
    rec = devtrace.extract(devtrace.latest_xplane(log_dir))
    win = devtrace.window_of(rec, "bench.window", run.seconds)
    scopes = {name: devtrace.hlo_scopes(t) for name, t in modules.values()}
    hosts = devtrace.host_label(rec, {
        "bench.decode_dispatch": "decode step dispatch (host)",
        "bench.chunk_dispatch": "prefill chunk dispatch (host)"})

    def label(s, e):
        t = (s - win[0]) / 1e9
        got = hosts(s, e)
        if not got:
            busy = any(r.due <= t and len(r.times) < r.max_new
                       for r in run.reqs)
            got = "serve loop (host)" if busy else "waiting for an arrival"
        return f"{got} at {t:.3f} s"

    red = devtrace.reduce(rec, win, scopes, label)
    if not red or red["busy_ns"] <= 0:
        raise RuntimeError("the trace shows no device operation in the "
                           "window")
    role = {v: k for k, v in run.programs.items()}
    ops = sorted(((f"{role.get(m, m)}/{s}", ns / 1e9)
                  for (m, s), ns in red["ops"].items()),
                 key=lambda x: -x[1])[:10]
    if not ops:
        ops = sorted(((role.get(m, m), v[1] / 1e9)
                      for m, v in red["modules"].items()),
                     key=lambda x: -x[1])[:10]
    return red, {"device_ops": [list(x) for x in ops],
                 "idle_gaps": red["gaps"][:10]}


def report(out: dict) -> None:
    """The comparison's lines, last on standard error, then the result as
    the last line of standard output."""
    for name, c in out["check"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
