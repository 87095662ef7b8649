#!/usr/bin/env python3
"""One traced run of a cell, read through the program's own spans.

  python3 bench/record_spans.py --workload <cell> --seed <n> --seconds 45
  python3 bench/record_spans.py --workload <cell> --seed <n> --seconds 8 \\
      --from-ms 5000 --keep-ms 150 --out bench/tests/data/<name>.json

Runs the cell as ``bench/run.py --trace 1`` does and prints, before the
run's result line, one JSON line ``{"progtrace": {...}}`` read from the
serve loop's spans (``bench/progtrace.py``): ``host_gap_ms``,
``prefill_p90_s``, the program's decode passes and ``kv_live_share``
beside the harness's, the device's idle time by program phase, the ten
longest idle gaps named after the phase the host was in, and host
milliseconds per decode step in each phase.  With ``--out`` it also
writes ``--keep-ms`` of the window from ``--from-ms`` on (device records,
host spans and the harness's decode calls, shifted so that the cut starts
at 0) for ``bench/tests/test_bench_progtrace.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from record_trace import cut as cut_record  # noqa: E402
from run import ROOT, configure  # noqa: E402

FALLBACK = {"bench.decode_dispatch": "decode step dispatch (host)",
            "bench.chunk_dispatch": "prefill chunk dispatch (host)"}


def readings(rec, spans, win, run, reqs) -> dict:
    """What the program's spans say about one traced window."""
    from bench import devtrace, harness, progtrace, window
    red = devtrace.reduce(rec, win, None, lambda a, b: (a, b), n_gaps=10**9)
    gaps = [tuple(g[0]) for g in red["gaps"]]
    named = progtrace.labeller(spans)
    hosts = devtrace.host_label(rec, FALLBACK)

    def label(a, b):
        got = named(a, b) or hosts(a, b)
        return f"{got or 'no program span'} at {(a - win[0]) / 1e9:.3f} s"
    gc = [s for s in progtrace.named(spans, "python.gc")
          if win[0] <= s[1] < win[1]]
    return {
        "host_gap_ms": progtrace.host_gap_ms(spans, win),
        "prefill_p90_s": progtrace.prefill_p90_s(reqs, run.seconds),
        "decode_passes": {
            "program": len(progtrace.stepped_passes(spans, win)),
            "harness": len(window.calls_in_window(run.decode_calls,
                                                  run.seconds))},
        "kv_live_share": {
            "program": progtrace.kv_live_share(spans, win, run.engine),
            "harness": harness.reader("kv_live_share")(run)},
        "idle": progtrace.idle_account(gaps, spans),
        "idle_gaps": [[label(a, b), (b - a) / 1e9] for a, b in gaps[:10]],
        "phase_ms_per_step": progtrace.phase_ms_per_step(spans, win),
        "gc": {"count": len(gc),
               "ms": sum(s[2] - s[1] for s in gc) / 1e6,
               "longest_ms": max((s[2] - s[1] for s in gc), default=0)
               / 1e6},
    }


def cut(rec, spans, calls, a: int, b: int) -> dict:
    """``record_trace.cut``'s records of [a, b), with the program spans
    that overlap it and the harness's decode calls (placed on the trace's
    clock) that start in it, all shifted by ``-a``."""
    def sh(r):
        return [r[0] - a, r[1] - a, *r[2:]]
    return {"record": cut_record(rec, a, b),
            "spans": [[s[0], *sh(s[1:])] for s in spans
                      if s[2] > a and s[1] < b],
            "decode_calls": [sh(c) for c in calls if a <= c[0] < b]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--from-ms", type=float, default=0.0)
    ap.add_argument("--keep-ms", type=float, default=150.0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if configure():
        return 2
    import jax
    from bench import devtrace, harness, progtrace
    cell = harness.load_cell(args.workload, ROOT)
    harness.check_device(cell.chips)
    kind = jax.devices()[0].device_kind
    got = {}

    def on_engine(engine):
        serve = engine.serve

        def keep(reqs):
            got["reqs"] = reqs
            return serve(reqs)
        engine.serve = keep

    def on_trace(log_dir, run, modules):
        path = devtrace.latest_xplane(log_dir)
        rec = devtrace.extract(path)
        spans = progtrace.extract(path)
        win = devtrace.window_of(rec, "bench.window", run.seconds)
        got["readings"] = readings(rec, spans, win, run, got["reqs"])
        if args.out:
            a = win[0] + int(args.from_ms * 1e6)
            calls = [(int(win[0] + s * 1e9), int(win[0] + e * 1e9),
                      [int(x) for x in ln])
                     for s, e, ln in run.decode_calls]
            got["file"] = {
                "device_kind": kind, "workload": args.workload,
                "from_ms": args.from_ms, "keep_ms": args.keep_ms,
                "programs": run.programs, "engine": run.engine,
                **cut(rec, spans, calls, a, a + int(args.keep_ms * 1e6))}

    out = harness.run_cell(cell, args.seed, args.seconds, True, T_START,
                           on_engine=on_engine, on_trace=on_trace)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(got["file"], f, separators=(",", ":"))
    print(json.dumps({"progtrace": got["readings"]}))
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
