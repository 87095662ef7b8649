#!/usr/bin/env python3
"""Record a small profiler trace of one cell on the chip, for the test of
the reduction from trace to metrics (``bench/tests/test_bench_trace.py``).

  python3 bench/record_trace.py --workload <cell> --seed <n> --seconds 3 \
      --keep-ms 200 --out bench/tests/data/<name>.json

One traced run of the cell, as ``bench/run.py --trace 1`` makes it; the
trace's records are cut to the first ``--keep-ms`` of the window and
shifted so that the window starts at 0, and written with the programs'
module names and the op-name paths of the ops kept (the trace's own op
events carry none on a v5e).  Also prints the run's result line and the
reading of ``attn_roofline``, which no cell declares yet.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, configure  # noqa: E402


def cut(rec: dict, a: int, b: int) -> dict:
    """The records that start in [a, b), host spans that overlap it, with
    times shifted by ``-a``."""
    def sh(r):
        return [r[0], r[1] - a, r[2] - a, *r[3:]]
    out = {"devices": {}, "host": [sh(h) for h in rec["host"]
                                   if h[2] > a and h[1] < b]}
    for name, dev in rec["devices"].items():
        out["devices"][name] = {
            kind: [sh(r) for r in dev[kind] if a <= r[1] < b]
            for kind in ("modules", "ops")}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-ms", type=float, default=200.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    if configure():
        return 2
    from bench import devtrace, harness
    cell = harness.load_cell(args.workload, ROOT)
    harness.check_device(cell.chips)
    got = {}

    def keep(log_dir, run, modules):
        rec = devtrace.extract(devtrace.latest_xplane(log_dir))
        a, _ = devtrace.window_of(rec, "bench.window", run.seconds)
        small = cut(rec, a, a + int(args.keep_ms * 1e6))
        names = {r[0] for d in small["devices"].values() for r in d["ops"]}
        scopes = {}
        for name, text in modules.values():
            s = devtrace.hlo_scopes(text)
            scopes[name] = {k: v for k, v in s.items() if k in names}
        got["file"] = {"device_kind": cell_kind, "workload": args.workload,
                       "keep_ms": args.keep_ms, "programs": run.programs,
                       "scopes": scopes, "record": small}
        got["attn_roofline"] = harness.reader("attn_roofline")(run)

    import jax
    cell_kind = jax.devices()[0].device_kind
    out = harness.run_cell(cell, args.seed, args.seconds, True,
                           T_START, on_trace=keep)
    with open(args.out, "w") as f:
        json.dump(got["file"], f, separators=(",", ":"))
    print(json.dumps({"attn_roofline": got["attn_roofline"]}))
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
