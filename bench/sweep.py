#!/usr/bin/env python3
"""Find a chat cell's knee: the highest fixed arrival rate the system
sustains without a growing backlog, by one sweep on the chip.

  python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
      --rates 1,2,3,4

One process builds the cell's engine once, then serves the cell's mix at
each rate for ``--seconds``.  Per rate it prints one JSON line: requests
due, admitted and finished in the window, output tokens per second, the
time to first token of the first and the last third of the arrivals, and
the queue left at the window's end.  A rate above the knee shows the last
third waiting far longer than the first and a queue left over.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import ROOT, configure  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    if configure():
        return 2
    from bench import harness, system, traffic, window
    from bench import weights as W
    cell = harness.load_cell(args.workload, ROOT)
    harness.check_device(cell.chips)
    params = system.program_params(cell.cfg, W.key_data(args.seed))
    engine = system.build_engine(cell.cfg, params, cell.engine)
    del params
    engine.serve(system.requests(harness.warmup_specs(cell.engine),
                                 lambda _: []))
    print(f"setup_s {time.perf_counter() - T_START!r}", file=sys.stderr)
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = dict(cell.mix, rate_per_s=rate)
        clock = harness.Clock()
        specs = traffic.generate(mix, args.seed, args.seconds,
                                 cell.cfg["vocab_size"])
        reqs = system.requests(specs, lambda _: harness.Stamped(clock))
        clock.t0 = time.perf_counter()
        clock.close = clock.t0 + args.seconds
        try:
            engine.serve(reqs)
        except harness.WindowClosed:
            pass
        W_ = args.seconds
        rec = [window.Req(r.rid, r.arrival_s, r.prompt.size,
                          r.max_new_tokens, r.out_tokens.times, r.t_admit)
               for r in reqs]
        due = window.arrived(rec, W_)
        third = max(len(due) // 3, 1)
        ttft = window.ttft_samples(due, W_)
        print(json.dumps({
            "rate_per_s": rate, "due": len(due),
            "admitted": sum(1 for r in due if r.t_admit is not None
                            and r.t_admit < W_),
            "finished": sum(1 for r in due if len(r.times) == r.max_new),
            "out_tok_s": window.tokens_in_window(rec, W_) / W_,
            "ttft_p50_first_third_s": window.pct(ttft[:third], 50),
            "ttft_p50_last_third_s": window.pct(ttft[-third:], 50),
            "ttft_p90_s": window.pct(ttft, 90),
            "itl_p95_ms": (window.pct(window.token_gaps(rec, W_), 95)
                           or 0.0) * 1e3,
            "left_waiting": sum(1 for r in due if r.t_admit is None)}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
