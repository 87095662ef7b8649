#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

  python3 bench/calibrate.py --workload <cell> --seconds <s> \
      --seeds <a,b,...> --control-seeds <c,d,...> [--out readings.jsonl]

In one process, for each seed: one run of the cell as ``bench/run.py``
makes it (weights from the seed, warm-up, the window at the cell's load),
then the widest gap of the served tokens below the float32 reference
(the lower reading's sample).  For each control seed, also the widest gap
of the fp8 control's own first choices at the same positions (the upper
reading's sample).  Prints one JSON line per seed.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if configure():
        return 2
    from bench import harness
    from bench.reference import dense
    cell = harness.load_cell(args.workload, ROOT)
    harness.check_device(cell.chips)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in seeds + sorted(control - set(seeds)):
        got = {}

        def gaps(cfg, kd, seqs, n_prompt):
            t = time.perf_counter()
            g = dense.served_gaps(cfg, kd, seqs, n_prompt)
            got["reference_s"] = time.perf_counter() - t
            if seed in control:
                c = dense.served_gaps(cfg, kd, seqs, n_prompt, control=True)
                got["control_widest_gap"] = float(max(x.max() for x in c))
                got["control_tokens"] = int(sum(x.size for x in c))
            return g

        out = harness.run_cell(cell, seed, args.seconds, False,
                               time.perf_counter(), gaps_fn=gaps)
        line = {"workload": args.workload, "seed": seed,
                "program_widest_gap": out["check"]["logit_gap"]["value"],
                "correct": out["correct"], **got, "notes": out["notes"],
                "metrics": {k: v["value"] for k, v in out["metrics"].items()}}
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
