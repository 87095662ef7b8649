"""Benchmark harness: one module per paper table/figure.

  bench_bots        -> Table 1 (BOTS vs SMT mode)
  bench_smt_models  -> Figs 1-4 (applications vs SMT mode)
  bench_autotune    -> §4.2 (per-region tuning vs single global knob)
  bench_kernels     -> kernel block tuning curve (VMEM occupancy model)
  bench_serve       -> paged vs slot vs static batching under staggered load

Prints ``name,us_per_call,derived`` CSV rows and exits non-zero when any
module failed (its row reads ``<name>_FAILED``).  Modules that populate a
``json_summary`` dict additionally get it written to ``BENCH_<name>.json``
(machine-readable: tok/s, latency percentiles, HBM high-water) so the perf
trajectory is tracked across PRs.
"""
from __future__ import annotations

import json
import sys
import time


def main() -> int:
    import benchmarks.bench_autotune as b_autotune
    import benchmarks.bench_bots as b_bots
    import benchmarks.bench_kernels as b_kernels
    import benchmarks.bench_serve as b_serve
    import benchmarks.bench_smt_models as b_smt

    mods = {"bots": b_bots, "smt_models": b_smt, "autotune": b_autotune,
            "kernels": b_kernels, "serve": b_serve}
    only = sys.argv[1] if len(sys.argv) > 1 else None
    print("name,us_per_call,derived")
    failed = []
    for name, mod in mods.items():
        if only and only != name:
            continue
        t0 = time.time()
        try:
            for row in mod.run():
                print(row, flush=True)
            summary = getattr(mod, "json_summary", None)
            if summary:
                path = f"BENCH_{name}.json"
                with open(path, "w") as f:
                    json.dump(summary, f, indent=2)
                    f.write("\n")
                print(f"# wrote {path}", flush=True)
        except Exception as e:  # run the other modules, then fail
            failed.append(name)
            print(f"{name}_FAILED,NaN,{type(e).__name__}: {str(e)[:80]}")
        print(f"# {name} finished in {time.time()-t0:.1f}s", flush=True)
    if failed:
        print(f"# failed: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
