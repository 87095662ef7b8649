#!/usr/bin/env python3
"""The chip benchmark's command.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process loads the cell's weights from the seed, warms up every program
the window uses, serves the cell's traffic for ``--seconds`` of wall time,
prints the metrics (``--trace 0``: the end-to-end ones; ``--trace 1``: the
per-layer ones, from a profiler trace of the window) and checks the served
tokens against the plain float32 reference.  The last stdout line is one
JSON object.  Without an accelerator, with fewer chips than the cell asks
for, or without the program's ``src/`` in the checkout, it exits non-zero
and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def configure() -> int:
    """Put the checkout and the program on the path and fix the compile
    cache; non-zero when the program's ``src/`` is missing."""
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: the program's src/repro is not in {ROOT}",
              file=sys.stderr)
        return 2
    import jax
    # the compile cache lives at a fixed path inside the checkout, unless
    # JAX_COMPILATION_CACHE_DIR names one (JAX then reads it itself)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if configure():
        return 2
    from bench import harness
    cell = harness.load_cell(args.workload, ROOT)
    try:
        harness.check_device(cell.chips)
    except SystemExit as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_START)
    harness.report(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
