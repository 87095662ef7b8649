import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Hillclimb driver: diagnose a dry-run cell in depth (per-region bytes,
largest individual collectives with shapes) and evaluate candidate plans,
logging hypothesis -> change -> before -> after rows to
experiments/perf_log.jsonl.

  python -m repro.launch.hillclimb diagnose --arch zamba2-2.7b --shape train_4k
  python -m repro.launch.hillclimb try --arch ... --shape ... --plan plans/x.json \
      --hypothesis "..."
"""
import argparse
import json
import re
import time

from repro.core import counters as counters_mod
from repro.core import roofline as roofline_mod
from repro.core.policy import RegionPlan
from repro.launch.dryrun import build_lowered
from repro.launch.mesh import make_production_mesh

PERF_LOG = "experiments/perf_log.jsonl"


def _largest_collectives(hlo_text: str, n: int = 12):
    """Scan entry + all computations for the biggest collective operands."""
    hc = counters_mod.HloCost(hlo_text)
    found = []
    for comp, lines in hc.computations.items():
        symbols = hc._symbols(comp)
        for line in lines:
            parsed = counters_mod._parse_instr(line)
            if not parsed:
                continue
            name, out_type, opcode, rest = parsed
            base = opcode.replace("-start", "")
            if base not in counters_mod.COLLECTIVES:
                continue
            shard, link, grp = counters_mod._collective_cost(
                base, rest, out_type, symbols)
            meta = counters_mod._METADATA_RE.search(line)
            region = "/".join(counters_mod._REGION_RE.findall(meta.group(1))) if meta else ""
            found.append((link, base, out_type.strip()[:60], region, comp[:24], grp))
    found.sort(reverse=True)
    return found[:n]


def diagnose(arch: str, shape: str, plan_path=None, microbatch=0):
    mesh = make_production_mesh(multi_pod=False)
    plan = None
    if plan_path:
        plan = RegionPlan.from_json(open(plan_path).read(), mesh=mesh)
    lowered, meta = build_lowered(arch, shape, mesh, plan, microbatch)
    compiled = lowered.compile()
    rc = counters_mod.collect(compiled)
    rl = roofline_mod.from_counters(rc.total)
    print(f"== {arch} x {shape} ==")
    print(f"roofline: compute={rl.compute_s:.2f}s memory={rl.memory_s:.2f}s "
          f"collective={rl.collective_s:.2f}s dominant={rl.dominant}")
    ma = compiled.memory_analysis()
    print(f"memory: args={ma.argument_size_in_bytes/2**30:.1f}GiB "
          f"temp={ma.temp_size_in_bytes/2**30:.1f}GiB")
    print("\ntop regions by bytes:")
    for r, b in rc.top_regions("bytes", 10):
        c = rc.regions[r]
        print(f"  {r:28s} bytes={b:.3e} ({b/max(rc.total.bytes,1)*100:4.1f}%) "
              f"flops={c.flops:.2e} AI={c.flops/max(b,1):.1f}")
    print("\ntop regions by link bytes:")
    for r, b in rc.top_regions("link_bytes", 8):
        print(f"  {r:28s} link={b:.3e} ({b/max(rc.total.link_bytes,1)*100:4.1f}%)")
    print("\nlargest single collectives (per-device link bytes x trip):")
    for link, op, typ, region, comp, grp in _largest_collectives(compiled.as_text()):
        print(f"  {op:18s} {link:.3e}B groups={grp:3d} region={region:24s} "
              f"{typ}  [in {comp}]")
    return rc, rl


def try_plan(arch: str, shape: str, plan_path: str, hypothesis: str,
             microbatch=0, label=""):
    mesh = make_production_mesh(multi_pod=False)
    plan = RegionPlan.from_json(open(plan_path).read(), mesh=mesh)
    t0 = time.time()
    lowered, meta = build_lowered(arch, shape, mesh, plan, microbatch)
    compiled = lowered.compile()
    rc = counters_mod.collect(compiled)
    rl = roofline_mod.from_counters(rc.total)
    ma = compiled.memory_analysis()
    row = {
        "arch": arch, "shape": shape, "plan": plan_path, "label": label,
        "hypothesis": hypothesis, "compile_s": round(time.time() - t0, 1),
        "roofline": rl.to_json(),
        "peak_gib": (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 2**30,
    }
    os.makedirs(os.path.dirname(PERF_LOG), exist_ok=True)
    with open(PERF_LOG, "a") as f:
        f.write(json.dumps(row) + "\n")
    print(json.dumps(row, indent=2))
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cmd", choices=["diagnose", "try"])
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--plan", default=None)
    ap.add_argument("--hypothesis", default="")
    ap.add_argument("--label", default="")
    ap.add_argument("--microbatch", type=int, default=0)
    args = ap.parse_args()
    if args.cmd == "diagnose":
        diagnose(args.arch, args.shape, args.plan, args.microbatch)
    else:
        try_plan(args.arch, args.shape, args.plan, args.hypothesis,
                 args.microbatch, args.label)


if __name__ == "__main__":
    main()
