"""Compiled-artifact performance counters (the libhpm/pmapi analog).

Parses post-SPMD HLO text (``compiled.as_text()``) into per-region counters:

  * flops          — 2·M·N·K for dots (from inline operand shapes +
                     contracting dims) and for the convolutions the TPU
                     backend lowers matmuls to (padding taps excluded),
                     element count for everything else
  * bytes          — operand + output bytes per instruction
  * collective_bytes / collective ops census (all-gather, all-reduce,
    reduce-scatter, all-to-all, collective-permute), with ring-cost link
    bytes for the collective roofline term
  * while loops    — bodies are multiplied by their trip count (parsed from
    the loop condition), fixing XLA cost_analysis's count-body-once of
    ``lax.scan`` — REQUIRED for the scan-based archs (rwkv6/mamba2)
  * fusions/calls  — recursively costed via their called computations

Region attribution: named-scope paths (``R.<name>``) survive in each op's
``metadata op_name``; an op belongs to the innermost region path.  Backward
ops carry the same scopes under ``transpose(jvp(...))`` and are attributed to
the same region (a region's cost = its fwd+bwd, as the paper's per-region
timers would see).

Shapes in post-SPMD HLO are per-partition, so all numbers are per-device.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, Optional

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1, "token": 0,
    "opaque": 0,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_LHS_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*")
_OPCODE_RE = re.compile(r"\s*([\w\-]+)\((.*)$")
_METADATA_RE = re.compile(r'op_name="([^"]*)"')
_REGION_RE = re.compile(r"R\.([\w.]+)")
_CALLS_RE = re.compile(r"(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_REPLICA_RE = re.compile(r"replica_groups=\{?\[?(\d+),(\d+)\]?")


def _parse_instr(line: str):
    """``(name, out_type, opcode, rest)`` of one HLO instruction line, or
    None.  The type is scanned bracket-balanced rather than by a character
    class, because TPU layouts carry tiling and memory-space annotations
    inside the braces (``bf16[4,2048]{1,0:T(4,128)(2,1)S(1)}``) and tuple
    types nest them (``(f32[8]{0:T(128)}, u32[]{:S(2)})``)."""
    m = _LHS_RE.match(line)
    if not m:
        return None
    start, depth = m.end(), 0
    for end in range(start, len(line)):
        ch = line[end]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            break
    else:
        return None
    op = _OPCODE_RE.match(line, end)
    if not op:
        return None
    return m.group(1), line[start:end], op.group(1), op.group(2)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        b = DTYPE_BYTES.get(dt)
        if b is None:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * b
    return total


def _shape_elems(type_str: str) -> int:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return 0
    n = 1
    for d in m.group(2).split(","):
        if d:
            n *= int(d)
    return n


def _first_shape_dims(type_str: str) -> list[int]:
    m = _SHAPE_RE.search(type_str)
    if not m or not m.group(2):
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Counters:
    flops: float = 0.0
    bytes: float = 0.0
    collective_bytes: float = 0.0   # sum of shard bytes through collectives
    link_bytes: float = 0.0         # ring-cost bytes through the busiest link
    collective_ops: int = 0
    ops: int = 0
    prefix_hit_rate: float = 0.0    # serve-side channel (not from HLO):
                                    # prefix-cache hits/lookups observed over
                                    # the tap window, so the decider can see
                                    # why mem_prefix_* classes earn reward
    fault_rate: float = 0.0         # serve-side channel (not from HLO):
                                    # HealthMonitor faulted-step fraction
                                    # over the tap window, so the decider
                                    # can learn degradation responses from
                                    # the corpus like any other knob
    step_latency_p99: float = 0.0   # serve-side channel (not from HLO):
                                    # windowed p99 decode-step latency,
                                    # quantized to coarse log10(1+ms)
                                    # steps (corpus.bucket_log_ms) so the
                                    # decider can learn from observed
                                    # tail latency, not just tok/s
    queue_delay: float = 0.0        # serve-side channel (not from HLO):
                                    # mean admission wait over the tap
                                    # window, same log-ms quantization

    def scaled(self, mult: float) -> "Counters":
        """A copy with flops/bytes terms scaled (e.g. by pool occupancy:
        the serve-time decider attributes a fixed-shape step's measured
        counters to the fraction of slots doing useful work).  Rates
        (prefix_hit_rate, fault_rate) and latency channels
        (step_latency_p99, queue_delay) are occupancy-invariant and
        copied through."""
        return Counters(flops=self.flops * mult, bytes=self.bytes * mult,
                        collective_bytes=self.collective_bytes * mult,
                        link_bytes=self.link_bytes * mult,
                        collective_ops=self.collective_ops, ops=self.ops,
                        prefix_hit_rate=self.prefix_hit_rate,
                        fault_rate=self.fault_rate,
                        step_latency_p99=self.step_latency_p99,
                        queue_delay=self.queue_delay)

    def add(self, other: "Counters", mult: float = 1.0,
            skip_bytes: bool = False):
        self.flops += other.flops * mult
        if not skip_bytes:
            self.bytes += other.bytes * mult
        self.collective_bytes += other.collective_bytes * mult
        self.link_bytes += other.link_bytes * mult
        self.collective_ops += int(other.collective_ops * mult)
        self.ops += int(other.ops * mult)


@dataclasses.dataclass
class Instr:
    name: str
    out_type: str
    opcode: str
    rest: str
    region: str
    counters: Counters
    called: list


def _split_operands(rest: str) -> list[str]:
    """Split the operand list at depth-0 commas (up to the closing paren)."""
    depth = 0
    out, cur = [], []
    for ch in rest:
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0:
                break
            depth -= 1
        if ch == "," and depth == 0:
            out.append("".join(cur))
            cur = []
        else:
            cur.append(ch)
    if cur:
        out.append("".join(cur))
    return out


_NAME_RE = re.compile(r"%([\w.\-]+)")


def _operand_type(op_str: str, symbols: Dict[str, str]) -> str:
    """Type of one operand: inline if present, else symbol-table lookup."""
    if _SHAPE_RE.search(op_str):
        return op_str
    m = _NAME_RE.search(op_str)
    if m:
        return symbols.get(m.group(1), "")
    return ""


def _dot_flops(out_type: str, rest: str, symbols: Dict[str, str]) -> float:
    ops = _split_operands(rest)
    if not ops:
        return 0.0
    lhs_dims = _first_shape_dims(_operand_type(ops[0], symbols))
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rest)
    k = 1
    if m and lhs_dims:
        for idx in m.group(1).split(","):
            if idx and int(idx) < len(lhs_dims):
                k *= lhs_dims[int(idx)]
    return 2.0 * _shape_elems(out_type) * k


def _window_taps(out_n: int, in_n: int, size: int, lo: int,
                 stride: int) -> int:
    """(output, kernel tap) pairs along one spatial dim that read real
    input, not padding."""
    taps = 0
    for o in range(out_n):
        start = o * stride - lo
        taps += max(0, min(start + size, in_n) - max(start, 0))
    return taps


def _conv_flops(out_type: str, rest: str, symbols: Dict[str, str]) -> float:
    """2 * multiply-adds of one ``convolution``, padding taps excluded.

    The TPU backend lowers matmuls to convolutions: ``bf_io->bf`` is a plain
    dot, and a dot with an extra output axis can become a windowed
    convolution over a size-1 spatial dim padded to the window
    (``bf0_i0o->b0f, window={size=32 pad=31_31}``), where all but one tap
    per output reads padding.  Each output element reduces over the rhs
    input features (per feature group) times its real window taps."""
    ops = _split_operands(rest)
    m = re.search(r"dim_labels=(\w+)_(\w+)->(\w+)", rest)
    if len(ops) < 2 or not m:
        return float(_shape_elems(out_type))
    lhs_l, rhs_l, out_l = m.groups()
    lhs = _first_shape_dims(_operand_type(ops[0], symbols))
    rhs = _first_shape_dims(_operand_type(ops[1], symbols))
    out = _first_shape_dims(out_type)
    if (len(lhs), len(rhs), len(out)) != (len(lhs_l), len(rhs_l),
                                         len(out_l)) or "i" not in rhs_l:
        return float(_shape_elems(out_type))
    k = rhs[rhs_l.index("i")]
    w = re.search(r"window=\{([^}]*)\}", rest)
    fields = dict(f.split("=", 1) for f in w.group(1).split()) if w else {}
    n_sp = sum(ch.isdigit() for ch in rhs_l)
    sizes = [int(x) for x in fields.get("size", "").split("x") if x]
    strides = [int(x) for x in fields.get("stride", "").split("x") if x]
    pads = [int(x.split("_")[0]) for x in fields.get("pad", "").split("x")
            if x]
    frac = 1.0
    for d in range(n_sp):
        o_n = out[out_l.index(str(d))]
        taps = _window_taps(o_n, lhs[lhs_l.index(str(d))],
                            sizes[d] if d < len(sizes) else 1,
                            pads[d] if d < len(pads) else 0,
                            strides[d] if d < len(strides) else 1)
        frac *= taps / max(o_n, 1)
    g = re.search(r"feature_group_count=(\d+)", rest)
    if g:
        k //= max(int(g.group(1)), 1)
    return 2.0 * _shape_elems(out_type) * k * frac


def _collective_cost(opcode: str, rest: str, out_type: str,
                     symbols: Dict[str, str]):
    """(shard_bytes, ring_link_bytes) for one collective instruction."""
    ops = _split_operands(rest)
    in_bytes = sum(_shape_bytes(_operand_type(o, symbols)) for o in ops)
    out_bytes = _shape_bytes(out_type)
    m = _REPLICA_RE.search(rest)
    n = 1
    if m:
        # replica_groups={{...}} textual form varies; [G,N] iota form preferred
        g, per = int(m.group(1)), int(m.group(2))
        n = per if per > 1 else g
    else:
        m2 = re.search(r"replica_groups=\{\{([\d,]+)\}", rest)
        if m2:
            n = len(m2.group(1).split(","))
    n = max(n, 1)
    if opcode == "all-gather":
        shard, link = in_bytes, in_bytes * max(n - 1, 0)
    elif opcode == "all-reduce":
        shard, link = in_bytes, 2.0 * in_bytes * max(n - 1, 0) / max(n, 1)
    elif opcode == "reduce-scatter":
        shard, link = out_bytes, out_bytes * max(n - 1, 0)
    elif opcode == "all-to-all":
        shard, link = in_bytes, in_bytes * max(n - 1, 0) / max(n, 1)
    else:  # collective-permute
        shard, link = in_bytes, in_bytes
    return float(shard), float(link), n


def _trip_count(cond_lines: list[str]) -> int:
    """Extract the while trip count from its condition computation."""
    consts = {}
    for ln in cond_lines:
        m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*s\d+\[\](?:\{[^}]*\})?\s*"
                     r"constant\((\d+)\)", ln)
        if m:
            consts[m.group(1)] = int(m.group(2))
    for ln in cond_lines:
        if "compare(" in ln:
            for name, val in consts.items():
                if re.search(r"%?" + re.escape(name) + r"\b", ln.split("compare(", 1)[1]):
                    return max(val, 1)
    return 1


class HloCost:
    """Cost model over one HLO module's text."""

    def __init__(self, hlo_text: str):
        self.computations: Dict[str, list[str]] = {}
        self.entry: Optional[str] = None
        self._parse_computations(hlo_text)
        self._comp_cache: Dict[str, tuple[Counters, Dict[str, Counters]]] = {}
        self._symbol_cache: Dict[str, Dict[str, str]] = {}
        self._root_cache: Dict[str, str] = {}
        self.total = Counters()
        self.regions: Dict[str, Counters] = defaultdict(Counters)
        self.collective_census: Dict[str, int] = defaultdict(int)
        if self.entry:
            total, regions = self._cost_computation(self.entry)
            self.total = total
            for r, c in regions.items():
                self.regions[r].add(c)

    # -- parsing -----------------------------------------------------------
    def _parse_computations(self, text: str):
        cur_name, cur_lines = None, []
        for line in text.splitlines():
            stripped = line.strip()
            m = re.match(r"(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{", stripped)
            if m and "=" not in stripped.split("(")[0]:
                cur_name = m.group(2)
                cur_lines = []
                self.computations[cur_name] = cur_lines
                if m.group(1):
                    self.entry = cur_name
                continue
            if stripped == "}":
                cur_name = None
                continue
            if cur_name is not None:
                cur_lines.append(line)

    # -- costing -----------------------------------------------------------
    def _fusion_root_opcode(self, comp: str) -> str:
        if comp in self._root_cache:
            return self._root_cache[comp]
        root = ""
        for line in self.computations.get(comp, ()):
            if line.strip().startswith("ROOT"):
                parsed = _parse_instr(line)
                if parsed:
                    root = parsed[2]
                break
        self._root_cache[comp] = root
        return root

    def _symbols(self, comp: str) -> Dict[str, str]:
        """name -> output type for every instruction in a computation."""
        if comp in self._symbol_cache:
            return self._symbol_cache[comp]
        table: Dict[str, str] = {}
        for line in self.computations.get(comp, ()):
            parsed = _parse_instr(line)
            if parsed:
                table[parsed[0]] = parsed[1]
        self._symbol_cache[comp] = table
        return table

    def _cost_instr(self, line: str, symbols: Dict[str, str],
                    in_loop: bool = False):
        parsed = _parse_instr(line)
        if not parsed:
            return None
        name, out_type, opcode, rest = parsed
        c = Counters(ops=1)
        meta = _METADATA_RE.search(line)
        region = ""
        if meta:
            parts = _REGION_RE.findall(meta.group(1))
            if parts:
                region = "/".join(parts)
        called = _CALLS_RE.findall(rest) if opcode in (
            "while", "fusion", "call", "conditional", "reduce", "map",
            "reduce-window", "scatter", "sort", "custom-call") else []

        out_bytes = _shape_bytes(out_type)
        ops_list = _split_operands(rest)
        in_bytes = sum(_shape_bytes(_operand_type(o, symbols))
                       for o in ops_list)

        if opcode in ("dot", "convolution"):
            c.flops = (_dot_flops if opcode == "dot" else _conv_flops)(
                out_type, rest, symbols)
            c.bytes = in_bytes + out_bytes
        elif opcode == "fusion" and called and (
                self._fusion_root_opcode(called[0]) in
                ("dynamic-update-slice", "scatter")
                or (in_loop and self._fusion_root_opcode(called[0]) in
                    ("convert", "bitcast", "copy")
                    and any(_shape_bytes(_operand_type(o, symbols))
                            >= 0.45 * out_bytes for o in ops_list))):
            # Loop-carry in-place patterns: (a) scan residual saves / KV
            # writes (DUS/scatter root) and (b) carry-sized convert/bitcast
            # fusions inside while bodies (grad-accumulator & remat-stack
            # juggling).  XLA aliases the big buffer; true traffic is the
            # slice-sized operands.  Counting the full buffer per iteration
            # would overstate memory by the trip count (see EXPERIMENTS.md
            # §Census-fidelity).
            big = max(out_bytes, max((_shape_bytes(_operand_type(o, symbols))
                                      for o in ops_list), default=0))
            small = sum(b for b in (_shape_bytes(_operand_type(o, symbols))
                                    for o in ops_list) if b < 0.45 * big)
            c.bytes = 2.0 * small
        elif opcode == "fusion" and called and self._fusion_root_opcode(
                called[0]) == "dynamic-slice":
            # slice read from a big buffer (scan residual loads)
            c.bytes = 2.0 * out_bytes
        elif opcode in COLLECTIVES or opcode.rstrip("-start") in COLLECTIVES:
            base = opcode.replace("-start", "")
            if base in COLLECTIVES:
                shard, link, n = _collective_cost(base, rest, out_type, symbols)
                c.collective_bytes = shard
                c.link_bytes = link
                c.collective_ops = 1
                c.bytes = in_bytes + out_bytes
                self.collective_census[base] += 1
        elif opcode in ("parameter", "constant", "get-tuple-element", "tuple",
                        "bitcast", "after-all", "all-gather-done",
                        "all-reduce-done"):
            pass  # free / bookkeeping
        elif opcode == "fusion":
            # fused intermediates never hit HBM: bytes = boundary traffic
            # (body contributes flops/collectives only — see _cost_computation)
            c.bytes = in_bytes + out_bytes
        elif opcode in ("dynamic-update-slice", "scatter"):
            # in-place update: traffic = the update (+indices), not the
            # whole operand (XLA aliases the big buffer)
            upd = (sum(_shape_bytes(_operand_type(o, symbols))
                       for o in ops_list[1:]) if len(ops_list) > 1 else 0)
            c.bytes = 2.0 * upd
        elif opcode == "dynamic-slice":
            c.bytes = 2.0 * out_bytes
        elif opcode in ("while", "call", "conditional"):
            c.bytes = 0  # body costs added by caller
        else:
            # elementwise-ish default: 1 flop per output element + traffic
            c.flops = float(_shape_elems(out_type))
            c.bytes = in_bytes + out_bytes
        return Instr(name, out_type, opcode, rest, region, c, called)

    def _cost_computation(self, comp: str, in_loop: bool = False):
        key = (comp, in_loop)
        if key in self._comp_cache:
            return self._comp_cache[key]
        total = Counters()
        regions: Dict[str, Counters] = defaultdict(Counters)
        # pre-insert to guard against recursion
        self._comp_cache[key] = (total, regions)
        symbols = self._symbols(comp)
        for line in self.computations.get(comp, ()):
            instr = self._cost_instr(line, symbols, in_loop)
            if instr is None:
                continue
            if instr.opcode == "while" and instr.called:
                mb = re.search(r"body=%?([\w.\-]+)", instr.rest)
                mc = re.search(r"condition=%?([\w.\-]+)", instr.rest)
                body = mb.group(1) if mb else None
                cond = mc.group(1) if mc else None
                # exact trip count from XLA's backend_config when present
                mt = re.search(r'known_trip_count.{0,8}?"n":"(\d+)"', line)
                if mt:
                    trip = int(mt.group(1))
                else:
                    trip = _trip_count(self.computations.get(cond, [])) if cond else 1
                if body:
                    bt, br = self._cost_computation(body, True)
                    total.add(bt, trip)
                    for r, cc in br.items():
                        regions[r].add(cc, trip)
            elif instr.called:
                fused = instr.opcode == "fusion"
                for callee in instr.called:
                    bt, br = self._cost_computation(callee, in_loop)
                    total.add(bt, skip_bytes=fused)
                    for r, cc in br.items():
                        regions[r or instr.region].add(cc, skip_bytes=fused)
            total.add(instr.counters)
            regions[instr.region].add(instr.counters)
        self._comp_cache[key] = (total, regions)
        return total, regions


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RegionCounters:
    """Per-region + total counters for one compiled step (per-device)."""
    total: Counters
    regions: Dict[str, Counters]
    collective_census: Dict[str, int]
    xla_flops: float = 0.0      # cost_analysis cross-check (scan bodies 1x)
    xla_bytes: float = 0.0

    def top_regions(self, key: str = "flops", n: int = 10):
        items = [(r, getattr(c, key)) for r, c in self.regions.items() if r]
        return sorted(items, key=lambda kv: -kv[1])[:n]


def collect(compiled, lowered=None) -> RegionCounters:
    """Build RegionCounters from a compiled executable."""
    text = compiled.as_text()
    hc = HloCost(text)
    rc = RegionCounters(total=hc.total, regions=dict(hc.regions),
                        collective_census=dict(hc.collective_census))
    try:
        ca = compiled.cost_analysis()
        if ca:
            rc.xla_flops = float(ca.get("flops", 0.0))
            rc.xla_bytes = float(ca.get("bytes accessed", 0.0))
    except Exception:
        pass
    return rc


def collect_from_text(hlo_text: str) -> RegionCounters:
    hc = HloCost(hlo_text)
    return RegionCounters(total=hc.total, regions=dict(hc.regions),
                          collective_census=dict(hc.collective_census))
