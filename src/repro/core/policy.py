"""Per-region parallelism plans — the tuner's output, the model's input.

The paper replaces the single global ``OMP_NUM_THREADS`` knob with a
per-parallel-region thread count.  Here the global knob is "one sharding
rule-set for the whole model"; a :class:`RegionPlan` carries a per-region
override of the logical-axis -> mesh-axis mapping plus the non-sharding knobs
(microbatch factor, remat policy, kernel block shapes).

Legality is centralised in :func:`legal_spec`: any logical dim whose size does
not divide the mapped mesh-axis size is silently replicated, so every spec the
framework emits is compilable by construction (the tuner never proposes an
illegal plan; see tests/test_policy.py property tests).
"""
from __future__ import annotations

import dataclasses
import json
from typing import Any, Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

# Logical axis vocabulary used by the model zoo.
LOGICAL_AXES = (
    "batch", "seq", "kv_seq", "embed", "ff", "heads", "kv_heads", "head_dim",
    "vocab", "experts", "ssm_heads", "ssm_dim", "state", "enc_seq", "layers",
)

# The "single global knob" baseline (analog of one OMP_NUM_THREADS value):
# batch -> data parallel (pod axis folded in), ff/heads/vocab -> tensor
# parallel, everything else replicated.
DEFAULT_RULES: dict[str, Any] = {
    "batch": ("pod", "data"),
    "seq": None,
    "kv_seq": None,
    "embed": None,
    "ff": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "vocab": "model",
    # experts shard over the model axis (EP): with einsum dispatch/combine
    # every expert matmul is local and both fwd+bwd TP reductions land at
    # (tokens x d_model) — found by the hillclimb (EXPERIMENTS.md §Perf);
    # non-divisible expert counts fall back to replicated via legal_spec
    "experts": "model",
    "ssm_heads": "model",
    "ssm_dim": "model",
    "state": None,
    "enc_seq": None,
    "layers": None,
}


def _axis_size(mesh: Mesh, entry) -> int:
    if entry is None:
        return 1
    if isinstance(entry, (tuple, list)):
        n = 1
        for a in entry:
            n *= mesh.shape[a]
        return n
    return mesh.shape[entry]


def legal_spec(shape: Sequence[int], axes: Sequence[Optional[str]],
               rules: Mapping[str, Any], mesh: Optional[Mesh]) -> P:
    """Build a PartitionSpec for ``shape`` with logical ``axes`` under ``rules``.

    Drops (replicates) any entry whose dim is not divisible by the mesh-axis
    size, and never assigns one mesh axis to two dims.
    """
    if mesh is None:
        return P()
    entries = []
    used: set[str] = set()
    for dim, ax in zip(shape, axes):
        entry = rules.get(ax) if ax is not None else None
        if entry is None:
            entries.append(None)
            continue
        flat = tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)
        # drop axes already used or absent from the mesh
        flat = tuple(a for a in flat if a in mesh.shape and a not in used)
        size = 1
        for a in flat:
            size *= mesh.shape[a]
        if not flat or size == 1 or dim % size != 0:
            entries.append(None)
            continue
        used.update(flat)
        entries.append(flat[0] if len(flat) == 1 else flat)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


@dataclasses.dataclass
class RegionConfig:
    """Per-region knobs (the "thread count" analog)."""
    rules: dict[str, Any] = dataclasses.field(default_factory=dict)
    remat: bool = False
    microbatch: int = 1
    block_q: int = 0        # Pallas / chunking block sizes (0 = impl default)
    block_k: int = 0
    chunk: int = 0          # SSM/linear-attention chunk length
    oversubscribe: int = 1  # kernel grid oversubscription factor ("SMT mode")
    moe_group: int = 0      # MoE dispatch group size (0 = impl default)
    moe_impl: str = ""      # '' = default ('einsum'), or 'scatter'
    ssm_impl: str = ""      # '' = default ('scan'), or 'chunked' (matmul SSD)
    page_size: int = 0      # paged-KV block granularity, tokens (0 = default)
    attn_impl: str = ""     # paged decode attention: '' = auto (the engine
                            # resolves: 'paged' on a TPU at tp 1, else
                            # 'gather'); 'gather' = dense gather of every
                            # block-table page; 'paged' = Pallas kernel
                            # over live pages (block_k = tokens per block)
    spec_depth: int = -1    # speculative decode draft depth per pool step
                            # (-1 = knob unset; 0 = no speculation; N>0 =
                            # draft N tokens, verify with q_len N+1)
    reservation: str = ""   # paged-KV admission policy ('' = unset;
                            # 'full' = reserve worst case up front;
                            # 'lazy' = prompt pages + 1, grow + preempt)
    mem_watermark: float = -1.0  # lazy-admission free-page high watermark
                                 # as a fraction of allocatable pages
                                 # (-1 = unset; engine default 0.1)
    prefix_cache: str = ""  # cross-request KV prefix sharing ('' = unset;
                            # 'on' = share + copy-on-write; 'off' = cold
                            # pool per request)
    tp_degree: int = 0      # serve-engine tensor-parallel degree: mesh
                            # "model"-axis width the paged pool and step
                            # shard over (0 = knob unset; 1 = single-shard).
                            # Reshapes the compiled step — the step cache
                            # keys on it, unlike the allocator-policy knobs.
    scan_mode: str = ""     # linear-attention scan variant ('' = unset;
                            # 'fused_recurrent' = sequential VMEM-resident
                            # recurrence, optimal at T=1 decode; 'chunk' =
                            # matmul-form chunked parallel scan, optimal
                            # for prefill; 'auto' = engine picks by phase).
                            # Recompiles the step — the slot-family step
                            # cache keys on the resolved mode.

    def to_json(self):
        return dataclasses.asdict(self)


@dataclasses.dataclass
class RegionPlan:
    """Sharding+tuning plan: default rules + per-region overrides.

    ``region_configs`` keys are region-path prefixes; the longest matching
    prefix wins (so a plan can address ``"block.attn"`` in every layer or
    ``"layer3/block.attn"`` in one).
    """
    mesh: Optional[Mesh] = None
    rules: dict[str, Any] = dataclasses.field(default_factory=lambda: dict(DEFAULT_RULES))
    region_configs: dict[str, RegionConfig] = dataclasses.field(default_factory=dict)

    # -- lookups -----------------------------------------------------------
    def config_for(self, region: str) -> RegionConfig:
        """Longest matching prefix wins; prefixes also match the canonical
        (digit-stripped) path, so "layer/attn" addresses attn in every layer."""
        import re as _re
        canon = _re.sub(r"\d+", "", region)
        best, best_len = None, -1
        for prefix, rc in self.region_configs.items():
            if ((region.startswith(prefix) or canon.startswith(prefix))
                    and len(prefix) > best_len):
                best, best_len = rc, len(prefix)
        return best if best is not None else RegionConfig()

    def rules_for(self, region: str) -> Mapping[str, Any]:
        rc = self.config_for(region)
        if not rc.rules:
            return self.rules
        merged = dict(self.rules)
        merged.update(rc.rules)
        return merged

    # -- application -------------------------------------------------------
    def constrain(self, x: jax.Array, region: str,
                  axes: Sequence[Optional[str]]) -> jax.Array:
        """Apply a with_sharding_constraint for activation ``x`` in ``region``."""
        if self.mesh is None:
            return x
        spec = legal_spec(x.shape, axes, self.rules_for(region), self.mesh)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def param_sharding(self, shape: Sequence[int],
                       axes: Sequence[Optional[str]],
                       region: str = "") -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        spec = legal_spec(shape, axes, self.rules_for(region), self.mesh)
        return NamedSharding(self.mesh, spec)

    # -- (de)serialisation (plans are artifacts, like PdtTagger's config file)
    def to_json(self) -> str:
        return json.dumps({
            "rules": {k: list(v) if isinstance(v, tuple) else v
                      for k, v in self.rules.items()},
            "regions": {k: rc.to_json() for k, rc in self.region_configs.items()},
        }, indent=2, default=list)

    @staticmethod
    def from_json(text: str, mesh: Optional[Mesh] = None) -> "RegionPlan":
        raw = json.loads(text)
        rules = {k: tuple(v) if isinstance(v, list) else v
                 for k, v in raw.get("rules", {}).items()}
        regions = {}
        for k, d in raw.get("regions", {}).items():
            d = dict(d)
            d["rules"] = {kk: tuple(vv) if isinstance(vv, list) else vv
                          for kk, vv in d.get("rules", {}).items()}
            regions[k] = RegionConfig(**d)
        return RegionPlan(mesh=mesh, rules={**dict(DEFAULT_RULES), **rules},
                          region_configs=regions)


def null_plan() -> RegionPlan:
    """Plan with no mesh: constraints become no-ops (CPU smoke tests)."""
    return RegionPlan(mesh=None)


def default_plan(mesh, kind: str = "train") -> RegionPlan:
    """The "single global knob" baseline plan (paper's OMP_NUM_THREADS
    analog): uniform DP(batch)+TP(ff/heads/vocab) rules everywhere, remat on
    every layer for training."""
    regions = {}
    rules = dict(DEFAULT_RULES)
    if kind == "train":
        regions["layer"] = RegionConfig(remat=True)   # prefix-matches layerN
        regions["enc"] = RegionConfig(remat=True)
        regions["dec"] = RegionConfig(remat=True)
        regions["shared_attn"] = RegionConfig(remat=True)
    if kind == "decode":
        # decode is KV-cache-bound: shard the cache sequence dim over the
        # model axis (flash-decode style partial softmax; XLA inserts the
        # small reductions).  Attention activations must then be
        # head-REPLICATED or XLA fully rematerialises the KV repeat
        # (heads-sharded scores conflict with seq-sharded KV).
        rules["kv_seq"] = "model"
        rules["heads"] = None
        rules["kv_heads"] = None
    return RegionPlan(mesh=mesh, rules=rules, region_configs=regions)


def default_microbatch(kind: str, global_batch: int, data_shards: int) -> int:
    """Baseline grad-accumulation factor: keep ~2 sequences per device."""
    if kind != "train":
        return 1
    per_dev = max(global_batch // max(data_shards, 1), 1)
    return max(per_dev // 2, 1)
