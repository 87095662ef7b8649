"""Serving engine: static lockstep batching plus continuous batching.

Two paths share one Engine:

* :meth:`Engine.generate` — the original static path: prefill a ``(B, S)``
  batch, then decode all rows in lockstep for a fixed number of steps.
  Simple, but every row pays for the slowest/longest row and nothing can
  join until the whole batch finishes.

* :meth:`Engine.serve` — continuous batching over a KV-cache pool
  (:mod:`repro.serve.cache`).  Requests are admitted FIFO from an arrival
  trace (:mod:`repro.serve.scheduler`); the decode step is ONE fixed-shape
  jitted function over the whole pool, so jit caches stay warm no matter
  how batch composition changes — inactive slots decode against the null
  page and their samples are masked.  Per-slot positions mean a request
  that finishes frees its memory immediately and the next request joins
  mid-flight, no lockstep barrier.

  Full-KV families run on the **paged pool** (default): KV lives in a
  global block pool (``page_size`` tokens per page, a tunable knob), each
  request holds only the pages its sequence occupies via a block table,
  and admission reserves a request's own worst case — not the pool-wide
  ``max_len`` — so mixed-length traffic fits far more in-flight requests
  into the same HBM.  Prompts prefill in ``prefill_chunk``-sized pieces
  *interleaved* with pool decode steps (at most ``prefill_chunks_per_step``
  chunks between consecutive steps), so a long prompt no longer stalls
  every in-flight decode.  The decode attention reads K/V through the
  block table: on a TPU at tp 1 the Pallas paged-attention kernel copies
  only each slot's live pages (``block_k`` tokens per compute block);
  elsewhere, or when the plan pins ``attn_impl='gather'``, the slots'
  pages are gathered dense into a grouped-GQA einsum
  (:meth:`Engine.attn_impl_for`).

  **Speculative multi-token decode** (``spec_depth`` > 0, greedy only):
  each pool step drafts up to ``spec_depth`` tokens per active slot by
  n-gram lookup over the slot's own generated history (no second model —
  :func:`draft_ngram`), then ONE fixed-shape jitted verify step scores
  pending-token + drafts for every slot at once (``q_len = spec_depth+1``
  queries against the block-table-gathered K/V).  The longest drafted
  prefix matching the verify step's own argmax chain is committed — so
  greedy outputs are bit-identical to the non-speculative path, token for
  token; acceptance only reorders work — and the rejected tail is rolled
  back in the :class:`repro.serve.cache.PagedKVPool` by pure length
  truncation (no page churn).  ``spec_depth`` is a first-class
  ``RegionConfig`` knob (decode candidates ``spec0/spec2/spec4``): with
  ``--spec-depth auto`` the serve-time :class:`PlanDecider` picks it per
  load bucket from measured counters scaled by occupancy — deep
  speculation on memory-bound low-occupancy pools, shallow under
  compute-bound high occupancy.

  **Elastic KV memory** (:mod:`repro.serve.memory`): admission and
  reclamation route through a :class:`MemoryGovernor`.  ``reservation=
  'full'`` (default) reserves each request's worst case up front —
  preemption-free; ``'lazy'`` admits with only the prompt's pages plus
  one decode page (watermark-gated so growth headroom survives), grows
  one page at a time at page boundaries, and when the allocator runs dry
  preempts the youngest resident decode — the victim re-queues through
  the scheduler's PREEMPTED state and re-enters as recompute-prefill
  over prompt + generated-so-far, so greedy output stays bit-identical.
  ``reservation``/``mem_watermark`` are ``RegionConfig`` knobs with
  serve-only candidates (``mem_full``/``mem_lazy``/``mem_lazy_wm*``), so
  with ``--reservation auto`` the PlanDecider picks memory policy per
  load bucket like any other knob — without ever recompiling the step.

  Families whose per-request state does not grow with the sequence
  (ssm/hybrid recurrent state, sliding-window rings) keep the **slot
  pool**: whole caches stacked on a slot axis, the single-request
  ``decode_step`` vmapped over it, prompts prefilled one slot at a time
  (padded up to ``prefill_bucket`` where right-padding is sound).  In both
  pools the last prompt token is fed through the shared decode step, so
  the first generated token takes the same code path as every later one.

The paper loop runs at serve time: when a :class:`repro.core.dtree
.DecisionTree` (trained on the autotuner's counter->winning-config corpus)
is supplied, :class:`PlanDecider` reads the decode step's measured region
counters (:mod:`repro.core.counters`), scales them by pool occupancy, and
predicts a per-region :class:`RegionConfig` — picking the ``RegionPlan``
for the current load without re-running search (§4.2's "suggest ... without
search" proposal, moved from offline tuning into the serving hot path).

With ``online_retrain`` the loop also *learns* at serve time
(:mod:`repro.autotune`): a measurement tap on both serving loops feeds
per-bucket step counters and observed tok/s rewards into an append-only
:class:`repro.autotune.corpus.Corpus`; every ``retrain_interval`` steps an
:class:`repro.autotune.trainer.OnlineTrainer` refits the tree (holdout
regret check: a worse tree is never swapped in) and hot-swaps it into the
decider — the version bump invalidates the load-bucket latch, so the new
tree takes effect on the next step.  An optional
:class:`repro.autotune.explorer.EpsilonGreedyExplorer` (``explore_eps``)
occasionally overrides the greedy choice so traffic populates candidate
classes the offline search never tried (it skips ``serve_only`` knobs);
with exploration off, greedy output stays bit-identical.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.autotune.candidates import canonical
from repro.autotune.decider import PlanDecider  # noqa: F401  (re-export:
                                                # moved to repro.autotune)
from repro.core.policy import RegionConfig, RegionPlan, null_plan
from repro.kernels.paged_attention import compiles_row, live_pages
from repro.models.model import Model
from repro.serve.faults import FaultInjector
from repro.serve.health import HealthMonitor, HealthPolicy
from repro.serve.scheduler import (Request, RequestState, Scheduler,
                                   summarize)
from repro.serve.telemetry import GcSpans, span


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 512
    temperature: float = 0.0
    seed: int = 0
    # -- continuous batching -------------------------------------------------
    max_slots: int = 4          # max in-flight requests (pool width)
    eos_id: int = -1            # -1: no EOS (per-request eos_id overrides)
    prefill_bucket: int = 0     # slot path: 0 = exact-length prefill jits;
                                # >0 = pad to the bucket where right-padding
                                # is sound
    autoplan: bool = True       # consult the dtree (when one is supplied)
    autoplan_top_n: int = 2     # hot regions consulted per (re)selection
    # -- online autotuning (repro.autotune: measure->corpus->train->decide) --
    online_retrain: bool = False   # tap step counters + tok/s rewards into a
                                   # corpus, retrain the dtree, hot-swap it
    retrain_interval: int = 32     # decode steps between corpus flush /
                                   # retrain attempts
    explore_eps: float = 0.0       # epsilon-greedy exploration over the
                                   # serve-only candidate menu (0 = off:
                                   # greedy output stays bit-identical)
    explore_budget: int = 64       # hard cap on exploration decisions
    # -- paged KV pool -------------------------------------------------------
    paged: str = "auto"         # "auto": paged wherever the family supports
                                # it; "on": require it; "off": slot pool
    page_size: int = 0          # tokens per KV page (0 = the plan's
                                # attn-region page_size knob, else 16)
    kv_pages: int = 0           # total pages incl. the null page (0 = the
                                # per-slot worst case — same HBM as the slot
                                # pool; set lower to trade HBM for queueing)
    # -- elastic KV memory (repro.serve.memory.MemoryGovernor) ---------------
    reservation: str = "auto"   # paged admission policy: "full" = worst
                                # case up front (preemption-free), "lazy" =
                                # prompt pages + 1 then grow/preempt,
                                # "auto" = the plan's attn-region
                                # reservation knob (the PlanDecider's
                                # mem_full/mem_lazy channel; unset = full)
    mem_watermark: float = -1.0  # lazy-admission free-page high watermark
                                 # fraction (-1 = auto: plan knob, else 0.1)
    max_preempts: int = 4       # per-request eviction cap; the oldest
                                # resident's mandatory headroom may still
                                # override it (progress guarantee)
    prefix_cache: str = "auto"  # cross-request KV prefix sharing: "on" /
                                # "off" pin it; "auto" = the plan's
                                # attn-region prefix_cache knob (the
                                # PlanDecider's mem_prefix_* channel;
                                # unset = off)
    prefill_chunk: int = 0      # chunked prefill piece size (0 = whole
                                # prompt in one chunk)
    prefill_chunks_per_step: int = 1   # prefill chunks interleaved between
                                       # consecutive pool decode steps
    # -- speculative decode (greedy only; paged pool or recurrent slots) -----
    spec_depth: int = -1        # draft tokens per pool step: -1 = auto (the
                                # plan's attn-region spec_depth knob, the
                                # PlanDecider's channel); 0 = off; N>0 fixed
    # -- recurrent scan mode (slot pool, ssm/hybrid families) ----------------
    scan_mode: str = "auto"     # wkv/ssd kernel variant: "chunk" (parallel
                                # intra-chunk matmuls, prefill-friendly) /
                                # "fused_recurrent" (sequential recurrence,
                                # decode-friendly) pin it for BOTH phases;
                                # "auto" = the plan's scan-region scan_mode
                                # knob (the PlanDecider's scan_chunk /
                                # scan_fused channel; unset = chunk for
                                # prefill, fused for decode).  Greedy output
                                # is bit-identical across modes — this knob
                                # trades state-traffic against matmul shape
                                # per load bucket, never tokens.
    # -- tensor parallelism (mesh-sharded paged serving) ---------------------
    tp: int = 0                 # tensor-parallel degree over the mesh
                                # "model" axis (pages shard on kv_heads,
                                # params on their logical axes, one
                                # all-gather at the sampling boundary):
                                # 0 = auto (the plan's attn-region
                                # tp_degree knob, the PlanDecider's
                                # tp1/tp2/tp4 channel; unset = 1); N >= 1
                                # pins it.  Degrees the host cannot
                                # satisfy (device count, kv-head
                                # divisibility) clamp down.
    # -- failure domains + graceful degradation (serve/{faults,health}.py) ---
    deadline_s: float = 0.0     # default time-to-admission budget for
                                # requests that don't set their own
                                # Request.deadline_s (0 = no deadline);
                                # expired waiters shed with EXPIRED
    max_queue: int = 0          # bound on the arrived-but-waiting queue;
                                # arrivals beyond it shed with REJECTED
                                # (0 = unbounded)
    max_retries: int = 3        # consecutive faulted steps a request may
                                # retry before the engine fails it and
                                # releases all its pages
    watchdog_s: float = 0.0     # per-step wall-clock budget; an overrun
                                # counts as a latency fault toward the
                                # HEALTHY->DEGRADED->SHEDDING ladder
                                # (0 = watchdog off)
    chaos_rate: float = 0.0     # fault-injection probability per site draw
                                # (0 = injector not even constructed: the
                                # hot paths check one attribute against
                                # None and pay nothing)
    chaos_seed: int = 0         # FaultInjector stream seed
    chaos_sites: tuple = ()     # subset of faults.FAULT_SITES (empty =
                                # all sites)
    # -- telemetry (serve/telemetry.py: request spans + sketches + exports) -
    telemetry: bool = False     # build the Telemetry subsystem (request
                                # span tracing, latency sketches,
                                # event log).  Off = the
                                # attribute stays None and every hot
                                # path pays one `is not None` check —
                                # the FaultInjector contract.  Any of
                                # the three output paths below implies
                                # it on.
    trace_out: str = ""         # write the last serve()'s Chrome
                                # trace-event JSON (Perfetto-loadable)
                                # here at serve() exit
    metrics_out: str = ""       # write a Prometheus text snapshot
                                # (Engine.metrics_text()) here at
                                # serve() exit
    log_out: str = ""           # stream the structured JSONL event log
                                # here ("" = bounded in-memory buffer
                                # only)
    log_level: str = "info"     # event-log threshold: "debug" adds
                                # per-step/injection events, "warning"
                                # keeps only health transitions


def sample_rows(logits: jax.Array, key, temperature: float) -> jax.Array:
    """THE sampler: (N, V) float32 logits -> (N,) int32 token per row —
    greedy argmax at temperature <= 0, else per-row categorical with an
    independent key per row (so a row's sample never depends on pool
    composition).  Every path — static lockstep, slot pool, paged pool and
    the speculative verify step — funnels through this one function."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = jax.random.split(key, logits.shape[0])
    return jax.vmap(
        lambda k, l: jax.random.categorical(k, l / temperature))(
            keys, logits).astype(jnp.int32)


def load_bucket(n_active: int) -> int:
    """Occupancy bucket for replan triggering: the next power of two >=
    n_active (1 for an empty/single-slot pool).  The decider re-runs only
    when the bucket changes, so plan churn is logarithmic in load swings
    while the counters it scales by still track occupancy."""
    return 1 << max(0, n_active - 1).bit_length()


def draft_ngram(history: np.ndarray, depth: int, *, max_ngram: int = 3,
                window: int = 512) -> np.ndarray:
    """Self-speculative draft: propose ``depth`` tokens by n-gram lookup
    over the request's own token history (prompt + generated output — no
    second model).  Finds the most recent earlier occurrence of the
    current suffix (longest n first) and copies the tokens that followed
    it; with no match — or to pad a short match — it repeats the last
    token, which is exactly the degenerate-loop continuation greedy decode
    of a converged sequence produces.  A bad draft costs only wasted
    verify compute, never a wrong token (the verify step's argmax chain is
    the ground truth)."""
    history = history[-window:]
    H = history.size
    out = np.full((depth,), history[-1], np.int32)
    for n in range(min(max_ngram, H - 1), 0, -1):
        # vectorised suffix search (this runs per slot per decode step —
        # a Python scan over the window would rival the device step time)
        windows = np.lib.stride_tricks.sliding_window_view(history, n)[:-1]
        hits = np.flatnonzero((windows == history[H - n:]).all(axis=1))
        if hits.size:
            i = int(hits[-1])             # most recent earlier occurrence
            cont = history[i + n:i + n + depth]
            out[:cont.size] = cont
            if cont.size < depth:
                out[cont.size:] = cont[-1]
            return out
    return out


def sample_pool(logits, active, key, temp):
    """Pool-step sampling via the shared :func:`sample_rows`, with the
    inactive-slot mask: freed (or mid-prefill) slots decode the null
    page, so their logits are garbage and may be non-finite — zero
    them before the sampler so NaNs never propagate into
    categorical(), and pin their sampled token to 0 so downstream
    state is occupancy-independent."""
    logits = jnp.where(active[:, None], logits, 0.0)
    return jnp.where(active, sample_rows(logits, key, temp), 0)


def paged_step_fn(model: Model, plan: RegionPlan, temperature: float,
                  mesh=None):
    """The paged pool's decode(+verify)+sample step, before jit:
    ``step(params, pages, tokens (B,S), block_tables, lengths, active,
    key) -> (tokens (B,S), finite (B,), pages)``.  ``plan`` is the
    already-resolved serve plan; a ``mesh`` (tp > 1) replicates the
    vocab-sharded logits before sampling.  :meth:`Engine._build_paged_step`
    compiles it for the live pool, and a compile check can lower it on
    abstract shapes for a device that is not attached."""

    def step(params, pages, tokens, block_tables, lengths, active, key):
        logits, pages = model.paged_decode(
            params, pages, tokens, block_tables, lengths, plan)
        B, S_, V = logits.shape
        flat = logits.astype(jnp.float32).reshape(B * S_, V)
        if mesh is not None:
            # THE collective boundary: replicate the vocab-sharded
            # logits (one all-gather) before sampling, so everything
            # downstream is shard-independent
            flat = jax.lax.with_sharding_constraint(
                flat, NamedSharding(mesh, P()))
        act = jnp.repeat(active, S_)
        finite = (jnp.isfinite(flat).all(axis=-1).reshape(B, S_)
                  .all(axis=-1) | ~active)
        return (sample_pool(flat, act, key, temperature).reshape(B, S_),
                finite, pages)

    return step


@jax.jit
def _carry_tokens(prev, host, carry):
    """The next pool step's input tokens ``(B, 1)``: where ``carry``, the
    token the step in flight sampled (``prev[:, :1]``, never read back to
    the host); elsewhere the host's pending token."""
    return jnp.where(carry[:, None], prev[:, :1], host)


@dataclasses.dataclass
class _Step:
    """A dispatched pool decode step whose tokens the host has not read."""
    index: int                      # 1-based step number
    out: Any                        # (B, S) sampled tokens, on the device
    finite: Any                     # (B,) health flags, on the device
    toks_in: np.ndarray             # (B, S) pending token + drafts (host)
    depth: int                      # spec depth D it ran at
    rows: dict                      # slot -> Request stepped
    written: dict                   # slot -> rows advanced at dispatch
    n_act: int                      # decode residents (the tap's load)
    t_start: float                  # perf_counter before its headroom
    # slots whose row was voided before the wait: their previous step
    # faulted, so the token this row consumed was garbage
    dropped: set = dataclasses.field(default_factory=set)


class Engine:
    # class-level defaults so resolution helpers (tp_for/spec_depth_for)
    # stay callable on partially-constructed engine shells (tests stub
    # Engine via object.__new__ to exercise them without a model)
    _force_safe = False                 # pin spec0/gather/tp1
    _fallback = None                    # (step, depth, tp, attn) to restore

    def __init__(self, model: Model, params, plan: Optional[RegionPlan] = None,
                 serve_cfg: Optional[ServeConfig] = None, dtree=None):
        self.model = model
        self.params = params
        self.plan = plan or null_plan()
        # a fresh ServeConfig per Engine (a dataclass default instance would
        # be shared by every Engine and mutate across instances)
        self.cfg = serve_cfg if serve_cfg is not None else ServeConfig()
        # the decider is the swappable tree handle (repro.autotune.decider);
        # with online_retrain it exists even before any tree does (cold
        # start: the first retrain swaps one in)
        self.decider: Optional[PlanDecider] = None
        if dtree is not None or self.cfg.online_retrain:
            self.decider = PlanDecider(dtree)
        self._decider_version: Optional[int] = None   # version at last replan

        def prefill_fn(params, batch):
            return model.prefill(params, batch, self.plan,
                                 max_len=self.cfg.max_len)

        def decode_fn(params, cache, tokens):
            return model.decode(params, cache, tokens, self.plan)

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn, donate_argnums=(1,))

        # -- continuous-batching state (built lazily by _ensure_pool) --------
        self._pool = None
        self._paged = False
        self.governor = None                        # paged memory governor
        self._build_step = None                     # plan -> compiled step
        self._slot_prefills: dict = {}              # (feed_len, scan_mode)
                                                    # -> jitted prefill fn
        self._chunk_step = None                     # paged prefill-chunk fn
        self._slot_chunks: dict = {}                # (width, scan_mode) ->
                                                    # jitted slot chunk fn
        self._decided_plan = self.plan              # last decider output —
                                                    # prefill-phase knobs
                                                    # (scan_mode) resolve
                                                    # against it at call time
        self._pool_steps: dict = {}                 # key -> (compiled, depth,
                                                    #         tp, attn_impl)
        self._pool_step = None
        self._spec_depth = 0                        # depth of _pool_step
        self._attn_impl = ""                        # its decode attention
        self._pool_rc = None                        # counters of base step
        # -- tensor-parallel serving state -----------------------------------
        self._serve_tp = 1                          # current pages/params
                                                    # placement degree
        self._tp_meshes: dict = {}                  # tp -> host fallback mesh
        self._tp_params: dict = {}                  # tp -> mesh-placed params
        self._load_bucket: Optional[int] = None
        self.decisions_log: list = []

        # -- failure domains + graceful degradation --------------------------
        self.faults = None                          # FaultInjector or None
        if self.cfg.chaos_rate > 0:
            self.faults = FaultInjector(
                seed=self.cfg.chaos_seed, rate=self.cfg.chaos_rate,
                sites=self.cfg.chaos_sites or None)
        self.health = HealthMonitor(HealthPolicy(
            max_retries=self.cfg.max_retries,
            watchdog_s=self.cfg.watchdog_s))
        self._force_safe = False                    # pin spec0/gather/tp1
        self._fallback = None                       # (step, depth, tp,
                                                    # attn) to restore on
                                                    # recovery

        # -- telemetry (request spans + sketches + exporters) ----------------
        # same zero-overhead contract as the injector: None unless asked
        # for, and every hot-path touch is one `is not None` check
        self.telemetry = None
        self._gc_spans = GcSpans()      # python.gc spans while profiled
        if (self.cfg.telemetry or self.cfg.trace_out or self.cfg.metrics_out
                or self.cfg.log_out):
            from repro.serve.telemetry import Telemetry
            self.telemetry = Telemetry(level=self.cfg.log_level,
                                       log_out=self.cfg.log_out)
        self.health.telemetry = self.telemetry
        if self.faults is not None:
            self.faults.telemetry = self.telemetry

        # -- online autotuning state (measure->corpus->train->decide) --------
        self.corpus = None
        self.trainer = None
        self.explorer = None
        self._init_autotune_state()
        self._tap_region: Optional[str] = None      # hottest attn-ish region
        self._reset_tap_state()

    def _init_autotune_state(self):
        """Fresh corpus/trainer/explorer from the ServeConfig (shared by
        __init__ and autotune_reset so the two can never drift apart)."""
        if not self.cfg.online_retrain:
            return
        from repro.autotune.corpus import Corpus
        from repro.autotune.explorer import EpsilonGreedyExplorer
        from repro.autotune.trainer import OnlineTrainer
        self.corpus = Corpus()
        self.trainer = OnlineTrainer(interval=self.cfg.retrain_interval)
        self.explorer = EpsilonGreedyExplorer(
            eps=self.cfg.explore_eps, budget=self.cfg.explore_budget,
            seed=self.cfg.seed)

    def _reset_tap_state(self):
        """Zero the per-trace measurement-tap accumulators and stats."""
        self._tap_acc: dict = {}        # bucket -> [steps, tokens, secs,
                                        #            prefix lookups, hits]
        self._tap_lat: dict = {}        # bucket -> LatencySketch over the
                                        # window (feeds step_latency_p99)
        self._tap_qd = [0.0, 0]         # window queue-delay [sum_s, n]
                                        # over fresh admissions
        self._tap_pending = 0           # taps since the last flush
        self._tap_prefix_last = None    # (lookups, hits) at the last tap —
                                        # pool counters are monotonic, the
                                        # tap wants per-window deltas
        self._bucket_class: dict = {}   # bucket -> class in effect (tap attn
                                        # region), for reward attribution
        self._exploring = False         # current plan carries an explored class
        self._force_replan = False      # explorer wants a mid-bucket re-decide
        self.autotune_stats = {
            "retrains": 0, "swaps": 0, "explored": 0, "explore_steps": 0,
            "steps": 0, "corpus_entries": 0,
            # tok/s before the first tree swap vs after the last one — the
            # post-swap delta the benchmark records
            "pre_tokens": 0, "pre_secs": 0.0,
            "post_tokens": 0, "post_secs": 0.0,
        }

    # -- the dtree is the decider's swappable handle -------------------------
    @property
    def dtree(self):
        return self.decider.tree if self.decider is not None else None

    @dtree.setter
    def dtree(self, tree):
        """Assigning a tree routes through PlanDecider.swap, so the version
        bump invalidates the load-bucket replan latch — a tree installed
        mid-bucket takes effect on the very next step."""
        if self.decider is None:
            self.decider = PlanDecider(tree)
        else:
            self.decider.swap(tree)

    def autotune_reset(self, tree=None):
        """Restart the online-autotune loop cold (fresh corpus / trainer /
        explorer / stats, ``tree`` as the incumbent) while keeping compiled
        pool steps warm — so a benchmark can measure repeated traces from
        an identical learning state without paying recompiles."""
        self._init_autotune_state()
        self.dtree = tree               # swap: bumps version, busts the latch
        self._reset_tap_state()

    def _sample(self, logits, key):
        return sample_rows(logits[:, -1, :].astype(jnp.float32), key,
                           self.cfg.temperature)

    # ------------------------------------------------------------------
    # Static lockstep batching (the baseline path)
    # ------------------------------------------------------------------
    def generate(self, prompts: jax.Array, n_steps: int,
                 extra_inputs: Optional[dict] = None) -> dict:
        """prompts: (B, S) int32 -> generated (B, n_steps) + stats."""
        batch = {"tokens": prompts}
        if extra_inputs:
            batch.update(extra_inputs)
        t0 = time.perf_counter()
        logits, cache = self._prefill(self.params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.perf_counter() - t0

        key = jax.random.PRNGKey(self.cfg.seed)
        tok = self._sample(logits, key)
        out = [tok]
        t0 = time.perf_counter()
        for i in range(n_steps - 1):
            key, sub = jax.random.split(key)
            logits, cache = self._decode(self.params, cache, tok[:, None])
            tok = self._sample(logits, sub)
            out.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.perf_counter() - t0
        tokens = jnp.stack(out, axis=1)
        B = prompts.shape[0]
        return {
            "tokens": tokens,
            "prefill_s": t_prefill,
            "decode_s": t_decode,
            "decode_tok_per_s": B * max(n_steps - 1, 1) / max(t_decode, 1e-9),
        }

    # ------------------------------------------------------------------
    # Continuous batching
    # ------------------------------------------------------------------
    def _pad_safe(self) -> bool:
        """Right-padding the prompt is sound only for positional full-KV
        caches: pad K/V land at positions >= pos (masked, then overwritten
        by decode writes).  Recurrent state (ssm/hybrid) and sliding-window
        rings would absorb the pads."""
        cfg = self.model.cfg
        return cfg.family in ("dense", "moe", "vlm") and not cfg.swa_window

    def _slot_cache_avals(self):
        tok = jax.ShapeDtypeStruct((1, 2), jnp.int32)
        return jax.eval_shape(
            lambda p, t: self.model.prefill(
                p, {"tokens": t}, self.plan, max_len=self.cfg.max_len)[1],
            self.params, tok)

    def _param_dtype(self):
        return jax.tree.leaves(self.params)[0].dtype

    def page_size(self) -> int:
        """page_size resolution: ServeConfig overrides the plan's attention
        region knob (the tuner/PlanDecider's channel), which overrides the
        default.  Consulted once, at pool build — the pool layout cannot
        change mid-flight (a replan only rebuilds the step)."""
        rc = self.plan.config_for("layer0/attn")
        return self.cfg.page_size or rc.page_size or 16

    def _spec_pool_ok(self) -> bool:
        """Whether the live pool can roll back a rejected draft: the paged
        pool truncates lengths (O(1)); the slot pool can snapshot/restore
        *fixed-size recurrent state* (ssm/hybrid — O(state), no context
        dependence).  A sliding-window ring absorbs multi-token writes
        destructively mid-ring and a growing slot KV cache has no
        truncation analogue, so those slot families never speculate."""
        if self._paged:
            return True
        cfg = self.model.cfg
        return (getattr(cfg, "family", "") in ("ssm", "hybrid")
                and not getattr(cfg, "swa_window", 0))

    def _spec_knob_live(self) -> bool:
        """Whether spec_depth is the PlanDecider's to choose: only in auto
        mode (ServeConfig.spec_depth < 0), greedy sampling (speculative
        verification is an argmax-chain identity — under temperature it
        would change the sampling distribution), non-MoE (capacity
        groups route by token-group length, so a multi-token step would
        route differently than sequential decode and break bit-identity),
        and on a pool that can roll a rejected draft back."""
        return (self._spec_pool_ok() and self.cfg.spec_depth < 0
                and self.cfg.temperature <= 0
                and not self.model.cfg.n_experts)

    def spec_depth_for(self, plan: RegionPlan) -> int:
        """spec_depth resolution, mirroring :meth:`page_size`: an explicit
        ServeConfig value pins it; in auto mode the plan's attn-region knob
        (the tuner/PlanDecider channel) decides; unset means off.  A
        degraded engine (``_force_safe``) pins 0 ahead of everything —
        the safe plan outranks even an explicit ServeConfig pin — and a
        pool with no rollback pins 0 regardless of any pin."""
        if self._force_safe:
            return 0
        if self.cfg.temperature > 0 or self.model.cfg.n_experts:
            return 0
        if not self._spec_pool_ok():
            return 0
        if self.cfg.spec_depth >= 0:
            return self.cfg.spec_depth
        return max(plan.config_for("layer0/attn").spec_depth, 0)

    # -- recurrent scan-mode resolution (slot pool, ssm/hybrid) --------------
    def _scan_region(self) -> str:
        """The region whose scan_mode knob steers the recurrent kernels:
        rwkv6's time-mix for the ssm family, the mamba block for hybrid."""
        fam = getattr(self.model.cfg, "family", "")
        return "layer0/tmix" if fam == "ssm" else "layer0/ssm"

    def scan_mode_for(self, plan: RegionPlan, phase: str = "decode") -> str:
        """scan_mode resolution (same precedence as the other serve knobs):
        an explicit ServeConfig value pins it; in auto mode the plan's
        scan-region knob (the PlanDecider's scan_chunk/scan_fused channel)
        decides; unset falls through to the phase heuristic — "chunk" for
        prefill (intra-chunk work becomes causal matmuls, state traffic
        drops by the chunk length), "fused_recurrent" for decode (a one-
        token step has no intra-chunk parallelism to win).  Returns ""
        for families without the choice (the plan is left untouched)."""
        cfg = self.model.cfg
        if self._paged or getattr(cfg, "family", "") not in ("ssm", "hybrid"):
            return ""
        mode = self.cfg.scan_mode
        if mode not in ("chunk", "fused_recurrent"):
            mode = plan.config_for(self._scan_region()).scan_mode or "auto"
        if mode == "auto":
            mode = "chunk" if phase == "prefill" else "fused_recurrent"
        return mode

    def _plan_with_scan_mode(self, plan: RegionPlan, mode: str) -> RegionPlan:
        """The plan a recurrent step/prefill lowers under: the decided
        plan's knobs with the scan region's mode pinned to the RESOLVED
        choice, so "auto" never reaches the model code (mirrors
        :meth:`_safe_plan`'s overlay pattern)."""
        if not mode:
            return plan
        import copy
        plan2 = copy.deepcopy(plan)
        rkey = ("layer/tmix" if getattr(self.model.cfg, "family", "") == "ssm"
                else "layer/ssm")
        base = plan2.region_configs.get(rkey, RegionConfig())
        plan2.region_configs[rkey] = dataclasses.replace(base, scan_mode=mode)
        return plan2

    def reservation_for(self, plan: RegionPlan) -> str:
        """Memory-reservation resolution, mirroring :meth:`spec_depth_for`:
        an explicit ServeConfig value pins it; in auto mode the plan's
        attn-region knob (the PlanDecider's mem_full/mem_lazy channel)
        decides; unset means full (the preemption-free PR 2 behaviour)."""
        if self.cfg.reservation in ("full", "lazy"):
            return self.cfg.reservation
        return plan.config_for("layer0/attn").reservation or "full"

    def mem_watermark_for(self, plan: RegionPlan) -> float:
        """Watermark resolution (same precedence as the other knobs)."""
        if self.cfg.mem_watermark >= 0:
            return self.cfg.mem_watermark
        wm = plan.config_for("layer0/attn").mem_watermark
        return wm if wm >= 0 else 0.1

    def prefix_cache_for(self, plan: RegionPlan) -> bool:
        """Prefix-sharing resolution (same precedence as the other memory
        knobs): an explicit ServeConfig value pins it; in auto mode the
        plan's attn-region knob (the PlanDecider's mem_prefix_on /
        mem_prefix_off channel) decides; unset means off.  Sharing is
        bit-identical either way — this knob trades index/CoW overhead
        against prefill savings per load bucket.  Forced off for MoE
        (mirroring :meth:`spec_depth_for`): capacity groups route by
        token-group length, so prefilling only the un-matched suffix —
        zero-padded back to the feed length — would route (and drop)
        tokens differently than whole-prompt cold prefill, producing
        different suffix K/V and breaking bit-identity."""
        if self.model.cfg.n_experts:
            return False
        if self.cfg.prefix_cache in ("on", "off"):
            return self.cfg.prefix_cache == "on"
        return plan.config_for("layer0/attn").prefix_cache == "on"

    def _tp_knob_live(self) -> bool:
        """Whether tp_degree is the PlanDecider's to choose: only in auto
        mode (ServeConfig.tp == 0) on the paged pool — the slot pool's
        vmapped whole-cache step has no kv-head page axis to shard."""
        return self._paged and self.cfg.tp == 0

    def tp_for(self, plan: RegionPlan) -> int:
        """tp-degree resolution (same precedence as the other serve knobs):
        an explicit ServeConfig value pins it; in auto mode the plan's
        attn-region tp_degree knob (the PlanDecider's tp1/tp2/tp4 channel)
        decides; unset means 1 — exactly the pre-mesh single-device path.
        The wanted degree then clamps DOWN to what this host + model can
        satisfy: it must fit the device count and split the kv-head count
        evenly (pages shard on the kv-head axis only; see
        :func:`repro.kernels.paged_attention.shard_kv_heads`), so an
        infeasible candidate class degrades gracefully instead of failing.
        """
        want = self.cfg.tp if self.cfg.tp > 0 else (
            max(plan.config_for("layer0/attn").tp_degree, 0) or 1)
        if self._force_safe:
            want = 1                # degraded: safe plan outranks the pin
        kvh = getattr(self.model.cfg, "n_kv_heads", 0) or 1
        n_dev = len(jax.devices())
        tp = max(int(want), 1)
        while tp > 1 and (tp > n_dev or kvh % tp):
            tp -= 1
        from repro.kernels.paged_attention import shard_kv_heads
        shard_kv_heads(kvh, tp)     # the centralised divisibility rule —
        return tp                   # cannot raise after the clamp above

    def attn_impl_for(self, plan: RegionPlan) -> str:
        """Decode-attention resolution on the paged pool: the plan's
        attn-region knob pins 'gather' or 'paged'; '' (auto) resolves from
        what the engine observes — 'paged' (the Pallas kernel, which reads
        only each slot's live pages, several per copy batch) when the step
        lowers for a TPU at the resolved tp degree 1, 'gather' otherwise:
        on the CPU the kernel would run interpreted, and at tp > 1 GSPMD
        cannot split the kernel's custom call over the kv-head axis.  A
        degraded engine (``_force_safe``) pins 'gather' ahead of
        everything, and so do page rows the compiled kernel refuses
        (:meth:`_kernel_refuses_rows`), over a 'paged' pin too."""
        if self._force_safe or self._kernel_refuses_rows():
            return "gather"
        impl = plan.config_for("layer0/attn").attn_impl
        if impl in ("gather", "paged"):
            return impl
        if jax.default_backend() == "tpu" and self.tp_for(plan) == 1:
            return "paged"
        return "gather"

    def _kernel_refuses_rows(self) -> bool:
        """On a TPU the compiled kernel takes only page rows (every KV head
        of a token, lane-dense) of whole 128-lane tiles
        (:func:`compiles_row`); the gather serves the rest, whatever the
        plan pins.  stablelm-1.6b's 32 x 64, stablelm-3b-4e1t's 32 x 80
        and qwen3-8b's 8 x 128 fill them; the reduced configs do not."""
        cfg = self.model.cfg
        return (jax.default_backend() == "tpu" and not compiles_row(
            cfg.n_kv_heads, cfg.resolved_head_dim))

    @staticmethod
    def _plan_with_attn_impl(plan: RegionPlan, impl: str,
                             override: bool = False) -> RegionPlan:
        """The plan with every unset (auto) attn_impl knob pinned to the
        resolved impl — the attention region's effective config included
        — so '' never reaches the model code (mirrors
        :meth:`_plan_with_scan_mode`).  ``override``: explicit pins are
        replaced too (rows the compiled kernel refuses)."""
        import copy
        plan2 = copy.deepcopy(plan)
        rcs = plan2.region_configs
        rcs.setdefault("layer/attn", plan2.config_for("layer/attn"))
        for key, rc in rcs.items():
            if override or not rc.attn_impl:
                rcs[key] = dataclasses.replace(rc, attn_impl=impl)
        return plan2

    def _tp_mesh(self, tp: int):
        """The ("data", "model") mesh a tp degree shards over: the
        engine-level plan's mesh when its model axis already matches
        (production: the launcher built the real device mesh), else a
        host mesh over whatever devices exist (the ``--tp`` fallback —
        e.g. CPU devices forced via
        ``XLA_FLAGS=--xla_force_host_platform_device_count``)."""
        m = self.plan.mesh
        if m is not None and dict(m.shape).get("model") == tp:
            return m
        mesh = self._tp_meshes.get(tp)
        if mesh is None:
            from repro.launch.mesh import make_host_mesh
            mesh = make_host_mesh(1, tp)
            self._tp_meshes[tp] = mesh
        return mesh

    def _serve_plan(self, plan: RegionPlan, tp: int) -> RegionPlan:
        """The plan a sharded serve step lowers under: the decided plan's
        rules with the pool-layout axes pinned — pages shard on kv_heads
        (never kv_seq: the host-side block table indexes page ids and
        in-page positions identically on every shard, so the per-page
        gather/DMA is unchanged and each shard just sees kv_heads/tp
        heads), and q heads follow their GQA groups.  ff/vocab keep the
        already-defined logical-axis rules, so MLP and unembed shard too;
        the vocab-sharded logits replicate at the sampling boundary
        (:meth:`_build_paged_step`'s single all-gather).  tp=1 returns the
        plan untouched — bit-for-bit the single-device path."""
        if tp <= 1:
            return plan
        rules = dict(plan.rules)
        rules.update({"kv_seq": None, "kv_heads": "model", "heads": "model"})
        return RegionPlan(mesh=self._tp_mesh(tp), rules=rules,
                          region_configs=plan.region_configs)

    @property
    def _step_params(self):
        """The params tree matching the current pool placement (mesh-sharded
        copies are cached per degree; tp=1 is ``self.params`` itself)."""
        return self._tp_params.get(self._serve_tp, self.params)

    def _apply_tp(self, tp: int, pages_placed: bool = False):
        """Move the pool pages + pick the params copy for a tp degree (no-op
        when already there).  Runs on every step-cache *fetch*, not just on
        builds: an AOT-compiled step is strict about its input shardings,
        so a cached tp2 step must never be invoked with tp1-placed pages.
        A switch costs one device_put of the pool — exactly the "one
        reshard per tp change" the tp candidate docs promise — and
        invalidates the chunk-prefill trace (it closes over the
        placement)."""
        tp = max(tp, 1)
        if self._pool is None or tp == self._serve_tp:
            return
        pool = self._pool
        splan = self._serve_plan(self.plan, tp)
        if not pages_placed:
            if tp == 1:
                pool.pages = jax.device_put(pool.pages, jax.devices()[0])
            else:
                from repro.distributed.sharding import cache_shardings
                pool.pages = jax.device_put(
                    pool.pages, cache_shardings(splan, pool.pages))
        if tp > 1 and tp not in self._tp_params:
            from repro.distributed.sharding import param_shardings
            self._tp_params[tp] = jax.device_put(
                self.params, param_shardings(self.model, splan))
        pool.tp_shards = tp
        self._serve_tp = tp
        self._chunk_step = None     # retraces under the new placement

    def _use_paged(self) -> bool:
        if self.cfg.paged == "off":
            return False
        if self.cfg.paged == "on":
            if not self.model.supports_paged:
                raise ValueError(
                    f"paged KV unsupported for family "
                    f"{self.model.cfg.family!r} (swa={self.model.cfg.swa_window})")
            return True
        return self.model.supports_paged

    def _ensure_pool(self):
        if self._pool is not None:
            return
        if self.model.cfg.family == "encdec":
            raise NotImplementedError(
                "continuous batching supports decoder-only families; "
                "use generate() for encdec")
        from repro.serve.cache import PagedKVPool, SlotKVPool, pages_for
        self._paged = self._use_paged()
        if self._paged:
            ps = self.page_size()
            max_pages = pages_for(self.cfg.max_len, ps)
            n_pages = self.cfg.kv_pages or (
                self.cfg.max_slots * max_pages + 1)
            avals = self.model.paged_cache_spec(n_pages, ps,
                                               dtype=self._param_dtype())
            # mesh-aware pool construction: at tp > 1 every page leaf is
            # built directly into its kv-head-sharded placement (no
            # single-device materialisation then reshard)
            tp = self.tp_for(self.plan)
            shardings = None
            if tp > 1:
                from repro.distributed.sharding import cache_shardings
                shardings = cache_shardings(self._serve_plan(self.plan, tp),
                                            avals)
            self._pool = PagedKVPool(avals, self.cfg.max_slots, ps,
                                     n_pages, max_pages, shardings=shardings)
            self._apply_tp(tp, pages_placed=True)
            from repro.serve.memory import MemoryGovernor, MemoryPolicy
            self.governor = MemoryGovernor(self._pool, MemoryPolicy(
                reservation=self.reservation_for(self.plan),
                watermark=self.mem_watermark_for(self.plan),
                max_preempts=self.cfg.max_preempts))
            self._pool.prefix_enabled = self.prefix_cache_for(self.plan)
            # thread the (optional) fault injector through the paged hot
            # paths; None keeps them zero-overhead
            self._pool.faults = self.faults
            self.governor.faults = self.faults
            self.governor.telemetry = self.telemetry
            self._build_step = self._build_paged_step
        else:
            self._pool = SlotKVPool(self._slot_cache_avals(),
                                    self.cfg.max_slots)
            self._build_step = self._build_pool_step
        built = self._build_step(self.plan)
        self._pool_step, self._spec_depth, _, self._attn_impl = built
        self._pool_steps[self._step_cache_key(self.plan)] = built
        if ((self.dtree is not None and self.cfg.autoplan)
                or self.cfg.online_retrain):
            from repro.core import counters as counters_mod
            self._pool_rc = counters_mod.collect(self._pool_step)
            # the measurement tap attributes rewards to the hottest
            # attention-ish region (the decider's main lever); fall back to
            # the hottest region of any kind
            tops = self._pool_rc.top_regions("flops", 16)
            attn = [r for r, _ in tops if "attn" in r]
            self._tap_region = (attn[0] if attn
                                else (tops[0][0] if tops else None))

    def _build_pool_step(self, plan: RegionPlan):
        """AOT-compile one decode(+verify)+sample step over the whole slot
        pool: the model's single-request ``decode_step`` vmapped over the
        slot axis.

        The plan's resolved ``spec_depth`` D sets the step's fixed query
        width S = D+1 exactly as on the paged pool — tokens (B, S) carry
        each slot's pending token followed by its drafted continuation and
        the returned (B, S) grid is the argmax chain the host's acceptance
        walk compares drafts against.  Only recurrent families (ssm /
        hybrid) resolve D > 0: their fixed-size state snapshots in
        :class:`SlotKVPool` make the rejection rollback O(state) (see
        ``_serve_slots``); D=0 degenerates to the plain one-token step,
        bit-for-bit the pre-speculation path.

        The plan's resolved ``scan_mode`` is baked into the plan the step
        lowers under (:meth:`_plan_with_scan_mode`), so a chunk/fused flip
        is a step-cache entry, never a retrace of a live executable.

        Carries the same always-on health guard as the paged step: a
        per-slot ``finite`` flag over the S logit rows, inactive slots
        forced healthy.  No page axis to shard, so tp is always 1, and no
        block table, so there is no attention impl to choose.  Returns
        (compiled, D, tp=1, attn_impl=""); the compiled step returns
        ``(tokens (B,S), finite (B,), pool)``."""
        model, temp = self.model, self.cfg.temperature
        depth = self.spec_depth_for(plan)
        S = depth + 1
        splan = self._plan_with_scan_mode(plan, self.scan_mode_for(plan))

        def step(params, pool, tokens, active, key):
            def one(cache, toks):
                logits, new_cache = model.decode(params, cache,
                                                 toks[None, :], splan)
                return logits[0].astype(jnp.float32), new_cache
            logits, pool = jax.vmap(one)(pool, tokens)      # (B, S, V)
            B, S_, V = logits.shape
            flat = logits.reshape(B * S_, V)
            act = jnp.repeat(active, S_)
            finite = (jnp.isfinite(flat).all(axis=-1).reshape(B, S_)
                      .all(axis=-1) | ~active)
            return (sample_pool(flat, act, key, temp).reshape(B, S_),
                    finite, pool)

        B = self._pool.n_slots
        return jax.jit(step, donate_argnums=(1,)).lower(
            self.params, self._pool.pool, jnp.zeros((B, S), jnp.int32),
            jnp.zeros((B,), jnp.bool_),
            jax.random.PRNGKey(0)).compile(), depth, 1, ""

    def _build_paged_step(self, plan: RegionPlan):
        """AOT-compile one decode(+verify)+sample step over the paged pool:
        natively batched over slots, K/V gathered through the block tables.

        The plan's resolved ``spec_depth`` D sets the step's fixed query
        width S = D+1: tokens (B, S) carry each slot's pending token
        followed by its drafted continuation, and the returned (B, S)
        token grid is the argmax chain the host's acceptance walk compares
        the drafts against.  D=0 degenerates to the plain one-token step.

        The plan's resolved tp degree shards the step over the mesh
        "model" axis (:meth:`_serve_plan`): pages on kv_heads, params on
        their logical axes, block tables / lengths / tokens replicated.
        The vocab-sharded logits replicate right before sampling — the
        step's single collective boundary — so the sampler and the host's
        acceptance walk are shard-count-independent and greedy output is
        bit-identical across degrees.

        The step also carries the always-on health guard: a per-slot
        ``finite`` flag, False when any of the slot's S logit rows
        contains a NaN/inf (one fused reduction over logits the step
        already produced — LIKWID-style monitoring that costs a rounding
        error next to the decode matmuls).  Inactive slots decode the
        null page and are garbage *by design*, so they are forced
        healthy.  The host commits nothing from a non-finite slot and
        retries it (see ``_serve_paged``).

        The decode attention lowers as the RESOLVED impl
        (:meth:`attn_impl_for`), pinned into the plan so '' never reaches
        the model code.  Returns (compiled, D, tp, attn_impl); the
        compiled step returns ``(tokens (B,S), finite (B,), pages)``.
        """
        depth = self.spec_depth_for(plan)
        S = depth + 1
        tp = self.tp_for(plan)
        impl = self.attn_impl_for(plan)
        self._apply_tp(tp)          # lowering captures the live placement
        splan = self._serve_plan(self._plan_with_attn_impl(
            plan, impl, override=self._kernel_refuses_rows()), tp)
        mesh = splan.mesh if tp > 1 else None
        step = paged_step_fn(self.model, splan, self.cfg.temperature, mesh)

        pool = self._pool
        B, MP = pool.n_slots, pool.max_pages_per_slot
        args = [self._step_params, pool.pages,
                jnp.zeros((B, S), jnp.int32), jnp.zeros((B, MP), jnp.int32),
                jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.bool_),
                jax.random.PRNGKey(0)]
        if mesh is not None:
            rep = NamedSharding(mesh, P())
            args[2:] = [jax.device_put(a, rep) for a in args[2:]]
        compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
        if mesh is None:
            return compiled, depth, tp, impl
        rep = NamedSharding(mesh, P())

        def call(params, pages, *rest):
            # AOT executables are strict about input shardings: the serve
            # loop's small host-built arrays must arrive replicated on the
            # mesh, matching how the step was lowered above
            return compiled(params, pages,
                            *(jax.device_put(r, rep) for r in rest))
        call.as_text = compiled.as_text         # counters.collect reads the
        call.cost_analysis = compiled.cost_analysis     # HLO through these
        return call, depth, tp, impl

    def _chunk_fn(self):
        """Jitted paged prefill-chunk step (pages donated; the block-table
        row and base position are traced, so every slot and chunk index
        shares one executable per chunk width — jit's shape-keyed cache
        handles the widths).  At tp > 1 the output pages are pinned to the
        pool's kv-head sharding: the chunk fn sits between AOT decode
        steps whose input-sharding checks are strict, so GSPMD must never
        drift the pages' placement.  A tp switch invalidates the trace
        (:meth:`_apply_tp`)."""
        if self._chunk_step is None:
            model, plan = self.model, self.plan
            out_sh = None
            if self._serve_tp > 1:
                from repro.distributed.sharding import cache_shardings
                out_sh = cache_shardings(
                    self._serve_plan(plan, self._serve_tp), self._pool.pages)

            def chunk_step(params, pages, tokens, bt_row, base):
                return model.paged_prefill_chunk(params, pages, tokens,
                                                 bt_row, base, plan)

            self._chunk_step = jax.jit(chunk_step, donate_argnums=(1,),
                                       out_shardings=out_sh)
        return self._chunk_step

    def _slot_chunk_fn(self, width: int, mode: str):
        """Jitted slot-pool prefill-chunk / re-advance step: fold ``width``
        tokens into one request's single-slot cache (state donated, logits
        discarded).  One executable per (width, scan-mode) — exact widths,
        because right-padding is unsound for recurrent state (the
        recurrence would absorb the pads); jit's shape-keyed cache would
        key the widths anyway, the dict just makes the mode explicit."""
        fn = self._slot_chunks.get((width, mode))
        if fn is None:
            model = self.model
            splan = self._plan_with_scan_mode(self.plan, mode)

            def chunk_step(params, cache, tokens):
                _, cache = model.decode(params, cache, tokens, splan)
                return cache

            fn = jax.jit(chunk_step, donate_argnums=(1,))
            self._slot_chunks[(width, mode)] = fn
        return fn

    def _prefill_slot(self, prompt: np.ndarray):
        """Fill a fresh single-request cache with prompt[:-1]; the last
        prompt token is returned to be fed through the pool decode step
        (which then yields the first generated token).  Recurrent families
        prefill under the resolved prefill-phase scan mode (chunk by
        default: the whole-prompt scan is exactly where the intra-chunk
        matmul form wins)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size < 2:
            return self._pool.empty_slot_cache(), int(prompt[-1])
        feed = prompt[:-1]
        true_len = feed.size
        if self.cfg.prefill_bucket and self._pad_safe():
            b = self.cfg.prefill_bucket
            padded = min(-(-true_len // b) * b, self.cfg.max_len)
            if padded > true_len:
                feed = np.pad(feed, (0, padded - true_len))
        mode = self.scan_mode_for(self._decided_plan, phase="prefill")
        fn = self._slot_prefills.get((feed.size, mode))
        if fn is None:
            plan = self._plan_with_scan_mode(self.plan, mode)

            def pf(params, tokens, true_len):
                _, cache = self.model.prefill(
                    params, {"tokens": tokens}, plan,
                    max_len=self.cfg.max_len)
                cache = dict(cache)
                cache["pos"] = jnp.asarray(true_len, jnp.int32)
                return cache
            fn = jax.jit(pf)
            self._slot_prefills[(feed.size, mode)] = fn
        cache = fn(self.params, jnp.asarray(feed)[None],
                   jnp.asarray(true_len, jnp.int32))
        return cache, int(prompt[-1])

    def _replan_due(self, n_active: int) -> bool:
        """Whether :meth:`_maybe_replan` would re-decide now."""
        if self._pool_rc is None or self.decider is None:
            return False
        if self._fallback is not None:
            # degraded: the safe plan is pinned; a replan would override
            # it.  Recovery (_exit_fallback) sets _force_replan so the
            # decider re-decides promptly once healthy.
            return False
        return (load_bucket(n_active) != self._load_bucket
                or self.decider.version != self._decider_version
                or self._force_replan)

    def _maybe_replan(self, n_active: int):
        """On load-bucket changes — or when the decider's tree was hot-
        swapped (version bump) or the explorer forced a re-decide — re-pick
        the decode plan via the dtree.  Without the version check a freshly
        retrained tree would silently never take effect until the next
        occupancy-bucket change (regression-tested)."""
        if not self._replan_due(n_active):
            return
        bucket = load_bucket(n_active)
        self._load_bucket = bucket
        self._decider_version = self.decider.version
        self._force_replan = False
        load_frac = min(bucket, self._pool.n_slots) / self._pool.n_slots
        plan, decisions = self.decider.decide(
            self._pool_rc, self.plan, load_frac=load_frac,
            top_n=self.cfg.autoplan_top_n)
        # reward attribution: the class actually in effect for the tap region
        tap_prefix = (canonical(self._tap_region) if self._tap_region
                      else None)
        cls_in_effect = "keep_default"
        for prefix, cls in decisions:
            if prefix == tap_prefix:
                cls_in_effect = self.decider.applied_class(prefix, cls)
        # epsilon-greedy exploration: override the greedy choice so serve
        # traffic populates classes the offline search never tried
        self._exploring = False
        if self.explorer is not None and tap_prefix is not None:
            explored = self.explorer.maybe_explore(plan, region=tap_prefix)
            if explored is not None:
                cls_in_effect, plan = explored
                decisions = decisions + [(f"explore:{tap_prefix}",
                                          cls_in_effect)]
                self.autotune_stats["explored"] = self.explorer.explored
                self._exploring = True
        # the class for this bucket is changing mid-window: flush the steps
        # accumulated under the OLD class first, or their reward would be
        # credited to the new class at the next _tap_flush (teaching the
        # tree the old class's throughput as the new class's)
        old_cls = self._bucket_class.get(bucket)
        if (old_cls is not None and old_cls != cls_in_effect
                and bucket in self._tap_acc):
            self._append_bucket_obs(bucket, self._tap_acc.pop(bucket),
                                    old_cls)
        self._bucket_class[bucket] = cls_in_effect
        # memory policy is decided on the same cadence as the plan: the
        # governor's reservation/watermark follow the decided (or explored)
        # class for the current bucket — an allocator-policy change, never
        # a recompile (the step cache strips the knobs)
        if self.governor is not None:
            self.governor.set_policy(self.reservation_for(plan),
                                     self.mem_watermark_for(plan),
                                     max_preempts=self.cfg.max_preempts)
            self._pool.prefix_enabled = self.prefix_cache_for(plan)
        # prefill-phase knobs (slot scan_mode) resolve against the decided
        # (or explored) plan at call time — prefill fns are jit-cached per
        # mode, so a flip retraces nothing that already compiled
        self._decided_plan = plan
        key = self._step_cache_key(plan)
        if key not in self._pool_steps:
            self._pool_steps[key] = self._build_step(plan)
        (self._pool_step, self._spec_depth, step_tp,
         self._attn_impl) = self._pool_steps[key]
        # a cache HIT can still be a tp switch (the decider flipping back
        # to a degree it compiled earlier): reshard the live pool/params
        # to the placement the cached executable was lowered against
        self._apply_tp(step_tp)
        self.decisions_log.append((n_active, decisions))

    # ------------------------------------------------------------------
    # Online autotuning: the measurement tap (measure -> corpus -> train
    # -> decide, closed inside the serving loop)
    # ------------------------------------------------------------------
    def _tap_step(self, n_active: int, tokens: int, dt_s: float):
        """Record one decode step's work into the per-bucket accumulators;
        every ``retrain_interval`` steps, flush to the corpus and retrain."""
        if self.corpus is None or self._pool_rc is None:
            return
        st = self.autotune_stats
        st["steps"] += 1
        if self._exploring:
            st["explore_steps"] += 1
        seg = "post" if st["swaps"] else "pre"
        st[seg + "_tokens"] += tokens
        st[seg + "_secs"] += dt_s
        bucket = load_bucket(n_active)
        acc = self._tap_acc.setdefault(bucket, [0, 0, 0.0, 0, 0])
        acc[0] += 1
        acc[1] += tokens
        acc[2] += dt_s
        # latency channel: per-bucket step-latency sketch over the window
        # (the p99 rides into the corpus as an occupancy-invariant
        # Counters feature, like prefix_hit_rate/fault_rate)
        lat = self._tap_lat.get(bucket)
        if lat is None:
            from repro.serve.telemetry import LatencySketch
            lat = self._tap_lat[bucket] = LatencySketch()
        lat.add(dt_s)
        # prefix-cache hit-rate channel: per-window deltas of the pool's
        # monotonic lookup/hit counters, attributed to this step's bucket
        # so the decider can see mem_prefix_* classes EARNING their reward
        if self._paged and self._pool is not None:
            idx = self._pool.prefix
            cur = (idx.lookups, idx.hits)
            if self._tap_prefix_last is not None:
                acc[3] += cur[0] - self._tap_prefix_last[0]
                acc[4] += cur[1] - self._tap_prefix_last[1]
            self._tap_prefix_last = cur
        self._tap_pending += 1
        if self._tap_pending >= max(self.cfg.retrain_interval, 1):
            self._tap_flush()

    def _append_bucket_obs(self, bucket: int, acc, cls: str):
        """Append one bucket's accumulated window (``[steps, toks, secs,
        prefix_lookups, prefix_hits]``) to the corpus as a rewarded
        observation attributed to ``cls``.  The window's prefix hit rate
        rides along as a counter channel (decile-quantized so identical
        windows still dedup), letting the tree split mem_prefix_* classes
        on the hits that explain their tok/s, not just the tok/s."""
        from repro.autotune.corpus import bucket_rate
        from repro.core.dtree import features
        steps, toks, secs = acc[0], acc[1], acc[2]
        if self.corpus is None or steps == 0 or secs <= 0 or toks == 0:
            return
        region = self._tap_region
        counters = (self._pool_rc.regions.get(region) if region else None)
        if counters is None:
            return
        load_frac = min(bucket, self._pool.n_slots) / self._pool.n_slots
        scaled = counters.scaled(load_frac)
        lookups = acc[3] if len(acc) > 3 else 0
        if lookups:
            scaled = dataclasses.replace(
                scaled, prefix_hit_rate=bucket_rate(acc[4] / lookups))
        # health channel: the monitor's windowed faulted-step fraction at
        # flush time, decile-quantized like prefix_hit_rate so identical
        # windows still dedup — lets the tree learn which classes earn
        # their reward under faults (degradation responses as decisions)
        fr = self.health.fault_rate()
        if fr > 0:
            scaled = dataclasses.replace(scaled, fault_rate=bucket_rate(fr))
        # latency channels: windowed p99 step latency for this bucket and
        # the window's mean admission wait, both quantized to coarse
        # log-ms steps (bucket_log_ms) so identical windows still dedup —
        # the decider learns from observed latency, not just tok/s
        from repro.autotune.corpus import bucket_log_ms
        # pop: a mid-window flush (_maybe_replan's class change) must not
        # leak the old class's latencies into the new class's window
        lat = self._tap_lat.pop(bucket, None)
        if lat is not None and lat.count:
            scaled = dataclasses.replace(
                scaled, step_latency_p99=bucket_log_ms(lat.quantile(0.99)))
        if self._tap_qd[1]:
            scaled = dataclasses.replace(
                scaled,
                queue_delay=bucket_log_ms(self._tap_qd[0] / self._tap_qd[1]))
        self.corpus.append(canonical(region), features(scaled),
                           cls, reward=toks / secs)

    def _tap_flush(self):
        """Corpus append (per-bucket features + class + tok/s reward) ->
        retrain -> hot-swap.  A swap bumps the decider version, which
        forces a replan on the very next step (the load-bucket latch is no
        longer trusted)."""
        self._tap_pending = 0
        for bucket, acc in self._tap_acc.items():
            self._append_bucket_obs(
                bucket, acc, self._bucket_class.get(bucket, "keep_default"))
        self._tap_acc.clear()
        self._tap_lat.clear()
        self._tap_qd = [0.0, 0]
        self.autotune_stats["corpus_entries"] = len(self.corpus)
        new_tree = self.trainer.maybe_retrain(self.corpus, self.decider.tree)
        self.autotune_stats["retrains"] = self.trainer.retrain_count
        if new_tree is not None:
            self.decider.swap(new_tree)     # version bump busts the latch
            self.autotune_stats["swaps"] += 1
            if self.telemetry is not None:
                self.telemetry.event(
                    "tree_swap", level="info",
                    retrains=self.trainer.retrain_count,
                    corpus_entries=len(self.corpus))
        elif self.explorer is not None and self.explorer.active:
            # no swap this round: give the explorer a mid-bucket chance at
            # the retrain cadence (bounded by its eps and budget) so new
            # classes keep entering the corpus even under steady load
            self._force_replan = True

    def autotune_summary(self) -> dict:
        """Machine-readable record of the online loop (serve() returns it)."""
        st = dict(self.autotune_stats)
        pre = st.pop("pre_tokens"), st.pop("pre_secs")
        post = st.pop("post_tokens"), st.pop("post_secs")
        st["pre_swap_tok_s"] = pre[0] / pre[1] if pre[1] > 0 else 0.0
        st["post_swap_tok_s"] = post[0] / post[1] if post[1] > 0 else 0.0
        st["post_swap_tok_s_delta"] = (
            st["post_swap_tok_s"] - st["pre_swap_tok_s"]
            if pre[1] > 0 and post[1] > 0 else 0.0)
        st["explore_fraction"] = (st["explore_steps"] / st["steps"]
                                  if st["steps"] else 0.0)
        if self.explorer is not None:
            st["explored"] = self.explorer.explored
        return st

    def _step_cache_key(self, plan: RegionPlan) -> str:
        """Compiled pool steps are cached by the plan's *step-affecting*
        content: pool-layout-only knobs (page_size — fixed at pool build)
        are stripped, and spec_depth is stripped whenever the knob isn't
        live (pinned by ServeConfig, temperature sampling, MoE, or the
        slot pool), so a dtree decision that couldn't change the
        executable never triggers a recompile stall mid-trace."""
        import json as _json
        raw = _json.loads(plan.to_json())
        for rc in raw.get("regions", {}).values():
            rc.pop("page_size", None)
            # memory-governor policy knobs steer admission/reclamation on
            # the host, never the compiled step
            rc.pop("reservation", None)
            rc.pop("mem_watermark", None)
            rc.pop("prefix_cache", None)
            if not self._spec_knob_live():
                rc.pop("spec_depth", None)
            # the raw tp_degree knob is replaced by the RESOLVED degree
            # below: tp4 clamped to 2 on a 2-device host must share the
            # tp2 executable, not mint a third identical compile
            rc.pop("tp_degree", None)
            # likewise scan_mode: "auto" and a concrete mode that resolves
            # identically must share one executable
            rc.pop("scan_mode", None)
        if self._paged:
            raw["tp"] = self.tp_for(plan)
            # attn_impl keys on its RESOLVED value: '' and the impl it
            # resolves to share one executable
            impl = self.attn_impl_for(plan)
            refused = self._kernel_refuses_rows()
            for rc in raw.get("regions", {}).values():
                rc["attn_impl"] = (not refused and rc.get("attn_impl")
                                   or impl)
            # the resolved depth rides alongside for the same reason —
            # and because resolution can change while the raw knob (or a
            # ServeConfig pin) does not: a degraded engine (_force_safe)
            # pins depth 0, and its safe step must never collide with
            # the healthy executable cached for the same plan
            raw["spec"] = self.spec_depth_for(plan)
        else:
            # the slot pool's step is shaped by the resolved draft depth
            # (query width S = D+1) and lowers under the resolved decode
            # scan mode — both cache-key, neither recompiles when a dtree
            # decision couldn't change the executable
            raw["spec"] = self.spec_depth_for(plan)
            raw["scan"] = self.scan_mode_for(plan)
        return _json.dumps(raw, sort_keys=True)

    def _validate(self, req: Request):
        cfg = self.model.cfg
        if cfg.family != "ssm" and not cfg.swa_window:
            need = req.prompt.size - 1 + req.max_new_tokens
            if need > self.cfg.max_len:
                raise ValueError(
                    f"request {req.rid}: prompt+generation ({need}) exceeds "
                    f"max_len ({self.cfg.max_len})")
            if self._paged:
                # a demand no admission can ever satisfy would make the
                # FIFO head spin forever — reject it up front
                from repro.serve.cache import pages_for
                n = pages_for(need, self._pool.page_size)
                cap = min(self._pool.max_pages_per_slot,
                          self._pool.n_pages - 1)
                if n > cap:
                    raise ValueError(
                        f"request {req.rid}: needs {n} KV pages but the "
                        f"pool can ever grant {cap} (kv_pages="
                        f"{self._pool.n_pages}, page_size="
                        f"{self._pool.page_size})")

    def serve(self, requests: Sequence[Request]) -> dict:
        """Run a trace of Requests to completion with continuous batching.

        Arrivals are replayed on the wall clock relative to serve() entry;
        requests with arrival_s=0 are all admissible immediately.  Mutates
        the Request objects in place (out_tokens, timings) and returns
        {"requests", "stats", "steps", "decisions", "failures", "health"}.

        Failure semantics (docs/failure-semantics.md): runtime faults —
        non-finite logits, allocator exhaustion, growth denial, injected
        chaos — never raise.  Each faulted request retries with capped
        backoff and, past ``max_retries``, lands in the terminal FAILED
        state with every page released; waiting requests past their
        deadline (EXPIRED) or beyond ``max_queue`` (REJECTED) are shed
        explicitly.  The only raises left are pre-serve validation
        (structurally infeasible requests — a programmer error, checked
        before any state exists) and engine-internal errors, which abort
        the trace after releasing every resident's pages.

        Under a profiler session the call is a ``serve.run`` span holding
        the loop's phase spans (docs/observability.md), and each garbage
        collection during it a ``python.gc`` span.
        """
        gc.callbacks.append(self._gc_spans)
        try:
            with span("serve.run"):
                return self._serve(requests)
        finally:
            gc.callbacks.remove(self._gc_spans)

    def _serve(self, requests: Sequence[Request]) -> dict:
        """:meth:`serve`'s body."""
        self._ensure_pool()
        for r in requests:
            self._validate(r)
        # each trace re-selects from scratch (compiled steps stay cached);
        # only this run's decisions are returned
        self._load_bucket = None
        log_start = len(self.decisions_log)
        # fresh health window per trace; a fallback left armed by the
        # previous trace is unwound so this one starts on the decided plan
        self.health.reset()
        self._exit_fallback()
        sched = Scheduler()
        tel = self.telemetry
        if tel is not None:
            tel.start_trace()           # fresh spans/ring/sketches per trace
            sched.tracer = tel.tracer
        for r in requests:
            sched.submit(r)
        sched.sort_queue()

        if self._paged:
            res = self._serve_paged(sched)
        else:
            res = self._serve_slots(sched)

        # satellite bugfix: a serve shorter than retrain_interval (or one
        # ending mid-interval) used to discard its residual accumulators —
        # short serves never fed the corpus.  Flush whatever the trace
        # accumulated so every serve's measurements reach the corpus.
        if self.corpus is not None and (self._tap_acc or self._tap_pending):
            self._tap_flush()

        out = {
            "requests": list(requests),
            "decisions": list(self.decisions_log[log_start:]),
            **self.observability(requests),
        }
        out.update(res)
        if tel is not None:
            tel.event("serve_done", level="info",
                      steps=res.get("steps", 0),
                      n_done=out["stats"].get("n_done", 0),
                      tok_per_s=round(out["stats"].get("tok_per_s", 0.0), 3))
            if self.cfg.trace_out:
                tel.write_trace(self.cfg.trace_out)
            if self.cfg.metrics_out:
                with open(self.cfg.metrics_out, "w") as f:
                    f.write(self.metrics_text())
        return out

    # ------------------------------------------------------------------
    # Observability: the one aggregate every reader consumes
    # ------------------------------------------------------------------
    def observability(self, requests: Optional[Sequence[Request]] = None
                      ) -> dict:
        """The per-subsystem ``summary()`` dicts behind one aggregate:
        autotune, health, faults, memory (+ mesh on the paged pool),
        telemetry, and — when ``requests`` is passed — the scheduler's
        trace stats and failure rollup.  ``serve()``'s return, the
        launcher report and ``metrics_text()`` all read from here, so a
        new subsystem tap shows up everywhere by editing one method.
        Keys match the historical ``serve()`` return exactly."""
        obs: dict = {
            "autotune": self.autotune_summary(),
            "health": self.health.summary(),
            "faults": (self.faults.summary() if self.faults is not None
                       else {"enabled": False, "injected_total": 0}),
        }
        if self._paged and self.governor is not None:
            pool = self._pool
            obs["memory"] = self.governor.summary()
            obs["step"] = {"attn_impl": self._attn_impl,
                           "pool_layout": pool.layout}
            # mesh placement: page bytes are per DEVICE (pages shard on
            # kv_heads, so each device holds 1/tp of every page);
            # page/watermark COUNTS are tp-invariant
            obs["mesh"] = {
                "tp": pool.tp_shards,
                "devices": len(jax.devices()),
                "page_bytes_per_device": pool.per_device_page_bytes(),
                "hbm_bytes_per_device": pool.per_device_hbm_bytes(),
                "high_water_bytes_per_device":
                    pool.per_device_high_water_bytes(),
            }
        elif self._pool is not None:
            # accounting parity with the paged pool: recurrent serves are
            # observable (HBM footprint, occupancy high-water) like paged
            pool = self._pool
            obs["memory"] = {"pool": "slot",
                             "slot_bytes": pool.slot_bytes(),
                             "hbm_bytes": pool.hbm_bytes(),
                             "high_water_slots": pool.high_water,
                             "high_water_bytes": pool.high_water_bytes()}
        if self.telemetry is not None:
            obs["telemetry"] = self.telemetry.summary()
        if requests is not None:
            stats = summarize(requests)
            obs["stats"] = stats
            obs["failures"] = {
                "failed": stats.get("failed", 0),
                "expired": stats.get("expired", 0),
                "rejected": stats.get("rejected", 0),
                "retries": stats.get("retries", 0),
                "errors": {r.rid: r.error for r in requests if r.error},
            }
        return obs

    def metrics_text(self) -> str:
        """Prometheus text-exposition snapshot of :meth:`observability`
        (plus the telemetry latency quantiles when telemetry is on) —
        the per-engine metrics export the replica layer scrapes."""
        from repro.serve.telemetry import prometheus_text
        return prometheus_text(self.observability(),
                               telemetry=self.telemetry)

    # ------------------------------------------------------------------
    # Graceful degradation: the safe-plan fallback
    # ------------------------------------------------------------------
    def _safe_plan(self) -> RegionPlan:
        """The degradation target: the live plan with the attention region
        forced to the boring-but-robust configuration — no speculation,
        the gather (non-Pallas) attention path, no tensor parallelism."""
        import copy
        plan = copy.deepcopy(self.plan)
        base = plan.region_configs.get("layer/attn", RegionConfig())
        plan.region_configs["layer/attn"] = dataclasses.replace(
            base, spec_depth=0, attn_impl="gather", tp_degree=1)
        return plan

    def _enter_fallback(self):
        """Pin the safe plan (spec0 / gather attn / tp1).  The safe step
        goes through the regular ``_pool_steps`` cache — healthy
        executables stay cached untouched and the fallback compiles at
        most once per engine; re-entry is a dictionary fetch.  The
        previous (step, depth, tp) is saved for :meth:`_exit_fallback`."""
        if self._fallback is not None or not self._paged:
            return
        prev = (self._pool_step, self._spec_depth, self._serve_tp,
                self._attn_impl)
        self._force_safe = True
        plan = self._safe_plan()
        key = self._step_cache_key(plan)
        if key not in self._pool_steps:
            self._pool_steps[key] = self._build_step(plan)
        step, depth, tp, impl = self._pool_steps[key]
        self._apply_tp(tp)
        self._pool_step, self._spec_depth, self._attn_impl = step, depth, impl
        self._fallback = prev
        self.health.taps["fallbacks"] += 1

    def _exit_fallback(self):
        """Recovered: restore the pre-fallback executable/placement and
        ask the decider to re-decide on the next step."""
        if self._fallback is None:
            return
        step, depth, tp, impl = self._fallback
        self._fallback = None
        self._force_safe = False
        self._apply_tp(tp)
        self._pool_step, self._spec_depth, self._attn_impl = step, depth, impl
        self._force_replan = True

    def _fallback_due(self) -> bool:
        """Whether the health ladder asks to enter or leave the fallback."""
        return self.health.degraded != (self._fallback is not None)

    def _apply_health(self):
        """Enter the safe-plan fallback while degraded, restore on
        recovery."""
        if self.health.degraded:
            self._enter_fallback()
        else:
            self._exit_fallback()

    def _commit_tokens(self, sched: Scheduler, out_np, n_cand, pending,
                       active, t, on_complete) -> dict:
        """Shared post-step bookkeeping for both pools: walk each active
        slot's verified token chain ``out_np[slot, :n_cand[slot]]`` in
        order, recording tokens until the budget or EOS cuts the chain,
        then complete and release.  The plain one-token step is the
        n_cand=1 case; n_cand=0 marks a slot that sat out this step
        (allocation-stalled: masked from the decode, nothing written).
        Returns {slot: tokens consumed this step} over stepped slots."""
        consumed: dict[int, int] = {}
        for slot in list(sched.active):
            if n_cand[slot] == 0:
                continue
            req = sched.active[slot]
            eos = req.eos_id if req.eos_id is not None else self.cfg.eos_id
            c, done = 0, False
            for i in range(n_cand[slot]):
                tok = int(out_np[slot, i])
                if not req.out_tokens:
                    req.t_first = t
                    if self.telemetry is not None:
                        self.telemetry.ttft.add(max(t - req.arrival_s, 0.0))
                req.out_tokens.append(tok)
                c += 1
                if len(req.out_tokens) >= req.max_new_tokens or tok == eos:
                    done = True
                    break
            consumed[slot] = c
            if done:
                sched.complete(req, t)
                active[slot] = False
                on_complete(slot, req)
            else:
                pending[slot] = int(out_np[slot, c - 1])
        return consumed

    def _serve_slots(self, sched: Scheduler) -> dict:
        """The slot-pool loop: vmapped decode over whole-cache slots, with
        the recurrent families now first-class citizens of continuous
        batching.

        **Chunked state prefill** (``prefill_chunk`` > 0): a prompt no
        longer prefills whole on admission — the request binds mid-prefill
        (the scheduler's PREFILL lifecycle, exactly as on the paged pool),
        its state accumulates in a host-held single-slot cache fed
        ``prefill_chunk`` tokens at a time, and at most
        ``prefill_chunks_per_step`` chunks run between consecutive pool
        decode steps.  A long prompt is spread across many steps instead
        of head-of-line blocking every in-flight decode.  The chunk fn
        runs under the resolved *prefill-phase* scan mode (chunk by
        default — the wkv/ssd chunked kernels turn the intra-chunk work
        into causal matmuls), while the decode step keeps its own mode.

        **Speculative decode on recurrent state** (resolved ``spec_depth``
        D > 0, greedy only): drafts come from :func:`draft_ngram` as on
        the paged pool and one fixed-shape S = D+1 verify step scores
        every slot at once.  A recurrence has no length-truncation
        rollback — rejected drafts are already folded into the state — so
        the rollback contract is **snapshot/restore**: each speculating
        slot's fixed-size state is copied before the verify step
        (O(state), independent of context length) and, on rejection,
        restored and re-advanced over exactly the inputs whose outputs
        committed.  Greedy output stays bit-identical to the
        non-speculative path.

        Faulted slots (non-finite logits, or chaos-injected) follow the
        paged pool's retry ladder: commit nothing, restore the pre-step
        snapshot when one exists, and fail terminally past
        ``max_retries``."""
        pool = self._pool
        B = pool.n_slots
        pending = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        prefills: list[Request] = []        # admitted, mid-prefill (FIFO)
        pcaches: dict[int, Any] = {}        # slot -> host-held prefill cache
        key = jax.random.PRNGKey(self.cfg.seed)
        t0 = time.perf_counter()
        now = lambda: time.perf_counter() - t0  # noqa: E731
        steps = 0
        committed_total = 0                 # tokens committed by decode steps
        slot_steps = 0                      # sum of stepped slots over steps
        max_depth = 0                       # deepest speculation actually run

        tel = self.telemetry
        while not sched.done():
            t = now()
            # admit: every free slot takes the next arrived request (FIFO)
            while pool.n_free and sched.has_ready(t):
                req = sched.pop_ready(t)
                # queue-delay tap (slot admissions are always fresh —
                # the slot pool never preempts): feeds the Counters
                # channel and, when on, the telemetry sketch
                qd = max(t - req.arrival_s, 0.0)
                if self.corpus is not None:
                    self._tap_qd[0] += qd
                    self._tap_qd[1] += 1
                if tel is not None:
                    tel.on_admit(req.rid, qd, preempted=False)
                hist = req.token_history()
                slot = pool.alloc()
                if self.cfg.prefill_chunk > 0 and hist.size >= 2:
                    sched.bind_prefill(req, slot, now())
                    pcaches[slot] = pool.empty_slot_cache()
                    req.prefill_pos = 0
                    prefills.append(req)
                else:
                    cache, first_tok = self._prefill_slot(hist)
                    pool.write(slot, cache)
                    pending[slot] = first_tok
                    sched.bind(req, slot, now())
                    active[slot] = True
            # deadline/queue shedding applies to the slot path too — the
            # policy is scheduler-level, not a paged-pool feature
            sched.shed_waiting(now(), self.cfg.max_queue,
                               self.cfg.deadline_s)

            # interleaved chunked prefill: a bounded budget per loop pass
            budget = max(self.cfg.prefill_chunks_per_step, 1)
            pmode = self.scan_mode_for(self._decided_plan, phase="prefill")
            while budget > 0 and prefills:
                req = prefills[0]
                slot = req.slot
                feed = req.token_history()[:-1]
                chunk = feed[req.prefill_pos:
                             req.prefill_pos + self.cfg.prefill_chunk]
                tc0 = now() if tel is not None else 0.0
                pcaches[slot] = self._slot_chunk_fn(chunk.size, pmode)(
                    self.params, pcaches[slot], jnp.asarray(chunk)[None])
                if tel is not None:
                    tel.tracer.add(req.rid, "PREFILL_CHUNK", tc0, now(),
                                   tokens=int(chunk.size))
                budget -= 1
                req.prefill_pos += chunk.size
                if req.prefill_pos >= feed.size:
                    pool.write(slot, pcaches.pop(slot))
                    pending[slot] = int(req.token_history()[-1])
                    sched.start_decode(req, now())
                    active[slot] = True
                    prefills.pop(0)

            if not sched.active:
                if prefills:
                    continue                # keep prefilling
                nxt = sched.next_arrival()
                if nxt is None:
                    break
                dt = nxt - now()
                if dt > 0:
                    time.sleep(min(dt, 0.05))
                continue

            n_act = len(sched.active)
            self._maybe_replan(n_act)
            t_step0 = time.perf_counter()
            D = self._spec_depth
            S = D + 1
            max_depth = max(max_depth, D)
            dmode = self.scan_mode_for(self._decided_plan)

            toks_in = np.zeros((B, S), np.int32)
            toks_in[:, 0] = pending
            # snapshots make faults (and rejected drafts) recoverable; at
            # D=0 with no injector a non-finite retry would recompute the
            # identical garbage anyway, so the copies are skipped
            snaps: dict[int, Any] = {}
            if D or self.faults is not None:
                for slot, req in sched.active.items():
                    if D:
                        toks_in[slot, 1:] = draft_ngram(req.token_history(),
                                                        D)
                    snaps[slot] = pool.snapshot(slot)
            key, sub = jax.random.split(key)
            out, finite, pool.pool = self._pool_step(
                self.params, pool.pool, jnp.asarray(toks_in),
                jnp.asarray(active), sub)
            steps += 1
            out_np = np.asarray(out)
            finite_np = np.asarray(finite)

            # per-step health guard + acceptance walk (paged semantics on
            # the slot pool): a faulted slot commits nothing and retries
            # from its pre-step snapshot; draft i is valid iff it equals
            # the verify argmax after draft i-1 (and every earlier draft
            # held) — the longest such prefix commits
            faulted: set[int] = set()
            for slot in list(sched.active):
                if not bool(finite_np[slot]):
                    faulted.add(slot)
            if self.faults is not None:
                for slot in list(sched.active):
                    if slot not in faulted and self.faults.fire("logits.nan"):
                        faulted.add(slot)
            n_cand = np.ones((B,), np.int32)
            slot_steps += len(sched.active)
            for slot in list(sched.active):
                req = sched.active[slot]
                if slot in faulted:
                    n_cand[slot] = 0
                    req.retries += 1
                    req.fail_streak += 1
                    had_snap = slot in snaps
                    if had_snap:
                        pool.restore(slot, snaps.pop(slot))
                    if (req.fail_streak > self.health.policy.max_retries
                            or not had_snap):
                        # no snapshot means no injector and no drafts: the
                        # NaN is the model's own deterministic blowup — a
                        # retry would recompute it bit for bit
                        pool.free(slot)
                        active[slot] = False
                        pending[slot] = 0
                        sched.fail(req, now(),
                                   "non-finite logits on slot pool")
                    continue
                req.fail_streak = 0
                a = 0
                while a < D and toks_in[slot, a + 1] == out_np[slot, a]:
                    a += 1
                n_cand[slot] = a + 1
            consumed = self._commit_tokens(sched, out_np, n_cand, pending,
                                           active, now(),
                                           lambda slot, _req: pool.free(slot))
            committed_total += sum(consumed.values())
            if D:
                for slot, c in consumed.items():
                    if slot in sched.active and c < S:
                        # rejected tail: the state already absorbed the bad
                        # drafts — splice the pre-step snapshot back through
                        # a re-advance over exactly the c accepted inputs,
                        # the state a sequential decode of the committed
                        # tokens would hold (the snapshot is donated; it is
                        # dead after this)
                        pool.write(slot, self._slot_chunk_fn(c, dmode)(
                            self.params, snaps[slot],
                            jnp.asarray(toks_in[slot, :c])[None]))
            dt_step = time.perf_counter() - t_step0
            self.health.note_step(dt_step, n_slot_faults=len(faulted))
            self._tap_step(n_act, sum(consumed.values()), dt_step)
            if tel is not None:
                tel.on_step(steps, dt_step,
                            sum(consumed.values()), n_act, pool.n_free,
                            len(faulted),
                            self._bucket_class.get(load_bucket(n_act), ""))
        # memory/mesh accounting now comes from Engine.observability()
        # (the single aggregate serve() merges in)
        return {"steps": steps,
                "spec": {"committed_tokens": committed_total,
                         "slot_steps": slot_steps,
                         "max_depth": max_depth,
                         "accepted_drafts": committed_total - slot_steps,
                         "tokens_per_step":
                             committed_total / max(steps, 1)}}

    def _serve_paged(self, sched: Scheduler) -> dict:
        """The paged-pool loop: governor-mediated admission, prompt prefill
        in chunks interleaved with pool decode steps.

        **Elastic memory** (:class:`repro.serve.memory.MemoryGovernor`):
        admission routes through the governor — full reservation (the
        preemption-free default) or lazy (prompt pages + one decode page,
        watermark-gated).  Before every decode step each active slot's
        reserved reach is grown to cover the step's K/V write (one page at
        a time, at page boundaries); when the allocator runs dry the
        governor picks a LIFO victim among resident decodes, its pages are
        freed and the request re-queues through the scheduler's PREEMPTED
        state — it re-enters as recompute-prefill over
        prompt + generated-so-far, so greedy output stays bit-identical.
        The oldest resident may override victims' ``max_preempts`` cap
        (progress guarantee: the head of the line always finishes); a slot
        that can neither grow nor reclaim *stalls* — masked out of this
        step, retried next step.

        **Prefix caching** (``--prefix-cache``): admission looks the
        prompt up in the pool's :class:`repro.serve.cache.PrefixIndex`;
        a hit maps the cached leading page run into the new slot's block
        table (refcounts bumped) and prefill covers only the un-matched
        suffix — near-zero TTFT on repeated prompts, greedy output
        bit-identical to a cold pool because the pending token's row is
        always written fresh and shared pages are copy-on-write
        privatised (``cow_for_write``) before any decode write touches
        them.  Requests publish their fully-written pages on entering
        decode and again at completion.

        Between consecutive decode steps at most
        ``prefill_chunks_per_step`` prompt chunks run, so a long prompt is
        spread across many steps instead of stalling every in-flight
        decode until it finishes (the prefill head-of-line blocking the
        slot path suffers).  Decode-step inputs are masked per step: only
        DECODE slots expose their block table and length, so mid-prefill
        slots can never be written by the decode scatter.

        When the current plan's ``spec_depth`` D is positive, every step
        runs draft -> verify -> commit/rollback: :func:`draft_ngram`
        proposes D tokens per slot from its own history, the fixed-shape
        verify step scores pending+drafts with D+1 queries, and the
        longest drafted prefix matching the verify argmax chain commits
        (up to D+1 tokens per slot per step, never fewer than the 1 the
        plain step yields); the rejected tail is rolled back by pure
        length truncation — no page churn, greedy tokens bit-identical to
        the non-speculative path.

        **One step in flight** (spec depth 0): each pass dispatches its
        step before it waits on the step the previous pass dispatched,
        so the host's work of a pass (that step's commit, admission, a
        prefill chunk, headroom, the next inputs) runs while the device
        computes.  The next step needs none of step k's tokens on the
        host: a slot step k stepped takes the token step k sampled, on
        the device (:func:`_carry_tokens`); lengths advance at dispatch;
        a request whose budget step k fills sits the next step out.  A
        row of the next step whose request step k ended (EOS, failure),
        whose token step k faulted, or whose slot was preempted commits
        nothing, its advance undone like a stall's.  The loop waits on
        the step in flight first (a drain: the serial order) where the
        next step cannot be predicted or its program may change —
        speculation, a re-plan, the fallback — and where nothing is left
        to step.  ``overlap`` in the return counts ``steps_ahead``,
        ``drains`` and ``dropped_rows``.
        """
        pool = self._pool
        gov = self.governor
        tel = self.telemetry
        B = pool.n_slots
        pending = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        prefills: list[Request] = []        # admitted, mid-prefill (FIFO)
        key = jax.random.PRNGKey(self.cfg.seed)
        now = lambda: time.perf_counter() - t0  # noqa: E731
        steps = 0
        committed_total = 0                 # tokens committed by decode steps
        slot_steps = 0                      # sum of stepped slots over steps
        max_depth = 0                       # deepest speculation actually run
        prev_eff: set = set()               # last step's stepped slot set
        fl: Optional[_Step] = None          # the step in flight, unread
        overlap = {"steps_ahead": 0, "drains": 0, "dropped_rows": 0}
        pass_tokens: Optional[int] = None   # committed this pass (if any)
        t_done_prev = float("-inf")         # the last step's completion
        # the DECODE-masked block tables change only when pool composition
        # changes (admission / completion / preemption / stall), not every
        # step — cache the device array instead of re-uploading it per step
        bt_dev = {"arr": None, "dirty": True}

        def release_slot(slot, req=None):
            # publish the finished request's fully-written pages to the
            # prefix index before unmapping — the index takes its own
            # reference, so the K/V outlives the request and a later
            # prompt sharing the prefix admits with near-zero prefill
            if req is not None:
                pool.register_prefix(slot, req.token_history())
            pool.release(slot)
            bt_dev["dirty"] = True

        def preempt_victim(victim):
            """Evict a resident decode: pages back to the allocator, the
            request to the scheduler's preempted queue (re-enters as
            recompute-prefill over its committed history)."""
            sched.preempt(sched.active[victim], now())
            pool.preempt(victim)
            active[victim] = False
            pending[victim] = 0
            bt_dev["dirty"] = True

        def fail_request(slot, req, reason):
            """A resident request exhausted its retries: terminal FAILED
            with every page released — the failure domain is one request,
            and the allocator's invariants hold immediately after.  Its
            history is suspect, so nothing is published to the prefix
            index."""
            pool.release(slot)
            active[slot] = False
            pending[slot] = 0
            bt_dev["dirty"] = True
            sched.fail(req, now(), reason)

        def admit_ready(t) -> int:
            """Admit what is due and fits; returns how many were."""
            n = 0
            while True:
                req = sched.peek_ready(t)
                if req is None:
                    return n
                # SHEDDING rung of the degradation ladder: stop taking on
                # fresh work while faults are this frequent — preempted
                # residents still re-enter (their progress is paid for).
                # Only while something is resident: an empty pool has
                # nothing to protect, and gating it would idle-spin the
                # loop with no steps to ever recover health through.
                if (self.health.shedding
                        and req.state is RequestState.WAITING
                        and (sched.active or sched.prefilling)):
                    return n
                # duplicate-arrival dedup: a fresh request whose prompt
                # matches one still mid-prefill is HELD (head-of-line, FIFO
                # preserved) until the twin publishes its prefix pages —
                # it then admits as a near-total prefix hit instead of
                # double-prefilling the same prompt.  No deadlock: chunked
                # prefill progresses every loop pass regardless of
                # admission, and publication happens unconditionally at
                # prefill completion.  Only with sharing on (a hold without
                # a future hit would be pure added latency), and never for
                # PREEMPTED re-entries (their history already diverged).
                if (pool.prefix_enabled
                        and req.state is RequestState.WAITING):
                    pk = req.prompt_key()
                    if any(r.prompt_key() == pk
                           and np.array_equal(r.prompt, req.prompt)
                           for r in sched.prefilling.values()):
                        pool.dedup_holds += 1
                        return n
                # a preempted request re-enters as recompute-prefill over
                # prompt + generated-so-far; its worst case is unchanged
                # (every recomputed token replaces a remaining new one)
                hist = req.token_history()
                total = req.prompt.size - 1 + req.max_new_tokens
                # prefix-cache lookup: the longest cached leading page run
                # of the history (capped at hist.size - 1, so the pending
                # token's K/V row is always this request's own write) is
                # mapped shared and skipped by prefill — this includes a
                # preempted request re-hitting pages it published itself
                shared, matched = pool.prefix_lookup(hist)
                if (shared and gov.policy.reservation != "lazy"
                        and matched < len(shared) * pool.page_size):
                    # full reservation guarantees preemption-free decode,
                    # and the only shared page a request can ever write is
                    # a partially-adopted boundary page (its first fresh
                    # row lands mid-page) — privatising it at write time
                    # needs a free page a fully-committed pool cannot
                    # promise.  Trim the hit to fully-covered pages so a
                    # full-mode slot never CoWs; the boundary rows are
                    # prefilled fresh instead.
                    shared = shared[:-1]
                    matched = len(shared) * pool.page_size
                fresh = req.state is RequestState.WAITING
                slot = gov.admit(hist.size, total, shared_pages=shared)
                if slot is None:            # head-of-line waits for memory
                    return n
                sched.pop_ready(t)
                sched.bind_prefill(req, slot, now())
                n += 1
                # queue-delay tap: admission wait of fresh arrivals (a
                # PREEMPTED re-entry's wait is requeue_wait_s, tracked
                # separately) feeds the Counters channel and, when on,
                # the telemetry sketch
                if fresh and self.corpus is not None:
                    self._tap_qd[0] += max(t - req.arrival_s, 0.0)
                    self._tap_qd[1] += 1
                if tel is not None:
                    tel.on_admit(req.rid, max(t - req.arrival_s, 0.0),
                                 preempted=not fresh)
                if matched:
                    pool.advance(slot, matched)  # rows adopted, not written
                    pool.prefix_hit_requests += 1
                    pool.prefix_tokens_saved += matched
                    req.prefix_hit_tokens += matched
                req.prefill_pos = matched
                if hist.size - 1 <= matched:     # nothing left to prefill
                    pending[slot] = int(hist[-1])
                    pool.register_prefix(slot, hist)
                    sched.start_decode(req, now())
                    active[slot] = True
                    bt_dev["dirty"] = True
                else:
                    prefills.append(req)

        def dispatch(stepped, n_act, D, prev, t_start) -> _Step:
            """Build the step's inputs, call the pool step and advance each
            stepped slot by the rows it writes.  Reads nothing back from
            the device: a slot that ``prev`` (the step still in flight)
            stepped takes the token ``prev`` sampled, on the device."""
            nonlocal key, steps, max_depth, prev_eff
            S = D + 1
            with span("serve.step_dispatch") as ph:
                max_depth = max(max_depth, D)
                toks_in = np.zeros((B, S), np.int32)
                toks_in[:, 0] = pending
                if D:
                    for slot in stepped:
                        toks_in[slot, 1:] = draft_ngram(
                            sched.active[slot].token_history(), D)
                key, sub = jax.random.split(key)
                # expose only the stepped DECODE slots (null page
                # otherwise); a stalled slot keeps its pending token and
                # state untouched and simply retries next step
                eff = np.zeros((B,), bool)
                eff[stepped] = True
                if set(stepped) != prev_eff:
                    prev_eff = set(stepped)
                    bt_dev["dirty"] = True
                if bt_dev["dirty"]:
                    bt_dev["arr"] = jnp.asarray(
                        pool.block_tables * eff[:, None])
                    bt_dev["act"] = jnp.asarray(eff)
                    bt_dev["dirty"] = False
                lengths = pool.lengths * eff
                tokens = jnp.asarray(toks_in)
                if prev is not None:
                    carry = np.zeros((B,), bool)
                    for slot in stepped:
                        carry[slot] = (prev.rows.get(slot) is sched.active[slot]
                                       and slot not in prev.dropped)
                    if carry.any():
                        tokens = _carry_tokens(prev.out, tokens,
                                               jnp.asarray(carry))
                out, finite, pool.pages = self._pool_step(
                    self._step_params, pool.pages, tokens,
                    bt_dev["arr"], jnp.asarray(lengths), bt_dev["act"], sub)
                # rows past the reach of the slot's *reserved* pages go to
                # the null page; their logits are garbage, so acceptance
                # stops before them
                written = {}
                for slot in stepped:
                    written[slot] = min(S, pool.reserved_tokens(slot)
                                        - int(pool.lengths[slot]))
                    pool.advance(slot, written[slot])
                if ph is not None:
                    if self._attn_impl == "paged":
                        read = int(live_pages(lengths[eff] + 1, S,
                                              pool.page_size).sum())
                    else:
                        read = int(eff.sum()) * pool.max_pages_per_slot
                    ph.set_metadata(
                        active=int(eff.sum()), live_tokens=int(lengths.sum()),
                        kv_pages_read=read,
                        kv_bytes_read=read * pool.per_device_page_bytes())
            steps += 1
            return _Step(steps, out, finite, toks_in, D,
                         {s: sched.active[s] for s in stepped}, written,
                         n_act, t_start)

        def finish(f: _Step, nxt: Optional[_Step]) -> None:
            """Wait on step ``f``'s tokens, count it in the health ladder,
            then commit it.  ``nxt`` is the step dispatched after ``f`` and
            still in flight (None in the serial order); a row of ``nxt``
            whose slot ``f`` faults is dropped and its advance undone."""
            nonlocal committed_total, slot_steps, pass_tokens, t_done_prev
            with span("serve.step_wait"):
                out_np = np.asarray(f.out)
                finite_np = np.asarray(f.finite)
            t_done = time.perf_counter()
            with span("serve.commit"):
                # rows whose slot lost its request while the step ran
                # (completed, failed or preempted) or whose input token
                # was voided commit nothing
                live = {s: r for s, r in f.rows.items()
                        if sched.active.get(s) is r and s not in f.dropped}
                overlap["dropped_rows"] += len(f.rows) - len(live)
                # the per-step health guard: a stepped slot whose logits
                # came back non-finite (or was chaos-flagged as such)
                # commits NOTHING — its length is rolled back, so the
                # retry recomputes the very same rows deterministically
                order = [s for s in sched.active if s in live]
                faulted = {s for s in order if not finite_np[s]}
                if self.faults is not None:
                    for slot in order:
                        if (slot not in faulted
                                and self.faults.fire("logits.nan")):
                            faulted.add(slot)
                # the watchdog's latency: completion to completion, what
                # a user sees between tokens.  The step counts before its
                # commit can raise (a window's end in a token list).
                dt_step = t_done - max(f.t_start, t_done_prev)
                t_done_prev = t_done
                self.health.note_step(dt_step, n_slot_faults=len(faulted))

                # acceptance walk: draft i is valid iff it equals the
                # verify step's argmax after consuming draft i-1 (and
                # every earlier draft held) — the longest such prefix
                # commits
                n_cand = np.zeros((B,), np.int32)
                slot_steps += len(live)
                for slot in order:
                    req = live[slot]
                    if slot in faulted:
                        # faulted: the stall contract (no advance, no
                        # commit, pending untouched) plus retry
                        # accounting — capped backoff, then terminal
                        # FAILED with all pages released once the streak
                        # passes max_retries.  A row of the next step
                        # consumed this row's token: it goes too.
                        undo = f.written[slot]
                        if nxt is not None and nxt.rows.get(slot) is req:
                            undo += nxt.written[slot]
                            nxt.dropped.add(slot)
                        pool.rollback(slot, undo)
                        req.retries += 1
                        req.fail_streak += 1
                        if req.fail_streak > self.health.policy.max_retries:
                            fail_request(slot, req,
                                         "non-finite logits past "
                                         "max_retries")
                        else:
                            req.backoff = self.health.policy.backoff(
                                req.fail_streak)
                            if tel is not None:
                                tel.tracer.begin(req.rid, "RETRY_BACKOFF",
                                                 now(), steps=req.backoff)
                        continue
                    req.fail_streak = 0
                    a = 0
                    while (a < min(f.depth, f.written[slot] - 1)
                           and f.toks_in[slot, a + 1] == out_np[slot, a]):
                        a += 1
                    n_cand[slot] = a + 1
                consumed = self._commit_tokens(sched, out_np, n_cand,
                                               pending, active, now(),
                                               release_slot)
                n_tokens = sum(consumed.values())
                committed_total += n_tokens
                pass_tokens = (pass_tokens or 0) + n_tokens
                for slot, c in consumed.items():
                    if slot in sched.active:  # finished: released
                        pool.rollback(slot, f.written[slot] - c)

            with span("serve.tap"):
                # the fallback swaps the step program: only with no step
                # in flight (the loop drains first when one is due)
                if nxt is None:
                    self._apply_health()
                self._tap_step(f.n_act, n_tokens, dt_step)
                if tel is not None:
                    tel.on_step(f.index, dt_step, n_tokens, f.n_act,
                                pool.allocator.n_free, len(faulted),
                                self._bucket_class.get(load_bucket(f.n_act),
                                                       ""))

        def drain():
            """Wait on the step in flight and commit it before anything
            else: the serial order."""
            nonlocal fl
            overlap["drains"] += 1
            f, fl = fl, None
            finish(f, None)

        def run_pass(sp) -> bool:
            """One pass of the loop: admission, at most
            ``prefill_chunks_per_step`` prefill chunks, then one pool
            decode step, dispatched before the host waits on the step
            still in flight and commits it.  False when nothing is left
            to serve.  Each phase is a profiler span; ``sp`` is the
            pass's own (None while no profiler records)."""
            nonlocal fl
            with span("serve.admit") as ph:
                n_admitted = admit_ready(now())
                # load shedding right after admission: whatever is STILL
                # arrived-but-waiting is past-deadline fodder and counts
                # against the queue bound — explicit EXPIRED/REJECTED
                # outcomes instead of unbounded queueing
                sched.shed_waiting(now(), self.cfg.max_queue,
                                   self.cfg.deadline_s)
                if ph is not None:
                    ph.set_metadata(admitted=n_admitted,
                                    waiting=sched.n_waiting(now()))

            # interleaved chunked prefill: a bounded budget per loop pass
            budget = max(self.cfg.prefill_chunks_per_step, 1)
            n_chunks = 0
            while budget > 0 and prefills:
                with span("serve.chunk_dispatch") as ph:
                    req = prefills[0]
                    slot = req.slot
                    feed = req.token_history()[:-1]
                    # MoE capacity groups depend on the token-group
                    # length, so splitting a prompt would route (and
                    # drop) differently than whole-prompt prefill — keep
                    # MoE prompts one chunk
                    if self.model.cfg.n_experts:
                        C = feed.size
                    else:
                        C = self.cfg.prefill_chunk or feed.size
                    chunk = feed[req.prefill_pos:req.prefill_pos + C]
                    true_c = chunk.size
                    if ph is not None:
                        ph.set_metadata(rid=req.rid, tokens=int(true_c),
                                        base=int(req.prefill_pos))
                    if true_c < C:
                        chunk = np.pad(chunk, (0, C - true_c))
                    tc0 = now() if tel is not None else 0.0
                    pool.pages = self._chunk_fn()(
                        self._step_params, pool.pages,
                        jnp.asarray(chunk[None]),
                        jnp.asarray(pool.block_tables[slot]),
                        jnp.asarray(req.prefill_pos, jnp.int32))
                    if tel is not None:
                        tel.tracer.add(req.rid, "PREFILL_CHUNK", tc0, now(),
                                       tokens=int(true_c))
                    budget -= 1
                    n_chunks += 1
                    if (self.faults is not None
                            and self.faults.fire("prefill.nan")):
                        # the chunk's K/V is suspect: advance nothing, so
                        # the retry deterministically rewrites the same
                        # rows with the same values.  Rotate to the back
                        # of the prefill line so a repeat offender never
                        # head-of-line blocks healthy prompts.
                        req.retries += 1
                        req.fail_streak += 1
                        if req.fail_streak > self.health.policy.max_retries:
                            prefills.pop(0)
                            fail_request(slot, req,
                                         "prefill fault past max_retries")
                        else:
                            prefills.append(prefills.pop(0))
                        continue
                    req.fail_streak = 0
                    pool.advance(slot, true_c)
                    req.prefill_pos += true_c
                    if req.prefill_pos >= feed.size:
                        pending[slot] = int(req.token_history()[-1])
                        # the prompt's full pages are now written: publish
                        # them so concurrent same-prefix arrivals hit
                        # immediately
                        pool.register_prefix(slot, req.token_history())
                        sched.start_decode(req, now())
                        active[slot] = True
                        bt_dev["dirty"] = True
                        prefills.pop(0)
            if sp is not None:
                sp.set_metadata(chunks=n_chunks)

            # the step in flight fills the budget of some requests: they
            # sit out the next step (their one token is their last)
            ending = set()
            if fl is not None:
                ending = {s for s, r in fl.rows.items()
                          if sched.active.get(s) is r and s not in fl.dropped
                          and len(r.out_tokens) + 1 >= r.max_new_tokens}
            residents = [s for s in sched.active if s not in ending]
            # the next step cannot be predicted, or its program may
            # change (speculation, a re-plan, the fallback), or nothing is
            # left to step ahead: wait on the step in flight first
            if fl is not None and (not residents or self._spec_depth
                                   or self._replan_due(len(residents))
                                   or self._fallback_due()):
                drain()
                ending = set()
                residents = list(sched.active)

            if not sched.active:
                if prefills:
                    return True             # keep prefilling
                nxt = sched.next_arrival()
                if nxt is None:
                    return False
                dt = nxt - now()
                if dt > 0:
                    with span("serve.idle"):
                        time.sleep(min(dt, 0.05))
                return True

            n_act = len(residents)
            with span("serve.tap"):
                self._maybe_replan(n_act)
            t_step0 = time.perf_counter()
            D = self._spec_depth
            S = D + 1

            # elastic headroom: every slot that steps needs its next K/V
            # write inside reserved pages (else it lands in the null page
            # and the sampled token is garbage).  Oldest-admitted slots
            # grow first — consistent with LIFO victim selection — and the
            # oldest may evict past the preempt cap so the head of the
            # line always progresses; everyone else stalls when nothing is
            # reclaimable.  With a step in flight the lengths are already
            # advanced past its rows, so this covers the step after it.
            with span("serve.headroom") as ph:
                stalled: list[int] = []
                grown0 = gov.grown_pages
                cow0 = pool.cow_copies
                preempts0 = pool.n_preempts
                order = sorted(residents, key=lambda s: (
                    sched.active[s].t_admit or 0.0, sched.active[s].rid))
                for i, slot in enumerate(order):
                    if slot not in sched.active:
                        continue            # taken as an earlier victim
                    req = sched.active[slot]
                    if req.backoff > 0:
                        # capped-backoff retry: a recently-faulted slot
                        # sits out (masked like a stall — nothing written,
                        # nothing committed, pending untouched) and
                        # neither grows nor evicts anyone while it waits
                        req.backoff -= 1
                        if (req.backoff == 0 and tel is not None):
                            tel.tracer.end(req.rid, "RETRY_BACKOFF", now())
                        stalled.append(slot)
                        continue
                    cap = req.prompt.size - 1 + req.max_new_tokens
                    cow0_slot = pool.cow_copies if tel is not None else 0
                    # besides headroom, this step's K/V writes must land in
                    # *private* pages: cow_for_write copies any
                    # still-shared page in the write range first
                    # (copy-on-write), and a failed copy is handled
                    # exactly like a failed growth
                    while (slot in sched.active
                           and (gov.ensure_headroom(slot, S, cap) < 1
                                or not pool.cow_for_write(slot, S))):
                        # only strictly-younger residents are evictable
                        # (LIFO: a slot never discards its own K/V —
                        # stalling keeps it — and never inverts the order
                        # by evicting an older request); the oldest may
                        # override the preempt cap so the head of the
                        # line always finishes.  A request the step in
                        # flight completes is never a victim.
                        victim = gov.pick_victim(
                            sched.active, exclude=ending,
                            ignore_cap=(i == 0),
                            younger_than=(req.t_admit or 0.0, req.rid))
                        if victim is None:
                            stalled.append(slot)
                            break
                        preempt_victim(victim)
                    if tel is not None and pool.cow_copies > cow0_slot:
                        # shared pages privatised for this slot's write
                        # range
                        tel.tracer.instant(req.rid, "COW", now(),
                                           copies=pool.cow_copies - cow0_slot)
                stepped = [s for s in order
                           if s in sched.active and s not in stalled]
                stalled = [s for s in stalled if s in sched.active]
                if gov.grown_pages != grown0 or pool.cow_copies != cow0:
                    # growth and CoW edit block-table rows in place — the
                    # cached device copy is stale even though pool
                    # composition is not
                    bt_dev["dirty"] = True
                if ph is not None:
                    ph.set_metadata(grown=gov.grown_pages - grown0,
                                    cow=pool.cow_copies - cow0,
                                    preempted=pool.n_preempts - preempts0)
            if sp is not None:
                sp.set_metadata(active=n_act, stalled=len(stalled))
            if not stepped:
                # every decode is out of pages and nothing is reclaimable:
                # only the step in flight (completions) or resident
                # prefills (whose pages are pre-reserved) can free the jam
                # — keep prefilling, skip the step
                gov.note_step(len(stalled))
                if fl is not None:
                    drain()
                return True

            ahead = fl is not None
            nxt = dispatch(stepped, n_act, D, fl, t_step0)
            overlap["steps_ahead"] += ahead
            if sp is not None:
                sp.set_metadata(step=nxt.index, ahead=int(ahead))
            if (self.faults is not None
                    and self.faults.fire("step.latency")):
                # artificial latency spike, inside the step's measured
                # window so the watchdog (and the tap's reward) sees it
                time.sleep(self.faults.latency_s)
            gov.note_step(len(stalled))
            # the host's work for this pass ran while the device computed
            # the step in flight; only now wait on it
            if fl is not None:
                finish(fl, nxt)
            if D:
                # the acceptance walk must precede the next draft: the
                # serial order
                fl = None
                finish(nxt, None)
            else:
                fl = nxt
            return True

        with span("serve.loop"):
            # the loop's clock starts inside this span's start: a time on
            # it (arrival_s, t_admit, t_prefilled, t_first, the request
            # tracer's spans) sits on the profiler's clock at the span's
            # start plus that time
            t0 = time.perf_counter()
            try:
                while not sched.done():
                    with span("serve.pass") as sp:
                        pass_tokens = None
                        more = run_pass(sp)
                        if sp is not None and pass_tokens is not None:
                            sp.set_metadata(tokens=pass_tokens)
                        if not more:
                            break
            except BaseException as e:
                # engine-internal error mid-serve: the failure domain is
                # the whole trace, but the POOL must outlive it — release
                # every resident's pages (best-effort per slot: one bad
                # row must not strand the rest) and re-raise only after
                # the allocator's invariants are re-checked, so a later
                # serve on this engine starts from a provably consistent
                # pool
                for slot, req in (list(sched.prefilling.items())
                                  + list(sched.active.items())):
                    try:
                        pool.release(slot)
                    except Exception:
                        pass
                    sched.fail(req, now(), f"engine aborted: "
                                           f"{type(e).__name__}: {e}")
                pool.allocator.check_invariants()
                raise
        # serve-end audit: refcounts match owners AND no live page is
        # stranded outside the prefix index (every slot released)
        pool.allocator.check_invariants()
        leaked = pool.leaked_pages()
        # memory/mesh accounting now comes from Engine.observability()
        # (the single aggregate serve() merges in)
        return {"steps": steps,
                "page_leaks": leaked,
                "overlap": dict(overlap),
                "spec": {"committed_tokens": committed_total,
                         "slot_steps": slot_steps,
                         "max_depth": max_depth,
                         # accepted drafts = tokens beyond the one each
                         # stepped slot's step commits regardless
                         "accepted_drafts":
                             committed_total - slot_steps,
                         "tokens_per_step":
                             committed_total / max(steps, 1)}}
