"""Serving launcher: request-trace-driven continuous batching.

Builds a synthetic arrival trace (poisson / staggered / burst), replays it
against the continuous-batching engine (or the static lockstep baseline for
comparison), and reports throughput and latency percentiles.  A decision
tree trained by the autotuner (``--dtree``) switches on counter-driven plan
selection at serve time; ``--online-retrain`` closes the loop — measured
step counters and tok/s rewards feed a corpus (``--corpus-out``), the tree
is retrained every ``--retrain-interval`` steps and hot-swapped
(``--tree-out`` saves the final tree), and ``--explore-eps`` occasionally
trials candidates the offline search never saw (``--no-explore`` pins pure
exploitation, keeping greedy output bit-identical).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \
      --requests 8 --prompt-len 16 --gen-min 4 --gen-max 16 \
      --arrival poisson --rate 20 --slots 4

  # static lockstep baseline on the same trace
  PYTHONPATH=src python -m repro.launch.serve ... --mode static
"""
from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config
from repro.core.policy import RegionPlan
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_mod
from repro.serve.engine import Engine, ServeConfig
from repro.serve.scheduler import Request, summarize


def build_trace(args, vocab_size: int) -> list[Request]:
    """Deterministic request trace from the CLI arrival model."""
    rng = np.random.default_rng(args.seed)
    if args.arrival == "poisson":
        gaps = rng.exponential(1.0 / args.rate, args.requests)
    elif args.arrival == "staggered":
        gaps = np.full(args.requests, 1.0 / args.rate)
    else:  # burst
        gaps = np.zeros(args.requests)
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    reqs = []
    for i in range(args.requests):
        gen = int(rng.integers(args.gen_min, args.gen_max + 1))
        prompt = rng.integers(0, vocab_size, args.prompt_len).astype(np.int32)
        reqs.append(Request(rid=i, prompt=prompt, max_new_tokens=gen,
                            arrival_s=float(arrivals[i])))
    return reqs


def run_static(engine: Engine, reqs: list[Request], slots: int) -> dict:
    """Lockstep baseline: group FIFO into batches of ``slots``, wait for the
    whole group to arrive, decode everyone for the group's longest budget."""
    cfg = engine.model.cfg
    t0 = time.perf_counter()
    for i in range(0, len(reqs), slots):
        group = reqs[i:i + slots]
        wait = max(r.arrival_s for r in group) - (time.perf_counter() - t0)
        if wait > 0:
            time.sleep(wait)
        prompts = jnp.stack([jnp.asarray(r.prompt) for r in group])
        extra = None
        if cfg.family == "encdec":   # stub modality frontend (as in dry-run)
            extra = {"frames": jnp.zeros(
                (len(group), cfg.enc_len, cfg.d_model), jnp.bfloat16)}
        n_steps = max(r.max_new_tokens for r in group)
        t_gen0 = time.perf_counter() - t0
        res = engine.generate(prompts, n_steps, extra)
        out = np.asarray(res["tokens"])
        t = time.perf_counter() - t0
        # the group's first tokens land right after its prefill — TTFT is
        # prefill latency, not group completion
        t_first = t_gen0 + res["prefill_s"]
        for j, r in enumerate(group):
            r.out_tokens = out[j, :r.max_new_tokens].tolist()
            r.t_first = t_first
            r.t_done = t
            from repro.serve.scheduler import RequestState
            r.state = RequestState.DONE
    return {"requests": reqs, "stats": summarize(reqs)}


def main(argv=None):
    """Serve one trace from the CLI arguments and print the report.
    Returns the serve result (``Engine.serve``'s dict, or the static
    baseline's) with the engine itself under ``"engine"``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mode", choices=("continuous", "static"),
                    default="continuous")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-min", type=int, default=4)
    ap.add_argument("--gen-max", type=int, default=16)
    ap.add_argument("--arrival", choices=("poisson", "staggered", "burst"),
                    default="poisson")
    ap.add_argument("--rate", type=float, default=20.0,
                    help="arrival rate, requests/s (poisson/staggered)")
    ap.add_argument("--slots", type=int, default=4,
                    help="KV pool size / static batch width")
    ap.add_argument("--prefill-bucket", type=int, default=8)
    ap.add_argument("--paged", choices=("auto", "on", "off"), default="auto",
                    help="paged KV pool (auto: wherever the family "
                         "supports it)")
    ap.add_argument("--page-size", type=int, default=0,
                    help="tokens per KV page (0 = plan knob, else 16)")
    ap.add_argument("--kv-pages", type=int, default=0,
                    help="total KV pages incl. null (0 = per-slot worst "
                         "case; lower trades HBM for queueing)")
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="chunked prefill piece size (0 = whole prompt)")
    ap.add_argument("--reservation", choices=("full", "lazy", "auto"),
                    default="auto",
                    help="paged KV admission policy: 'full' reserves each "
                         "request's worst case up front (preemption-free); "
                         "'lazy' admits with prompt pages + one decode page "
                         "and grows at page boundaries, preempting the "
                         "youngest decode when pages run dry (more "
                         "in-flight requests at the same --kv-pages, "
                         "greedy output bit-identical); 'auto' lets the "
                         "serve-time PlanDecider pick the mem_full/"
                         "mem_lazy candidates per load bucket (unset = "
                         "full)")
    ap.add_argument("--mem-watermark", type=float, default=-1.0,
                    help="lazy-admission free-page high watermark as a "
                         "fraction of allocatable pages: new requests are "
                         "admitted only while the free list stays above "
                         "it, protecting decode growth headroom (-1 = "
                         "auto: plan knob, else 0.1)")
    ap.add_argument("--max-preempts", type=int, default=4,
                    help="per-request eviction cap for the memory "
                         "governor's victim selection (the oldest "
                         "resident's progress guarantee may override it)")
    ap.add_argument("--prefix-cache", choices=("on", "off", "auto"),
                    default="auto",
                    help="cross-request KV prefix sharing: fully-written "
                         "pages of finished (or decode-started) requests "
                         "stay indexed by hash(token run), a new prompt "
                         "whose prefix is resident maps those pages and "
                         "prefills only the suffix (near-zero TTFT on "
                         "cache hits), and shared pages are copy-on-write "
                         "privatised before any divergent write — greedy "
                         "output stays bit-identical to a cold pool.  "
                         "'auto' lets the serve-time PlanDecider pick the "
                         "mem_prefix_on/mem_prefix_off candidates per "
                         "load bucket (unset = off).  Forced off for MoE "
                         "models: capacity groups route by token-group "
                         "length, so suffix-only prefill would break "
                         "bit-identity (same rule as speculation)")
    ap.add_argument("--spec-depth", default="auto",
                    choices=("auto", "0", "1", "2", "3", "4"),
                    help="speculative decode draft depth per pool step "
                         "(greedy only): N drafts per slot via n-gram "
                         "self-lookup, verified by one multi-query step — "
                         "greedy tokens stay bit-identical to "
                         "non-speculative decode.  Paged pools roll a "
                         "rejected tail back by length truncation; "
                         "recurrent slot pools (ssm/hybrid) by state "
                         "snapshot/restore.  'auto' lets the serve-time "
                         "PlanDecider pick the spec0/spec2/spec4 decode "
                         "candidates per load bucket from occupancy-"
                         "scaled counters (requires --dtree; otherwise off)")
    ap.add_argument("--scan-mode", default="auto",
                    choices=("auto", "chunk", "fused_recurrent"),
                    help="recurrent scan kernel variant for ssm/hybrid "
                         "slot-pool families: 'chunk' runs the wkv/ssd "
                         "recurrence as intra-chunk causal matmuls with an "
                         "inter-chunk state carry (prefill-friendly: state "
                         "HBM traffic drops by the chunk length), "
                         "'fused_recurrent' is the sequential recurrence "
                         "(decode-friendly).  Greedy output is "
                         "bit-identical across modes.  'auto' resolves "
                         "chunk for prefill and fused for decode, unless a "
                         "--dtree PlanDecider picks the scan_chunk/"
                         "scan_fused candidates per load bucket")
    ap.add_argument("--tp", default="1", choices=("1", "2", "4", "auto"),
                    help="tensor-parallel degree of the paged serve step "
                         "over the device mesh's 'model' axis: K/V pages "
                         "shard on the kv-head dim (block tables stay "
                         "host-side and replicated, so the paged-attention "
                         "gather is unchanged per shard), attention/MLP/"
                         "unembed params shard on their logical axes, and "
                         "the vocab-sharded logits replicate once at the "
                         "sampling boundary — greedy output is "
                         "bit-identical across degrees.  Mesh selection: "
                         "the engine uses its plan's mesh when the model "
                         "axis matches, else builds a (1, tp) host mesh "
                         "over whatever devices exist (on CPU force them "
                         "with XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N).  Degrees the host or the "
                         "model's kv-head count cannot satisfy clamp "
                         "down.  'auto' lets the serve-time PlanDecider "
                         "pick the tp1/tp2/tp4 candidates per load "
                         "bucket (unset = 1); a tp switch costs one step "
                         "recompile + one pool reshard")
    ap.add_argument("--max-len", type=int, default=0,
                    help="cache length (default: prompt+gen headroom)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--plan", default="",
                    help="RegionPlan json to serve under (e.g. a tuned "
                         "candidate's knobs, such as attn_impl='paged' for "
                         "the Pallas paged-attention kernel); default: the "
                         "null plan")
    ap.add_argument("--dtree", default="",
                    help="DecisionTree json from the autotuner corpus")
    ap.add_argument("--online-retrain", action="store_true",
                    help="close the paper loop online: tap measured step "
                         "counters + tok/s rewards into a corpus, retrain "
                         "the decision tree every --retrain-interval steps "
                         "and hot-swap it (works from a cold start — no "
                         "--dtree needed)")
    ap.add_argument("--retrain-interval", type=int, default=32,
                    help="decode steps between corpus flush / retrain "
                         "attempts (with --online-retrain)")
    ap.add_argument("--explore-eps", type=float, default=0.1,
                    help="epsilon-greedy exploration rate over the "
                         "serve-only candidate menu (with --online-retrain; "
                         "0 keeps greedy output bit-identical)")
    ap.add_argument("--explore-budget", type=int, default=64,
                    help="hard cap on exploration decisions per engine")
    ap.add_argument("--no-explore", action="store_true",
                    help="disable exploration (equivalent to "
                         "--explore-eps 0)")
    ap.add_argument("--corpus-in", default="",
                    help="corpus JSONL to merge before serving (e.g. the "
                         "offline tuner's corpus; requires "
                         "--online-retrain)")
    ap.add_argument("--corpus-out", default="",
                    help="write the accumulated observation corpus (JSONL) "
                         "after serving (requires --online-retrain)")
    ap.add_argument("--tree-out", default="",
                    help="write the final (possibly online-retrained) "
                         "decision tree JSON after serving")
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="default time-to-admission deadline per request: "
                         "a request still WAITING this many seconds after "
                         "its arrival is shed as EXPIRED instead of served "
                         "(0 = no deadline; per-request Request.deadline_s "
                         "overrides)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bound on the post-admission waiting queue: "
                         "arrived requests beyond this many are shed as "
                         "REJECTED (0 = unbounded)")
    ap.add_argument("--chaos-rate", type=float, default=0.0,
                    help="fault-injection Bernoulli rate per site draw "
                         "(0 = chaos off, injector never constructed)")
    ap.add_argument("--chaos-seed", type=int, default=0,
                    help="fault-injection seed (per-site independent "
                         "streams; same seed+rate = same fault schedule)")
    ap.add_argument("--telemetry", action="store_true",
                    help="enable the serve telemetry subsystem (span "
                         "tracer, step metrics ring, latency sketches) "
                         "without any file exports; implied by "
                         "--trace-out/--metrics-out/--log-out.  Greedy "
                         "output stays bit-identical with telemetry on")
    ap.add_argument("--trace-out", default="",
                    help="write the request span trace as Chrome "
                         "trace-event JSON after serving (load in "
                         "Perfetto / chrome://tracing; enables telemetry)")
    ap.add_argument("--metrics-out", default="",
                    help="write a Prometheus text-exposition metrics "
                         "snapshot after serving (enables telemetry)")
    ap.add_argument("--log-out", default="",
                    help="stream structured telemetry events as JSONL to "
                         "this file while serving (enables telemetry)")
    ap.add_argument("--log-level", choices=("debug", "info", "warning"),
                    default="info",
                    help="telemetry event threshold: debug adds per-step "
                         "and per-injection events, warning keeps only "
                         "health transitions")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = model_mod.build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))

    max_len = args.max_len or args.prompt_len + args.gen_max + 1
    dtree = None
    if args.dtree:
        from repro.core.dtree import DecisionTree
        dtree = DecisionTree.from_json(open(args.dtree).read())
    plan = (RegionPlan.from_json(open(args.plan).read()) if args.plan
            else None)
    engine = Engine(model, params, plan=plan, serve_cfg=ServeConfig(
        max_len=max_len, temperature=args.temperature, seed=args.seed,
        max_slots=args.slots, eos_id=args.eos_id,
        prefill_bucket=args.prefill_bucket, paged=args.paged,
        page_size=args.page_size, kv_pages=args.kv_pages,
        prefill_chunk=args.prefill_chunk,
        reservation=args.reservation, mem_watermark=args.mem_watermark,
        max_preempts=args.max_preempts, prefix_cache=args.prefix_cache,
        spec_depth=-1 if args.spec_depth == "auto" else int(args.spec_depth),
        scan_mode=args.scan_mode,
        tp=0 if args.tp == "auto" else int(args.tp),
        online_retrain=args.online_retrain,
        retrain_interval=args.retrain_interval,
        explore_eps=0.0 if args.no_explore else args.explore_eps,
        explore_budget=args.explore_budget,
        deadline_s=args.deadline_s, max_queue=args.max_queue,
        chaos_rate=args.chaos_rate, chaos_seed=args.chaos_seed,
        telemetry=args.telemetry, trace_out=args.trace_out,
        metrics_out=args.metrics_out, log_out=args.log_out,
        log_level=args.log_level),
        dtree=dtree)
    # explicit serve knobs must route or reject — never silently drop.
    # Slot-pool families: chunked prefill and speculation route only for
    # recurrent state (ssm/hybrid without a sliding window), whose
    # fixed-size snapshots give the rollback/chunk contracts a footing.
    recurrent = (cfg.family in ("ssm", "hybrid")
                 and not getattr(cfg, "swa_window", 0))
    if args.scan_mode != "auto" and not recurrent:
        ap.error(f"--scan-mode {args.scan_mode}: only the recurrent "
                 f"families (ssm/hybrid) have a chunk/fused kernel "
                 f"choice; {args.arch} is family={cfg.family!r}")
    if (args.mode == "continuous" and not engine._use_paged()
            and not recurrent):
        if args.prefill_chunk > 0:
            ap.error(f"--prefill-chunk: chunked prefill on the slot pool "
                     f"requires a recurrent family (ssm/hybrid, no "
                     f"sliding window); {args.arch} is "
                     f"family={cfg.family!r}")
        if args.spec_depth not in ("auto", "0"):
            ap.error(f"--spec-depth {args.spec_depth}: the slot pool can "
                     f"only roll back rejected drafts via recurrent-state "
                     f"snapshots (ssm/hybrid, no sliding window); "
                     f"{args.arch} is family={cfg.family!r}")
    if (args.corpus_in or args.corpus_out) and engine.corpus is None:
        print("[autotune] warning: --corpus-in/--corpus-out need "
              "--online-retrain (no corpus exists without it) — ignoring")
    if args.corpus_in and engine.corpus is not None:
        from repro.autotune.corpus import Corpus
        engine.corpus.merge(Corpus.load_jsonl(args.corpus_in))

    reqs = build_trace(args, cfg.vocab_size)
    if args.mode == "static":
        res = run_static(engine, reqs, args.slots)
    else:
        res = engine.serve(reqs)
        for n_active, decisions in res["decisions"]:
            print(f"[plan] load={n_active}: " + ", ".join(
                f"{r}->{c}" for r, c in decisions))

    for r in reqs:
        tail = (f"latency {(r.t_done - r.arrival_s)*1e3:7.1f} ms"
                if r.state.value == "done" else
                f"{r.state.value}" + (f" ({r.error})" if r.error else ""))
        print(f"req {r.rid:3d} arrive {r.arrival_s*1e3:7.1f} ms  "
              f"gen {len(r.out_tokens):3d} tok  " + tail)
    s = res["stats"]
    print(f"{args.mode}: {s['n_done']} requests, {s['tokens']} tokens in "
          f"{s['wall_s']:.2f} s -> {s['tok_per_s']:.1f} tok/s  "
          f"p50 {s['latency_p50_s']*1e3:.0f} ms  "
          f"p99 {s['latency_p99_s']*1e3:.0f} ms")
    fl = res.get("failures", {})
    if any(fl.get(k, 0) for k in ("failed", "expired", "rejected", "retries")):
        hs = res.get("health", {})
        print(f"[failures] failed={fl['failed']} expired={fl['expired']} "
              f"rejected={fl['rejected']} retries={fl['retries']} "
              f"health={hs.get('state', 'n/a')} "
              f"fallbacks={hs.get('fallbacks', 0)}")
    fi = res.get("faults", {})
    if fi.get("enabled"):
        inj = " ".join(f"{k}={v}" for k, v in
                       sorted(fi.get("injected", {}).items()))
        print(f"[chaos] seed={fi['seed']} rate={fi['rate']} "
              f"injected_total={fi['injected_total']}" +
              (f"  ({inj})" if inj else ""))
    if args.mode == "continuous" and engine._paged:
        pool = engine._pool
        mesh_info = res.get("mesh", {})
        if mesh_info:
            print(f"[mesh] tp={mesh_info['tp']} "
                  f"devices={mesh_info['devices']} "
                  f"hbm_per_device="
                  f"{mesh_info['hbm_bytes_per_device']/2**20:.1f} MiB "
                  f"high_water_per_device="
                  f"{mesh_info['high_water_bytes_per_device']/2**20:.1f} MiB")
        print(f"[paged] page_size={pool.page_size} pages={pool.n_pages} "
              f"pool={pool.hbm_bytes()/2**20:.1f} MiB "
              f"high-water={pool.high_water_bytes()/2**20:.1f} MiB "
              f"({pool.allocator.high_water} pages)")
        mem = res.get("memory", {})
        if mem:
            frag = "+".join(f"{n}x{c}" for n, c in
                            sorted(mem["fragmentation"].items()))
            print(f"[pool] reservation={mem['reservation']} "
                  f"watermark={mem['watermark']:.2f} "
                  f"peak_inflight={mem['peak_resident']} "
                  f"preemptions={mem['preemptions']} "
                  f"stall_steps={mem['stall_steps']} "
                  f"grown_pages={mem['grown_pages']} "
                  f"free_pages_min={mem['free_pages_min']} "
                  f"frag_runs={frag or 'none'}")
        if s.get("preempts"):
            print(f"[pool] preempted {s['preempted_requests']} requests "
                  f"{s['preempts']} times, requeue wait "
                  f"p50 {s['requeue_wait_p50_s']*1e3:.1f} ms "
                  f"max {s['requeue_wait_max_s']*1e3:.1f} ms")
        pf = mem.get("prefix", {}) if mem else {}
        if pf.get("enabled"):
            print(f"[prefix] hits={pf['hit_requests']} requests / "
                  f"{pf['tokens_saved']} prefill tokens saved  "
                  f"indexed={pf['indexed_pages']} pages "
                  f"({pf['reclaimable_pages']} reclaimable)  "
                  f"cow={pf['cow_copies']} evictions={pf['evictions']} "
                  f"victims_spared={mem.get('shared_spared', 0)}")
        sp = res.get("spec", {})
        if sp.get("max_depth", 0) > 0:      # speculation actually ran
            print(f"[spec] depth={args.spec_depth} (max used "
                  f"{sp['max_depth']}) committed {sp['committed_tokens']} "
                  f"tokens in {res['steps']} steps "
                  f"-> {sp['tokens_per_step']:.2f} tokens/step")
    elif args.mode == "continuous":
        # slot-pool accounting parity: recurrent serves are observable
        # (HBM footprint, occupancy high-water, speculation) like paged
        mem = res.get("memory", {})
        if mem.get("pool") == "slot":
            print(f"[pool] slots={engine._pool.n_slots} "
                  f"slot={mem['slot_bytes']/2**20:.2f} MiB "
                  f"pool={mem['hbm_bytes']/2**20:.1f} MiB "
                  f"high-water={mem['high_water_bytes']/2**20:.1f} MiB "
                  f"({mem['high_water_slots']} slots)")
        if recurrent:
            print(f"[scan] mode={args.scan_mode} resolved: prefill="
                  f"{engine.scan_mode_for(engine._decided_plan, 'prefill')} "
                  f"decode={engine.scan_mode_for(engine._decided_plan)}")
        sp = res.get("spec", {})
        if sp.get("max_depth", 0) > 0:      # speculation actually ran
            print(f"[spec] depth={args.spec_depth} (max used "
                  f"{sp['max_depth']}) committed {sp['committed_tokens']} "
                  f"tokens in {res['steps']} steps "
                  f"-> {sp['tokens_per_step']:.2f} tokens/step")
    if args.mode == "continuous" and args.online_retrain:
        at = res["autotune"]
        print(f"[autotune] retrains={at['retrains']} swaps={at['swaps']} "
              f"rejected={engine.trainer.reject_count} "
              f"explored={at['explored']} "
              f"explore_fraction={at['explore_fraction']:.2f} "
              f"corpus_entries={at['corpus_entries']} "
              f"pre_swap_tok_s={at['pre_swap_tok_s']:.1f} "
              f"post_swap_tok_s={at['post_swap_tok_s']:.1f}")
    if args.mode == "continuous" and engine.telemetry is not None:
        tm = res.get("telemetry", {})
        lat = tm.get("step_latency_s", {})
        qd = tm.get("queue_delay_s", {})
        print(f"[telemetry] level={tm.get('level', args.log_level)} "
              f"spans={tm.get('spans', 0)} "
              f"(dropped={tm.get('spans_dropped', 0)}) "
              f"events={tm.get('events', 0)} "
              f"ring={tm.get('ring', {}).get('kept', 0)}/"
              f"{tm.get('ring', {}).get('steps', 0)} steps  "
              f"step p50 {lat.get('p50', 0.0)*1e3:.1f} ms "
              f"p99 {lat.get('p99', 0.0)*1e3:.1f} ms  "
              f"queue p99 {qd.get('p99', 0.0)*1e3:.1f} ms")
        if args.trace_out:
            print(f"[telemetry] trace -> {args.trace_out} (Perfetto / "
                  f"chrome://tracing)")
        if args.metrics_out:
            print(f"[telemetry] metrics -> {args.metrics_out} "
                  f"(Prometheus text)")
        if args.log_out:
            print(f"[telemetry] events -> {args.log_out} (JSONL)")
        engine.telemetry.close()
    if args.corpus_out and engine.corpus is not None:
        n = engine.corpus.save_jsonl(args.corpus_out)
        print(f"[autotune] corpus -> {args.corpus_out} ({n} entries)")
    if args.tree_out and engine.dtree is not None:
        with open(args.tree_out, "w") as f:
            f.write(engine.dtree.to_json())
        print(f"[autotune] dtree -> {args.tree_out}")
    res["engine"] = engine
    return res


def cli(argv=None) -> int:
    """Process entry point with failure-aware exit codes.

    0 = every request completed; 1 = served but some requests ended in a
    non-DONE terminal state (failed / expired / rejected); 2 = the engine
    itself aborted (an exception escaped ``serve()`` — per-request faults
    never do, so this means a crashed step or a programmer error)."""
    try:
        res = main(argv)
    except Exception as e:  # engine abort, not per-request failure
        print(f"[fatal] {type(e).__name__}: {e}", file=sys.stderr)
        return 2
    fl = (res or {}).get("failures", {})
    bad = sum(fl.get(k, 0) for k in ("failed", "expired", "rejected"))
    if bad:
        print(f"[exit] {bad} request(s) not served "
              f"(failed={fl.get('failed', 0)} expired={fl.get('expired', 0)} "
              f"rejected={fl.get('rejected', 0)})", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(cli())
