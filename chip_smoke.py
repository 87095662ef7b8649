#!/usr/bin/env python3
"""Chip smoke: the paged continuous-batching serve path of ``stablelm-1.6b``
at its full published width on one TPU chip, driven through the serve
launcher (``repro.launch.serve.main``) with random weights from ``--seed``.

  python3 chip_smoke.py               # one chip: serve, kernel, autotune
  python3 chip_smoke.py --four-chips  # four chips: --tp 4 against --tp 1
  JAX_PLATFORMS=cpu python3 chip_smoke.py --reduced   # rehearsal, tiny widths

Phases, all in this one process (it holds the chip and starts no child):

* serve: 8 requests, 256-token prompts, 32-64 new tokens, 64-token prefill
  chunks, on the paged KV pool under the default plan (on a TPU at tp 1
  the engine resolves its attention to the Pallas kernel).
* kernel: the same trace under the ``attn_paged_kernel`` candidate's plan.
  The step must resolve to the kernel, and on a TPU hold its custom call at
  stablelm-1.6b's 64-wide heads (the pool's lane-dense page rows, 2,048
  lanes; rows that are not whole 128-lane tiles resolve to the gather).  The
  kernel must match the ``kernels/ref.py`` oracle at the model's head
  counts and width, on pages in the pool's layout.
* autotune: the same trace with ``--online-retrain``, so measure -> decide
  runs on the counters parsed from the TPU step's HLO.
* four-chips (only with ``--four-chips``): the trace on the engine at tp=4
  against tp=1, f32 weights, with the device placement of pages and params.

Every phase checks that each request ends DONE with no failure, retry or
leaked page, health stays HEALTHY and every token is in the vocabulary.  The
tok/s, TTFT, compile-second and HBM lines are smoke readings of one short
trace, not benchmark numbers.

Exit code 0 and the last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``
only when every check passed on a TPU.  Without a TPU, or without the
repository's ``src/`` beside this file, it exits non-zero and prints no
result; ``--reduced`` runs the phases anyway as a rehearsal, then exits 1.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "stablelm-1.6b"
TRACE = {"requests": 8, "prompt_len": 256, "gen_min": 32, "gen_max": 64,
         "prefill_chunk": 64, "slots": 4}
# bf16 kernel output against the f32 oracle on the same bf16 inputs: the
# output is an average of unit-normal V rows, so bf16 rounding of it is
# below 1e-2; 2e-2 leaves room for the online-softmax reassociation
KERNEL_ATOL = 2e-2
# f32 logits of the tp=4 step against tp=1 on the same inputs at "highest"
# matmul precision: only the reduction order differs, so the gap is f32
# rounding, orders of magnitude below 1e-4 of the logit scale
TP_LOGITS_RTOL = 1e-4
SPEC_DEPTH = 2                  # the spec2 candidate: verify width S = 3


class Checks:
    """Named pass/fail records; a phase fails if any of its checks do."""

    def __init__(self):
        self.failed: list[str] = []

    def __call__(self, name: str, ok: bool, detail: str = "") -> bool:
        print(f"[check] {'PASS' if ok else 'FAIL'} {name}"
              + (f": {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(name)
        return ok


class CompileClock:
    """Seconds spent in XLA backend compiles (persistent-cache hits skip
    the backend and add nothing)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.total += duration


def launcher_argv(args, *extra) -> list[str]:
    argv = ["--arch", ARCH, "--paged", "on", "--seed", str(args.seed),
            "--requests", str(TRACE["requests"]),
            "--prompt-len", str(TRACE["prompt_len"]),
            "--gen-min", str(TRACE["gen_min"]),
            "--gen-max", str(TRACE["gen_max"]),
            "--prefill-chunk", str(TRACE["prefill_chunk"]),
            "--slots", str(TRACE["slots"])]
    if not args.reduced:
        argv.append("--full")
    return argv + list(extra)


def check_served(ck: Checks, label: str, res: dict, vocab: int) -> list:
    """The pass conditions every served trace must meet; returns the
    greedy tokens per request."""
    from repro.serve.scheduler import RequestState
    reqs = res["requests"]
    ck(f"{label}: every request DONE",
       all(r.state is RequestState.DONE for r in reqs),
       " ".join(r.state.value for r in reqs))
    fl = res["failures"]
    bad = {k: fl[k] for k in ("failed", "expired", "rejected", "retries")}
    ck(f"{label}: no failed/expired/rejected/retried request",
       not any(bad.values()), str(bad))
    ck(f"{label}: health HEALTHY", res["health"]["state"] == "healthy",
       res["health"]["state"])
    ck(f"{label}: page_leaks == 0", res["page_leaks"] == 0,
       str(res["page_leaks"]))
    toks = [list(map(int, r.out_tokens)) for r in reqs]
    ck(f"{label}: every token inside the vocabulary",
       all(0 <= t < vocab for ts in toks for t in ts)
       and all(len(ts) == r.max_new_tokens for ts, r in zip(toks, reqs)),
       f"vocab={vocab} tokens={sum(map(len, toks))}")
    return toks


def agreement(a: list, b: list) -> float:
    pairs = [(x, y) for ta, tb in zip(a, b) for x, y in zip(ta, tb)]
    return sum(x == y for x, y in pairs) / max(len(pairs), 1)


def serve_once(args, ck: Checks, clock: CompileClock, label: str, *extra):
    """One launcher run: checks plus the smoke readings.  Returns
    (engine, result, tokens)."""
    from repro.launch import serve as serve_mod
    c0 = clock.total
    print(f"== phase {label}: repro.launch.serve.main "
          f"{' '.join(launcher_argv(args, *extra))}", flush=True)
    res = serve_mod.main(launcher_argv(args, *extra))
    engine = res.pop("engine")
    toks = check_served(ck, label, res, engine.model.cfg.vocab_size)
    s = res["stats"]
    print(f"[smoke reading, not a benchmark] {label}: "
          f"tok/s={s['tok_per_s']!r} ttft_p50_s={s['ttft_p50_s']!r} "
          f"compile_s={clock.total - c0!r} "
          f"pool_hbm_bytes={engine._pool.hbm_bytes()} "
          f"steps={res['steps']}", flush=True)
    return engine, res, toks


def kernel_oracle(ck: Checks, cfg, page_size: int, on_tpu: bool, seed: int):
    """``paged_attention_mq`` (compiled on TPU) against the f32 oracle at
    the model's head counts and width, on pages in the pool's layout
    (:func:`repro.kernels.paged_attention.page_row`), for plain decode
    (S=1) and the spec2 verify width (S=3)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    from repro.kernels.paged_attention import page_row
    kvh = cfg.n_kv_heads
    g = cfg.n_heads // kvh
    hd = cfg.resolved_head_dim
    B, MP = TRACE["slots"], 32
    n_pages = B * MP + 1
    pages = (n_pages, page_size) + page_row(kvh, hd)
    kk, kv, kq, kb = jax.random.split(jax.random.PRNGKey(seed), 4)
    k_pages = jax.random.normal(kk, pages, jnp.bfloat16)
    v_pages = jax.random.normal(kv, pages, jnp.bfloat16)
    # each slot owns a scattered set of pages, as the allocator leaves them
    bt = (jax.random.permutation(kb, n_pages - 1) + 1).reshape(B, MP)
    for S in (1, SPEC_DEPTH + 1):
        lengths = jnp.array([1, 77, 256, MP * page_size - S + 1],
                            jnp.int32)
        q = jax.random.normal(kq, (B, S, kvh, g, hd), jnp.bfloat16)
        out = ops.paged_attention_mq(q, k_pages, v_pages, bt, lengths)
        with jax.default_matmul_precision("highest"):   # a true f32 oracle
            want = ref.paged_attention_mq(q, k_pages, v_pages, bt, lengths)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - want)))
        ck(f"kernel: paged_attention_mq S={S} matches ref "
           f"(KVH={kvh} G={g} HD={hd} page={page_size}"
           f"{'' if on_tpu else ', interpret mode'})",
           err <= KERNEL_ATOL, f"max|err|={err!r} atol={KERNEL_ATOL}")


def phase_serve(args, ck, clock, on_tpu, state):
    _, _, state["serve_tokens"] = serve_once(args, ck, clock, "serve")


def phase_kernel(args, ck, clock, on_tpu, state):
    from repro.autotune.candidates import default_candidates
    from repro.core.policy import RegionPlan
    cand = next(c for c in default_candidates("decode")
                if c.name == "attn_paged_kernel")
    plan = RegionPlan(region_configs={"layer/attn": cand.config})
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "attn_paged_kernel.json")
        with open(path, "w") as f:
            f.write(plan.to_json())
        engine, _, toks = serve_once(args, ck, clock, "kernel",
                                     "--plan", path)
    from repro.kernels.paged_attention import compiles_row
    cfg, page_size = engine.model.cfg, engine._pool.page_size
    want = ("paged" if compiles_row(cfg.n_kv_heads, cfg.resolved_head_dim)
            or not on_tpu else "gather")
    impl = engine.observability()["step"]["attn_impl"]
    ck(f"kernel: the pinned step resolves to {want!r}", impl == want,
       f"attn_impl={impl!r} kv_heads={cfg.n_kv_heads} "
       f"head_dim={cfg.resolved_head_dim}")
    hlo = engine._pool_step.as_text()
    if on_tpu:
        ck("kernel: compiled step holds the Pallas kernel",
           "tpu_custom_call" in hlo,
           f"tpu_custom_call x{hlo.count('tpu_custom_call')}")
    else:
        print("[check] skipped tpu_custom_call: kernels run in interpret "
              "mode off the TPU")
    del engine
    gc.collect()
    kernel_oracle(ck, cfg, page_size, on_tpu, args.seed)
    if "serve_tokens" in state:
        print(f"[smoke reading] greedy token agreement default plan vs kernel pin: "
              f"{agreement(state['serve_tokens'], toks)!r}", flush=True)


def phase_autotune(args, ck, clock, on_tpu, state):
    engine, res, toks = serve_once(args, ck, clock, "autotune",
                                   "--online-retrain", "--no-explore")
    rc = engine._pool_rc
    ratio = rc.total.flops / rc.xla_flops if rc.xla_flops else float("nan")
    print(f"[counters] step flops parsed from HLO={rc.total.flops!r} "
          f"cost_analysis()={rc.xla_flops!r} ratio={ratio!r}", flush=True)
    if on_tpu:
        ck("autotune: parsed TPU-step flops within 2x of cost_analysis",
           0.5 <= ratio <= 2.0, f"ratio={ratio!r}")
    at = res["autotune"]
    ck("autotune: the loop measured and retrained",
       at["corpus_entries"] >= 1 and at["retrains"] >= 1,
       f"corpus_entries={at['corpus_entries']} retrains={at['retrains']} "
       f"swaps={at['swaps']}")
    if "serve_tokens" in state:
        ck("autotune: greedy tokens identical to the serve phase "
           "(exploration off)", toks == state["serve_tokens"],
           f"agreement={agreement(state['serve_tokens'], toks)!r}")


def _devices_of(tree) -> list[int]:
    import jax
    return sorted({d.id for leaf in jax.tree.leaves(tree)
                   for d in leaf.devices()})


def phase_four_chips(args, ck, clock, on_tpu, state):
    """tp=4 against tp=1 in one process on the engine API (the launcher's
    ``--tp``), with f32 weights as in ``tests/test_tp_serving.py``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.configs.registry import get_config
    from repro.distributed.sharding import cache_shardings
    from repro.launch.serve import build_trace
    from repro.models.model import build
    from repro.serve.engine import Engine, ServeConfig
    n_dev = len(jax.devices())
    if not ck("four-chips: four devices", n_dev >= 4, f"{n_dev}"):
        return
    cfg = get_config(ARCH)
    if args.reduced:
        cfg = cfg.reduced()
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(args.seed), dtype=jnp.float32)
    trace_args = argparse.Namespace(
        seed=args.seed, arrival="poisson", rate=20.0,
        requests=TRACE["requests"], prompt_len=TRACE["prompt_len"],
        gen_min=TRACE["gen_min"], gen_max=TRACE["gen_max"])
    engines, toks = {}, {}
    for tp in (1, 4):
        c0 = clock.total
        eng = Engine(model, params, serve_cfg=ServeConfig(
            max_len=TRACE["prompt_len"] + TRACE["gen_max"] + 1,
            max_slots=TRACE["slots"], prefill_chunk=TRACE["prefill_chunk"],
            tp=tp, seed=args.seed))
        res = eng.serve(build_trace(trace_args, cfg.vocab_size))
        toks[tp] = check_served(ck, f"tp{tp}", res, cfg.vocab_size)
        pages_on = _devices_of(eng._pool.pages)
        params_on = _devices_of(eng._step_params)
        print(f"[placement] tp={eng._pool.tp_shards} pages on devices "
              f"{pages_on} params on devices {params_on} "
              f"hbm_bytes_per_device={res['mesh']['hbm_bytes_per_device']}",
              flush=True)
        print(f"[smoke reading, not a benchmark] tp{tp}: "
              f"tok/s={res['stats']['tok_per_s']!r} "
              f"ttft_p50_s={res['stats']['ttft_p50_s']!r} "
              f"compile_s={clock.total - c0!r}", flush=True)
        if tp == 4:
            ck("four-chips: tp4 pages and params span four devices",
               len(pages_on) == 4 and len(params_on) == 4,
               f"pages={pages_on} params={params_on}")
        engines[tp] = eng
    print(f"[smoke reading] greedy token agreement tp1 vs tp4: "
          f"{agreement(toks[1], toks[4])!r}", flush=True)

    # the same decode step on the same inputs, unsharded and sharded.  At
    # the TPU's default matmul precision an f32 matmul is one bf16 pass,
    # so the two reduction orders round apart by ~1e-3 of the logit scale
    # and greedy streams can part at near-ties; at "highest" precision
    # only f32 rounding is left
    e1, e4 = engines[1], engines[4]
    splan = e4._serve_plan(e4.plan, 4)
    rep = NamedSharding(splan.mesh, P())
    B, MP, ps = TRACE["slots"], e1._pool.max_pages_per_slot, e1._pool.page_size
    n_pages = e1._pool.n_pages
    keys = iter(jax.random.split(jax.random.PRNGKey(args.seed + 1), 64))
    pages = jax.tree.map(
        lambda a: jax.random.normal(next(keys), a.shape, a.dtype),
        model.paged_cache_spec(n_pages, ps, dtype=jnp.float32))
    pages4 = jax.device_put(pages, cache_shardings(splan, pages))
    tokens = jax.random.randint(next(keys), (B, 1), 0, cfg.vocab_size,
                                jnp.int32)
    bt = jnp.arange(1, B * MP + 1, dtype=jnp.int32).reshape(B, MP)
    lengths = jnp.array([0, 7, 100, MP * ps - 1], jnp.int32)
    small4 = [jax.device_put(a, rep) for a in (tokens, bt, lengths)]

    def logits_fn(plan):
        return jax.jit(lambda p, pg, t, b, ln: model.paged_decode(
            p, pg, t, b, ln, plan)[0])

    rel = {}
    for precision in ("default", "highest"):
        with jax.default_matmul_precision(precision):
            l1 = jax.device_get(logits_fn(e1.plan)(params, pages, tokens,
                                                   bt, lengths))
            l4 = jax.device_get(logits_fn(splan)(e4._step_params, pages4,
                                                 *small4))
        diff = float(np.max(np.abs(l1 - l4)))
        scale = float(np.max(np.abs(l1)))
        top2 = np.sort(l1.reshape(B, -1), axis=-1)[:, -2:]
        rel[precision] = diff / scale
        print(f"[tp logits] matmul precision {precision}: "
              f"max|dlogit|={diff!r} max|logit|={scale!r} "
              f"relative={rel[precision]!r} "
              f"min top1-top2 gap={float(np.min(top2[:, 1] - top2[:, 0]))!r}"
              f" same argmax={bool((l1.argmax(-1) == l4.argmax(-1)).all())}",
              flush=True)
    ck("four-chips: tp4 decode-step logits equal tp1 up to f32 rounding "
       "(matmul precision highest)", rel["highest"] <= TP_LOGITS_RTOL,
       f"relative={rel['highest']!r} rtol={TP_LOGITS_RTOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the tp=4 against tp=1 phase (needs four "
                         "devices)")
    ap.add_argument("--reduced", action="store_true",
                    help="rehearsal at the reduced config's widths on any "
                         "backend; never prints a result off the TPU")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not on_tpu and not args.reduced:
        print(f"chip_smoke: JAX found no TPU (platform={dev.platform!r})",
              file=sys.stderr)
        return 1
    try:
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not beside this "
              f"script ({e})", file=sys.stderr)
        return 2
    enable_compile_cache()
    clock = CompileClock()
    ck = Checks()
    phases = ([phase_four_chips] if args.four_chips
              else [phase_serve, phase_kernel, phase_autotune])
    state: dict = {}
    t0 = time.perf_counter()
    for phase in phases:
        try:
            phase(args, ck, clock, on_tpu, state)
        except Exception:      # report, then run the remaining phases
            traceback.print_exc()
            ck(f"{phase.__name__} ran to its end", False)
        gc.collect()
    print(f"[smoke] wall_s={time.perf_counter() - t0!r} "
          f"compile_s={clock.total!r}", flush=True)
    if ck.failed:
        print(f"chip_smoke: FAILED {len(ck.failed)} check(s): "
              f"{'; '.join(ck.failed)}", file=sys.stderr)
        return 1
    if not on_tpu:
        print(f"chip_smoke: rehearsal passed on {dev.platform!r}; no TPU, "
              f"so no result", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
