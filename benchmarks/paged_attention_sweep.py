"""Decode attention alone on a TPU: the Pallas paged kernel's ``block_k``
against the dense gather, at the benchmark's qwen3-8b-h18 pool shapes.

  python3 benchmarks/paged_attention_sweep.py [--seed N] [--out FILE.json]

32 slots, 3,000 pages of 16 tokens, block tables of 160 pages, GQA 32/8,
head_dim 128, bf16 pages.  Each mix draws its slots' lengths from the seed;
the rest of the 32 slots are inactive, sent as the engine sends them (a
zeroed block-table row, length 0):

  batch  26 slots at 600-2,000 tokens, S=1 (the batch-decode cell)
  chat   19 slots at 200-1,200 tokens, S=1 (the chat cell)
  s3     the batch slots at S=3 (the speculative verify width)

One jit chains 18 calls (one a layer; each call's output is the next
call's query, so none is folded away); the time is the best of 20 runs
over 18, in microseconds a call.  ``err`` is the widest gap to the f32
oracle (``kernels/ref.py``).  Off the TPU it exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax                                                  # noqa: E402
import jax.numpy as jnp                                     # noqa: E402

from repro.kernels import ref                               # noqa: E402
from repro.kernels.paged_attention import paged_attention_mq  # noqa: E402

SLOTS, POOL, PAGE, MAX_PAGES = 32, 3000, 16, 160
KVH, GRP, HD, LAYERS = 8, 4, 128, 18
MIXES = {"batch": (26, 600, 2000, 1), "chat": (19, 200, 1200, 1),
         "s3": (26, 600, 2000, 3)}
BLOCK_K = (128, 256, 512)


def gather(q, k_pages, v_pages, bt, lengths):
    """The gather path's math (``models/attention.py``): every slot's 160
    pages dense, the grouped-GQA einsums, the softmax in f32."""
    B, S = q.shape[:2]
    k = k_pages[bt].reshape(B, -1, KVH, HD)
    v = v_pages[bt].reshape(B, -1, KVH, HD)
    valid = (jnp.arange(k.shape[1])[None, None, :]
             < (lengths[:, None] + jnp.arange(S)[None, :])[:, :, None])
    s = jnp.einsum("bshge,bkhe->bhsgk", q, k) / math.sqrt(HD)
    s = jnp.where(valid[:, None, :, None, :], s.astype(jnp.float32), -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhsgk,bkhe->bshge", p, v)


def inputs(key, mix):
    n, lo, hi, S = MIXES[mix]
    kl, kp, kk, kv, kq = jax.random.split(key, 5)
    lengths = jnp.zeros((SLOTS,), jnp.int32).at[:n].set(
        jax.random.randint(kl, (n,), lo, hi + 1))
    # live pages scattered over the pool, as the allocator leaves them
    # (a mix that outgrows the pool reuses ids: only the timing matters)
    ids = jax.random.permutation(kp, POOL - 1) + 1
    live = jnp.arange(MAX_PAGES)[None, :] * PAGE < (lengths + S - 1)[:, None]
    nth = (jnp.cumsum(live.reshape(-1)) - 1) % (POOL - 1)
    bt = jnp.where(live, ids[nth].reshape(SLOTS, MAX_PAGES), 0)
    bt = bt.astype(jnp.int32)
    pages = (POOL, PAGE, KVH, HD)
    k_pages = jax.random.normal(kk, pages, jnp.bfloat16)
    v_pages = jax.random.normal(kv, pages, jnp.bfloat16)
    q = jax.random.normal(kq, (SLOTS, S, KVH, GRP, HD), jnp.bfloat16)
    return q, k_pages, v_pages, bt, lengths


def time_us(fn, args) -> float:
    def chained(q, *rest):
        for _ in range(LAYERS):
            q = fn(q, *rest).astype(q.dtype)
        return q
    run = jax.jit(chained)
    jax.block_until_ready(run(*args))
    best = float("inf")
    for _ in range(20):
        t0 = time.perf_counter()
        jax.block_until_ready(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best / LAYERS * 1e6


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("paged_attention_sweep: needs a TPU", file=sys.stderr)
        return 1
    rows = []
    for mix in MIXES:
        a = inputs(jax.random.PRNGKey(args.seed), mix)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(ref.paged_attention_mq)(*a)
        fns = {"gather": gather}
        fns.update({f"kernel_bk{bk}": (lambda *x, bk=bk: paged_attention_mq(
            *x, block_k=bk)) for bk in BLOCK_K})
        act = a[4] > 0
        for name, fn in fns.items():
            err = float(jnp.max(jnp.abs(
                jax.jit(fn)(*a).astype(jnp.float32) - want)[act]))
            rows.append({"mix": mix, "fn": name, "us": round(time_us(fn, a), 2),
                         "err": err})
            print(json.dumps(rows[-1]), flush=True)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
