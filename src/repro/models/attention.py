"""Attention: full/causal, GQA, sliding-window, qk_norm, KV-cache decode.

Design notes (see DESIGN.md §7):

* GQA repeat: K/V are repeated to the full head count *contiguously* per
  kv-head, so a kv-head-sharded tensor repeats into a q-head-sharded tensor
  with no communication when both divide the model axis; when kv_heads < tp
  the plan replicates K/V (they are small under GQA) and only q-heads shard.
* q-block chunking: prefill at 32k must not materialise the full S×S score
  matrix.  The q loop is an *unrolled* Python loop (`n_q_blocks` small), so
  the dry-run's ``cost_analysis()`` stays honest (scan bodies are counted
  once by XLA — DESIGN.md §8).
* Sliding-window attention restricts each q block to a statically-sliced KV
  range (an actual FLOP saving, not just a mask) — this is what makes
  h2o-danube's long_500k path sub-quadratic.
* Context parallelism (whisper: 20 heads don't divide tp=16) comes from the
  plan mapping ``seq -> model`` in attention regions; the einsums below then
  induce KV all-gathers instead of head sharding.
"""
from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.policy import RegionPlan
from repro.core.regions import region
from repro.models.layers import Spec, apply_rope

NEG_INF = -1e30


def attn_spec(cfg, cross: bool = False) -> Any:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": Spec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wv": Spec((d, kv, hd), ("embed", "kv_heads", "head_dim")),
        "wo": Spec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = Spec((hd,), (None,), "ones")
        p["k_norm"] = Spec((hd,), (None,), "ones")
    return p


def _rms(x, scale, eps=1e-6):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), -1, keepdims=True) + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def _repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """(B,S,KV,HD) -> (B,S,H,HD), contiguous per kv head (sharding-friendly)."""
    kv = k.shape[2]
    if kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // kv, axis=2)


def _project_qkv(cfg, p, x, kv_x, plan, rpath, positions, kv_positions,
                 rope: bool):
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k = jnp.einsum("bsd,dhe->bshe", kv_x, p["wk"])
    v = jnp.einsum("bsd,dhe->bshe", kv_x, p["wv"])
    if cfg.qk_norm and "q_norm" in p:
        q = _rms(q, p["q_norm"])
        k = _rms(k, p["k_norm"])
    if rope:
        q = apply_rope(cfg, q, positions)
        k = apply_rope(cfg, k, kv_positions)
    q = plan.constrain(q, rpath, ("batch", "seq", "heads", "head_dim"))
    k = plan.constrain(k, rpath, ("batch", "kv_seq", "kv_heads", "head_dim"))
    v = plan.constrain(v, rpath, ("batch", "kv_seq", "kv_heads", "head_dim"))
    return q, k, v


def _scores_block(cfg, q_blk, k, v, q_pos, k_pos, plan, rpath, causal):
    """One q-block of attention. q_blk: (B,Q,H,HD); k,v: (B,K,H,HD)."""
    hd = q_blk.shape[-1]
    s = jnp.einsum("bqhe,bkhe->bhqk", q_blk, k) / math.sqrt(hd)
    s = plan.constrain(s, rpath, ("batch", "heads", "seq", "kv_seq"))
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if cfg.swa_window:
        mask &= q_pos[:, None] - k_pos[None, :] < cfg.swa_window
    s = jnp.where(mask, s.astype(jnp.float32), NEG_INF)
    pmax = jnp.max(s, -1, keepdims=True)
    pexp = jnp.exp(s - jax.lax.stop_gradient(pmax))
    probs = (pexp / jnp.sum(pexp, -1, keepdims=True)).astype(q_blk.dtype)
    return jnp.einsum("bhqk,bkhe->bqhe", probs, v)


def default_block_q(seq: int) -> int:
    """Keep the per-block score matrix bounded while staying unrolled."""
    if seq <= 8192:
        return seq
    return max(seq // 4, 8192)


def apply_attention(cfg, p, x, plan: RegionPlan, *, positions=None,
                    kv_x=None, kv_positions=None, causal=None,
                    rope: bool = True, name: str = "attn") -> jax.Array:
    """Full-sequence attention (train / prefill / encoder / cross)."""
    with region(name) as rpath:
        B, S, _ = x.shape
        causal = cfg.causal if causal is None else causal
        if positions is None:
            positions = jnp.arange(S, dtype=jnp.int32)
        if kv_x is None:
            kv_x, kv_positions = x, positions
        elif kv_positions is None:
            kv_positions = jnp.arange(kv_x.shape[1], dtype=jnp.int32)
        q, k, v = _project_qkv(cfg, p, x, kv_x, plan, rpath,
                               positions, kv_positions, rope)
        k = _repeat_kv(k, cfg.n_heads)
        v = _repeat_kv(v, cfg.n_heads)

        rc = plan.config_for(rpath)
        blk = rc.block_q or default_block_q(S)
        outs = []
        for start in range(0, S, blk):          # unrolled (dry-run honesty)
            q_blk = q[:, start:start + blk]
            q_pos = positions[start:start + blk]
            if cfg.swa_window and causal and kv_x is x:
                # static KV slice: only the window can be attended to
                lo = max(0, (start - cfg.swa_window + blk) // blk * blk - blk)
                lo = min(lo, start)
                k_use, v_use = k[:, lo:start + blk], v[:, lo:start + blk]
                k_pos = kv_positions[lo:start + blk]
            else:
                k_use, v_use, k_pos = k, v, kv_positions
            outs.append(_scores_block(cfg, q_blk, k_use, v_use,
                                      q_pos, k_pos, plan, rpath, causal))
        attn = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)
        out = jnp.einsum("bshe,hed->bsd", attn, p["wo"])
        return plan.constrain(out, rpath, ("batch", "seq", "embed"))


# ---------------------------------------------------------------------------
# KV cache (decode)
# ---------------------------------------------------------------------------


def kv_cache_spec(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Cache shapes for one attention instance. SWA uses a ring of window size."""
    size = min(max_len, cfg.swa_window) if cfg.swa_window else max_len
    kv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "k": jax.ShapeDtypeStruct((batch, size, kv, hd), dtype),
        "v": jax.ShapeDtypeStruct((batch, size, kv, hd), dtype),
    }


def init_kv_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        kv_cache_spec(cfg, batch, max_len, dtype))


def apply_attention_decode(cfg, p, x, cache, pos, plan: RegionPlan,
                           name: str = "attn") -> tuple[jax.Array, Any]:
    """Decode a short block of T tokens against a KV cache.

    x: (B, T, D); cache: {"k","v"}: (B, C, KV, HD); pos: scalar int32 —
    number of tokens already in the cache (same for the whole batch).
    T=1 is the classic single-token step (SWA rings supported); T>1
    writes T rows at pos..pos+T-1 and attends under the staircase mask
    (chunked state-prefill and speculative verify for slot families;
    rings unsupported — a chunk larger than the window would wrap over
    its own writes).
    """
    if x.shape[1] > 1:
        return _attention_decode_block(cfg, p, x, cache, pos, plan, name)
    with region(name) as rpath:
        B = x.shape[0]
        C = cache["k"].shape[1]
        ring = bool(cfg.swa_window) and C == cfg.swa_window
        positions = jnp.full((1,), pos, jnp.int32)
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
        k_new = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        v_new = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
        if cfg.qk_norm and "q_norm" in p:
            q = _rms(q, p["q_norm"])
            k_new = _rms(k_new, p["k_norm"])
        q = apply_rope(cfg, q, positions)
        k_new = apply_rope(cfg, k_new, positions)

        slot = jnp.mod(pos, C) if ring else pos
        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, slot, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, slot, axis=1)
        new_cache = {"k": k, "v": v}
        k = plan.constrain(k, rpath, ("batch", "kv_seq", "kv_heads", "head_dim"))
        v = plan.constrain(v, rpath, ("batch", "kv_seq", "kv_heads", "head_dim"))

        # absolute position of each cache slot
        idx = jnp.arange(C, dtype=jnp.int32)
        if ring:
            # slots hold positions pos-C+1..pos once full; invalid before fill
            k_pos = pos - jnp.mod(pos - idx, C)
        else:
            k_pos = idx
        valid = (k_pos <= pos) & (k_pos >= 0)
        hd = q.shape[-1]
        # grouped GQA einsum: no materialised KV repeat (4x cache traffic)
        kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(B, 1, kvh, g, hd)
        s = jnp.einsum("bqhge,bkhe->bhgqk", qg, k) / math.sqrt(hd)
        s = plan.constrain(s, rpath,
                           ("batch", "kv_heads", None, "seq", "kv_seq"))
        s = jnp.where(valid[None, None, None, None, :],
                      s.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bhgqk,bkhe->bqhge", probs, v)
        attn = attn.reshape(B, 1, cfg.n_heads, hd)
        out = jnp.einsum("bshe,hed->bsd", attn, p["wo"])
        return plan.constrain(out, rpath, ("batch", "seq", "embed")), new_cache


def _attention_decode_block(cfg, p, x, cache, pos, plan: RegionPlan,
                            name: str = "attn") -> tuple[jax.Array, Any]:
    """T>1 branch of :func:`apply_attention_decode`: contiguous rows at
    pos..pos+T-1, staircase-masked (query i sees everything through its
    own row).  Non-ring caches only."""
    with region(name) as rpath:
        B, T, _ = x.shape
        C = cache["k"].shape[1]
        assert not (bool(cfg.swa_window) and C == cfg.swa_window), \
            "multi-token decode unsupported on SWA ring caches"
        positions = pos + jnp.arange(T, dtype=jnp.int32)
        q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
        k_new = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        v_new = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
        if cfg.qk_norm and "q_norm" in p:
            q = _rms(q, p["q_norm"])
            k_new = _rms(k_new, p["k_norm"])
        q = apply_rope(cfg, q, positions)
        k_new = apply_rope(cfg, k_new, positions)

        k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, pos, axis=1)
        v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, pos, axis=1)
        new_cache = {"k": k, "v": v}
        k = plan.constrain(k, rpath, ("batch", "kv_seq", "kv_heads", "head_dim"))
        v = plan.constrain(v, rpath, ("batch", "kv_seq", "kv_heads", "head_dim"))

        k_pos = jnp.arange(C, dtype=jnp.int32)
        # staircase: query i sees every cache row through its own write
        valid = k_pos[None, :] <= positions[:, None]        # (T, C)
        hd = q.shape[-1]
        kvh, g = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        qg = q.reshape(B, T, kvh, g, hd)
        s = jnp.einsum("bshge,bkhe->bhsgk", qg, k) / math.sqrt(hd)
        s = plan.constrain(s, rpath,
                           ("batch", "kv_heads", None, None, "kv_seq"))
        s = jnp.where(valid[None, None, :, None, :],
                      s.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bhsgk,bkhe->bshge", probs, v)
        attn = attn.reshape(B, T, cfg.n_heads, hd)
        out = jnp.einsum("bshe,hed->bsd", attn, p["wo"])
        return plan.constrain(out, rpath, ("batch", "seq", "embed")), new_cache


# ---------------------------------------------------------------------------
# Paged KV cache (block-pool decode + chunked prefill)
# ---------------------------------------------------------------------------


def paged_kv_spec(cfg, n_pages: int, page_size: int, dtype=jnp.bfloat16):
    """Page-pool shapes for one attention instance: a global block pool
    instead of per-request whole caches.  A page row (one token, every KV
    head) is laid out as the paged kernel reads it
    (:func:`repro.kernels.paged_attention.page_row`): ``(KV, HD)`` for
    heads of whole 128-lane tiles, ``(KV * HD,)`` lane-dense otherwise."""
    from repro.kernels.paged_attention import page_row
    shape = (n_pages, page_size) + page_row(cfg.n_kv_heads,
                                            cfg.resolved_head_dim)
    return {
        "k_pages": jax.ShapeDtypeStruct(shape, dtype),
        "v_pages": jax.ShapeDtypeStruct(shape, dtype),
    }


def _paged_write(pages, new, block_tables, offsets):
    """Scatter per-token K or V rows into the page pool.

    pages: (P, ps) + page row; new: (N, KV, HD); block_tables: (N, MP) —
    the owning slot's block-table row per written token; offsets: (N,)
    absolute token offsets within each token's sequence.  Live slots never
    share pages (allocator invariant); slots parked on the all-zero block
    table, and offsets beyond the block table's reach (a padded final
    prefill chunk overhanging max_len), are routed explicitly to page 0 —
    the sink.
    """
    ps = pages.shape[1]
    mp = block_tables.shape[1]
    idx = offsets // ps
    in_range = idx < mp
    page_ids = jnp.take_along_axis(block_tables,
                                   jnp.clip(idx, 0, mp - 1)[:, None],
                                   axis=1)[:, 0]
    page_ids = jnp.where(in_range, page_ids, 0)
    slot_off = jnp.where(in_range, offsets % ps, 0)
    rows = new.reshape(new.shape[:1] + pages.shape[2:])
    return pages.at[page_ids, slot_off].set(rows.astype(pages.dtype))


def _qkv_rope(cfg, p, x, positions):
    """Shared decode/chunk preamble: project q and the new K/V rows,
    qk-norm, rope at the given absolute positions."""
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"])
    k_new = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
    v_new = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
    if cfg.qk_norm and "q_norm" in p:
        q = _rms(q, p["q_norm"])
        k_new = _rms(k_new, p["k_norm"])
    q = apply_rope(cfg, q, positions)
    k_new = apply_rope(cfg, k_new, positions)
    return q, k_new, v_new


def _paged_gather(pages, block_table, kvh: int, hd: int):
    """(P, ps) + page row gathered through (..., MP) -> (..., MP*ps, KV, HD)."""
    return pages[block_table].reshape(block_table.shape[:-1] + (-1, kvh, hd))


def apply_attention_paged_decode(cfg, p, x, pages, block_tables, lengths,
                                 plan: RegionPlan,
                                 name: str = "attn") -> tuple[jax.Array, Any]:
    """Decode a short block of S tokens for every pool slot against the
    paged KV pool (S=1: plain decode; S=spec_depth+1: the speculative
    verify step scoring a drafted block in one pass).

    x: (B, S, D) — B is the slot axis; pages: {"k_pages","v_pages"}:
    (P, ps) + page row (:func:`paged_kv_spec`); block_tables: (B, MP)
    int32; lengths: (B,) int32 tokens already written per slot.  Token i
    of a slot lands at offset ``lengths[b] + i`` and its query attends
    causally up to and including its own row (the staircase mask), so
    slots carry independent positions natively — no vmap over
    single-request caches.  Rejected speculative
    rows are rolled back host-side (lengths truncate; the rows are
    overwritten by the next step's writes before any mask admits them).

    The attention impl is a region knob, resolved by the engine
    (``Engine.attn_impl_for``): 'gather' (or '') gathers each slot's
    pages dense and runs the grouped-GQA einsum (identical math to the
    slot path's ``apply_attention_decode``); ``attn_impl='paged'`` calls
    the multi-query Pallas paged-attention kernel, which copies only each
    slot's live pages through the block table, ``block_k`` tokens per
    compute block, all S queries sharing each copy.
    """
    with region(name) as rpath:
        B, S, _ = x.shape
        positions = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
        q, k_new, v_new = _qkv_rope(cfg, p, x, positions)

        kvh, hd = cfg.n_kv_heads, q.shape[-1]
        # S is static: the plain decode step (S=1) keeps the exact
        # single-row scatter — the repeat/reshape generalisation measurably
        # slows the hot path it doesn't need
        if S == 1:
            bt_rows, offsets, new_rows = block_tables, lengths, k_new[:, 0]
            v_rows = v_new[:, 0]
        else:
            bt_rows = jnp.repeat(block_tables, S, axis=0)   # (B*S, MP)
            offsets = positions.reshape(-1)
            new_rows = k_new.reshape(B * S, kvh, hd)
            v_rows = v_new.reshape(B * S, kvh, hd)
        k_pages = _paged_write(pages["k_pages"], new_rows, bt_rows, offsets)
        v_pages = _paged_write(pages["v_pages"], v_rows, bt_rows, offsets)
        new_pages = {"k_pages": k_pages, "v_pages": v_pages}

        grp = cfg.n_heads // kvh
        rc = plan.config_for(rpath)
        if rc.attn_impl == "paged":
            from repro.kernels import ops
            qg = q.reshape(B, S, kvh, grp, hd)
            # an inactive slot's row is all null page (page 0, which no
            # request owns): at length 0 the kernel copies nothing for it
            seen = jnp.where(block_tables[:, 0] > 0, lengths + 1, 0)
            attn = ops.paged_attention_mq(qg, k_pages, v_pages, block_tables,
                                          seen, block_k=rc.block_k)
            attn = attn.astype(x.dtype)
        elif S == 1:
            qg = q.reshape(B, kvh, grp, hd)
            k = _paged_gather(k_pages, block_tables, kvh, hd)   # (B,T,KV,HD)
            v = _paged_gather(v_pages, block_tables, kvh, hd)
            T = k.shape[1]
            # valid: every written position, including this step's token
            valid = jnp.arange(T, dtype=jnp.int32)[None, :] <= lengths[:, None]
            s = jnp.einsum("bhge,bkhe->bhgk", qg, k) / math.sqrt(hd)
            s = plan.constrain(s, rpath,
                               ("batch", "kv_heads", None, "kv_seq"))
            s = jnp.where(valid[:, None, None, :],
                          s.astype(jnp.float32), NEG_INF)
            probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            attn = jnp.einsum("bhgk,bkhe->bhge", probs, v)
        else:
            qg = q.reshape(B, S, kvh, grp, hd)
            k = _paged_gather(k_pages, block_tables, kvh, hd)   # (B,T,KV,HD)
            v = _paged_gather(v_pages, block_tables, kvh, hd)
            T = k.shape[1]
            # staircase: query i sees every written position through its own
            valid = (jnp.arange(T, dtype=jnp.int32)[None, None, :]
                     <= positions[:, :, None])              # (B, S, T)
            s = jnp.einsum("bshge,bkhe->bhsgk", qg, k) / math.sqrt(hd)
            s = plan.constrain(s, rpath,
                               ("batch", "kv_heads", None, None, "kv_seq"))
            s = jnp.where(valid[:, None, :, None, :],
                          s.astype(jnp.float32), NEG_INF)
            probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
            attn = jnp.einsum("bhsgk,bkhe->bshge", probs, v)
        attn = attn.reshape(B, S, cfg.n_heads, hd)
        out = jnp.einsum("bshe,hed->bsd", attn, p["wo"])
        return plan.constrain(out, rpath, ("batch", "seq", "embed")), new_pages


def apply_attention_paged_chunk(cfg, p, x, pages, block_table, base,
                                plan: RegionPlan,
                                name: str = "attn") -> tuple[jax.Array, Any]:
    """One prefill chunk of a single request against its paged KV range.

    x: (1, C, D) — C prompt tokens starting at absolute position ``base``
    (scalar int32); the chunk's K/V are written into the request's pages
    first, then its queries attend causally over everything the request
    has written so far (earlier chunks + itself), gathered through
    ``block_table`` (MP,).  Padded tail tokens (the last chunk is padded to
    the fixed chunk width) write beyond the true length: within the block
    table's reach they land in the request's own reserved pages (positions
    a later write always overwrites before any masked-in read); beyond it
    the write scatter routes them to the null page explicitly.
    """
    with region(name) as rpath:
        C = x.shape[1]
        positions = base + jnp.arange(C, dtype=jnp.int32)   # (C,) absolute
        q, k_new, v_new = _qkv_rope(cfg, p, x, positions)

        bt_rows = jnp.broadcast_to(block_table, (C, block_table.shape[0]))
        k_pages = _paged_write(pages["k_pages"], k_new[0], bt_rows, positions)
        v_pages = _paged_write(pages["v_pages"], v_new[0], bt_rows, positions)
        new_pages = {"k_pages": k_pages, "v_pages": v_pages}

        hd = q.shape[-1]
        kvh, grp = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads
        k = _paged_gather(k_pages, block_table[None, :], kvh, hd)  # (1,T,KV,HD)
        v = _paged_gather(v_pages, block_table[None, :], kvh, hd)
        T = k.shape[1]
        qg = q.reshape(1, C, kvh, grp, hd)
        s = jnp.einsum("bqhge,bkhe->bhgqk", qg, k) / math.sqrt(hd)
        s = plan.constrain(s, rpath,
                           ("batch", "kv_heads", None, "seq", "kv_seq"))
        kpos = jnp.arange(T, dtype=jnp.int32)
        causal = kpos[None, :] <= positions[:, None]        # (C, T)
        s = jnp.where(causal[None, None, None, :, :],
                      s.astype(jnp.float32), NEG_INF)
        probs = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        attn = jnp.einsum("bhgqk,bkhe->bqhge", probs, v)
        attn = attn.reshape(1, C, cfg.n_heads, hd)
        out = jnp.einsum("bshe,hed->bsd", attn, p["wo"])
        return plan.constrain(out, rpath, ("batch", "seq", "embed")), new_pages


def prefill_kv(cfg, p, x, plan: RegionPlan, max_len: int, name: str = "attn"):
    """Compute K/V for a full prompt and write them into a fresh cache."""
    with region(name + ".fill"):
        B, S, _ = x.shape
        positions = jnp.arange(S, dtype=jnp.int32)
        k = jnp.einsum("bsd,dhe->bshe", x, p["wk"])
        if cfg.qk_norm and "k_norm" in p:
            k = _rms(k, p["k_norm"])
        k = apply_rope(cfg, k, positions)
        v = jnp.einsum("bsd,dhe->bshe", x, p["wv"])
        C = min(max_len, cfg.swa_window) if cfg.swa_window else max_len
        ring = bool(cfg.swa_window) and C == cfg.swa_window
        if S >= C:
            k_c, v_c = k[:, S - C:], v[:, S - C:]
            if ring:
                # ring invariant: slot j holds absolute position p, p mod C == j
                k_c = jnp.roll(k_c, S % C, axis=1)
                v_c = jnp.roll(v_c, S % C, axis=1)
        else:
            pad = [(0, 0), (0, C - S), (0, 0), (0, 0)]
            k_c, v_c = jnp.pad(k, pad), jnp.pad(v, pad)
        return {"k": k_c, "v": v_c}
