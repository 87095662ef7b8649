"""Paged-attention decode (Pallas TPU): a short block of query tokens per
sequence attending over K/V scattered across a global page pool, read
through a scalar-prefetched block table — only the pages a sequence has
written, several pages per compute block.

Layout: q is (B, S, KVH, G, HD) — S is the per-slot query length (1 for
plain decode, ``spec_depth + 1`` for the speculative verify step) and the
heads are GQA-grouped so K/V are never materialised at the full head count;
k_pages/v_pages are (P, page_size, KVH, HD) (a page holds every KV head,
contiguous); the block table is (B, max_pages) int32 page ids (zero-padded
— page 0 is the pool's null sink) and lengths is (B,) int32: the number of
KV positions visible to query 0 (each later query sees one more — the
staircase causal mask of a speculative block whose own K/V rows are
already written).

The grid walks the slots; each slot runs a loop over its live compute
blocks only.  A slot's live pages are ``ceil((lengths + S - 1) /
page_size)`` (what its last query sees) and a compute block is ``block_k``
tokens, ``block_k // page_size`` pages: the block's live pages are copied
HBM -> VMEM one async copy per page (the pages stay in HBM,
``memory_space=pl.ANY``), and the next block's copies — the next slot's
first block at a slot's end — start before this block is computed, into
the other half of a double buffer.  Pages past a slot's length are never
copied and never computed on; a slot of length 0 does nothing.

Within a block, the page rows (token, kv head) stack into one
(block_k * KVH, HD) matrix, so the scores of every (query, head) row come
from one MXU product; a row keeps only its own kv head's columns and the
positions its query sees.  K and V enter the MXU in the pool's dtype with
float32 accumulation; scores, the running max, the denominator and the
accumulator stay float32 (online softmax across the slot's blocks).

Compiled, the kernel takes only heads that fill whole 128-lane tiles
(:func:`compiles_head_dim`); interpreted, any width runs.

Rows whose length is 0 (inactive pool slots) write zeros; the engine masks
them out at sampling.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_K = 256       # tokens per compute block, picked on a v5e


def shard_kv_heads(kv_heads: int, tp: int) -> int:
    """KV heads each shard sees under a ``tp``-way mesh "model" axis.

    Tensor parallelism splits the page pool on the kv-head dim only
    (pages: ``(P, page_size, KVH/tp, HD)`` per shard) — page ids, in-page
    positions, and the host-side block table are identical on every shard.
    Raises when the head count cannot split evenly — the engine clamps
    requested degrees through this rule before building a sharded step.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if kv_heads % tp:
        raise ValueError(
            f"tp={tp} does not divide kv_heads={kv_heads}: pages shard on "
            f"the kv-head axis, so the degree must split heads evenly")
    return kv_heads // tp


def live_pages(lengths, s_len: int, page_size: int):
    """Pages each slot's attention reads: what its last query sees,
    ``lengths + s_len - 1`` positions, rounded up to whole pages; 0 for a
    slot of length 0.  Works on numpy and jax arrays alike."""
    n = (lengths + s_len - 1 + page_size - 1) // page_size
    return n * (lengths > 0)


def compiles_head_dim(head_dim: int) -> bool:
    """Whether the compiled (TPU) kernel takes this head width.  A page is
    copied by slicing the pool's leading dim, and Mosaic slices a ref only
    when its minor dim fills whole 128-lane tiles: at 64-wide heads the
    compile for a v5e fails with "Slice shape along dimension 3 must be
    aligned to tiling (128), but is 64".  Interpreted, any width runs."""
    return head_dim % 128 == 0


def _row_masks(length, rows: int, cols: int, kvh: int, grp: int):
    """A row (query s, kv head h, group g) keeps the columns (token t, kv
    head h) of its own head whose position its query sees: the additive
    head bias (rows, cols), the positions each row sees (rows, 1) and each
    column's token (1, cols)."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    head_bias = jnp.where((row // grp) % kvh == col % kvh, 0.0, NEG_INF)
    return head_bias, length + row // (kvh * grp), col // kvh


def _attend(q, k, v, first_tok, masks, scale: float, carry):
    """One online-softmax step over a (cols, HD) block of stacked page rows
    whose first token is ``first_tok``: K and V enter the MXU in the
    pool's dtype, everything else stays float32."""
    head_bias, row_sees, col_tok = masks
    m_prev, l_prev, acc = carry
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = jnp.where(first_tok + col_tok < row_sees, s * scale + head_bias,
                  NEG_INF)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    pexp = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_prev + jnp.sum(pexp, axis=-1, keepdims=True)
    pv = jnp.dot(pexp.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return m_new, l_new, acc * alpha + pv


def _paged_mq_kernel(bt_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                     k_buf, v_buf, sems, state, *,
                     scale: float, s_len: int, kvh: int, grp: int,
                     page_size: int, ppb: int, max_pages: int, n_slots: int):
    b = pl.program_id(0)
    bk = ppb * page_size
    rows, cols = s_len * kvh * grp, bk * kvh

    def n_pages(slot):
        return jnp.minimum(live_pages(len_ref[slot], s_len, page_size),
                           max_pages)

    def copy(page, buf, j, kv):
        src, dst = (k_hbm, k_buf) if kv == 0 else (v_hbm, v_buf)
        return pltpu.make_async_copy(src.at[page], dst.at[buf, j],
                                     sems.at[kv, buf])

    def n_live(slot, blk):
        return jnp.clip(n_pages(slot) - blk * ppb, 0, ppb)

    def start(slot, blk, buf):
        """Copy the block's live pages into one half of the buffer."""
        def one(j, carry):
            page = bt_ref[slot * max_pages + blk * ppb + j]
            copy(page, buf, j, 0).start()
            copy(page, buf, j, 1).start()
            return carry
        jax.lax.fori_loop(0, n_live(slot, blk), one, 0)

    def wait(slot, blk, buf):
        """Wait for those copies: each wait takes one page's bytes off the
        half's semaphores, so any page's descriptor serves."""
        def one(j, carry):
            copy(0, buf, 0, 0).wait()
            copy(0, buf, 0, 1).wait()
            return carry
        jax.lax.fori_loop(0, n_live(slot, blk), one, 0)

    @pl.when(b == 0)
    def _init():
        # state: [0] the buffer half the next block lands in, [1] whether
        # any block is in flight.  Stale halves must be finite: a dead
        # page's rows meet a zero weight in the P @ V product.
        state[0] = 0
        state[1] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    n_blocks = (n_pages(b) + ppb - 1) // ppb

    @pl.when((n_blocks > 0) & (state[1] == 0))
    def _first():
        start(b, 0, state[0])
        state[1] = 1

    masks = _row_masks(len_ref[b], rows, cols, kvh, grp)
    q = q_ref[0]                                            # (rows, HD)

    def body(i, carry):
        cur = state[0]
        nxt = 1 - cur

        @pl.when(i + 1 < n_blocks)
        def _next_block():
            start(b, i + 1, nxt)

        @pl.when(i + 1 == n_blocks)
        def _next_slot():
            # the next slot with live pages starts its first block now
            def skip(s):
                return jnp.where(
                    (s < n_slots) & (n_pages(jnp.minimum(s, n_slots - 1))
                                     == 0), s + 1, s)
            nb = jax.lax.fori_loop(b + 1, n_slots, lambda _, s: skip(s),
                                   b + 1)

            @pl.when(nb < n_slots)
            def _():
                start(nb, 0, nxt)

        wait(b, i, cur)
        hd = k_buf.shape[-1]
        carry = _attend(q, k_buf[cur].reshape(cols, hd),
                        v_buf[cur].reshape(cols, hd), i * bk, masks, scale,
                        carry)
        state[0] = nxt
        return carry

    hd = q_ref.shape[-1]
    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    a0 = jnp.zeros((rows, hd), jnp.float32)
    _, l_fin, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l_fin, 1e-30)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def paged_attention_mq(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       block_k: int = 0, interpret: bool = False) -> jax.Array:
    """q: (B, S, KVH, G, HD); pages: (P, page_size, KVH, HD) -> same as q.

    ``block_tables``: (B, max_pages) int32; ``lengths``: (B,) int32 KV
    positions visible to query 0 (query ``s`` sees ``lengths + s`` — the
    speculative block's own rows are already in the pages).  ``block_k``:
    tokens per compute block, a multiple of ``page_size`` (0 = the
    default, :data:`DEFAULT_BLOCK_K`, cut to the block table's reach).
    """
    B, s_len, kvh, g, hd = q.shape
    _, page_size, kvh_p, hd_p = k_pages.shape
    assert (kvh_p, hd_p) == (kvh, hd), "page layout mismatch"
    max_pages = block_tables.shape[1]
    if block_k % page_size:
        raise ValueError(f"block_k={block_k} is not a multiple of "
                         f"page_size={page_size}")
    if not interpret and not compiles_head_dim(hd):
        raise ValueError(f"head_dim={hd} does not fill whole 128-lane "
                         f"tiles: the compiled kernel cannot copy its pages")
    ppb = block_k // page_size or min(
        max(DEFAULT_BLOCK_K // page_size, 1), max_pages)
    rows = s_len * kvh * g

    scale = 1.0 / math.sqrt(hd)
    q_spec = pl.BlockSpec((1, rows, hd), lambda b, *_: (b, 0, 0))
    kern = functools.partial(
        _paged_mq_kernel, scale=scale, s_len=s_len, kvh=kvh, grp=g,
        page_size=page_size, ppb=ppb, max_pages=max_pages, n_slots=B)
    buf = pltpu.VMEM((2, ppb, page_size, kvh, hd), k_pages.dtype)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,),
        in_specs=[q_spec, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=q_spec,
        scratch_shapes=[buf, buf, pltpu.SemaphoreType.DMA((2, 2)),
                        pltpu.SMEM((2,), jnp.int32)])
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, rows, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(block_tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q.reshape(B, rows, hd), k_pages, v_pages)
    return out.reshape(B, s_len, kvh, g, hd)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_tables: jax.Array, lengths: jax.Array, *,
                    block_k: int = 0, interpret: bool = False) -> jax.Array:
    """Single-query decode: q (B, KVH, G, HD) -> (B, KVH, G, HD).

    The S=1 specialisation of :func:`paged_attention_mq` (kept as the
    stable entry point for plain decode callers and the kernel tests).
    """
    out = paged_attention_mq(q[:, None], k_pages, v_pages, block_tables,
                             lengths, block_k=block_k, interpret=interpret)
    return out[:, 0]
