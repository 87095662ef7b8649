"""Paged-attention decode (Pallas TPU): a short block of query tokens per
sequence attending over K/V scattered across a global page pool, read
through a scalar-prefetched block table — only the pages a sequence has
written, several pages per compute block.

Layout: q is (B, S, KVH, G, HD) — S is the per-slot query length (1 for
plain decode, ``spec_depth + 1`` for the speculative verify step) and the
heads are GQA-grouped so K/V are never materialised at the full head count;
k_pages/v_pages hold one page per leading index, each page row (one token,
every KV head) laid out by :func:`page_row`: ``(KVH, HD)`` where a head
fills whole 128-lane tiles, else ``(KVH * HD,)`` lane-dense, so that a row
of narrow heads is not padded to whole tiles in HBM or VMEM.  Compiled,
the kernel takes rows of whole lane tiles (:func:`compiles_row`);
interpreted, any width.  The block table is (B, max_pages) int32 page ids
(zero-padded — page 0 is the pool's null sink) and lengths is (B,) int32:
the number of KV positions visible to query 0 (each later query sees one
more — the staircase causal mask of a speculative block whose own K/V
rows are already written).

The grid walks the slots; each slot runs a loop over its live compute
blocks only.  A slot's live pages are ``ceil((lengths + S - 1) /
page_size)`` (what its last query sees) and a compute block is ``block_k``
tokens, ``block_k // page_size`` pages: the block's live pages are copied
HBM -> VMEM one async copy per page (the pages stay in HBM,
``memory_space=pl.ANY``), and the next block's copies — the next slot's
first block at a slot's end — start before this block is computed, into
the other half of a double buffer.  Pages past a slot's length are never
copied and never computed on; a slot of length 0 does nothing.

Every (query, head) row's scores come from one MXU product per block.
Whole-tile heads: the page rows (token, kv head) stack into one
(block_k * KVH, HD) matrix and a row keeps only its own kv head's columns.
Lane-dense rows: the block is one (block_k, KVH * HD) matrix and each
(query, group, kv head) row of q is zero outside its own head's lanes, so
the product sums only that head's lanes; P @ V then gives every head's
lanes and each row keeps its own head's.  K and V enter the MXU in the
pool's dtype with float32 accumulation; scores, the running max, the
denominator and the accumulator stay float32 (online softmax across the
slot's blocks).

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
LANES = 128                 # the minor dim of a TPU vector tile
#: The kernel's ``pallas_call`` name: the custom call's kernel name in the
#: compiled step, which tells its device time apart from the projections
#: that share its ``R.attn`` scope.
KERNEL_NAME = "paged_attention_kernel"


def shard_kv_heads(kv_heads: int, tp: int) -> int:
    """KV heads each shard sees under a ``tp``-way mesh "model" axis.

    Tensor parallelism splits the page pool on the kv-head dim only
    (pages: ``(P, page_size, KVH/tp, HD)`` per shard, or ``(P, page_size,
    KVH/tp * HD)`` lane-dense: whole heads either way) — page ids, in-page
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


def page_row(kv_heads: int, head_dim: int) -> tuple:
    """The shape of one page row (one token, every KV head) in the pool:
    ``(kv_heads, head_dim)`` where a head fills whole 128-lane tiles, else
    ``(kv_heads * head_dim,)``.  A minor dim narrower than a lane tile is
    padded to one in HBM (80-wide heads would take 1.6x their bytes) and
    cannot be sliced out of the pool by the compiled kernel's page copy."""
    if head_dim % LANES == 0:
        return (kv_heads, head_dim)
    return (kv_heads * head_dim,)


def compiles_row(kv_heads: int, head_dim: int) -> bool:
    """Whether the compiled (TPU) kernel takes pages of these rows.  Mosaic
    slices a page out of the pool and q's block only when their minor dim
    fills whole 128-lane tiles: a row of 320 lanes (4 heads of 80) fails
    the compile for a v5e with "Slice shape along dimension 2 must be
    aligned to tiling (128), but is 320".  Interpreted, any width runs."""
    return kv_heads * head_dim % LANES == 0


def _row_masks(length, rows: int, cols: int, kvh: int, grp: int,
               stacked: bool):
    """The positions each (query, head) row sees (rows, 1) and each
    column's token (1, cols); stacked rows also keep only their own kv
    head's columns (token t, kv head h): an additive head bias (rows,
    cols), else 0.  Rows are query-major either way."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    row_sees = length + row // (kvh * grp)
    if not stacked:
        return 0.0, row_sees, col
    head_bias = jnp.where((row // grp) % kvh == col % kvh, 0.0, NEG_INF)
    return head_bias, row_sees, col // kvh


def _own_lanes(rows: int, kvh: int, hd: int):
    """Lane-dense rows (query, group, kv head): each row's own head's
    lanes of a (rows, KVH * HD) block."""
    row = jax.lax.broadcasted_iota(jnp.int32, (rows, kvh * hd), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, kvh * hd), 1)
    return col // hd == row % kvh


def _attend(q, k, v, first_tok, masks, scale: float, carry):
    """One online-softmax step over a (cols, width) block of page rows
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
                     scale: float, s_len: int, kvh: int, grp: int, hd: int,
                     page_size: int, ppb: int, max_pages: int, n_slots: int):
    b = pl.program_id(0)
    bk = ppb * page_size
    stacked = k_buf.ndim == 5           # (2, ppb, page_size, KVH, HD)
    rows = s_len * kvh * grp
    cols, width = (bk * kvh, hd) if stacked else (bk, kvh * hd)

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

    masks = _row_masks(len_ref[b], rows, cols, kvh, grp, stacked)
    if stacked:
        q = q_ref[0]                                        # (rows, HD)
    else:
        # each (query, group) row of q once per kv head, zero outside
        # that head's lanes
        own = _own_lanes(rows, kvh, hd)
        qs = q_ref[0].astype(jnp.float32)                   # (S*G, width)
        q = jnp.broadcast_to(qs[:, None, :], (s_len * grp, kvh, width))
        q = jnp.where(own, q.reshape(rows, width), 0.0).astype(k_buf.dtype)

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
        carry = _attend(q, k_buf[cur].reshape(cols, width),
                        v_buf[cur].reshape(cols, width), i * bk, masks,
                        scale, carry)
        state[0] = nxt
        return carry

    m0 = jnp.full((rows, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    a0 = jnp.zeros((rows, width), jnp.float32)
    _, l_fin, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    out = acc / jnp.maximum(l_fin, 1e-30)
    if not stacked:
        # each row's own head's lanes, summed over the kv heads of its
        # (query, group): one nonzero term per lane
        out = jnp.where(own, out, 0.0).reshape(s_len * grp, kvh, width)
        out = jnp.sum(out, axis=1)
    o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def paged_attention_mq(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       block_k: int = 0, interpret: bool = False) -> jax.Array:
    """q: (B, S, KVH, G, HD); pages: (P, page_size) + :func:`page_row`
    -> same as q.

    ``block_tables``: (B, max_pages) int32; ``lengths``: (B,) int32 KV
    positions visible to query 0 (query ``s`` sees ``lengths + s`` — the
    speculative block's own rows are already in the pages).  ``block_k``:
    tokens per compute block, a multiple of ``page_size`` (0 = the
    default, :data:`DEFAULT_BLOCK_K`, cut to the block table's reach).
    """
    B, s_len, kvh, g, hd = q.shape
    page_size = k_pages.shape[1]
    row = k_pages.shape[2:]
    if row != page_row(kvh, hd):
        raise ValueError(f"pages of rows {row} do not hold {kvh} heads of "
                         f"{hd} in the pool's layout {page_row(kvh, hd)}")
    if not (interpret or compiles_row(kvh, hd)):
        raise ValueError(f"rows of {kvh * hd} lanes are not whole "
                         f"{LANES}-lane tiles: the compiled kernel cannot "
                         f"copy their pages")
    max_pages = block_tables.shape[1]
    if block_k % page_size:
        raise ValueError(f"block_k={block_k} is not a multiple of "
                         f"page_size={page_size}")
    ppb = block_k // page_size or min(
        max(DEFAULT_BLOCK_K // page_size, 1), max_pages)
    if len(row) == 2:
        q_rows = q.reshape(B, s_len * kvh * g, hd)
    else:
        # rows (query, group), lanes (kv head, dim): a page row's order
        q_rows = q.transpose(0, 1, 3, 2, 4).reshape(B, s_len * g, kvh * hd)

    scale = 1.0 / math.sqrt(hd)
    q_spec = pl.BlockSpec((1,) + q_rows.shape[1:], lambda b, *_: (b, 0, 0))
    kern = functools.partial(
        _paged_mq_kernel, scale=scale, s_len=s_len, kvh=kvh, grp=g, hd=hd,
        page_size=page_size, ppb=ppb, max_pages=max_pages, n_slots=B)
    buf = pltpu.VMEM((2, ppb, page_size) + row, k_pages.dtype)
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
        out_shape=jax.ShapeDtypeStruct(q_rows.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(block_tables.astype(jnp.int32).reshape(-1), lengths.astype(jnp.int32),
      q_rows, k_pages, v_pages)
    if len(row) == 2:
        return out.reshape(B, s_len, kvh, g, hd)
    return out.reshape(B, s_len, g, kvh, hd).transpose(0, 1, 3, 2, 4)


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
