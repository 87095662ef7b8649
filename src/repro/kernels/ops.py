"""Public jit'd wrappers around the Pallas kernels, with model-layout
adapters and the interpret switch (CPU backend -> interpret=True; TPU ->
compiled), decided per call so importing this module never touches the
backend.  The tuner's kernel knobs (block sizes, time tiles) surface here as
keyword args fed from RegionConfig.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.linear_scan import (ssd_chunk_kernel, ssd_kernel,
                                       wkv_chunk_kernel, wkv_kernel)
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.paged_attention import paged_attention_mq as _paged_mq
from repro.kernels.tuned_matmul import tuned_matmul


def _interpret() -> bool:
    """Interpret mode on the CPU backend, compiled kernels on TPU.  Any
    other backend raises: a kernel must never fall back to the interpreter
    on a device it was not written for."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"no Pallas kernel path for backend {backend!r}: kernels compile "
            f"on 'tpu' and run in interpret mode only on 'cpu'")
    return backend == "cpu"


def matmul(x, y, *, bm=128, bn=128, bk=128):
    return tuned_matmul(x, y, bm=bm, bn=bn, bk=bk, interpret=_interpret())


def attention(q, k, v, *, causal=True, window=0, block_q=128, block_k=128):
    """q,k,v: (B,S,H,D) model layout -> (B,S,H,D)."""
    B, S, H, D = q.shape
    fold = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, t.shape[1], D)
    out = _flash(fold(q), fold(k), fold(v), causal=causal, window=window,
                 bq=block_q, bk=block_k, interpret=_interpret())
    return out.reshape(B, H, S, D).transpose(0, 2, 1, 3)


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    block_k=0):
    """Paged decode attention, already in kernel layout (B, KVH, G, HD)."""
    return _paged(q, k_pages, v_pages, block_tables, lengths,
                  block_k=block_k, interpret=_interpret())


def paged_attention_mq(q, k_pages, v_pages, block_tables, lengths, *,
                       block_k=0):
    """Multi-query paged decode attention (speculative verify), kernel
    layout q: (B, S, KVH, G, HD); query s sees lengths + s positions."""
    return _paged_mq(q, k_pages, v_pages, block_tables, lengths,
                     block_k=block_k, interpret=_interpret())


def wkv(r, k, v, w, u, s0, *, bt=256, mode="fused_recurrent"):
    """Model layout (B,T,H,N) -> kernel layout (B,H,T,N) and back.

    ``mode``: 'fused_recurrent' streams the sequential recurrence through
    a VMEM-resident state; 'chunk' runs the matmul-form chunked parallel
    scan (``bt`` is the chunk size there — default it smaller)."""
    kern = wkv_chunk_kernel if mode == "chunk" else wkv_kernel
    if mode == "chunk":
        bt = min(bt, 64)
    tr = lambda t: jnp.moveaxis(t, 1, 2).astype(jnp.float32)
    out, s = kern(tr(r), tr(k), tr(v), tr(w), u.astype(jnp.float32),
                  s0.astype(jnp.float32), bt=bt, interpret=_interpret())
    return jnp.moveaxis(out, 1, 2), s


def ssd(x, b, c, dt, a, s0, *, bt=256, mode="fused_recurrent"):
    """Model layout x:(B,T,H,P), dt:(B,T,H) -> kernel layout and back.
    ``mode`` as in :func:`wkv`."""
    kern = ssd_chunk_kernel if mode == "chunk" else ssd_kernel
    if mode == "chunk":
        bt = min(bt, 64)
    xk = jnp.moveaxis(x, 1, 2).astype(jnp.float32)
    dtk = jnp.moveaxis(dt, 1, 2).astype(jnp.float32)
    y, s = kern(xk, b.astype(jnp.float32), c.astype(jnp.float32),
                dtk, a.astype(jnp.float32), s0.astype(jnp.float32),
                bt=bt, interpret=_interpret())
    return jnp.moveaxis(y, 1, 2), s
