"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def matmul(x: jax.Array, y: jax.Array) -> jax.Array:
    return jnp.dot(x.astype(jnp.float32), y.astype(jnp.float32))


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0):
    """q,k,v: (B, S, H, D) -> (B, S, H, D); plain softmax attention."""
    B, S, H, D = q.shape
    s = jnp.einsum("bqhe,bkhe->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    qpos = jnp.arange(S)[:, None]
    kpos = jnp.arange(k.shape[1])[None, :]
    mask = jnp.ones((S, k.shape[1]), bool)
    if causal:
        mask &= qpos >= kpos
    if window:
        mask &= qpos - kpos < window
    s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhe->bqhe", p, v.astype(jnp.float32))


def paged_attention_mq(q, k_pages, v_pages, block_tables, lengths):
    """Multi-query paged decode oracle (the speculative verify step's
    attention).  q: (B, S, KVH, G, HD); pages: (P, ps, KVH, HD) or
    lane-dense (P, ps, KVH * HD); block_tables: (B, MP) int32; lengths:
    (B,) int32 -> same shape as q.

    Gathers every sequence's pages dense and runs grouped-GQA softmax
    attention with the staircase mask: query ``s`` sees ``lengths + s``
    positions (the speculative block's own K/V rows are already written,
    each query attending causally up to and including its own row).
    """
    B, S, KVH, G, D = q.shape
    ps = k_pages.shape[1]
    k = k_pages[block_tables]                  # (B, MP, ps) + page row
    v = v_pages[block_tables]
    T = k.shape[1] * ps
    k = k.reshape(B, T, KVH, D)
    v = v.reshape(B, T, KVH, D)
    s = jnp.einsum("bshge,bkhe->bshgk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / math.sqrt(D)
    qpos = lengths[:, None] + jnp.arange(S)[None, :]       # (B, S)
    valid = jnp.arange(T)[None, None, :] < qpos[:, :, None]
    s = jnp.where(valid[:, :, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bshgk,bkhe->bshge", p, v.astype(jnp.float32))


def paged_attention(q, k_pages, v_pages, block_tables, lengths):
    """Paged decode oracle. q: (B, KVH, G, HD); pages: (P, ps, KVH, HD);
    block_tables: (B, MP) int32; lengths: (B,) int32 -> (B, KVH, G, HD).

    The S=1 specialisation of :func:`paged_attention_mq`: one query token
    per sequence over its first ``lengths`` positions.
    """
    return paged_attention_mq(q[:, None], k_pages, v_pages, block_tables,
                              lengths)[:, 0]


def wkv_linear_scan(r, k, v, w, u, s0):
    """RWKV6 WKV oracle. r,k,v,w: (B,T,H,N); u: (H,N); s0: (B,H,N,N)."""
    def step(s, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhj,bhji->bhi", rt, s + u[..., :, None] * kv)
        s = wt[..., :, None] * s + kv
        return s, out
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    s, outs = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(outs, 0, 1), s


def ssd_linear_scan(x, b, c, dt, a, s0):
    """Mamba2 SSD oracle. x: (B,T,H,P); b,c: (B,T,N); dt: (B,T,H); a: (H,)."""
    def step(s, inp):
        x_t, b_t, c_t, dt_t = inp
        decay = jnp.exp(dt_t * a)
        upd = (dt_t[..., None] * x_t)[..., :, None] * b_t[:, None, None, :]
        s = decay[..., None, None] * s + upd
        y = jnp.einsum("bhpn,bn->bhp", s, c_t)
        return s, y
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (x, b, c, dt))
    s, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1), s


def wkv_chunk(r, k, v, w, u, s0, chunk: int = 64):
    """Chunked parallel-scan WKV oracle — same recurrence as
    :func:`wkv_linear_scan`, reassociated into matmul form per chunk.

    Per chunk, with L the *inclusive* log-decay cumsum over local time:
    the state r_t reads excludes kv_t (the recurrence adds kv after the
    output), so the intra-chunk term is strictly causal and the ``u``
    bonus supplies the diagonal.  Every exponent that survives the causal
    mask is <= 0 (decay ratios of w in (0,1)), so the log-space form is
    numerically stable at any chunk size.
    """
    B, T, H, N = r.shape
    uf = u.astype(jnp.float32)
    s = s0.astype(jnp.float32)
    outs = []
    for lo in range(0, T, chunk):
        C = min(chunk, T - lo)
        rc, kc, vc, wc = (t[:, lo:lo + C].astype(jnp.float32)
                          for t in (r, k, v, w))
        lw = jnp.log(wc)                        # (B,C,H,N)
        linc = jnp.cumsum(lw, axis=1)           # decay through step t
        lexc = linc - lw                        # decay through step t-1
        # cross-chunk: r_t reads the entry state decayed by w_0..w_{t-1}
        out = jnp.einsum("bthj,bhji->bthi", rc * jnp.exp(lexc), s)
        # intra-chunk (strictly causal): kv_tau decays by w_{tau+1}..w_{t-1}
        tidx = jnp.arange(C)
        causal = tidx[:, None] > tidx[None, :]
        expnt = lexc[:, :, None] - linc[:, None]          # (B,C,C,H,N)
        expnt = jnp.where(causal[None, :, :, None, None], expnt, -jnp.inf)
        att = jnp.einsum("bthj,btshj,bshj->bths", rc, jnp.exp(expnt), kc)
        out = out + jnp.einsum("bths,bshi->bthi", att, vc)
        # diagonal bonus: out_t also reads u * kv_t
        dcoef = jnp.einsum("bthj,hj->bth", rc * kc, uf)
        out = out + dcoef[..., None] * vc
        # carry: S <- exp(L_C) * S + sum_tau exp(L_C - L_tau) k_tau v_tau^T
        wlast = linc[:, -1]                               # (B,H,N)
        kw = kc * jnp.exp(wlast[:, None] - linc)
        s = (jnp.exp(wlast)[..., :, None] * s
             + jnp.einsum("bthj,bthi->bhji", kw, vc))
        outs.append(out)
    return jnp.concatenate(outs, axis=1), s


def ssd_chunk(x, b, c, dt, a, s0, chunk: int = 64):
    """Chunked parallel-scan SSD oracle — same recurrence as
    :func:`ssd_linear_scan` in matmul form per chunk.  The output is read
    *after* the state update, so the intra-chunk mask includes the
    diagonal (tau <= t)."""
    B, T, H, P = x.shape
    s = s0.astype(jnp.float32)
    outs = []
    for lo in range(0, T, chunk):
        C = min(chunk, T - lo)
        xc = x[:, lo:lo + C].astype(jnp.float32)
        bc = b[:, lo:lo + C].astype(jnp.float32)
        cc = c[:, lo:lo + C].astype(jnp.float32)
        dtc = dt[:, lo:lo + C].astype(jnp.float32)
        la = dtc * a.astype(jnp.float32)[None, None, :]   # (B,C,H)
        linc = jnp.cumsum(la, axis=1)
        # cross-chunk: y_t reads the entry state decayed through step t
        y = jnp.exp(linc)[..., None] * jnp.einsum("bhpn,btn->bthp", s, cc)
        # intra-chunk (inclusive): upd_tau decays by la_{tau+1}..la_t
        tidx = jnp.arange(C)
        mask = tidx[:, None] >= tidx[None, :]
        cb = jnp.einsum("btn,bsn->bts", cc, bc)
        expnt = linc[:, :, None] - linc[:, None]          # (B,C,C,H)
        expnt = jnp.where(mask[None, :, :, None], expnt, -jnp.inf)
        M = cb[..., None] * jnp.exp(expnt) * dtc[:, None]
        y = y + jnp.einsum("btsh,bshp->bthp", M, xc)
        # carry: S <- exp(L_C) * S + sum_tau exp(L_C - L_tau) dt_tau x b^T
        wlast = linc[:, -1]                               # (B,H)
        wgt = jnp.exp(wlast[:, None] - linc) * dtc        # (B,C,H)
        s = (jnp.exp(wlast)[..., None, None] * s
             + jnp.einsum("bthp,btn,bth->bhpn", xc, bc, wgt))
        outs.append(y)
    return jnp.concatenate(outs, axis=1), s
