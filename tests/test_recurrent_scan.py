"""Property tests (hypothesis) on the dual-mode recurrent-scan contracts.

Two invariants the serving engine leans on:

* **chunk vs fused equivalence at arbitrary boundaries** — the matmul-form
  chunked scans (``wkv_chunked`` / ``ssd_chunked``) must agree with the
  exact sequential recurrences for any (T, chunk) pair.  When the chunk
  does not divide T the kernels fall back to the fused scan by contract,
  so the outputs are *bitwise* equal; on the chunked path they agree up
  to f32 reassociation (tight tolerance — this is what keeps greedy
  decode token-identical across ``scan_mode``).
* **snapshot/restore rollback** — speculative decode on a recurrence has
  no length-truncation rollback (rejected drafts are already folded into
  the state), so the engine snapshots before the verify step and splices
  the snapshot back on rejection.  Restoring and re-advancing only the
  accepted tokens must be bitwise identical to a run that never drafted.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

import hypothesis.strategies as st
from hypothesis import given, settings

from repro.models.mamba2 import ssd_chunked, ssd_scan
from repro.models.rwkv6 import wkv_chunked, wkv_scan
from repro.serve.cache import SlotKVPool


def _wkv_inputs(seed, B, T, H, N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    r, k, v = [jax.random.normal(kk, (B, T, H, N)) * 0.3 for kk in ks[:3]]
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    s0 = jax.random.normal(ks[5], (B, H, N, N)) * 0.1
    return r, k, v, w, u, s0


def _ssd_inputs(seed, B, T, H, P, N):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.3
    b = jax.random.normal(ks[1], (B, T, N)) * 0.3
    c = jax.random.normal(ks[2], (B, T, N)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    s0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.1
    return x, b, c, dt, a, s0


def _assert_same(got, want, exact, tol=1e-5):
    g, w = np.asarray(got), np.asarray(want)
    if exact:
        np.testing.assert_array_equal(g, w)
    else:
        np.testing.assert_allclose(g, w, rtol=tol, atol=tol)


@given(T=st.integers(1, 64), C=st.integers(1, 64),
       seed=st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_wkv_chunk_vs_fused_arbitrary_boundaries(T, C, seed):
    r, k, v, w, u, s0 = _wkv_inputs(seed, 1, T, 2, 8)
    out_f, s_f = wkv_scan(r, k, v, w, u, s0)
    out_c, s_c = wkv_chunked(r, k, v, w, u, s0, C)
    ragged = T % min(C, T) != 0          # fallback contract: exact fused
    _assert_same(out_c, out_f, exact=ragged)
    _assert_same(s_c, s_f, exact=ragged)


@given(T=st.integers(1, 64), C=st.integers(1, 64),
       seed=st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_ssd_chunk_vs_fused_arbitrary_boundaries(T, C, seed):
    x, b, c, dt, a, s0 = _ssd_inputs(seed, 1, T, 2, 8, 8)
    y_f, s_f = ssd_scan(x, b, c, dt, a, s0)
    y_c, s_c = ssd_chunked(x, b, c, dt, a, s0, C, precise=True)
    ragged = T % min(C, T) != 0          # fallback contract: exact fused
    _assert_same(y_c, y_f, exact=ragged)
    _assert_same(s_c, s_f, exact=ragged)


@given(Tp=st.integers(1, 32), D=st.integers(1, 4), A=st.integers(0, 4),
       seed=st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_wkv_snapshot_restore_rollback(Tp, D, A, seed):
    """Snapshot -> draft D tokens -> restore -> re-advance A accepted
    tokens == an uninterrupted scan over Tp + A tokens (bitwise)."""
    A = min(A, D)
    r, k, v, w, u, s0 = _wkv_inputs(seed, 1, Tp + D, 2, 8)
    sl = lambda t, lo, hi: t[:, lo:hi]
    _, snap = wkv_scan(*(sl(t, 0, Tp) for t in (r, k, v, w)), u, s0)
    # draft advance: folds the (to-be-rejected) tokens into the state
    _, s_draft = wkv_scan(*(sl(t, Tp, Tp + D) for t in (r, k, v, w)), u, snap)
    # restore + re-advance only the accepted prefix of the draft
    s_roll = snap if A == 0 else wkv_scan(
        *(sl(t, Tp, Tp + A) for t in (r, k, v, w)), u, snap)[1]
    _, s_want = wkv_scan(*(sl(t, 0, Tp + A) for t in (r, k, v, w)), u, s0)
    np.testing.assert_array_equal(np.asarray(s_roll), np.asarray(s_want))


@given(Tp=st.integers(1, 32), D=st.integers(1, 4), A=st.integers(0, 4),
       seed=st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_ssd_snapshot_restore_rollback(Tp, D, A, seed):
    A = min(A, D)
    x, b, c, dt, a, s0 = _ssd_inputs(seed, 1, Tp + D, 2, 8, 8)
    sl = lambda t, lo, hi: t[:, lo:hi]
    _, snap = ssd_scan(*(sl(t, 0, Tp) for t in (x, b, c, dt)), a, s0)
    _, s_draft = ssd_scan(*(sl(t, Tp, Tp + D) for t in (x, b, c, dt)), a, snap)
    s_roll = snap if A == 0 else ssd_scan(
        *(sl(t, Tp, Tp + A) for t in (x, b, c, dt)), a, snap)[1]
    _, s_want = ssd_scan(*(sl(t, 0, Tp + A) for t in (x, b, c, dt)), a, s0)
    np.testing.assert_array_equal(np.asarray(s_roll), np.asarray(s_want))


def _rand_cache(avals, key):
    leaves, treedef = jax.tree.flatten(avals)
    ks = jax.random.split(key, len(leaves))
    vals = [jax.random.randint(kk, l.shape, 0, 100, dtype=l.dtype)
            if jnp.issubdtype(l.dtype, jnp.integer)
            else jax.random.normal(kk, l.shape, l.dtype)
            for kk, l in zip(ks, leaves)]
    return jax.tree.unflatten(treedef, vals)


@given(n_slots=st.integers(1, 4), seed=st.integers(0, 2**16))
@settings(max_examples=20, deadline=None)
def test_slot_pool_snapshot_restore_bitwise(n_slots, seed):
    """The pool-level contract: state after a rejected draft is exactly
    the state before the draft, and restoring one slot never perturbs a
    neighbour (snapshots survive the pool's donating writes)."""
    avals = {"s": jax.ShapeDtypeStruct((1, 2, 4, 4), jnp.float32),
             "x_prev": jax.ShapeDtypeStruct((1, 8), jnp.float32),
             "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    pool = SlotKVPool(avals, n_slots)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    slot = pool.alloc()
    before = _rand_cache(avals, ks[0])
    pool.write(slot, before)
    snap = pool.snapshot(slot)
    other, held = (pool.alloc(), _rand_cache(avals, ks[2])) if n_slots > 1 \
        else (None, None)
    if other is not None:
        pool.write(other, held)
    pool.write(slot, _rand_cache(avals, ks[1]))     # the draft advance
    pool.restore(slot, snap)
    got = pool.read(slot)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(before)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    if other is not None:
        for g, w in zip(jax.tree.leaves(pool.read(other)),
                        jax.tree.leaves(held)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_launcher_rejects_recurrent_knobs_on_attention_families():
    """Explicit serve knobs must route or reject, never silently drop:
    --scan-mode / --prefill-chunk / --spec-depth on a slot-pool attention
    family (no recurrent state to chunk or snapshot) exit with a clear
    argparse error instead of serving with the flag ignored."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    base = [sys.executable, "-m", "repro.launch.serve", "--arch",
            "stablelm-1.6b", "--reduced", "--requests", "1",
            "--prompt-len", "8", "--gen-min", "2", "--gen-max", "2"]
    for extra, msg in [
            (["--scan-mode", "chunk"], "only the recurrent"),
            (["--paged", "off", "--prefill-chunk", "8"],
             "requires a recurrent family"),
            (["--paged", "off", "--spec-depth", "2"],
             "recurrent-state")]:
        r = subprocess.run(base + extra, cwd="/root/repo", env=env,
                           capture_output=True, text=True, timeout=600)
        assert r.returncode == 2, (extra, r.stderr[-800:])
        assert msg in r.stderr, (extra, r.stderr[-800:])
