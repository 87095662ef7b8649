"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels import paged_attention as PA
from repro.kernels.tuned_matmul import tuned_matmul


@pytest.mark.parametrize("shape,blocks", [
    ((128, 128, 128), (64, 64, 64)),
    ((256, 512, 128), (128, 128, 128)),
    ((64, 384, 256), (32, 128, 128)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_tuned_matmul(shape, blocks, dtype, key):
    M, K, N = shape
    bm, bn, bk = blocks
    x = (jax.random.normal(key, (M, K)) * 0.5).astype(dtype)
    y = (jax.random.normal(jax.random.PRNGKey(7), (K, N)) * 0.5).astype(dtype)
    out = tuned_matmul(x, y, bm=bm, bn=bn, bk=bk, interpret=True)
    want = ref.matmul(x, y)
    tol = 5e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 10)


@pytest.mark.parametrize("S,D,bq,bk", [(128, 64, 64, 64), (256, 128, 128, 64),
                                       (128, 64, 32, 128)])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 32)])
def test_flash_attention(S, D, bq, bk, causal, window, key):
    B, H = 2, 2
    ks = jax.random.split(key, 3)
    q, k, v = [(jax.random.normal(kk, (B, S, H, D)) * 0.5).astype(jnp.float32)
               for kk in ks]
    out = ops.attention(q, k, v, causal=causal, window=window,
                        block_q=bq, block_k=bk)
    want = ref.flash_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# (kv heads, group, head width): MHA and GQA at 64- and 80-wide heads,
# which the pool lays out lane-dense, and at 128-wide heads, which keep a
# row per kv head
HEADS = [pytest.param(kvh, g, d, id=f"{kind}{d}")
         for d in (64, 80, 128)
         for kind, kvh, g in (("mha", 4, 1), ("gqa", 2, 3))]
NARROW = [h for h in HEADS if h.values[2] % 128]
# the head sets that run every combination of a case list; the others run
# a few of them, which keeps the interpreted file's time in bounds
FULL = ("gqa128", "mha80")


def _cases(every, few):
    """Each combination of ``every`` on the FULL head sets, and those in
    ``few`` on every head set too: pytest params of combination + heads."""
    return [pytest.param(*c, *h.values,
                         id="-".join(map(str, c)) + f"-{h.id}")
            for c in every for h in HEADS if c in few or h.id in FULL]


def _pages(key, n_pages, ps, kvh, d, dtype=jnp.float32):
    """K and V page pools in the layout the engine gives these heads."""
    shape = (n_pages, ps) + PA.page_row(kvh, d)
    kk, kv = jax.random.split(key)
    if dtype == jnp.float32:
        return [jax.random.normal(k, shape) * 0.5 for k in (kk, kv)]
    return [jax.random.normal(k, shape, dtype) for k in (kk, kv)]


@pytest.mark.parametrize("ps,MP,bk,KVH,G,D", _cases(
    [(8, 4, 0), (8, 4, 8), (16, 3, 16), (16, 3, 32)],
    [(8, 4, 0), (16, 3, 32)]))
def test_paged_attention(ps, MP, bk, KVH, G, D, key):
    """Paged decode kernel vs the dense-gather oracle: random non-aliasing
    block tables, mixed lengths (page-aligned, ragged, and zero-length
    inactive rows are garbage by contract and skipped)."""
    B = 3
    P = 1 + B * MP                        # page 0 is the null sink
    ks = jax.random.split(key, 2)
    q = (jax.random.normal(ks[0], (B, KVH, G, D)) * 0.5).astype(jnp.float32)
    kp, vp = _pages(ks[1], P, ps, KVH, D)
    rng = np.random.default_rng(0)
    perm = rng.permutation(np.arange(1, P))
    bt = np.zeros((B, MP), np.int32)
    lengths = np.array([ps * MP, ps + 3, 0], np.int32)[:B]
    used = 0
    for b in range(B):
        n = -(-int(lengths[b]) // ps)
        bt[b, :n] = perm[used:used + n]
        used += n
    bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    out = ops.paged_attention(q, kp, vp, bt, lengths, block_k=bk)
    want = ref.paged_attention(q, kp, vp, bt, lengths)
    act = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(want)[act],
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ps,MP,S,bk,KVH,G,D", _cases(
    [(8, 4, 3, 0), (8, 4, 5, 8), (16, 3, 2, 16), (16, 3, 4, 48)],
    [(8, 4, 3, 0), (16, 3, 4, 48)]))
def test_paged_attention_multiquery(ps, MP, S, bk, KVH, G, D, key):
    """Multi-query (speculative verify) paged kernel vs the dense-gather
    oracle: S queries per row share the K/V DMA under the staircase mask
    (query s sees lengths + s positions).  Random non-aliasing block
    tables, ragged lengths, one zero-length inactive row (garbage by
    contract, skipped)."""
    B = 3
    P = 1 + B * MP                        # page 0 is the null sink
    ks = jax.random.split(key, 2)
    q = (jax.random.normal(ks[0], (B, S, KVH, G, D)) * 0.5).astype(jnp.float32)
    kp, vp = _pages(ks[1], P, ps, KVH, D)
    rng = np.random.default_rng(1)
    perm = rng.permutation(np.arange(1, P))
    bt = np.zeros((B, MP), np.int32)
    # query S-1 must stay within the block table: length + S - 1 <= MP*ps
    lengths = np.array([ps * MP - (S - 1), ps + 2, 0], np.int32)[:B]
    used = 0
    for b in range(B):
        n = -(-int(lengths[b] + S - 1) // ps) if lengths[b] else 0
        bt[b, :n] = perm[used:used + n]
        used += n
    bt, lengths = jnp.asarray(bt), jnp.asarray(lengths)
    out = ops.paged_attention_mq(q, kp, vp, bt, lengths, block_k=bk)
    want = ref.paged_attention_mq(q, kp, vp, bt, lengths)
    act = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(want)[act],
                               rtol=1e-5, atol=1e-5)


def _scattered_tables(rng, lengths, S, ps, MP, P):
    """Block tables over shuffled, non-contiguous page ids: every slot's
    live pages (what its last query sees) drawn from a permutation of the
    pool with every other id skipped."""
    ids = rng.permutation(np.arange(1, P, 2))
    bt = np.zeros((len(lengths), MP), np.int32)
    used = 0
    for b, n in enumerate(PA.live_pages(np.asarray(lengths), S, ps)):
        bt[b, :n] = ids[used:used + n]
        used += n
    return bt


@pytest.mark.parametrize("S,mult,KVH,G,D", _cases(
    [(1, 1), (1, 4), (1, 16), (3, 1), (3, 4), (3, 16)],
    [(1, 4), (3, 1), (3, 16)]))
def test_paged_attention_live_pages(S, mult, KVH, G, D, key):
    """Blocks of several pages over live pages only: lengths 0, 1,
    page_size - 1, page_size, a block boundary -1/0/+1 and max_len - S in
    one batch, page ids shuffled and not contiguous, block_k of 1, 4 and
    16 pages (16 pages: the last block is only partly live).  Slots of
    length 0 lead, sit between live ones and trail, so the next block's
    copies skip dead slots in every position."""
    ps, MP = 8, 20
    bk = mult * ps
    lengths = np.array([0, 1, ps - 1, 0, 0, ps, bk - 1, bk, bk + 1,
                        MP * ps - S, 0], np.int32)
    B = len(lengths)
    P = 2 * B * MP + 2
    ks = jax.random.split(key, 2)
    q = (jax.random.normal(ks[0], (B, S, KVH, G, D)) * 0.5).astype(jnp.float32)
    kp, vp = _pages(ks[1], P, ps, KVH, D)
    bt = jnp.asarray(_scattered_tables(np.random.default_rng(mult), lengths,
                                       S, ps, MP, P))
    out = ops.paged_attention_mq(q, kp, vp, bt, jnp.asarray(lengths),
                                 block_k=bk)
    want = ref.paged_attention_mq(q, kp, vp, bt, jnp.asarray(lengths))
    act = lengths > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(want)[act],
                               rtol=1e-5, atol=1e-5)
    # a slot of length 0 computes nothing and writes a finite zero
    assert np.all(np.asarray(out)[~act] == 0)


@pytest.mark.parametrize("KVH,G,D", NARROW)
@pytest.mark.parametrize("S", [1, 3])
def test_paged_attention_narrow_heads_walk_pages(S, KVH, G, D, key):
    """Heads narrower than a lane tile, on the pool's lane-dense rows: the
    same kernel walks the same lengths mix over scattered pages, two pages
    a block, nothing past a slot's live pages."""
    ps, MP = 8, 6
    assert PA.page_row(KVH, D) == (KVH * D,)
    lengths = np.array([0, 1, ps - 1, 0, ps, ps + 1, MP * ps - S], np.int32)
    B = len(lengths)
    P = 2 * B * MP + 2
    ks = jax.random.split(key, 2)
    q = (jax.random.normal(ks[0], (B, S, KVH, G, D)) * 0.5).astype(jnp.float32)
    kp, vp = _pages(ks[1], P, ps, KVH, D)
    bt = jnp.asarray(_scattered_tables(np.random.default_rng(S), lengths,
                                       S, ps, MP, P))
    out = ops.paged_attention_mq(q, kp, vp, bt, jnp.asarray(lengths),
                                 block_k=2 * ps)
    want = ref.paged_attention_mq(q, kp, vp, bt, jnp.asarray(lengths))
    act = lengths > 0
    np.testing.assert_allclose(np.asarray(out)[act], np.asarray(want)[act],
                               rtol=1e-5, atol=1e-5)
    assert np.all(np.asarray(out)[~act] == 0)


@pytest.mark.parametrize("KVH,G,D", HEADS)
def test_paged_attention_bf16_pool_matches_f32_oracle(KVH, G, D, key):
    """The pool's dtype: bf16 K/V enter the MXU as stored, the softmax
    stays f32 — within bf16 rounding of the f32 oracle."""
    ps, MP, S = 16, 8, 1
    lengths = np.array([5, 16, 100, MP * ps - S], np.int32)
    P = 2 * len(lengths) * MP + 2
    ks = jax.random.split(key, 2)
    q = jax.random.normal(ks[0], (4, S, KVH, G, D), jnp.bfloat16)
    kp, vp = _pages(ks[1], P, ps, KVH, D, jnp.bfloat16)
    bt = jnp.asarray(_scattered_tables(np.random.default_rng(3), lengths,
                                       S, ps, MP, P))
    out = ops.paged_attention_mq(q, kp, vp, bt, jnp.asarray(lengths),
                                 block_k=4 * ps)
    want = ref.paged_attention_mq(q, kp, vp, bt, jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_paged_attention_block_k_must_cover_whole_pages(key):
    """block_k counts tokens of whole pages: one that splits a page is
    refused rather than silently rounded."""
    q = jnp.zeros((1, 1, 1, 1, 8), jnp.float32)
    pages = jnp.zeros((3, 8) + PA.page_row(1, 8), jnp.float32)
    bt = jnp.zeros((1, 2), jnp.int32)
    with pytest.raises(ValueError, match="multiple of page_size"):
        ops.paged_attention_mq(q, pages, pages, bt, jnp.ones((1,), jnp.int32),
                               block_k=4)


def test_paged_attention_pages_must_match_the_pool_layout():
    """Pages whose rows are not the layout the pool gives these heads are
    refused: a per-head page of narrow heads, a lane-dense page of
    whole-tile heads."""
    bt = jnp.zeros((1, 2), jnp.int32)
    for d, row in ((64, (2, 64)), (128, (256,))):
        q = jnp.zeros((1, 1, 2, 1, d), jnp.float32)
        pages = jnp.zeros((3, 8) + row, jnp.float32)
        with pytest.raises(ValueError, match="layout"):
            ops.paged_attention_mq(q, pages, pages, bt,
                                   jnp.ones((1,), jnp.int32))


def test_paged_attention_compiled_refuses_rows_off_lane_tiles():
    """Compiled, a page row that is not whole 128-lane tiles (2 heads of
    16, 4 of 80) cannot be copied out of the pool by Mosaic: it is refused
    before Mosaic sees it; 2 heads of 64 pass that check."""
    bt = jnp.ones((1, 2), jnp.int32)
    for kvh, d in ((2, 16), (4, 80)):
        assert not PA.compiles_row(kvh, d)
        q = jnp.zeros((1, 1, kvh, 1, d), jnp.bfloat16)
        pages = jnp.zeros((3, 8) + PA.page_row(kvh, d), jnp.bfloat16)
        with pytest.raises(ValueError, match="128-lane"):
            PA.paged_attention_mq(q, pages, pages, bt,
                                  jnp.ones((1,), jnp.int32), interpret=False)
    assert PA.compiles_row(2, 64) and PA.compiles_row(32, 80)


@pytest.mark.parametrize("KVH,G,D", HEADS)
def test_paged_attention_mq_reduces_to_single_query(KVH, G, D, key):
    """The S=1 multi-query kernel is exactly the single-query kernel."""
    B, ps, MP = 2, 8, 3
    P = 1 + B * MP
    ks = jax.random.split(key, 2)
    q = (jax.random.normal(ks[0], (B, KVH, G, D)) * 0.5).astype(jnp.float32)
    kp, vp = _pages(ks[1], P, ps, KVH, D)
    bt = jnp.asarray(np.arange(1, 1 + B * MP).reshape(B, MP), jnp.int32)
    lengths = jnp.asarray([ps * MP, 5], jnp.int32)
    single = ops.paged_attention(q, kp, vp, bt, lengths)
    multi = ops.paged_attention_mq(q[:, None], kp, vp, bt, lengths)[:, 0]
    np.testing.assert_allclose(np.asarray(single), np.asarray(multi),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("T,N,bt", [(64, 16, 32), (128, 32, 128), (96, 8, 32)])
def test_wkv_kernel(T, N, bt, key):
    B, H = 2, 3
    ks = jax.random.split(key, 5)
    r, k, v = [jax.random.normal(kk, (B, T, H, N)) * 0.3 for kk in ks[:3]]
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, N))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, N)) * 0.1
    s0 = jnp.zeros((B, H, N, N))
    from repro.kernels.linear_scan import wkv_kernel
    tr = lambda t: jnp.moveaxis(t, 1, 2)
    out, s = wkv_kernel(tr(r), tr(k), tr(v), tr(w), u, s0, bt=bt,
                        interpret=True)
    want, s_want = ref.wkv_linear_scan(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(jnp.moveaxis(out, 1, 2)),
                               np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,P,N,bt", [(64, 8, 16, 32), (128, 16, 16, 64)])
def test_ssd_kernel(T, P, N, bt, key):
    B, H = 2, 3
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, T, H, P)) * 0.3
    b = jax.random.normal(ks[1], (B, T, N)) * 0.3
    c = jax.random.normal(ks[2], (B, T, N)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    a = -jnp.exp(jax.random.normal(ks[4], (H,)) * 0.3)
    s0 = jnp.zeros((B, H, P, N))
    y, s = ops.ssd(x, b, c, dt, a, s0, bt=bt)
    want, s_want = ref.ssd_linear_scan(x, b, c, dt, a, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want),
                               rtol=1e-5, atol=1e-5)


def test_wkv_kernel_chunked_state_passing(key):
    """Multiple time tiles must thread state exactly (tile boundaries)."""
    B, T, H, N = 1, 64, 1, 8
    ks = jax.random.split(key, 4)
    r, k, v = [jax.random.normal(kk, (B, T, H, N)) * 0.3 for kk in ks[:3]]
    w = jnp.full((B, T, H, N), 0.9)
    u = jnp.zeros((H, N))
    s0 = jax.random.normal(ks[3], (B, H, N, N)) * 0.1
    out8, _ = ops.wkv(r, k, v, w, u, s0, bt=8)
    out64, _ = ops.wkv(r, k, v, w, u, s0, bt=64)
    np.testing.assert_allclose(np.asarray(out8), np.asarray(out64),
                               rtol=1e-6, atol=1e-6)
