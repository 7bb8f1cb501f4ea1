"""The width-1 paged decode kernel (tpunet/ops/paged_decode.py) against
the dense gather path of ``Attention._paged_decode_attend`` on one pool.

CPU, ``interpret=True`` (the kernel's own body, the scheme of
test_attention.py's flash tests), tiny shapes: an odd head count with
``head_dim`` 64 (columns padded to the lane tile, two heads to a tile),
ragged live lengths around every boundary the kernel has (a page, a
chunk of pages, the full table), inactive rows, and garbage — NaN — in
every pool row a live length does not cover. The dispatch is by shape
and by what the pool is, never by an option: the last tests pin it.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpunet.models.vit import Attention, PagedKV
from tpunet.ops import paged_decode
from tpunet.ops.paged_decode import (kernel_applies,
                                     paged_decode_attention, pool_width)

PT = 16                      # page_tokens: a bfloat16 sublane tile
PPS = 12                     # pages per slot: 192 keys, 1.5 chunks
CHUNK = paged_decode._CHUNK_TOKENS   # 128 keys: 8 pages
# 1, a page boundary +- 1, a chunk boundary +- 1, the full table
LENGTHS = (1, PT - 1, PT, PT + 1, CHUNK - 1, CHUNK, CHUNK + 1, PPS * PT)
GEOMETRIES = {"h5xd64": (5, 64),     # odd heads, 320 -> 384 columns
              "h2xd64": (2, 64),     # exactly one lane tile
              "h3xd32": (3, 32)}     # four heads' room, three heads
TOL = {"float32": 2e-6, "bfloat16": 2e-2}


def make_pool(rng, heads, head_dim, dtype, slots, garbage=0.0):
    """(k_pool, v_pool, page_table): every slot owns PPS shuffled pages;
    page 0 (the garbage page) and the padding columns are ``garbage`` /
    zeros as a live pool has them."""
    pages = slots * PPS + 1
    w, hd = pool_width(heads, head_dim), heads * head_dim
    pools = []
    for _ in range(2):
        pool = np.zeros((pages * PT, w), np.float32)
        pool[:, :hd] = rng.normal(size=(pages * PT, hd))
        pool[:PT, :hd] = garbage
        pools.append(pool)
    table = rng.permutation(np.arange(1, pages)).reshape(
        slots, PPS).astype(np.int32)
    return (jnp.asarray(pools[0], dtype), jnp.asarray(pools[1], dtype),
            table)


def dense_reference(q, k_pool, v_pool, table, lengths, kv_heads=None,
                    window=None):
    """What the dense path computes, in float64 on the host; grouped
    (``kv_heads`` < the query heads), query head h reads KV head
    ``h // group``; with ``window`` a row's last ``window`` keys."""
    b, q_heads, head_dim = q.shape
    heads = q_heads if kv_heads is None else kv_heads
    hd = heads * head_dim
    q = np.asarray(q, np.float64)
    out = np.zeros(q.shape, np.float64)
    for i in range(b):
        n = int(lengths[i])
        if n == 0:
            continue
        rows = (table[i][:, None] * PT + np.arange(PT)).reshape(-1)[
            max(0, n - window) if window else 0:n]
        k = np.asarray(k_pool, np.float64)[rows, :hd].reshape(
            len(rows), heads, head_dim)
        v = np.asarray(v_pool, np.float64)[rows, :hd].reshape(
            len(rows), heads, head_dim)
        k, v = (np.repeat(x, q_heads // heads, axis=1) for x in (k, v))
        s = np.einsum("hd,khd->hk", q[i], k) * head_dim ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hk,khd->hd", p, v)
    return out


def run_kernel(q, k_pool, v_pool, table, lengths, **kw):
    return np.asarray(paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32), page_tokens=PT,
        interpret=True, **kw), np.float64)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("q_heads,kv_heads,head_dim",
                         [(16, 2, 256), (6, 3, 128), (4, 1, 128)])
def test_grouped_heads_match_dense(q_heads, kv_heads, head_dim, dtype):
    """Fewer KV heads than query heads over one pool row: the ragged
    lengths of the ungrouped test, inactive rows between them, NaN in
    the garbage page."""
    rng = np.random.default_rng(q_heads)
    lengths = np.array((0,) + LENGTHS + (0,), np.int32)
    k_pool, v_pool, table = make_pool(rng, kv_heads, head_dim, dtype,
                                      len(lengths), garbage=np.nan)
    q = jnp.asarray(rng.normal(size=(len(lengths), q_heads, head_dim)),
                    dtype)
    got = run_kernel(q, k_pool, v_pool, table, lengths, kv_heads=kv_heads)
    want = dense_reference(q, k_pool, v_pool, table, lengths, kv_heads)
    assert got.shape == (len(lengths), q_heads, head_dim)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)
    assert not got[[0, -1]].any()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("q_heads,kv_heads,head_dim,window",
                         [(32, 2, 128, 40),      # group 16, 2.5 pages
                          (32, 2, 128, CHUNK),   # exactly one chunk
                          (5, 5, 64, PT + 3),    # ungrouped, odd heads
                          (4, 1, 128, 1)])       # the token itself
def test_window_starts_at_its_chunk_and_masks_before_it(
        q_heads, kv_heads, head_dim, window, dtype):
    """A sliding layer: lengths below, at and past the window, across a
    page and a chunk edge. Every pool row that lies in a chunk BEFORE
    the one holding key ``length - window`` is NaN: the work list must
    start past it. (The rows of that first chunk before the window are
    fetched and masked, so they hold numbers, as a live pool's do.)"""
    rng = np.random.default_rng(window)
    lengths = np.array((0,) + tuple(sorted(
        {1, window - 1 or 1, window, window + 1, CHUNK - 1, CHUNK,
         CHUNK + 1, min(CHUNK + window, PPS * PT - 1), PPS * PT}))
        + (0,), np.int32)
    k_pool, v_pool, table = make_pool(rng, kv_heads, head_dim, dtype,
                                      len(lengths), garbage=np.nan)
    k_pool, v_pool = np.array(k_pool, np.float32), np.array(v_pool,
                                                             np.float32)
    for i, n in enumerate(lengths):
        dead = max(0, int(n) - window) // CHUNK * CHUNK   # whole chunks
        rows = (table[i][:, None] * PT + np.arange(PT)).reshape(-1)[:dead]
        k_pool[rows], v_pool[rows] = np.nan, np.nan
    k_pool, v_pool = jnp.asarray(k_pool, dtype), jnp.asarray(v_pool, dtype)
    q = jnp.asarray(rng.normal(size=(len(lengths), q_heads, head_dim)),
                    dtype)
    got = run_kernel(q, k_pool, v_pool, table, lengths, kv_heads=kv_heads,
                     window=window)
    want = dense_reference(q, k_pool, v_pool, table, lengths, kv_heads,
                           window)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)
    assert not got[[0, -1]].any()


def test_grouped_heads_need_whole_groups_and_lane_wide_heads():
    rng = np.random.default_rng(0)
    k_pool, v_pool, table = make_pool(rng, 2, 64, "float32", 1)
    q = jnp.zeros((1, 4, 64), jnp.float32)
    with pytest.raises(ValueError, match="lanes"):
        run_kernel(q, k_pool, v_pool, table, [4], kv_heads=2)
    with pytest.raises(ValueError, match="whole"):
        run_kernel(jnp.zeros((1, 5, 64)), k_pool, v_pool, table, [4],
                   kv_heads=2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry", list(GEOMETRIES))
def test_ragged_lengths_match_dense(geometry, dtype):
    heads, head_dim = GEOMETRIES[geometry]
    rng = np.random.default_rng(7)
    k_pool, v_pool, table = make_pool(rng, heads, head_dim, dtype,
                                      len(LENGTHS))
    q = jnp.asarray(rng.normal(size=(len(LENGTHS), heads, head_dim)),
                    dtype)
    got = run_kernel(q, k_pool, v_pool, table, LENGTHS)
    want = dense_reference(q, k_pool, v_pool, table, LENGTHS)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_inactive_rows_read_nothing_and_return_zeros(dtype):
    """Length 0 = an inactive slot: its table row points at pages of
    NaN, its output is exactly zero, and the live rows between and
    after the inactive ones are what they are without them."""
    heads, head_dim = GEOMETRIES["h5xd64"]
    rng = np.random.default_rng(11)
    lengths = np.array([0, 40, 0, 0, 130, 0], np.int32)
    k_pool, v_pool, table = make_pool(rng, heads, head_dim, dtype,
                                      len(lengths))
    poison = np.ones(k_pool.shape[0], bool)
    for i, n in enumerate(lengths):
        if n:
            poison[(table[i][:, None] * PT
                    + np.arange(PT)).reshape(-1)] = False
    k_pool = jnp.where(poison[:, None], jnp.nan, k_pool)
    v_pool = jnp.where(poison[:, None], jnp.nan, v_pool)
    q = jnp.asarray(rng.normal(size=(len(lengths), heads, head_dim)),
                    dtype)
    got = run_kernel(q, k_pool, v_pool, table, lengths)
    assert np.all(got[lengths == 0] == 0.0)
    want = dense_reference(q, k_pool, v_pool, table, lengths)
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("length", [1, PT + 1, CHUNK - 1, CHUNK + 1])
def test_garbage_past_the_live_length_never_reaches_the_output(
        length, dtype):
    """NaN in the garbage page, in the stale rows of the row's own last
    page, in its allocated-but-unwritten pages and in table entries it
    has not reached (page 0): the output is that of a clean pool."""
    heads, head_dim = GEOMETRIES["h5xd64"]
    rng = np.random.default_rng(13)
    k_pool, v_pool, table = make_pool(rng, heads, head_dim, dtype, 2)
    lengths = np.array([length, PPS * PT], np.int32)
    q = jnp.asarray(rng.normal(size=(2, heads, head_dim)), dtype)
    want = dense_reference(q, k_pool, v_pool, table, lengths)
    rows = (table[0][:, None] * PT + np.arange(PT)).reshape(-1)
    stale = np.zeros(k_pool.shape[0], bool)
    stale[rows[length:]] = True
    stale[:PT] = True                         # the garbage page
    table = table.copy()
    table[0, -(-length // PT) + 1:] = 0       # not allocated yet
    k_bad = jnp.where(stale[:, None], jnp.nan, k_pool)
    v_bad = jnp.where(stale[:, None], jnp.nan, v_pool)
    got = run_kernel(q, k_bad, v_bad, table, lengths)
    assert np.all(np.isfinite(got))
    np.testing.assert_allclose(got, want, atol=TOL[dtype], rtol=0)


def test_bfloat16_probabilities_are_not_rounded_to_the_pool_dtype():
    """Against a bfloat16 pool the probabilities enter the second
    product as a bfloat16 head plus a bfloat16 remainder: the result is
    the float32-probability result, not the one 8 bits of p give."""
    heads, head_dim = GEOMETRIES["h2xd64"]
    rng = np.random.default_rng(17)
    k_pool, v_pool, table = make_pool(rng, heads, head_dim, "bfloat16", 1)
    q = jnp.asarray(rng.normal(size=(1, heads, head_dim)), "bfloat16")
    lengths = [PPS * PT]
    want = dense_reference(q, k_pool, v_pool, table, lengths)
    got = np.asarray(paged_decode_attention(
        q.astype(jnp.float32), k_pool, v_pool, jnp.asarray(table),
        jnp.asarray(lengths, jnp.int32), page_tokens=PT,
        interpret=True), np.float64)
    # float32 q and output: what is left is the rounding of p alone
    # (measured: 1e-6 as it is, 6e-4 with p rounded to bfloat16)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ---------------------------------------------------------------------------
# the dispatch inside Attention._paged_decode_attend
# ---------------------------------------------------------------------------

HEADS, HIDDEN, SLOTS = 5, 320, 3


def attend(monkeypatch, *, width, kv_dtype="auto", kernel_backend,
           mesh_sharded=False, dtype=jnp.float32):
    """One decode call of ``Attention`` at token width ``width`` over a
    pool that already holds 20 / 33 / 0 keys per row (row 2 inactive),
    with the dispatch seeing a TPU backend or not. Returns (y, pool
    leaves, how often the kernel was entered)."""
    calls = []
    real = paged_decode.paged_decode_attention

    def spy(*args, **kw):
        calls.append(args[0].shape)
        return real(*args, **kw)

    monkeypatch.setattr(paged_decode, "_on_tpu", lambda: kernel_backend)
    monkeypatch.setattr(paged_decode, "paged_decode_attention", spy)
    paged = PagedKV(pages=SLOTS * 4 + 1, page_tokens=PT, dtype=kv_dtype,
                    mesh_sharded=mesh_sharded)
    attn = Attention(HEADS, dtype=dtype, param_dtype=jnp.float32)
    rng = np.random.default_rng(5)
    table = jnp.asarray(np.arange(1, SLOTS * 4 + 1).reshape(SLOTS, 4),
                        jnp.int32)
    x0 = jnp.asarray(rng.normal(size=(SLOTS, 40, HIDDEN)), dtype)
    variables = attn.init(jax.random.PRNGKey(0), x0, decode=True,
                          paged_kv=paged, page_table=table)
    params = variables["params"]
    # fill the pool with a dense-path prefill of 33 positions per row
    _, mut = attn.apply(
        {"params": params, "cache": variables["cache"]}, x0[:, :33],
        decode=True, positions=jnp.zeros((SLOTS,), jnp.int32),
        active=jnp.ones((SLOTS,), bool), paged_kv=dataclasses.replace(
            paged, mesh_sharded=True), page_table=table,
        mutable=["cache"])
    assert not calls
    positions = jnp.asarray([20, 33, 7], jnp.int32)
    active = jnp.asarray([True, True, False])
    x = jnp.asarray(rng.normal(size=(SLOTS, width, HIDDEN)), dtype)
    y, mut = attn.apply({"params": params, "cache": mut["cache"]}, x,
                        decode=True, positions=positions, active=active,
                        paged_kv=paged, page_table=table,
                        mutable=["cache"])
    return (np.asarray(y, np.float32),
            jax.tree_util.tree_leaves(mut["cache"]), len(calls))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_width_one_step_kernel_and_dense_agree_on_one_pool(monkeypatch,
                                                           dtype):
    """The same decode step through both paths: the live rows' outputs
    agree, the new rows land in the same pool rows (the scatter is
    shared), and the kernel path was entered exactly once."""
    y_k, pool_k, n_k = attend(monkeypatch, width=1, kernel_backend=True,
                              dtype=jnp.dtype(dtype))
    y_d, pool_d, n_d = attend(monkeypatch, width=1, kernel_backend=False,
                              dtype=jnp.dtype(dtype))
    assert (n_k, n_d) == (1, 0)
    np.testing.assert_allclose(y_k[:2], y_d[:2], atol=TOL[dtype] * 4,
                               rtol=0)
    for a, b in zip(pool_k, pool_d):
        assert a.shape == (13 * PT, pool_width(HEADS, HIDDEN // HEADS))
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("case", ["width4", "int8", "mesh", "cpu",
                                  "small_page"])
def test_everything_but_width_one_on_one_tpu_takes_the_dense_path(
        monkeypatch, case):
    kw = dict(width=1, kernel_backend=True)
    if case == "width4":
        kw["width"] = 4              # a prefill / spec verify program
    elif case == "int8":
        kw["kv_dtype"] = "int8"
    elif case == "mesh":
        kw["mesh_sharded"] = True    # the engine serves over a mesh
    elif case == "cpu":
        kw["kernel_backend"] = False
    if case == "small_page":
        # a bfloat16 page of 8 rows is half a packed sublane tile
        assert not kernel_applies(PagedKV(9, 8), 1, jnp.bfloat16)
        assert kernel_applies(PagedKV(9, 8), 1, jnp.float32) \
            == (jax.default_backend() == "tpu")
        return
    y, _, n = attend(monkeypatch, **kw)
    assert n == 0
    assert np.all(np.isfinite(y))
