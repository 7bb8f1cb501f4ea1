"""Attention ops: dense / blockwise / ring equivalence (fwd + grad).

Ring attention is the sequence-parallel primitive (tpunet/ops/attention.py);
these tests run it over a real multi-device mesh (8 virtual CPU devices,
conftest.py) and check exact agreement with the dense reference.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpunet.ops import (blockwise_attention, dense_attention,
                        ring_attention, ring_self_attention,
                        ulysses_self_attention)

B, T, H, D = 2, 32, 4, 8


def _qkv(seed=0, t=T, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(B, t, H, D)), dtype)
    return mk(), mk(), mk()


def _naive(q, k, v, causal=False):
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (D ** -0.5)
    if causal:
        mask = jnp.tril(jnp.ones((q.shape[1], k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_dense_matches_naive(causal):
    q, k, v = _qkv()
    np.testing.assert_allclose(dense_attention(q, k, v, causal=causal),
                               _naive(q, k, v, causal), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block", [4, 8, 32])
def test_blockwise_matches_dense(causal, block):
    q, k, v = _qkv(1)
    out = blockwise_attention(q, k, v, block_size=block, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_causal_cross_lengths_fully_masked_rows_zero():
    """tq > tk: top q rows attend to nothing -> zeros from every variant
    (plain softmax would leak a uniform average of all values)."""
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(B, 8, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, 4, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, 4, H, D)), jnp.float32)
    d = dense_attention(q, k, v, causal=True)
    bw = blockwise_attention(q, k, v, block_size=2, causal=True)
    np.testing.assert_allclose(d, bw, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(d[:, :4]), 0.0)
    assert np.abs(np.asarray(d[:, 4:])).max() > 0


def test_blockwise_rejects_indivisible():
    q, k, v = _qkv()
    with pytest.raises(ValueError):
        blockwise_attention(q, k, v, block_size=5)


def _seq_mesh(seq=4, data=2):
    devs = np.asarray(jax.devices()[:data * seq]).reshape(data, seq)
    return Mesh(devs, ("data", "seq"))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_dense(causal):
    mesh = _seq_mesh()
    q, k, v = _qkv(2)
    out = ring_self_attention(q, k, v, mesh, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_under_jit_with_sharded_inputs():
    mesh = _seq_mesh()
    q, k, v = _qkv(3)
    sh = NamedSharding(mesh, P("data", "seq", None, None))
    qs, ks, vs = (jax.device_put(a, sh) for a in (q, k, v))
    fn = jax.jit(functools.partial(ring_self_attention, mesh=mesh))
    out = fn(qs, ks, vs)
    assert out.sharding.is_equivalent_to(sh, 4)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_ring_gradients_match_dense(causal):
    mesh = _seq_mesh()
    q, k, v = _qkv(4)

    def loss_ring(q, k, v):
        return jnp.sum(ring_self_attention(q, k, v, mesh,
                                           causal=causal) ** 2)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gr, gd in zip(g_ring, g_dense):
        np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_dense(causal):
    mesh = _seq_mesh()  # seq=4; H=4 heads divisible
    q, k, v = _qkv(8)
    out = ulysses_self_attention(q, k, v, mesh, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_ulysses_gradients_match_dense(causal):
    mesh = _seq_mesh()
    q, k, v = _qkv(9)

    def loss_u(q, k, v):
        return jnp.sum(ulysses_self_attention(q, k, v, mesh,
                                              causal=causal) ** 2)

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_merge_attention_states_exact():
    """Splitting K/V into two blocks and merging the flash states must
    reproduce whole-sequence attention exactly."""
    from tpunet.ops.flash import (local_flash_attention_state,
                                  merge_attention_states)
    q, k, v = _qkv(12)
    half = k.shape[1] // 2
    sa = local_flash_attention_state(q, k[:, :half], v[:, :half],
                                     interpret=True)
    sb = local_flash_attention_state(q, k[:, half:], v[:, half:],
                                     interpret=True)
    out, lse = merge_attention_states(sa, sb)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # lse is the whole-sequence log-sum-exp
    s = np.einsum("bqhd,bkhd->bhqk", np.asarray(q), np.asarray(k))
    s *= q.shape[-1] ** -0.5
    ref_lse = np.log(np.exp(s - s.max(-1, keepdims=True)).sum(-1)) \
        + s.max(-1)
    np.testing.assert_allclose(np.asarray(lse), ref_lse, rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_core_matches_dense(causal):
    """The flash-core ring (fused local kernel + state merging +
    lax.cond step classification) against dense on the 8-device mesh."""
    mesh = _seq_mesh()
    q, k, v = _qkv(13)
    out = ring_self_attention(q, k, v, mesh, causal=causal, core="flash")
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_flash_core_bf16_f32_accumulator():
    """The flash ring's merged-output carry stays f32 across all folds
    (one bf16 cast at the end), so bf16 accuracy matches a single
    bf16 attention, not n accumulated roundings."""
    mesh = _seq_mesh()
    q, k, v = _qkv(15, dtype=jnp.bfloat16)
    out = ring_self_attention(q, k, v, mesh, causal=True, core="flash")
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), rtol=0.02, atol=0.02)


def test_ring_unknown_core_raises():
    mesh = _seq_mesh()
    q, k, v = _qkv(16)
    with pytest.raises(ValueError, match="unknown attention core"):
        ring_self_attention(q, k, v, mesh, core="blokwise")


@pytest.mark.slow
def test_ring_flash_core_gradients():
    mesh = _seq_mesh()
    q, k, v = _qkv(14)

    def loss_r(q, k, v):
        return jnp.sum(ring_self_attention(q, k, v, mesh, causal=True,
                                           core="flash") ** 2)

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_flash_core_matches_dense(causal):
    """core='flash' runs the Pallas kernel (interpret mode off-TPU)
    inside the shard_map body — the TPU-default composition of
    sequence parallelism with the fused local kernel."""
    mesh = _seq_mesh()
    q, k, v = _qkv(10)
    out = ulysses_self_attention(q, k, v, mesh, causal=causal,
                                 core="flash")
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow
def test_ulysses_flash_core_gradients():
    mesh = _seq_mesh()
    q, k, v = _qkv(11)

    def loss_u(q, k, v):
        return jnp.sum(ulysses_self_attention(q, k, v, mesh, causal=True,
                                              core="flash") ** 2)

    def loss_d(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

    gu = jax.grad(loss_u, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gu, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ulysses_rejects_indivisible_heads():
    devs = np.asarray(jax.devices()[:3]).reshape(1, 3)
    mesh = Mesh(devs, ("data", "seq"))
    rng = np.random.default_rng(0)
    # T=6 divisible by 3, H=4 not divisible by 3
    q = jnp.asarray(rng.normal(size=(2, 6, 4, 8)), jnp.float32)
    with pytest.raises(ValueError):
        ulysses_self_attention(q, q, q, mesh)


@pytest.mark.parametrize("impl", [ring_self_attention,
                                  ulysses_self_attention])
def test_seq_parallel_with_tensor_parallel_heads(impl):
    """dp x sp x tp mesh: the head dim stays sharded over 'model'
    through the sequence-parallel cores (no forced all-gather), and the
    result still matches dense."""
    devs = np.asarray(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devs, ("data", "seq", "model"))
    q, k, v = _qkv(11)  # H=4 heads; 2 per model shard, divisible by seq 2
    sh = NamedSharding(mesh, P("data", "seq", "model", None))
    qs, ks, vs = (jax.device_put(a, sh) for a in (q, k, v))
    out = impl(qs, ks, vs, mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_ring_single_device_axis():
    """seq axis of size 1 degrades to plain blockwise == dense."""
    devs = np.asarray(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "seq"))
    q, k, v = _qkv(5)
    out = ring_self_attention(q, k, v, mesh)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(dense_attention(q, k, v)),
                               rtol=2e-5, atol=2e-5)


def test_bfloat16_path_close_to_f32():
    mesh = _seq_mesh()
    q, k, v = _qkv(6, dtype=jnp.bfloat16)
    out = ring_self_attention(q, k, v, mesh)
    ref = dense_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32))
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0.05, atol=0.05)


# ---------------------------------------------------------------- flash


class TestFlashAttention:
    """Pallas flash kernel vs the dense/blockwise reference, exercised
    in interpret mode on CPU (same scheme as the depthwise kernel)."""

    def _qkv(self, b=2, t=128, h=4, d=32, tk=None, seed=0):
        rng = np.random.default_rng(seed)
        shape_k = (b, tk or t, h, d)
        q = rng.standard_normal((b, t, h, d)).astype(np.float32)
        k = rng.standard_normal(shape_k).astype(np.float32)
        v = rng.standard_normal(shape_k).astype(np.float32)
        return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv()
        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32, interpret=True)
        ref = dense_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_odd_lengths_fall_back_to_divisor_blocks(self):
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=65, d=16)  # ViT-like: 65 tokens (cls+8x8)
        out = flash_attention(q, k, v, interpret=True)
        ref = dense_attention(q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_cross_length_causal_offset(self):
        """tq < tk (decode window): the tk - tq diagonal offset must
        match dense_attention."""
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=32, tk=128)
        out = flash_attention(q, k, v, causal=True, block_q=16,
                              block_k=32, interpret=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_triangular_grid_many_blocks(self):
        """Causal self-attention takes the fused lower-triangular grid
        (no dead steps); exercise many q blocks so the sqrt-based
        (qi, ki) inversion crosses every triangular-number boundary."""
        from tpunet.ops.flash import _use_tri, flash_attention
        assert _use_tri(True, 256, 256, 16, 16)
        assert not _use_tri(True, 128, 256, 16, 16)   # cross-length
        assert not _use_tri(True, 256, 256, 16, 32)   # unequal blocks
        assert not _use_tri(False, 256, 256, 16, 16)  # non-causal
        # float32 sqrt inversion bound: past ~2**23 linearized steps
        # sqrt's ~2^-24 relative error can exceed the ±1 correction's
        # reach — fall back to the rectangular grid (nq=4096 -> 8.39M
        # steps > 2**23)
        assert _use_tri(True, 2048 * 512, 2048 * 512, 512, 512)
        assert not _use_tri(True, 4096 * 8, 4096 * 8, 8, 8)
        q, k, v = self._qkv(t=256, d=16)
        out = flash_attention(q, k, v, causal=True, block_q=16,
                              block_k=16, interpret=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_tri_qi_ki_inversion_exact(self):
        from tpunet.ops.flash import _tri_qi_ki
        n = 128  # rows; covers t up to 8255
        ts = jnp.arange(n * (n + 1) // 2)
        qi, ki = jax.vmap(_tri_qi_ki)(ts)
        expect = [(i, j) for i in range(n) for j in range(i + 1)]
        np.testing.assert_array_equal(np.asarray(qi),
                                      np.asarray([e[0] for e in expect]))
        np.testing.assert_array_equal(np.asarray(ki),
                                      np.asarray([e[1] for e in expect]))

    def test_tri_ki_qi_upper_inversion_exact(self):
        from tpunet.ops.flash import _tri_ki_qi_upper
        for n in (1, 2, 5, 64):
            ts = jnp.arange(n * (n + 1) // 2)
            ki, qi = jax.vmap(lambda t: _tri_ki_qi_upper(t, n))(ts)
            expect = [(k, q) for k in range(n) for q in range(k, n)]
            np.testing.assert_array_equal(
                np.asarray(ki), np.asarray([e[0] for e in expect]))
            np.testing.assert_array_equal(
                np.asarray(qi), np.asarray([e[1] for e in expect]))

    @pytest.mark.parametrize("causal", [False, True])
    def test_segment_ids_match_dense(self, causal):
        """Packed-sequence masking (VERDICT r1 item 5): queries attend
        only within their own segment; parity vs the dense reference
        with the same mask, forward AND gradients."""
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=128, d=16)
        rng = np.random.default_rng(3)
        # 3 packed docs + trailing padding (id 0 reserved for pad)
        bounds = sorted(rng.choice(np.arange(8, 120), 3, replace=False))
        seg_row = np.zeros(128, np.int32)
        start = 0
        for si, b_ in enumerate([*bounds, 128]):
            seg_row[start:b_] = si + 1
            start = b_
        seg_row[120:] = 0                     # padding
        seg = jnp.asarray(np.stack([seg_row, np.roll(seg_row, 13)]))

        def f_flash(q, k, v):
            return flash_attention(q, k, v, causal=causal, block_q=32,
                                   block_k=32, interpret=True,
                                   segment_ids=(seg, seg)).sum()

        def f_dense(q, k, v):
            return dense_attention(q, k, v, causal=causal,
                                   segment_ids=(seg, seg)).sum()

        out = flash_attention(q, k, v, causal=causal, block_q=32,
                              block_k=32, interpret=True,
                              segment_ids=(seg, seg))
        ref = dense_attention(q, k, v, causal=causal,
                              segment_ids=(seg, seg))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        g_flash = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(f_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_flash, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-4, atol=2e-4)

    def test_segment_ids_block_cross_attention(self):
        """No probability mass may leak across segments: with two
        segments holding identical k/v but different v offsets, each
        query's output must equal single-segment attention over its own
        half."""
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=128, d=16)
        seg = jnp.concatenate([jnp.ones((2, 64), jnp.int32),
                               jnp.full((2, 64), 2, jnp.int32)], axis=1)
        out = flash_attention(q, k, v, block_q=32, block_k=32,
                              interpret=True, segment_ids=(seg, seg))
        left = dense_attention(q[:, :64], k[:, :64], v[:, :64])
        right = dense_attention(q[:, 64:], k[:, 64:], v[:, 64:])
        np.testing.assert_allclose(np.asarray(out[:, :64]),
                                   np.asarray(left), rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(out[:, 64:]),
                                   np.asarray(right), rtol=1e-5, atol=1e-5)

    def test_gradients_match_dense(self):
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=64, d=16)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=16, block_k=16,
                                           interpret=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=True) ** 2)

        g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_flash, g_dense):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_cross_length_unequal_blocks(self, causal):
        """The hand-written backward kernels' decode-window offset
        ((tk - tq) in both mask and skip condition) and unequal
        block_q/block_k paths, against the dense vjp."""
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=32, tk=128, d=16, seed=3)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal,
                                           block_q=16, block_k=32,
                                           interpret=True) ** 2)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, causal=causal) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gf, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    def test_bf16_accumulates_in_f32(self):
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=64)
        qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
        out = flash_attention(qb, kb, vb, causal=True, block_q=32,
                              block_k=32, interpret=True)
        assert out.dtype == jnp.bfloat16
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref), rtol=0.05, atol=0.05)

    def test_off_tpu_entry_falls_back_to_dense(self):
        if jax.default_backend() == "tpu":
            pytest.skip("on TPU the entry runs the real kernel")
        from tpunet.ops.flash import flash_attention
        q, k, v = self._qkv(t=32)
        out = flash_attention(q, k, v, causal=True)  # interpret=None
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_degenerate_lengths_fall_back_to_dense(self):
        """A prime length ABOVE the block cap has only tiny divisors
        (bq would be 1); the entry must return the dense path instead of
        building a 1-row-block grid (same policy as _auto_block).
        t <= the cap is NOT degenerate — it runs as one t-row block."""
        from tpunet.ops import flash as F
        assert F._divisor_block(521, 512) == 1          # the trigger
        assert F._divisor_block(97, 512) == 97          # single block
        q, k, v = self._qkv(t=521, d=16)
        # interpret=True would be ignored on the fallback path; leave it
        # unset so this also passes on a TPU host.
        out = F.flash_attention(q, k, v, causal=True)
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.slow
    def test_spmd_partitions_over_batch_and_heads(self):
        """The mesh split (tpunet/ops/partition.py): under a (data,
        model) mesh with batch- and head-sharded inputs the kernel runs
        per-shard (each device's pallas_call sees 1/4 batch x 1/2
        heads) and still matches dense."""
        from jax.sharding import NamedSharding
        from tpunet.config import MeshConfig
        from tpunet.ops.flash import flash_attention
        from tpunet.ops.partition import traced_under
        from tpunet.parallel import make_mesh

        mesh = make_mesh(MeshConfig(data=4, model=2))
        q, k, v = self._qkv(b=4, t=64, h=4, d=16)
        sh = NamedSharding(mesh, P("data", None, "model", None))
        qs, ks, vs = (jax.device_put(x, sh) for x in (q, k, v))
        fn = jax.jit(traced_under(mesh, functools.partial(
            flash_attention, causal=True, block_q=32, block_k=32,
            interpret=True)))
        out = fn(qs, ks, vs)
        # Normalize: newer jax trims trailing Nones in PartitionSpec,
        # older jax keeps them — same sharding either way.
        def _trim(spec):
            parts = list(spec)
            while parts and parts[-1] is None:
                parts.pop()
            return tuple(parts)

        assert _trim(out.sharding.spec) == ("data", None, "model")
        ref = dense_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

        # Gradients under the mesh: exercises the res-forward (two
        # outputs, mixed 4-D/3-D specs) and the 6-operand backward.
        gfn = jax.jit(traced_under(mesh, jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32,
                interpret=True) ** 2), argnums=(0, 1, 2))))
        gq, gk, gv = gfn(qs, ks, vs)
        assert _trim(gq.sharding.spec) == ("data", None, "model")
        dref = jax.grad(
            lambda q, k, v: jnp.sum(dense_attention(
                q, k, v, causal=True) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip((gq, gk, gv), dref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_spmd_partitions_with_segment_ids(self):
        """The segmented trio (5/8-operand specs): batch-sharded q/k/v
        AND segment ids run per-shard and match dense, forward and
        gradients."""
        from jax.sharding import NamedSharding
        from tpunet.config import MeshConfig
        from tpunet.ops.flash import flash_attention
        from tpunet.ops.partition import traced_under
        from tpunet.parallel import make_mesh

        mesh = make_mesh(MeshConfig(data=4))
        q, k, v = self._qkv(b=4, t=64, h=4, d=16)
        seg = jnp.asarray(
            np.repeat(np.arange(1, 5, dtype=np.int32)[None], 4, 0),
        ).repeat(16, axis=1)                      # [4, 64], 4 docs/row
        sh4 = NamedSharding(mesh, P("data"))
        sh2 = NamedSharding(mesh, P("data"))
        qs, ks, vs = (jax.device_put(x, sh4) for x in (q, k, v))
        segs = jax.device_put(seg, sh2)

        fn = jax.jit(traced_under(mesh, lambda q, k, v, s: flash_attention(
            q, k, v, causal=True, block_q=32, block_k=32,
            interpret=True, segment_ids=(s, s))))
        out = fn(qs, ks, vs, segs)
        ref = dense_attention(q, k, v, causal=True,
                              segment_ids=(seg, seg))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

        gfn = jax.jit(traced_under(mesh, jax.grad(
            lambda q, k, v, s: jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=32, block_k=32,
                interpret=True, segment_ids=(s, s)) ** 2),
            argnums=(0, 1, 2))))
        gq, gk, gv = gfn(qs, ks, vs, segs)
        dref = jax.grad(
            lambda q, k, v: jnp.sum(dense_attention(
                q, k, v, causal=True,
                segment_ids=(seg, seg)) ** 2), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip((gq, gk, gv), dref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-4)

    @pytest.mark.slow
    def test_lm_trains_with_flash_config(self):
        """attention='flash' wires through the model registry (dense
        fallback on the CPU backend) and trains end-to-end."""
        from tpunet.config import (CheckpointConfig, DataConfig,
                                   MeshConfig, ModelConfig, OptimConfig,
                                   TrainConfig)
        from tpunet.train.loop import Trainer
        cfg = TrainConfig(
            epochs=1,
            data=DataConfig(dataset="synthetic_lm", batch_size=16,
                            synthetic_train_size=32,
                            synthetic_test_size=16, seq_len=64,
                            vocab_size=32),
            model=ModelConfig(name="lm", vit_hidden=64, vit_depth=2,
                              vit_heads=4, dropout_rate=0.0,
                              dtype="float32", vocab_size=32,
                              max_seq_len=64, attention="flash"),
            optim=OptimConfig(learning_rate=3e-3),
            mesh=MeshConfig(),
            checkpoint=CheckpointConfig(save_best=False, save_last=False),
        )
        trainer = Trainer(cfg)
        try:
            m = trainer.train_one_epoch(1)
            assert np.isfinite(m["loss"])
        finally:
            trainer.close()


# -- flash_prefill: grouped K/V and a sliding window (forward only) -----------

def _prefill_reference(q, k, v, window):
    """float64 on the host: query head h on KV head h // group, query t
    on keys t - window < s <= t."""
    q, k, v = (np.asarray(x, np.float64) for x in (q, k, v))
    b, t, h, d = q.shape
    grp = h // k.shape[2]
    out = np.zeros_like(q)
    at = np.arange(t)
    keep = at[None, :] <= at[:, None]
    if window:
        keep &= at[None, :] > at[:, None] - window
    for i in range(h):
        s = np.einsum("bqd,bkd->bqk", q[:, :, i], k[:, :, i // grp]) \
            * d ** -0.5
        s = np.where(keep, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        out[:, :, i] = np.einsum("bqk,bkd->bqd", p / p.sum(-1, keepdims=True),
                                 v[:, :, i // grp])
    return out


@pytest.mark.parametrize("t,h,hkv,d,window,block", [
    (64, 8, 2, 128, 24, 8),       # a band of 4 blocks, group 4
    (96, 6, 3, 128, 33, 32),      # a window that is no multiple of the block
    (128, 16, 1, 128, 17, 16),    # group 16, a band of 2
    (64, 8, 8, 64, 16, 16),       # ungrouped under a window
    (64, 4, 2, 64, None, 16),     # grouped on the causal triangle
    (64, 8, 2, 128, 200, 16),     # a window wider than the row: none
])
def test_flash_prefill_kernel_matches_the_grouped_windowed_reference(
        t, h, hkv, d, window, block):
    from tpunet.ops.flash import flash_prefill, grouped_window_attention
    rng = np.random.default_rng(t + h)
    q, k, v = (jnp.asarray(rng.standard_normal((2, t, n, d)), jnp.float32)
               for n in (h, hkv, hkv))
    want = _prefill_reference(q, k, v, 0 if (window or 0) >= t else window)
    got = flash_prefill(q, k, v, window=window, block=block, interpret=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # off the TPU the entry takes the dense form: the same numbers
    dense = flash_prefill(q, k, v, window=window, block=block)
    np.testing.assert_allclose(np.asarray(dense), want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(grouped_window_attention(
        q, k, v, scale=d ** -0.5, window=window or 0)), want, atol=1e-5)


def test_flash_prefill_grouped_is_the_repeated_kernel_bit_for_bit():
    """K and V read by head group = K and V repeated to the query heads
    through the same kernel: not one bit differs (what the hybrid
    decoder's prefill did before)."""
    from tpunet.ops.flash import flash_attention, flash_prefill
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 64, n, 128)),
                           jnp.bfloat16) for n in (8, 2, 2))
    got = flash_prefill(q, k, v, block=16, interpret=True)
    want = flash_attention(q, jnp.repeat(k, 4, axis=2),
                           jnp.repeat(v, 4, axis=2), causal=True,
                           block_q=16, block_k=16, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_flash_prefill_band_walks_only_the_blocks_in_sight():
    from tpunet.ops.flash import _band_blocks, flash_prefill
    assert _band_blocks(4096, 512, 16) == 9      # 8 behind and its own
    assert _band_blocks(4097, 512, 16) == 9
    assert _band_blocks(4098, 512, 16) == 10
    assert _band_blocks(1, 512, 16) == 1 and _band_blocks(24, 8, 8) == 4
    assert _band_blocks(4096, 512, 4) == 4       # never more than the row
    with pytest.raises(ValueError, match="whole groups"):
        flash_prefill(jnp.zeros((1, 16, 6, 64)), jnp.zeros((1, 16, 4, 64)),
                      jnp.zeros((1, 16, 4, 64)))


# -- flash_prefill differentiated: the log-sum-exp under a window, dQ on the
# -- band, dK/dV by KV head over the group and the band transposed --------------

@pytest.mark.parametrize("group", [7, 1])
@pytest.mark.parametrize("t,window,block", [
    (96, 0, 16),        # no window: the triangle forward, every later block back
    (96, 5, 16),        # a window shorter than a block
    (96, 40, 16),       # spanning blocks, T no multiple of the window
    (80, 24, 16),       # a band of 3, T no multiple of the window
    (96, 200, 16),      # a window at least the row: none
])
def test_flash_prefill_backward_matches_the_grouped_windowed_reference(
        group, t, window, block):
    from tpunet.ops.flash import flash_prefill, grouped_window_attention
    hkv, d = 2, 16
    rng = np.random.default_rng(t + window + group)
    q, k, v, g = (jnp.asarray(rng.standard_normal((2, t, n, d)), jnp.float32)
                  for n in (hkv * group, hkv, hkv, hkv * group))
    seen = 0 if window >= t else window

    def through(attend):
        return jax.value_and_grad(
            lambda q_, k_, v_: jnp.sum(attend(q_, k_, v_) * g),
            argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        got, (dq, dk, dv) = through(lambda *a: flash_prefill(
            *a, window=window, block=block, interpret=True))
        want, (rq, rk, rv) = through(lambda *a: grouped_window_attention(
            *a, scale=d ** -0.5, window=seen))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for name, a, b in (("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5,
                                   err_msg=name)


def test_flash_forward_keeps_its_log_sum_exp_under_a_window():
    from tpunet.ops.flash import _forward_impl
    rng = np.random.default_rng(11)
    t, d, window = 64, 16, 24
    q, k, v = (jnp.asarray(rng.standard_normal((1, t, n, d)), jnp.float32)
               for n in (4, 2, 2))
    with jax.default_matmul_precision("highest"):
        out, lse = _forward_impl(q, k, v, True, d ** -0.5, 16, 16, True,
                                 with_lse=True, window=window)
        s = jnp.einsum("bqngd,bknd->bngqk", q.reshape(1, t, 2, 2, d), k,
                       precision="highest") * d ** -0.5
    at = np.arange(t)
    keep = (at[None, :] <= at[:, None]) & (at[None, :] > at[:, None] - window)
    want = jax.nn.logsumexp(jnp.where(keep, s, -jnp.inf), axis=-1)
    np.testing.assert_allclose(lse, want.reshape(1, 4, t), atol=1e-5)
    assert out.shape == q.shape


def test_causal_blocks_visited_is_the_bands_count():
    from tpunet.ops.flash import causal_blocks_visited
    # 8192 tokens in 512-blocks under a 4096-key window: a band of 9
    assert causal_blocks_visited(8192, 4096) == (108, 136)
    assert causal_blocks_visited(16384, 4096) == (
        sum(min(i + 1, 9) for i in range(32)), 32 * 33 // 2)
    assert causal_blocks_visited(8192, 0) == (136, 136)
    assert causal_blocks_visited(4096, 4096) == (36, 36)
    assert causal_blocks_visited(96, 40, 16) == (1 + 2 + 3 + 4 + 4 + 4, 21)
