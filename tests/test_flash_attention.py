"""Pallas flash-attention kernel vs the dense reference (interpret mode on
CPU; the same kernel compiles for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import dense_attention


def _dense_with_lse(q, k, v, causal, window=None):
    """Unfused f32 attention that also returns the per-row log-sum-exp —
    the numerics reference for flash_attention_with_lse."""
    from horovod_tpu.parallel.ring_attention import _tile_fwd_math
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    return _tile_fwd_math(q, k, v, 0, causal, window,
                          1.0 / (q.shape[3] ** 0.5))


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(1, 128, 2, 32), (2, 256, 4, 64)])
def test_flash_matches_dense(hvd_init, causal, shape):
    b, s, h, d = shape
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, causal, 128, True)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_ragged_tail_falls_back(hvd_init):
    # 200 <= default block: runs as a single-block kernel; lengths that
    # exceed the block size with no 128-multiple divisor (checked via
    # _pick_block) take the dense fallback — numerics must match either
    # way.
    shape = (1, 200, 2, 16)
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(key, 3))
    out = flash_attention(q, k, v, True, 128, True)
    ref = dense_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_flash_gradients_match_dense(hvd_init):
    shape = (1, 128, 2, 32)
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(key, 3))

    g_flash = jax.grad(
        lambda *xs: (flash_attention(*xs, True, 128, True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda *xs: (dense_attention(*xs, causal=True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_transformer_flash_matches_dense(hvd_init):
    """attention_impl='flash' produces the same logits as 'dense'."""
    import dataclasses
    from horovod_tpu.models import transformer as tfm
    base = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                 n_layers=2, d_ff=64, max_seq=128,
                                 dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), base)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0, 64)
    ref = tfm.forward(params, tokens, base)
    # interpret mode so the kernel runs on CPU in tests
    flash_cfg = dataclasses.replace(base, attention_impl="flash",
                                    flash_interpret=True)
    out = tfm.forward(params, tokens, flash_cfg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_kernels_multiblock(hvd_init, causal):
    """Fused backward across several q/k blocks (block=128, s=256: a
    2x2 grid — a block under 128 is not a kernel tile)."""
    shape = (2, 256, 2, 32)
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, shape, jnp.float32)
               for kk in jax.random.split(key, 3))
    cot = jax.random.normal(jax.random.PRNGKey(4), shape, jnp.float32)

    _, vjp_flash = jax.vjp(
        lambda *xs: flash_attention(*xs, causal, 128, True), q, k, v)
    _, vjp_dense = jax.vjp(
        lambda *xs: dense_attention(*xs, causal=causal), q, k, v)
    for a, b in zip(vjp_flash(cot), vjp_dense(cot)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_backward_bf16(hvd_init):
    """bf16 inputs: kernel math runs in f32, grads land close to the f32
    dense reference."""
    shape = (1, 128, 2, 32)
    key = jax.random.PRNGKey(5)
    q32, k32, v32 = (jax.random.normal(kk, shape, jnp.float32)
                     for kk in jax.random.split(key, 3))
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))

    g_flash = jax.grad(
        lambda *xs: (flash_attention(*xs, True, 128, True)
                     .astype(jnp.float32) ** 2).sum(),
        argnums=(0, 1, 2))(qb, kb, vb)
    g_ref = jax.grad(
        lambda *xs: (dense_attention(*xs, causal=True) ** 2).sum(),
        argnums=(0, 1, 2))(q32, k32, v32)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a, dtype=np.float32),
                                   np.asarray(b), atol=0.15, rtol=0.05)


@pytest.mark.parametrize("group", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_matches_dense(hvd_init, group, causal):
    """Grouped-query attention: H query heads share H/group K/V heads;
    the kernel must match the dense repeat-heads baseline."""
    # S = 256 with block 128 -> 2x2 blocks: the kernel path (NOT the
    # dense fallback) runs, exercising the bh // group K/V index maps
    B, S, H, D = 2, 256, 8, 16
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // group, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // group, D), jnp.float32)
    ref = dense_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_size=128,
                          interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gqa_gradients_match_dense(hvd_init):
    # multi-block kernel path (256/128), incl. the dk/dv group-sum
    B, S, H, D, G = 1, 256, 4, 8, 2
    key = jax.random.PRNGKey(8)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 128, True) ** 2).sum()

    def loss_dense(q, k, v):
        return (dense_attention(q, k, v, causal=True) ** 2).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_gqa_gradients_bf16_f32_group_sum(hvd_init):
    """bf16 K/V with a large group: the dk/dv group-sum must accumulate in
    f32 (partials cast to bf16 BEFORE the sum lose the low bits — this
    test's tolerance fails against that ordering)."""
    B, S, H, D, G = 1, 256, 8, 8, 8  # one kv head, 8-way group sum
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.bfloat16)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, True, 128, True)
                .astype(jnp.float32) ** 2).sum()

    def loss_dense(q, k, v):
        # dense reference in f32 end-to-end: the truth to approach
        return (dense_attention(q.astype(jnp.float32),
                                k.astype(jnp.float32),
                                v.astype(jnp.float32), causal=True) ** 2
                ).sum()

    gf = jax.grad(loss_flash, argnums=(1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        assert a.dtype == jnp.bfloat16  # API dtype preserved
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b),
            atol=0.15, rtol=0.08)


def test_flash_gqa_bad_ratio_raises(hvd_init):
    q = jnp.ones((1, 32, 6, 8))
    k = jnp.ones((1, 32, 4, 8))
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, k, True, 32, True)
    # K/V head mismatch is caught even on the kernel path
    q2 = jnp.ones((1, 128, 4, 8))
    k2 = jnp.ones((1, 128, 2, 8))
    v2 = jnp.ones((1, 128, 4, 8))
    with pytest.raises(ValueError, match="same head count"):
        flash_attention(q2, k2, v2, True, 128, True)


def test_ring_gqa_dense_matches_and_flash_guards(hvd_init):
    """Dense-tile ring supports GQA (K/V stream with REDUCED heads, the
    per-tile repeat restores the group); ring x flash still guards."""
    from jax.sharding import Mesh, PartitionSpec as P
    from horovod_tpu.parallel.ring_attention import (dense_attention,
                                                     ring_attention)
    B, S, H, G, D = 1, 32, 4, 2, 8
    key = jax.random.PRNGKey(11)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)
    ref = dense_attention(q, k, v, causal=True)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    f = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp"),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               atol=2e-5)
    # GQA + window compose on the dense ring too
    refw = dense_attention(q, k, v, causal=True, window=9)
    fw = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", window=9),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(fw(q, k, v)), np.asarray(refw),
                               atol=2e-5)

    g = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", impl="flash",
                                       interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(g(q, k, v)), np.asarray(ref),
                               atol=2e-3)


def test_flash_with_lse_gqa(hvd_init):
    """flash_attention_with_lse handles grouped-query K/V (the gate was
    lifted for ring x flash GQA) — out AND lse match the dense math."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse
    B, S, H, G, D = 1, 128, 4, 2, 16
    key = jax.random.PRNGKey(17)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)
    out, lse = flash_attention_with_lse(q, k, v, True, 64, True)
    ref_out, ref_lse = _dense_with_lse(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=3e-5, rtol=3e-5)


def test_ulysses_gqa(hvd_init):
    """GQA composes with ulysses SP: q splits H, k/v split H_kv over sp."""
    from horovod_tpu.parallel.ulysses import ulysses_attention
    from jax.sharding import Mesh, PartitionSpec as P

    B, S, H, G, D = 1, 64, 8, 2, 16
    key = jax.random.PRNGKey(9)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)
    ref = dense_attention(q, k, v, causal=True)

    mesh = Mesh(np.array(jax.devices()[:4]), ("sp",))
    f = jax.jit(jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    np.testing.assert_allclose(np.asarray(f(q, k, v)), np.asarray(ref),
                               atol=2e-5)


@pytest.mark.parametrize("window", [32, 100, 256])
def test_flash_sliding_window_matches_dense(hvd_init, window):
    """Sliding-window attention (causal band of `window` positions) on
    the kernel path (S=256, block=128) vs the dense masked baseline —
    including window < block, non-multiple, and window >= S."""
    B, S, H, D = 1, 256, 2, 16
    key = jax.random.PRNGKey(11)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    ref = dense_attention(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, True, 128, True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_sliding_window_gradients(hvd_init):
    B, S, H, D, W = 1, 256, 2, 8, 100
    key = jax.random.PRNGKey(12)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))

    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, True, 128, True, window=W) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: (dense_attention(
        q, k, v, causal=True, window=W) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


def test_flash_sliding_window_gqa(hvd_init):
    """Window composes with grouped-query K/V."""
    B, S, H, G, D, W = 1, 256, 4, 2, 16, 64
    key = jax.random.PRNGKey(13)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)
    ref = dense_attention(q, k, v, causal=True, window=W)
    out = flash_attention(q, k, v, True, 128, True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_sliding_window_validation(hvd_init):
    q = jnp.ones((1, 128, 2, 8))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, 128, True, window=32)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, q, q, True, 128, True, window=0)
    with pytest.raises(ValueError, match="causal"):
        dense_attention(q, q, q, causal=False, window=32)


@pytest.mark.parametrize("S", [200, 300, 1000])
def test_flash_ragged_length_pads_not_dense(hvd_init, S):
    """Causal sequences with no 128-multiple divisor pad to a block
    multiple instead of falling back to O(S^2) dense — outputs and
    gradients stay exact."""
    B, H, D = 1, 2, 16
    key = jax.random.PRNGKey(21)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    ref = dense_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, 128, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)

    gf = jax.grad(lambda q, k, v: (flash_attention(
        q, k, v, True, 128, True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: (dense_attention(
        q, k, v, causal=True) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-3, rtol=1e-3)
        assert np.isfinite(np.asarray(a)).all()


def test_flash_ragged_with_window_and_gqa(hvd_init):
    B, S, H, G, D, W = 1, 200, 4, 2, 16, 64
    key = jax.random.PRNGKey(22)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)
    ref = dense_attention(q, k, v, causal=True, window=W)
    out = flash_attention(q, k, v, True, 128, True, window=W)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)


def test_flash_with_lse_ragged_causal(hvd_init):
    """flash_attention_with_lse at a ragged causal length takes the
    padded kernel path in BOTH directions (the backward previously
    re-ran the O(S^2) dense vjp)."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse

    B, S, H, D = 1, 200, 2, 16
    key = jax.random.PRNGKey(23)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    out, lse = flash_attention_with_lse(q, k, v, True, 128, True)
    ref_out, ref_lse = _dense_with_lse(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out),
                               atol=3e-5, rtol=3e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=3e-5, rtol=3e-5)

    def loss_f(q, k, v):
        o, l = flash_attention_with_lse(q, k, v, True, 128, True)
        return (o ** 2).sum() + (l ** 2).sum()

    def loss_d(q, k, v):
        o, l = _dense_with_lse(q, k, v, True)
        return (o ** 2).sum() + (l ** 2).sum()

    gf = jax.grad(loss_f, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_d, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-3, rtol=2e-3)


def test_band_bwd_rejects_nonfinite_lse():
    """Round-4 verdict #7: the band backward path's finite-lse
    precondition is asserted in interpret mode — a globally-dead row
    (lse ~ -1e30) must fail loudly instead of fabricating gradients."""
    from horovod_tpu.ops.flash_attention import _tile_bwd_dispatch
    b, s, h, d = 1, 8, 1, 4
    key = jax.random.PRNGKey(0)
    q, k, v, g = (jax.random.normal(jax.random.fold_in(key, i),
                                    (b, s, h, d), jnp.float32)
                  for i in range(4))
    good_lse = jnp.zeros((b, h, s), jnp.float32)
    delta = jnp.zeros((b, h, s), jnp.float32)
    off = jnp.int32(s)  # band tile: every row sees the whole kv tile
    # healthy lse passes and returns finite grads
    dq, dk, dv = _tile_bwd_dispatch(q, k, v, g, good_lse, delta, off,
                                    True, None, 8, True)
    assert np.all(np.isfinite(np.asarray(dq)))
    # a globally-dead row's sentinel lse fires the contract check
    bad_lse = good_lse.at[0, 0, 3].set(-1e30)
    with pytest.raises(Exception, match="finite"):
        out = _tile_bwd_dispatch(q, k, v, g, bad_lse, delta, off,
                                 True, None, 8, True)
        jax.block_until_ready(out)


def test_flash_noncausal_ragged_raises_not_dense(hvd_init):
    """A non-causal length that exceeds one block and tiles into no
    128-multiple has no kernel path (padded keys cannot be hidden
    without a mask). It must raise — in the plain, the lse and the band
    entry points — instead of quietly running unfused attention."""
    from horovod_tpu.ops.flash_attention import (_band_tile_fwd,
                                                 flash_attention_with_lse)
    q = jnp.ones((1, 200, 2, 16), jnp.float32)
    with pytest.raises(ValueError, match="no 128-multiple block"):
        flash_attention(q, q, q, False, 128, True)
    with pytest.raises(ValueError, match="no 128-multiple block"):
        flash_attention_with_lse(q, q, q, False, 128, True)
    with pytest.raises(ValueError, match="no 128-multiple block"):
        _band_tile_fwd(q, q, q, jnp.int32(200), None, 128, True)


# --- the forward's tile body: interior, edge and dead tiles ---------------

@pytest.mark.parametrize("dtype,out_tol,lse_tol", [
    (jnp.float32, 2e-5, 2e-5),
    # bf16 operands cross the matmuls as they arrive (f32 accumulation,
    # f32 statistics): q * scale and p are rounded to bf16 once each
    (jnp.bfloat16, 2e-2, 2e-2),
])
@pytest.mark.parametrize("S,heads,kv_heads,window", [
    (1024, 2, 2, 300),      # MHA: unmasked, diagonal, far-edge and dead tiles
    (1024, 4, 2, 300),      # GQA over the same grid
    (1024, 2, 2, 129),      # one past a block: a live tile with no kept pair
    (1024, 2, 2, None),     # no window: unmasked + diagonal + dead
    (1000, 4, 2, 300),      # ragged: padded to 1024, sliced back
])
def test_flash_forward_output_and_lse(hvd_init, S, heads, kv_heads,
                                      window, dtype, out_tol, lse_tol):
    """Forward output AND lse against dense attention on a grid that holds
    every kind of tile (block 128: 8x8 tiles; window 300 reaches three
    blocks back and cuts through the third)."""
    from horovod_tpu.ops.flash_attention import flash_attention_with_lse
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(31), 3)
    q = jax.random.normal(kq, (1, S, heads, 32), jnp.float32)
    k = jax.random.normal(kk, (1, S, kv_heads, 32), jnp.float32)
    v = jax.random.normal(kv, (1, S, kv_heads, 32), jnp.float32)
    q, k, v = (x.astype(dtype) for x in (q, k, v))
    # f32 math on the same (rounded) inputs
    ref_out, ref_lse = _dense_with_lse(q, k, v, True, window)
    out, lse = flash_attention_with_lse(q, k, v, True, 128, True, window)
    assert out.dtype == dtype and lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref_out), atol=out_tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               atol=lse_tol)
    plain = flash_attention(q, k, v, True, 128, True, window)
    np.testing.assert_array_equal(np.asarray(plain, dtype=np.float32),
                                  np.asarray(out, dtype=np.float32))
