"""Ring attention correctness vs dense attention (no reference analog — the
reference has no sequence parallelism; SURVEY.md §5)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu.parallel.ring_attention import dense_attention, ring_attention


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sp",))


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense(hvd_init, sp, causal):
    B, S, H, D = 2, 32, 4, 16
    key = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    ref = dense_attention(q, k, v, causal=causal)
    mesh = _mesh(sp)
    f = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=causal),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("sp", [2, 4, 8])
@pytest.mark.parametrize("window", [1, 3, 7, 9, 31, 64])
def test_ring_window_matches_dense(hvd_init, sp, window):
    """Sliding-window ring attention == windowed dense attention, for
    windows inside one shard, spanning shard boundaries, and >= the whole
    sequence (the ring prunes out-of-window shards in every case)."""
    B, S, H, D = 2, 32, 4, 16
    key = jax.random.PRNGKey(3)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    ref = dense_attention(q, k, v, causal=True, window=window)
    mesh = _mesh(sp)
    f = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=True,
                                       window=window),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = f(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_window_prunes_steps(hvd_init):
    """The windowed ring runs 1 + ceil((W-1)/S_local) rotations, not
    sp_size — asserted on the traced scan length (the cost claim, not
    just numerics)."""
    B, S, H, D = 1, 64, 2, 8
    mesh = _mesh(8)  # S_local = 8
    q = jnp.ones((B, S, H, D), jnp.float32)

    def scan_lengths(jaxpr):
        # the ring scan sits inside shard_map + the custom_vjp call
        out = []
        for e in jaxpr.eqns:
            if e.primitive.name == "scan":
                out.append(e.params["length"])
            for sub in jax.core.jaxprs_in_params(e.params):
                out.extend(scan_lengths(sub))
        return out

    def scan_length(window):
        traced = jax.make_jaxpr(jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, "sp", causal=True,
                                           window=window),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))(q, q, q)
        lengths = scan_lengths(traced.jaxpr)
        assert len(lengths) == 1, lengths
        return lengths[0]

    assert scan_length(None) == 8      # full ring
    assert scan_length(8) == 2         # one shard back
    assert scan_length(9) == 2         # W-1=8 still reaches only 1 back
    assert scan_length(10) == 3
    assert scan_length(1) == 1         # self-attention only
    assert scan_length(64) == 8        # window >= sequence: full ring


def test_ring_window_gradients_match_dense(hvd_init):
    B, S, H, D = 1, 16, 2, 8
    window = 5
    key = jax.random.PRNGKey(4)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    mesh = _mesh(4)
    ring = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=True,
                                       window=window),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    gr = jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                  argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(lambda q, k, v: (dense_attention(
        q, k, v, causal=True, window=window) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_ring_window_guards(hvd_init):
    q = jnp.ones((1, 8, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        ring_attention(q, q, q, "sp", causal=False, window=4)
    with pytest.raises(ValueError, match=">= 1"):
        ring_attention(q, q, q, "sp", causal=True, window=0)
    with pytest.raises(ValueError, match="scale"):
        ring_attention(q, q, q, "sp", causal=True, scale=0.5, impl="flash")


def test_ring_gradients_match_dense(hvd_init):
    B, S, H, D = 1, 16, 2, 8
    key = jax.random.PRNGKey(1)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    mesh = _mesh(4)
    ring = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))

    g_ring = jax.grad(lambda *xs: (ring(*xs) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda *xs: (dense_attention(*xs, causal=True) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5)


def test_ring_long_sequence_bf16(hvd_init):
    """Long-context smoke: 8-way sp, 1024 global tokens, bf16 inputs."""
    B, S, H, D = 1, 1024, 2, 32
    key = jax.random.PRNGKey(2)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.bfloat16)
               for kk in jax.random.split(key, 3))
    mesh = _mesh(8)
    f = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = np.asarray(f(q, k, v), np.float32)
    ref = np.asarray(dense_attention(q, k, v, causal=True), np.float32)
    np.testing.assert_allclose(out, ref, atol=3e-2)


@pytest.mark.parametrize("impl", ["dense", "flash"])
@pytest.mark.parametrize("window", [None, 5, 20])
def test_ring_gqa_window_gradients(hvd_init, impl, window):
    """Grad parity vs dense attention for the flagship defaults the ring
    must support under SP: grouped-query K/V, sliding windows, and the
    two combined — on BOTH tile impls (the flash path runs the
    band-offset kernels for windowed visiting tiles). Exercises the
    custom-VJP blockwise backward end to end."""
    B, S, H, G, D = 1, 32, 4, 2, 8
    key = jax.random.PRNGKey(7)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, H // G, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, H // G, D), jnp.float32)
    mesh = _mesh(4)
    ring = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=True,
                                       impl=impl, window=window,
                                       interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    out = ring(q, k, v)
    ref = dense_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    gr = jax.jit(jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(lambda q, k, v: (dense_attention(
        q, k, v, causal=True, window=window) ** 2).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_ring_noncausal_gradients(hvd_init, impl):
    """Non-causal ring grads through the custom VJP (every tile fully
    visible; no cond/dead path)."""
    B, S, H, D = 1, 32, 2, 8
    key = jax.random.PRNGKey(8)
    q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
               for kk in jax.random.split(key, 3))
    mesh = _mesh(4)
    ring = jax.jit(jax.shard_map(
        lambda a, b, c: ring_attention(a, b, c, "sp", causal=False,
                                       impl=impl, interpret=True),
        mesh=mesh, in_specs=(P(None, "sp"),) * 3, out_specs=P(None, "sp"),
        check_vma=False))
    gr = jax.jit(jax.grad(lambda q, k, v: (ring(q, k, v) ** 2).sum(),
                          argnums=(0, 1, 2)))(q, k, v)
    gd = jax.grad(lambda q, k, v: (dense_attention(
        q, k, v, causal=False) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("impl", ["dense", "flash"])
def test_ring_backward_memory_constant(hvd_init, impl):
    """THE memory property of blockwise ring attention: backward
    residuals per device do NOT grow with the ring size. Fixed per-shard
    shape, sp=2 vs sp=8 (global S 4x larger): the custom VJP saves only
    q/k/v/out/lse — total residual bytes scale with S_global, so
    per-device bytes stay constant. (Autodiff through the forward scan
    would instead stack per-step score tiles: per-device residuals
    proportional to ring size — sp=8 would be ~4x sp=2.)"""
    B, S_LOCAL, H, D = 1, 64, 2, 16

    def residual_bytes_per_device(sp):
        mesh = _mesh(sp)
        S = S_LOCAL * sp
        key = jax.random.PRNGKey(9)
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                   for kk in jax.random.split(key, 3))
        f = jax.shard_map(
            lambda a, b, c: ring_attention(a, b, c, "sp", causal=True,
                                           impl=impl, interpret=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)
        _, vjp_fn = jax.vjp(f, q, k, v)
        total = sum(x.nbytes for x in jax.tree_util.tree_leaves(vjp_fn)
                    if hasattr(x, "nbytes"))
        return total / sp

    b2 = residual_bytes_per_device(2)
    b8 = residual_bytes_per_device(8)
    assert b8 <= b2 * 1.25, (
        f"backward residuals grew with ring size: {b2} B/device at sp=2 "
        f"vs {b8} B/device at sp=8")


def test_ring_flash_matches_dense(hvd_init, eight_devices):
    """ring x flash: the Pallas-tiled ring must match single-device dense
    attention exactly (fwd and grads), causal and not."""
    import jax
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.parallel.mesh import create_mesh

    mesh = create_mesh(dp=1, sp=8)
    b, s, h, d = 2, 256, 2, 32
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, d), jnp.float32)

    for causal in (True, False):
        ring = jax.jit(jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                           causal=causal, impl="flash",
                                           interpret=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False))
        out = np.asarray(ring(q, k, v))
        ref = np.asarray(dense_attention(q, k, v, causal=causal))
        np.testing.assert_allclose(out, ref, atol=2e-3)

    # gradients through the ring x flash composition (lse cotangent path)
    def ring_loss(q, k, v):
        o = jax.shard_map(
            lambda q, k, v: ring_attention(q, k, v, axis_name="sp",
                                           causal=True, impl="flash",
                                           interpret=True),
            mesh=mesh, in_specs=(P(None, "sp"),) * 3,
            out_specs=P(None, "sp"), check_vma=False)(q, k, v)
        return (o.astype(jnp.float32) ** 2).sum()

    def dense_loss(q, k, v):
        return (dense_attention(q, k, v, causal=True)
                .astype(jnp.float32) ** 2).sum()

    gr = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
    gd = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b_ in zip(gr, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=5e-3)
