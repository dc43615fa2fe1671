"""Ring attention: exact long-context attention over a sequence-parallel axis.

No reference equivalent — the reference never shards the sequence dimension
(SURVEY.md §5 long-context: absent). This is the TPU-native long-context
pillar: the sequence axis is sharded over mesh axis ``sp``; each device holds
a query block and streams key/value blocks around the ICI ring with
``lax.ppermute``, accumulating exact softmax online (flash-attention
numerics: running max ``m``, normalizer ``l``, weighted accumulator ``acc``).
Compute on one block overlaps the DMA of the next around the ring, so ICI
latency hides behind the per-block matmuls (Liu et al., Ring Attention with
Blockwise Transformers, 2023 — public technique).

Meant to run inside ``shard_map`` with the sequence dim sharded over
``axis_name``. Differentiable via a custom VJP that implements the
blockwise backward from the same paper: the forward saves only the local
q/k/v shards, the output, and the per-row log-sum-exp — O(S_local) per
device — and the backward re-rotates the ring, recomputing each visiting
tile's probabilities from the saved lse. (Autodiff through the forward
scan would instead stack every step's score residuals — O(S_local x
S_global) per device, the exact memory blowup blockwise attention exists
to avoid.) Gradient accumulators for K/V travel the ring together with
their blocks and arrive home after a full rotation.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def gqa_group(h_q, h_kv, h_v=None):
    """Query-heads-per-kv-head ratio with validation; 1 = plain MHA.
    Shared by dense_attention and the flash kernels."""
    if h_v is not None and h_v != h_kv:
        raise ValueError(
            f"K and V must carry the same head count (got K={h_kv}, "
            f"V={h_v})")
    if h_q == h_kv:
        return 1
    if h_q % h_kv != 0:
        raise ValueError(
            f"GQA needs n_q_heads ({h_q}) divisible by n_kv_heads "
            f"({h_kv})")
    return h_q // h_kv


def _block_attn(q, k, v, mask, scale):
    """One (q-block, kv-block) tile: returns unnormalized partial results.

    q: (B, Sq, H, D), k/v: (B, Sk, H_kv, D) with H % H_kv == 0 (GQA
    repeats per tile — the ring still streams the REDUCED K/V heads, so
    the ICI traffic keeps the grouped-query saving), mask: (Sq, Sk)
    True=keep. Contraction runs in f32 on the MXU regardless of input
    dtype.
    """
    rep = gqa_group(q.shape[2], k.shape[2], v.shape[2])
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)                      # (B, H, Sq)
    p = jnp.exp(s - m[..., None])                # (B, H, Sq, Sk)
    l = jnp.sum(p, axis=-1)                      # (B, H, Sq)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return m, l, acc


def _tile_masks(sq, sk, off, causal, window):
    """(Sq, Sk) keep-mask for a tile whose q rows sit ``off`` global
    positions after its k columns (off may be traced). None = all kept."""
    if not causal:
        return None
    q_pos = off + jnp.arange(sq)[:, None]
    k_pos = jnp.arange(sk)[None, :]
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    return keep


def _tile_fwd_math(q, k, v, off, causal, window, scale):
    """One tile's normalized attention + per-row lse, in plain jnp — the
    numerics baseline for the Pallas tile kernels
    (ops/flash_attention.py). off = q_global_start -
    kv_global_start (may be traced). GQA-aware (k/v carry reduced heads).

    Fully-masked rows come back with lse ~ NEG_INF and a garbage-but-
    finite out row; the ring's log-sum-exp merge weights them by
    exp(lse - merged_lse) = 0, so they never contaminate the result
    (same contract as the Pallas kernels)."""
    rep = gqa_group(q.shape[2], k.shape[2], v.shape[2])
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    keep = _tile_masks(q.shape[1], k.shape[1], off, causal, window)
    if keep is not None:
        s = jnp.where(keep[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32) / (
                         l.transpose(0, 2, 1)[..., None])
    return out.astype(q.dtype), m + jnp.log(l)


def _tile_bwd_math(q, k, v, do, lse, delta, off, causal, window, scale):
    """One tile's gradient contributions given the GLOBAL per-row lse and
    delta = rowsum(dout * out) — the blockwise backward's recompute step
    (Liu et al. 2023; FlashAttention-2 backward math). Returns
    (dq_tile, dk_tile, dv_tile) in f32, dk/dv with the reduced (GQA)
    head count. Masked entries are zeroed explicitly, so tiles entirely
    outside the causal/window band contribute exact zeros."""
    h_kv = k.shape[2]
    rep = gqa_group(q.shape[2], h_kv, v.shape[2])
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    do = do.astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    keep = _tile_masks(q.shape[1], k.shape[1], off, causal, window)
    p = jnp.exp(s - lse[..., None])
    if keep is not None:
        p = jnp.where(keep[None, None], p, 0.0)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do,
                    preferred_element_type=jnp.float32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do, v.astype(jnp.float32),
                    preferred_element_type=jnp.float32)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k,
                    preferred_element_type=jnp.float32) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q,
                    preferred_element_type=jnp.float32) * scale
    if rep > 1:
        b, sk = dk.shape[0], dk.shape[1]
        dk = dk.reshape(b, sk, h_kv, rep, -1).sum(axis=3)
        dv = dv.reshape(b, sk, h_kv, rep, -1).sum(axis=3)
    return dq, dk, dv


def ring_attention(q, k, v, axis_name="sp", causal=True, scale=None,
                   impl="dense", block_size=512, interpret=False,
                   window=None):
    """Exact attention with K/V ring-streamed over ``axis_name``.

    Args:
      q, k, v: per-shard blocks (B, S_local, H, D); global sequence is
        S_local * axis_size, sharded contiguously (shard i holds positions
        [i*S_local, (i+1)*S_local)).
      causal: apply causal masking in *global* positions.
      scale: attention scale, default 1/sqrt(D).
      impl: "dense" computes each (q-shard, kv-shard) tile unfused;
        "flash" runs the Pallas fused kernel per tile and merges partials
        exactly via their log-sum-exps (ring x flash composition — VMEM
        stays bounded by one kernel tile at any context length). Both
        support grouped-query K/V (the ring streams the REDUCED heads
        over ICI) and sliding windows.
      block_size / interpret: forwarded to the flash kernel.
      window: sliding-window span in GLOBAL positions (requires causal):
        each query attends the previous ``window`` positions. Shards
        wholly outside the band never visit — the ring runs
        1 + ceil((window-1) / S_local) rotations instead of axis_size, so
        cost scales with the window, not the context (the SP analog of
        the flash kernel's two-sided block pruning). Under impl="flash"
        the partially-banded visiting tiles run the band-offset Pallas
        kernels (ops/flash_attention.py::_band_tile_fwd).

    Returns (B, S_local, H, D) attention output for the local query block.

    Training memory: the custom VJP saves only q/k/v/out/lse per shard
    (O(S_local)) and recomputes tiles in the backward ring — backward
    peak memory does NOT grow with the ring size (asserted by
    tests/test_ring_attention.py::test_ring_backward_memory_constant).
    """
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if impl == "flash":
        if scale is not None:
            raise ValueError("impl='flash' uses the 1/sqrt(D) scale; "
                             "custom scale is only supported with 'dense'")
    elif impl != "dense":
        raise ValueError(f"unknown ring attention impl {impl!r}")
    gqa_group(q.shape[2], k.shape[2], v.shape[2])  # validate head counts
    return _ring_core(q, k, v, axis_name, causal,
                      None if scale is None else float(scale), impl,
                      block_size, interpret, window)


def _ring_steps(n, s_local, causal, window):
    """Ring rotations needed: under a window, step t's tile (nearest pair
    distance (t-1)*S_local + 1) is dead once that distance reaches the
    window — every shard computes the same static bound, so truncating
    the scan is globally consistent and skips the pruned shards'
    ppermutes entirely."""
    if window is not None and causal:
        return min(n, max(1, 2 + (window - 2) // s_local))
    return n


def _ring_forward(q, k, v, axis_name, causal, scale, impl, block_size,
                  interpret, window):
    """Shared forward: returns (out, lse) — lse is the O(S_local) residual
    the blockwise backward recomputes tiles from."""
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    num_steps = _ring_steps(n, s_local, causal, window)
    perm = [(i, (i + 1) % n) for i in range(n)]

    if impl == "flash":
        from ..ops.flash_attention import _band_tile_fwd, _tile_lse

        # Diagonal tile first (static offset 0: the clamped causal
        # kernel), then the scan streams visiting tiles.
        acc, lse = _tile_lse(q, k, v, causal, window, block_size,
                             interpret)
        acc = acc.astype(jnp.float32)

        def dead():
            return (jnp.zeros(q.shape, q.dtype),
                    jnp.full((b, h, s_local), NEG_INF, jnp.float32))

        def step(carry, t):
            k_blk, v_blk, acc, lse = carry
            if causal:
                def live():
                    if window is None:
                        # fully-visible tile: the unmasked static kernel
                        return _tile_lse(q, k_blk, v_blk, False, None,
                                         block_size, interpret)
                    return _band_tile_fwd(q, k_blk, v_blk, t * s_local,
                                          window, block_size, interpret)
                o_j, lse_j = lax.cond(t <= idx, live, dead)
            else:
                o_j, lse_j = _tile_lse(q, k_blk, v_blk, False, None,
                                       block_size, interpret)
            new_lse = jnp.logaddexp(lse, lse_j)
            w_old = jnp.exp(lse - new_lse).transpose(0, 2, 1)[..., None]
            w_new = jnp.exp(lse_j - new_lse).transpose(0, 2, 1)[..., None]
            acc = acc * w_old + o_j.astype(jnp.float32) * w_new
            k_nxt = lax.ppermute(k_blk, axis_name, perm)
            v_nxt = lax.ppermute(v_blk, axis_name, perm)
            return (k_nxt, v_nxt, acc, new_lse), None

        if num_steps > 1:
            k_blk = lax.ppermute(k, axis_name, perm)
            v_blk = lax.ppermute(v, axis_name, perm)
            (_, _, acc, lse), _ = lax.scan(
                step, (k_blk, v_blk, acc, lse),
                jnp.arange(1, num_steps))
        return acc.astype(q.dtype), lse

    # dense tiles: online-softmax accumulation, uniform over all steps
    # (masks in global positions cover diagonal / visible / dead tiles).
    q_pos = idx * s_local + jnp.arange(s_local)

    def mask_for(src_idx):
        if not causal:
            return jnp.ones((s_local, s_local), bool)
        k_pos = src_idx * s_local + jnp.arange(s_local)
        keep = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            keep = keep & (q_pos[:, None] - k_pos[None, :] < window)
        return keep

    def step(carry, t):
        k_blk, v_blk, m, l, acc = carry
        src = (idx - t) % n
        bm, bl, bacc = _block_attn(q, k_blk, v_blk, mask_for(src), scale)
        new_m = jnp.maximum(m, bm)
        alpha = jnp.exp(m - new_m)
        beta = jnp.exp(bm - new_m)
        l = l * alpha + bl * beta
        acc = (acc * alpha.transpose(0, 2, 1)[..., None]
               + bacc * beta.transpose(0, 2, 1)[..., None])
        k_nxt = lax.ppermute(k_blk, axis_name, perm)
        v_nxt = lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, new_m, l, acc), None

    m0 = jnp.full((b, h, s_local), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local), jnp.float32)
    acc0 = jnp.zeros((b, s_local, h, d), jnp.float32)
    (_, _, m, l, acc), _ = lax.scan(step, (k, v, m0, l0, acc0),
                                    jnp.arange(num_steps))
    # Fully-masked rows (can't happen with causal self-attention, but guard
    # the l=0 division anyway).
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype), m + jnp.log(l)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _ring_core(q, k, v, axis_name, causal, scale, impl, block_size,
               interpret, window):
    out, _ = _ring_forward(q, k, v, axis_name, causal, scale, impl,
                           block_size, interpret, window)
    return out


def _ring_core_fwd(q, k, v, axis_name, causal, scale, impl, block_size,
                   interpret, window):
    out, lse = _ring_forward(q, k, v, axis_name, causal, scale, impl,
                             block_size, interpret, window)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(axis_name, causal, scale, impl, block_size, interpret,
                   window, res, g):
    """Blockwise backward (Liu et al. 2023): re-rotate the ring,
    recomputing each tile's probabilities from the saved global lse; dK/dV
    accumulators travel WITH their K/V blocks and come home after the
    rotation, so peak memory stays O(S_local) per device regardless of
    ring size."""
    q, k, v, out, lse = res
    n = lax.axis_size(axis_name)
    idx = lax.axis_index(axis_name)
    b, s_local, h, d = q.shape
    h_kv = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    num_steps = _ring_steps(n, s_local, causal, window)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # delta = rowsum(dout * out): one elementwise pass, shared by every
    # tile's recompute (FlashAttention-2's D term).
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)  # (B, H, S_local)

    def tile_bwd(k_blk, v_blk, off, tile_causal, tile_window):
        # off=None marks a static-offset-0 tile (the diagonal) so the
        # flash dispatch can use the clamped static kernels; traced
        # offsets take the band kernels.
        if impl == "flash":
            from ..ops.flash_attention import _tile_bwd_dispatch
            return _tile_bwd_dispatch(q, k_blk, v_blk, g, lse, delta, off,
                                      tile_causal, tile_window, block_size,
                                      interpret)
        return _tile_bwd_math(q, k_blk, v_blk, g, lse, delta,
                              0 if off is None else off, tile_causal,
                              tile_window, scale)

    # Diagonal tile (static offset 0), then the rotating scan.
    dq, dk_blk, dv_blk = tile_bwd(k, v, None, causal, window)

    def dead():
        return (jnp.zeros((b, s_local, h, d), jnp.float32),
                jnp.zeros((b, s_local, h_kv, d), jnp.float32),
                jnp.zeros((b, s_local, h_kv, d), jnp.float32))

    def step(carry, t):
        k_blk, v_blk, dk_blk, dv_blk, dq = carry
        if causal:
            # Visiting live tiles sit a full shard (or more) in the
            # past, so the causal constraint is always satisfied inside
            # them: without a window they are fully visible (static
            # unmasked kernels); with one, the band kernels mask at the
            # traced offset. Wrapped sources (t > idx) are entirely in
            # the future: exact-zero grads.
            if window is None:
                def live():
                    return tile_bwd(k_blk, v_blk, None, False, None)
            else:
                off = jnp.where(t > idx, t - n, t) * s_local

                def live():
                    return tile_bwd(k_blk, v_blk, off, True, window)
            dq_t, dk_t, dv_t = lax.cond(t <= idx, live, dead)
        else:
            dq_t, dk_t, dv_t = tile_bwd(k_blk, v_blk, None, False, None)
        dq = dq + dq_t
        dk_blk = dk_blk + dk_t
        dv_blk = dv_blk + dv_t
        rotated = [lax.ppermute(x, axis_name, perm)
                   for x in (k_blk, v_blk, dk_blk, dv_blk)]
        return tuple(rotated) + (dq,), None

    if num_steps > 1:
        k_blk = lax.ppermute(k, axis_name, perm)
        v_blk = lax.ppermute(v, axis_name, perm)
        dk_blk = lax.ppermute(dk_blk, axis_name, perm)
        dv_blk = lax.ppermute(dv_blk, axis_name, perm)
        (_, _, dk_blk, dv_blk, dq), _ = lax.scan(
            step, (k_blk, v_blk, dk_blk, dv_blk, dq),
            jnp.arange(1, num_steps))
        if num_steps < n:
            # Window-pruned partial rotation: dK/dV sit num_steps hops
            # downstream of their owners — one permute brings them home.
            home = [(i, (i - num_steps) % n) for i in range(n)]
            dk_blk = lax.ppermute(dk_blk, axis_name, home)
            dv_blk = lax.ppermute(dv_blk, axis_name, home)
    return (dq.astype(q.dtype), dk_blk.astype(k.dtype),
            dv_blk.astype(v.dtype))


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


def dense_attention(q, k, v, causal=True, scale=None, window=None):
    """Single-device exact attention with the same interface — the sp=1
    fallback and the numerical baseline ring_attention must match.
    Grouped-query attention: k/v may carry fewer heads (H % H_kv == 0);
    they broadcast per group (numerics baseline for the GQA flash
    kernel)."""
    rep = gqa_group(q.shape[2], k.shape[2], v.shape[2])
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    b, s, h, d = q.shape
    scale = scale if scale is not None else (1.0 / jnp.sqrt(d).astype(jnp.float32))
    s_ = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * scale
    if window is not None and window < 1:
        raise ValueError(f"window must be >= 1, got {window}")
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        if window is not None:
            pos = jnp.arange(s)
            mask = mask & (pos[:, None] - pos[None, :] < window)
        s_ = jnp.where(mask[None, None], s_, NEG_INF)
    elif window is not None:
        raise ValueError("window requires causal=True")
    p = jax.nn.softmax(s_, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
