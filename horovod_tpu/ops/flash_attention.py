"""Fused causal attention as Pallas TPU kernels (forward + backward).

No reference analog (the reference has no model-side kernels); this is the
TPU-native "hot op" layer: attention without materializing the S x S score
matrix in HBM — in either direction, with VMEM bounded by one (block_q,
block_k) tile pair regardless of sequence length.

Grid: (batch*heads, outer blocks, inner blocks) — the innermost grid axis
streams the opposing side's blocks sequentially (TPU grids execute in
order on a core), with the online-softmax state (running max m, normalizer
l, accumulator acc) held in VMEM scratch that persists across the inner
axis. Block-level causal pruning wraps each body in ``pl.when``: pruned
cells do no compute.

The forward's tile body (``_tile_scores`` / ``_softmax_update``, shared
with the band forward):

- *State in one layout.* m and l are (block, 128) f32, every lane of a
  row holding the same value (as in JAX's own TPU flash kernel). The row
  max and row sum keep their dimension and broadcast into that state;
  ``s - m`` and the rescale of acc read it back as whole-vreg copies
  (``_lanes``). A rank-1 (block,) statistic would lie along lanes and
  cost a lane <-> sublane re-layout at every use, five or six per tile;
  the only rank-1 value left is the lse row written once per query row.
- *Operands at the input type.* q (scaled in f32, rounded back once), k
  and v go to the MXU in the type they arrive in (bf16 in one pass; f32
  is not narrowed), k contracted over its last dimension without a
  materialised transpose, p cast to v's type for the second matmul; both
  accumulate in f32, and m, l, acc and lse are f32 throughout. For bf16
  these are the scores the backward kernels recompute: their f32
  ``q * scale`` crosses the MXU rounded to bf16 as well.

Every live causal tile runs the mask; the backward kernels still cast
their tiles to f32 (PERF.md section 5 has the per-tile costs of all
three, section 6 what masking only the edge tiles measured).

Backward (FlashAttention-2 style): the forward additionally saves the
per-row log-sum-exp L = m + log(l); the backward recomputes P = exp(S - L)
blockwise and accumulates

    D_i  = rowsum(dO_i * O_i)
    dS   = P * (dO V^T - D)
    dQ_i = scale * sum_j dS_ij K_j      (grid inner axis over k blocks)
    dK_j = scale * sum_i dS_ij Q_i      (second kernel, inner axis over
    dV_j = sum_i P_ij dO_i               q blocks)

so gradients are exact without an S x S intermediate. Sequences up to
one block run as a single kernel cell; longer lengths use the largest
128-multiple divisor as the block. A causal length with no such divisor
pads up to a block multiple (still the kernels); a non-causal or band-tile
one raises — nothing here quietly computes attention densely. Callers that
want the unfused math ask for it (``attention_impl="dense"``,
``ring_attention(impl="dense")``).

``flash_attention(..., interpret=True)`` runs the kernels in the Pallas
interpreter, which is how CPU tests validate them without a TPU.

Grouped-query attention (GQA): k/v may carry H_kv < H heads with
H % H_kv == 0. The kernels never materialize expanded K/V — q-head slab
row ``bh`` simply streams kv row ``bh // group`` (forward and dq), so
the K/V HBM footprint stays at H_kv heads; dK/dV come back per q-head
and reduce over each group in one XLA sum. This includes the lse/tile
variants ring attention composes with.

Band tiles (ring attention under a sliding window): a visiting K/V shard
sits a traced number of global positions before the local queries — the
offset is a ``lax.scan`` carry, so it cannot be a static kernel
parameter. The ``_band_*`` kernels below take it as an SMEM scalar
operand: block-level compute pruning and the in-tile mask read it at run
time. K/V DMAs are NOT clamped by the offset (index maps stay static) —
the whole tile already crossed ICI to get here, so clamping would save
only local HBM reads on the at-most-one partially-banded tile per ring
step; the compute pruning is what matters.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANES = 128  # minor dimension of the forward's softmax state: one vreg row


def _window_blocks(window, block):
    """ceil(window / block): how many kv/q blocks a sliding window can
    reach past the diagonal — the single source for every kernel's
    pruning bound and the callers' DMA clamps."""
    return -(-window // block)


def _tile_mask(off, qi, kj, block, window):
    """(block, block) causal keep-mask of tile (qi, kj): q row r sits at
    global position off + qi*block + r relative to the kv origin — 0 for
    the static kernels, a traced SMEM scalar for a band tile."""
    q_pos = off + qi * block + jax.lax.broadcasted_iota(
        jnp.int32, (block, 1), 0)
    k_pos = kj * block + jax.lax.broadcasted_iota(
        jnp.int32, (1, block), 1)
    keep = q_pos >= k_pos
    if window is not None:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return keep


def _tile_scores(q_ref, k_ref, scale):
    """(block_q, block_k) f32 scores of one tile. q and k cross the MXU in
    the type they arrive in (bf16 in one pass, f32 unnarrowed), contracted
    over their last dimension with no transpose materialised, accumulated
    in f32. The scale goes on q, in f32 and rounded back to q's type once:
    for bf16 that is what the MXU makes of the backward kernels' f32
    ``q * scale``, so forward and backward see the same scores."""
    q = (q_ref[0].astype(jnp.float32) * scale).astype(q_ref.dtype)
    return jax.lax.dot_general(q, k_ref[0], (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _lanes(x, n):
    """The lane-replicated (rows, _LANES) statistic ``x`` as an operand
    against (rows, n): whole-vreg copies where n is a multiple of _LANES
    (the tile path: no lane broadcast at all), else its first column
    (blocks and head sizes off the 128 lanes)."""
    if n % _LANES:
        return x[:, :1]
    return x if n == _LANES else jnp.tile(x, (1, n // _LANES))


def _softmax_init(m_scr, l_scr, acc_scr):
    m_scr[...] = jnp.full_like(m_scr, NEG_INF)
    l_scr[...] = jnp.zeros_like(l_scr)
    acc_scr[...] = jnp.zeros_like(acc_scr)


def _softmax_update(s, v_ref, m_scr, l_scr, acc_scr):
    """One online-softmax step over the (masked) f32 scores ``s`` of a
    tile. The running max and normaliser are (block, _LANES) f32, every
    lane of a row holding the same value; the row reductions keep their
    dimension and broadcast into them, so no value changes between the
    lane and the sublane layout per tile (see :func:`_lanes`)."""
    m_prev = m_scr[...]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - _lanes(m_next, s.shape[-1]))
    m_scr[...] = m_next
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=-1, keepdims=True)
    v = v_ref[0]
    acc_scr[...] = acc_scr[...] * _lanes(alpha, v.shape[-1]) + jnp.dot(
        p.astype(v.dtype), v, preferred_element_type=jnp.float32)


def _softmax_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr):
    l = jnp.maximum(l_scr[...], 1e-30)
    o_ref[0] = (acc_scr[...] / _lanes(l, acc_scr.shape[-1])).astype(
        o_ref.dtype)
    # the one rank-1 value: a row of lse per query block, once per row
    lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, 0]


def _softmax_scratch(block, d):
    return [pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, _LANES), jnp.float32),
            pltpu.VMEM((block, d), jnp.float32)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, block, num_kv, scale, causal, window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    # Causal block pruning: kv blocks strictly above the diagonal
    # contribute nothing — skip their compute entirely. A sliding window
    # additionally prunes blocks wholly below q_block_start - window + 1.
    live = jnp.logical_or(not causal, kj <= qi)
    if window is not None:
        live = jnp.logical_and(live,
                               kj >= qi - _window_blocks(window, block))

    @pl.when(live)
    def _body():
        s = _tile_scores(q_ref, k_ref, scale)
        if causal:
            s = jnp.where(_tile_mask(0, qi, kj, block, window), s, NEG_INF)
        _softmax_update(s, v_ref, m_scr, l_scr, acc_scr)

    last = qi if causal else num_kv - 1

    @pl.when(kj == last)
    def _finalize():
        _softmax_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, block, num_kv, scale, causal, window=None):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = jnp.logical_or(not causal, kj <= qi)
    if window is not None:
        live = jnp.logical_and(live,
                               kj >= qi - _window_blocks(window, block))

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale       # (block, D)
        do = do_ref[0].astype(jnp.float32)             # (block, D)
        lse = lse_ref[0, 0]                            # (block,)
        delta = delta_ref[0, 0]                        # (block,)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            k_pos = kj * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            keep = q_pos >= k_pos
            if window is not None:
                keep = jnp.logical_and(keep, q_pos - k_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # (block, block)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    last = qi if causal else num_kv - 1

    @pl.when(kj == last)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, block, num_q, scale,
                    causal, window=None):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == (ki if causal else 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    # Under causality only q blocks at or below the diagonal contribute;
    # a sliding window additionally bounds them to ki + ceil(W/block).
    live = jnp.logical_or(not causal, qi >= ki)
    if window is not None:
        live = jnp.logical_and(live,
                               qi <= ki + _window_blocks(window, block))

    @pl.when(live)
    def _body():
        k = k_ref[0].astype(jnp.float32)               # (block, D)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block + jax.lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            k_pos = ki * block + jax.lax.broadcasted_iota(
                jnp.int32, (1, block), 1)
            keep = q_pos >= k_pos
            if window is not None:
                keep = jnp.logical_and(keep, q_pos - k_pos < window)
            s = jnp.where(keep, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                  # (q_block, k_block)
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        # q already carries `scale`, so ds^T q absorbs it.
        dk_scr[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _band_live(off, qi, kj, block, window):
    """Block-level pruning for a band tile: live iff some (q, k) pair has
    0 <= q_pos - k_pos [< window]. off is a traced SMEM scalar."""
    dist_max = off + (qi + 1) * block - 1 - kj * block
    live = dist_max >= 0
    if window is not None:
        dist_min = off + qi * block - ((kj + 1) * block - 1)
        live = jnp.logical_and(live, dist_min < window)
    return live


def _band_fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                     m_scr, l_scr, acc_scr, *, block, num_kv, scale,
                     window):
    """Forward tile at a traced global offset (see module docstring).
    Rows fully masked within the tile finalize with lse ~ NEG_INF, so the
    ring's log-sum-exp merge weights them to zero — same contract as
    _tile_fwd_math."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    off = off_ref[0]

    @pl.when(kj == 0)
    def _init():
        _softmax_init(m_scr, l_scr, acc_scr)

    @pl.when(_band_live(off, qi, kj, block, window))
    def _body():
        s = _tile_scores(q_ref, k_ref, scale)
        s = jnp.where(_tile_mask(off, qi, kj, block, window), s, NEG_INF)
        _softmax_update(s, v_ref, m_scr, l_scr, acc_scr)

    @pl.when(kj == num_kv - 1)
    def _finalize():
        _softmax_finalize(o_ref, lse_ref, m_scr, l_scr, acc_scr)


def _band_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dq_ref, dq_scr, *, block, num_kv, scale,
                    window):
    """dQ contribution of one band tile, recomputing P from the GLOBAL
    lse (finite for every live row, so masked entries underflow to exact
    zeros — no garbage-row hazard in the backward)."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    off = off_ref[0]

    @pl.when(kj == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(_band_live(off, qi, kj, block, window))
    def _body():
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = jnp.where(_tile_mask(off, qi, kj, block, window), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        dq_scr[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kj == num_kv - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _band_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, block,
                     num_q, scale, window):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    off = off_ref[0]

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(_band_live(off, qi, ki, block, window))
    def _body():
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        q = q_ref[0].astype(jnp.float32) * scale
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        s = jnp.where(_tile_mask(off, qi, ki, block, window), s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dv_scr[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None])
        # q already carries `scale`, so ds^T q absorbs it.
        dk_scr[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _named_pallas_call(name, kernel, **kwargs):
    """``pl.pallas_call`` under a name of its own: the kernel's ``name=``
    plus a ``jax.named_scope`` of the same name around the call alone, so
    that in a device trace the scope's time is the kernel's. The names
    (``hvd_flash_fwd`` / ``_dq`` / ``_dkv``, ``hvd_flash_band_*`` for the
    ring tiles) are what the per-kernel metrics select on; none contains
    a step-program phase name (``hvd_forward`` ...), so the phase an op is
    filed under does not change."""
    call = pl.pallas_call(kernel, name=name, **kwargs)

    def run(*args):
        with jax.named_scope(name):
            return call(*args)

    return run


def _pick_block(s, block_size):
    """Largest kernel-friendly block that divides s, or None (ragged: the
    caller pads or raises). Short sequences use one block; otherwise
    blocks stay multiples of 128 so tiles land on the (8, 128) TPU lanes
    — a 640-long sequence gets block 128."""
    if s <= block_size:
        return s
    for b in range((block_size // 128) * 128, 0, -128):
        if s % b == 0:
            return b
    return None


def _ragged_error(s, block_size):
    return ValueError(
        f"flash attention: sequence length {s} exceeds one block "
        f"({block_size}) and has no 128-multiple block dividing it. Only "
        "a causal tile at offset 0 can pad (the mask hides the padded "
        "keys); pad the sequence to a multiple of 128, or ask for the "
        "unfused math (attention_impl='dense' / ring impl='dense')")


def _softmax_scale(d, scale=None):
    """The factor on q.k before the softmax: ``scale`` where the model
    states one, else 1 / sqrt(head size). A Python float: it is folded
    into the kernels as a constant."""
    return 1.0 / (d ** 0.5) if scale is None else float(scale)


def _to_slab(x):
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _from_slab(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=True, block_size=512, interpret=False,
                    window=None, scale=None):
    """Fused attention. q/k/v: (B, S, H, D); returns (B, S, H, D). v may
    have a head size of its own, which is then the output's (q and k of
    192 beside v of 128 in a latent-attention layer).

    Same contract as ring_attention/dense_attention (parallel/
    ring_attention.py) — drop-in for the per-shard attention inside the
    transformer. ``window`` (requires causal) restricts each query to the
    previous ``window`` positions (Mistral-style sliding window): both
    compute and K/V DMAs prune outside the band, so cost scales with
    S * window instead of S^2. ``scale`` multiplies q.k before the softmax
    (None: 1 / sqrt(D)).
    """
    out, _ = _flash_fwd_impl(q, k, v, causal, block_size, interpret,
                             window, scale)
    return out


def _gqa_group(q, k, v):
    """Query-heads-per-kv-head ratio (validated, incl. K==V head match);
    1 = plain MHA. Slab row bh = b*Hq+hq maps to K/V slab row
    bh // group (valid because Hq = group * Hkv, so consecutive `group`
    q-head rows share one kv head)."""
    from ..parallel.ring_attention import gqa_group
    return gqa_group(q.shape[2], k.shape[2], v.shape[2])


def _pad_seq(x, s_pad):
    s = x.shape[1]
    if s == s_pad:
        return x
    return jnp.pad(x, ((0, 0), (0, s_pad - s), (0, 0), (0, 0)))


def _flash_fwd_impl(q, k, v, causal, block_size, interpret, window=None,
                    scale=None):
    """Returns (out, lse) with lse shaped (B*H, 1, S). q and k share one
    head size, v and out another (``v.shape[-1]``: latent attention has q
    and k of 192 beside v of 128); one size for all four is the usual
    case."""
    b, s, h, d = q.shape
    dv = v.shape[-1]
    group = _gqa_group(q, k, v)
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    scale = _softmax_scale(d, scale)
    block = _pick_block(s, block_size)
    if block is None and causal:
        # Ragged causal length: pad the sequence up to a block multiple
        # and slice the result — padded K rows sit at FUTURE positions,
        # so the causal mask hides them from every real query, and real
        # K rows feed padded queries whose outputs are discarded (their
        # zero cotangents contribute nothing in backward). This keeps
        # O(S * block) memory where unfused attention would be O(S^2).
        s_pad = -(-s // 128) * 128
        bs = max(block_size, 128)  # 128 is the minimum ragged tile
        out, lse = _flash_fwd_impl(
            _pad_seq(q, s_pad), _pad_seq(k, s_pad), _pad_seq(v, s_pad),
            causal, bs, interpret, window, scale)
        return out[:, :s], lse[:, :, :s]
    if block is None:
        # non-causal ragged tail: the kernel has no length concept to
        # hide padded K rows
        raise _ragged_error(s, block_size)

    n = s // block
    qs, ks, vs = _to_slab(q), _to_slab(k), _to_slab(v)
    kernel = functools.partial(_fwd_kernel, block=block, num_kv=n,
                               scale=scale, causal=causal, window=window)
    # Causal pruning must also kill the K/V DMAs, not just the compute:
    # map pruned cells (kj > qi) to the diagonal block they already hold,
    # so the pipeline sees an unchanged block index and skips the copy —
    # otherwise upper-triangle cells still stream K/V from HBM, roughly
    # doubling memory traffic at long sequence lengths. Under GQA the
    # K/V slab has Hkv rows; q-head row bh reads kv row bh // group, so
    # grouped-query attention never materializes expanded K/V.
    if causal and window is not None:
        wb = _window_blocks(window, block)
        kv_map = lambda bh, qi, kj: (bh // group,  # noqa: E731
                                     jnp.clip(kj, qi - wb, qi), 0)
    elif causal:
        kv_map = lambda bh, qi, kj: (bh // group,  # noqa: E731
                                     jnp.minimum(kj, qi), 0)
    else:
        kv_map = lambda bh, qi, kj: (bh // group, kj, 0)  # noqa: E731
    out, lse = _named_pallas_call(
        "hvd_flash_fwd",
        kernel,
        grid=(b * h, n, n),
        in_specs=[
            pl.BlockSpec((1, block, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block, d), kv_map),
            pl.BlockSpec((1, block, dv), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block, dv), lambda bh, qi, kj: (bh, qi, 0)),
            # lse rides as (B*H, 1, block-of-S): TPU lowering needs the
            # trailing two block dims to tile (8, 128) or match the array.
            pl.BlockSpec((1, 1, block), lambda bh, qi, kj: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=_softmax_scratch(block, dv),
        interpret=interpret,
    )(qs, ks, vs)
    return _from_slab(out, b, h), lse


def _flash_fwd(q, k, v, causal, block_size, interpret, window=None,
               scale=None):
    out, lse = _flash_fwd_impl(q, k, v, causal, block_size, interpret,
                               window, scale)
    return out, (q, k, v, out, lse)


def _tile_lse(q, k, v, causal, window, block_size, interpret):
    """Static-offset tile with lse (B, H, S): ring attention's diagonal
    (and fully-visible) tile compute — GQA and window ride the static
    kernels' own masks and DMA clamps."""
    b, s, h, d = q.shape
    out, lse = _flash_fwd_impl(q, k, v, causal, block_size, interpret,
                               window)
    return out, lse.reshape(b, h, s)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention_with_lse(q, k, v, causal=True, block_size=512,
                             interpret=False, window=None):
    """Like :func:`flash_attention` but also returns the per-row
    log-sum-exp, shaped (B, H, S) — the quantity needed to merge partial
    attention results exactly (ring attention's cross-shard combine:
    ``out = sum_j out_j * exp(lse_j - logsumexp_j lse_j)``). Supports
    grouped-query K/V and sliding windows like the plain kernel."""
    return _tile_lse(q, k, v, causal, window, block_size, interpret)


def _flash_lse_fwd(q, k, v, causal, block_size, interpret, window):
    out, lse = flash_attention_with_lse(q, k, v, causal, block_size,
                                        interpret, window)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, block_size, interpret, window, res, g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    b, s, h, d = q.shape
    # The lse cotangent enters dS as +P*g_lse, i.e. exactly -delta's slot:
    # dS = P * (dO V^T - (delta - g_lse))  — see _flash_bwd's math.
    return _flash_bwd_impl(causal, block_size, interpret, q, k, v, out,
                           lse.reshape(b * h, 1, s), g_out, g_lse, window)


flash_attention_with_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def _flash_bwd(causal, block_size, interpret, window, scale, res, g):
    q, k, v, out, lse = res
    return _flash_bwd_impl(causal, block_size, interpret, q, k, v, out,
                           lse, g, None, window, scale=scale)


def _flash_bwd_impl(causal, block_size, interpret, q, k, v, out, lse, g,
                    g_lse, window=None, delta=None, scale=None):
    """``delta`` (B*H, 1, S) f32, when given, replaces the rowsum(dO*O)
    pass (``out`` may then be None) — ring attention computes one global
    delta and feeds every tile's backward from it."""
    b, s, h, d = q.shape
    dv = v.shape[-1]    # v, out and their cotangents; q and k have d
    group = _gqa_group(q, k, v)
    h_kv = k.shape[2]
    scale = _softmax_scale(d, scale)
    block = _pick_block(s, block_size)
    if block is None:
        # ragged causal length: mirror the forward's pad-to-block path.
        # Padded rows carry zero cotangents and out=0 (delta=0); lse pads
        # to +1e30 so p = exp(score - lse) underflows to exactly 0 for
        # padded queries (0 * inf NaNs are impossible).
        if not causal:
            raise _ragged_error(s, block_size)
        s_pad = -(-s // 128) * 128
        bs = max(block_size, 128)  # mirror of the forward's ragged choice
        lse_pad = jnp.pad(lse, ((0, 0), (0, 0), (0, s_pad - s)),
                          constant_values=1e30)
        g_lse_pad = None
        if g_lse is not None:
            g_lse_pad = jnp.pad(
                g_lse.reshape(b * h, 1, s),
                ((0, 0), (0, 0), (0, s_pad - s))).reshape(b, h, s_pad)
        delta_pad = None
        if delta is not None:
            delta_pad = jnp.pad(delta, ((0, 0), (0, 0), (0, s_pad - s)))
        dq, dk, dv_ = _flash_bwd_impl(
            causal, bs, interpret, _pad_seq(q, s_pad),
            _pad_seq(k, s_pad), _pad_seq(v, s_pad),
            None if out is None else _pad_seq(out, s_pad),
            lse_pad, _pad_seq(g, s_pad), g_lse_pad, window, delta_pad,
            scale)
        return dq[:, :s], dk[:, :s], dv_[:, :s]
    n = s // block

    qs, ks, vs = _to_slab(q), _to_slab(k), _to_slab(v)
    dos = _to_slab(g)
    if delta is None:
        # D_i = rowsum(dO * O): cheap elementwise pass outside the
        # kernels. An lse cotangent enters dS as +P*g_lse — the same slot
        # delta occupies with opposite sign, so it folds in here.
        os_ = _to_slab(out)
        delta = jnp.sum(dos.astype(jnp.float32) * os_.astype(jnp.float32),
                        axis=-1)[:, None, :]            # (B*H, 1, S)
        if g_lse is not None:
            delta = delta - g_lse.astype(jnp.float32).reshape(b * h, 1, s)

    def q_blk(w):
        return pl.BlockSpec((1, block, w), lambda bh, i, j: (bh, i, 0))

    wb = None if window is None else _window_blocks(window, block)
    # same DMA clamp as the forward: pruned (j > i) cells re-address the
    # diagonal K/V block instead of streaming a block they won't use
    # (K/V rows indexed through // group for GQA, as in the forward);
    # a window additionally clamps below the band start
    if causal and window is not None:
        kv_map = lambda bh, i, j: (bh // group,  # noqa: E731
                                   jnp.clip(j, i - wb, i), 0)
    elif causal:
        kv_map = lambda bh, i, j: (bh // group,  # noqa: E731
                                   jnp.minimum(j, i), 0)
    else:
        kv_map = lambda bh, i, j: (bh // group, j, 0)  # noqa: E731
    vec_q = pl.BlockSpec((1, 1, block), lambda bh, i, j: (bh, 0, i))

    dq = _named_pallas_call(
        "hvd_flash_dq",
        functools.partial(_bwd_dq_kernel, block=block, num_kv=n,
                          scale=scale, causal=causal, window=window),
        grid=(b * h, n, n),
        in_specs=[q_blk(d), pl.BlockSpec((1, block, d), kv_map),
                  pl.BlockSpec((1, block, dv), kv_map), q_blk(dv), vec_q,
                  vec_q],
        out_specs=q_blk(d),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
    )(qs, ks, vs, dos, lse, delta)

    # dkv grid: (bh, k block, q block) — inner axis streams q blocks.
    # Pruned cells here are j (q block) < i (k block): clamp the q-side
    # DMAs up to the diagonal.
    if causal and window is not None:
        q_map = lambda bh, i, j: (bh, jnp.clip(j, i, i + wb), 0)  # noqa: E731
        vec_in = pl.BlockSpec(
            (1, 1, block),
            lambda bh, i, j: (bh, 0, jnp.clip(j, i, i + wb)))
    elif causal:
        q_map = lambda bh, i, j: (bh, jnp.maximum(j, i), 0)  # noqa: E731
        vec_in = pl.BlockSpec((1, 1, block),
                              lambda bh, i, j: (bh, 0, jnp.maximum(j, i)))
    else:
        q_map = lambda bh, i, j: (bh, j, 0)  # noqa: E731
        vec_in = pl.BlockSpec((1, 1, block), lambda bh, i, j: (bh, 0, j))
    k_map = lambda bh, i, j: (bh // group, i, 0)  # noqa: E731
    # dK/dV accumulate across the `group` query heads sharing each kv
    # head. The kernel writes per-q-head partials (scratch accumulation
    # across grid dim 0 would be clobbered by the inner k-block loop);
    # the group-sum happens outside as one cheap XLA reduction. With
    # group > 1 the partials stay f32 so that reduction keeps the f32
    # accumulation used everywhere else (casting to bf16 before the
    # group-sum would lose the low bits the sum is meant to carry).
    part_dtype = jnp.float32 if group > 1 else k.dtype
    dk, dv_ = _named_pallas_call(
        "hvd_flash_dkv",
        functools.partial(_bwd_dkv_kernel, block=block, num_q=n,
                          scale=scale, causal=causal, window=window),
        grid=(b * h, n, n),
        in_specs=[pl.BlockSpec((1, block, d), q_map),
                  pl.BlockSpec((1, block, d), k_map),
                  pl.BlockSpec((1, block, dv), k_map),
                  pl.BlockSpec((1, block, dv), q_map), vec_in, vec_in],
        out_specs=[q_blk(d), q_blk(dv)],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), part_dtype),
                   jax.ShapeDtypeStruct((b * h, s, dv), part_dtype)],
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        interpret=interpret,
    )(qs, ks, vs, dos, lse, delta)

    if group > 1:
        dk = dk.reshape(b, h_kv, group, s, d).sum(axis=2).reshape(
            b * h_kv, s, d).astype(k.dtype)
        dv_ = dv_.reshape(b, h_kv, group, s, dv).sum(axis=2).reshape(
            b * h_kv, s, dv).astype(v.dtype)
    return (_from_slab(dq, b, h), _from_slab(dk, b, h_kv),
            _from_slab(dv_, b, h_kv))


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# Ring-attention band tiles: traced-offset kernels (see module docstring).
# These are NOT differentiable entry points — ring_attention's custom VJP
# calls the forward during its ring pass and the backward during the
# re-rotation, feeding both from its own saved lse/delta.

def _band_tile_fwd(q, k, v, off, window, block_size, interpret):
    """(out, lse) for one causal band tile whose q rows sit ``off``
    (traced) global positions after the visiting kv tile's origin.
    GQA-aware; a ragged length raises (a padded key at a traced offset
    cannot be hidden by the mask)."""
    b, s, h, d = q.shape
    group = _gqa_group(q, k, v)
    scale = _softmax_scale(d)
    block = _pick_block(s, block_size)
    if block is None:
        raise _ragged_error(s, block_size)
    n = s // block
    qs, ks, vs = _to_slab(q), _to_slab(k), _to_slab(v)
    off_arr = jnp.asarray(off, jnp.int32).reshape(1)
    out, lse = _named_pallas_call(
        "hvd_flash_band_fwd",
        functools.partial(_band_fwd_kernel, block=block, num_kv=n,
                          scale=scale, window=window),
        grid=(b * h, n, n),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block, d),
                         lambda bh, qi, kj: (bh // group, kj, 0)),
            pl.BlockSpec((1, block, d),
                         lambda bh, qi, kj: (bh // group, kj, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block, d), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, 1, block), lambda bh, qi, kj: (bh, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, 1, s), jnp.float32),
        ],
        scratch_shapes=_softmax_scratch(block, d),
        interpret=interpret,
    )(off_arr, qs, ks, vs)
    return _from_slab(out, b, h), lse.reshape(b, h, s)


def _band_tile_bwd(q, k, v, g, lse, delta, off, window, block_size,
                   interpret):
    """f32 (dq, dk, dv) for one band tile, recomputed from the GLOBAL
    lse (B, H, S) and delta (B, H, S). dk/dv carry the reduced (GQA)
    head count."""
    b, s, h, d = q.shape
    group = _gqa_group(q, k, v)
    h_kv = k.shape[2]
    scale = _softmax_scale(d)
    block = _pick_block(s, block_size)
    if block is None:
        raise _ragged_error(s, block_size)
    n = s // block
    qs, ks, vs, dos = _to_slab(q), _to_slab(k), _to_slab(v), _to_slab(g)
    lse_s = lse.astype(jnp.float32).reshape(b * h, 1, s)
    delta_s = delta.astype(jnp.float32).reshape(b * h, 1, s)
    off_arr = jnp.asarray(off, jnp.int32).reshape(1)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_blk = pl.BlockSpec((1, block, d), lambda bh, i, j: (bh, i, 0))
    kv_blk = pl.BlockSpec((1, block, d),
                          lambda bh, i, j: (bh // group, j, 0))
    vec_q = pl.BlockSpec((1, 1, block), lambda bh, i, j: (bh, 0, i))
    dq = _named_pallas_call(
        "hvd_flash_band_dq",
        functools.partial(_band_dq_kernel, block=block, num_kv=n,
                          scale=scale, window=window),
        grid=(b * h, n, n),
        in_specs=[smem, q_blk, kv_blk, kv_blk, q_blk, vec_q, vec_q],
        out_specs=q_blk,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
    )(off_arr, qs, ks, vs, dos, lse_s, delta_s)
    # dkv grid: (bh, k block, q block) — q-side operands stream over the
    # inner axis; dk/dv come back per q-head and group-reduce outside
    # (same layout decisions as _flash_bwd_impl).
    q_in = pl.BlockSpec((1, block, d), lambda bh, i, j: (bh, j, 0))
    vec_in = pl.BlockSpec((1, 1, block), lambda bh, i, j: (bh, 0, j))
    k_in = pl.BlockSpec((1, block, d), lambda bh, i, j: (bh // group, i, 0))
    dk_out = pl.BlockSpec((1, block, d), lambda bh, i, j: (bh, i, 0))
    dk, dv = _named_pallas_call(
        "hvd_flash_band_dkv",
        functools.partial(_band_dkv_kernel, block=block, num_q=n,
                          scale=scale, window=window),
        grid=(b * h, n, n),
        in_specs=[smem, q_in, k_in, k_in, q_in, vec_in, vec_in],
        out_specs=[dk_out, dk_out],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), jnp.float32)] * 2,
        scratch_shapes=[pltpu.VMEM((block, d), jnp.float32),
                        pltpu.VMEM((block, d), jnp.float32)],
        interpret=interpret,
    )(off_arr, qs, ks, vs, dos, lse_s, delta_s)
    if group > 1:
        dk = dk.reshape(b, h_kv, group, s, d).sum(axis=2).reshape(
            b * h_kv, s, d)
        dv = dv.reshape(b, h_kv, group, s, d).sum(axis=2).reshape(
            b * h_kv, s, d)
    return (_from_slab(dq, b, h), _from_slab(dk, b, h_kv),
            _from_slab(dv, b, h_kv))


def _assert_finite_lse(lse):
    """Interpret/debug-mode contract check for the band backward path
    (round-4 verdict #7)."""
    import numpy as _np
    lse = _np.asarray(lse)
    if not bool(_np.all(_np.isfinite(lse) & (lse > -1e20))):
        raise FloatingPointError(
            "band backward kernels require the GLOBAL lse to be finite "
            "for every query row: a row whose softmax saw no live key "
            "anywhere carries lse ~ -1e30, and exp(s - lse) in "
            "_band_dq/_band_dkv then produces garbage (non-NaN, wrong) "
            "gradients. The ring layout guarantees the precondition — "
            "every row's diagonal tile contributes at least its own key "
            "— but a standalone caller feeding a windowed non-ring "
            "layout must ensure every row attends >= 1 key (see "
            "_tile_bwd_dispatch).")


def _tile_bwd_dispatch(q, k, v, g, lse, delta, off, causal, window,
                       block_size, interpret):
    """Backward for one ring tile given the GLOBAL lse/delta (B, H, S):
    static kernels for the diagonal (off=None, offset 0) and
    fully-visible (causal=False) tiles, band kernels for traced offsets.
    Returns f32 (dq, dk, dv) with dk/dv at
    the reduced (GQA) head count — the ring's traveling-accumulator
    contract (parallel/ring_attention.py::_ring_core_bwd).

    PRECONDITION (band tiles, off is not None): ``lse`` must be finite
    (> -1e20) for EVERY query row. Rows that are dead *in this tile* are
    fine — their scores mask to -1e30 and exp(-1e30 - lse) underflows to
    exact zero — but a row that is dead *globally* has lse ~ -1e30 and
    exp(s - lse) silently fabricates gradients. Ring attention
    guarantees the precondition (each row's diagonal tile always sees
    its own key); interpret mode asserts it for any other caller."""
    b, s, h, d = q.shape
    if off is not None:
        if interpret:
            jax.debug.callback(_assert_finite_lse, lse)
        # band tile: causal-with-offset (+ optional window)
        dq, dk, dv = _band_tile_bwd(q, k, v, g, lse, delta, off,
                                    window, block_size, interpret)
    else:
        # static tile: diagonal (causal, window) or fully-visible; the
        # causal-ragged case takes _flash_bwd_impl's pad-to-block path
        dq, dk, dv = _flash_bwd_impl(
            causal, block_size, interpret, q, k, v, None,
            lse.astype(jnp.float32).reshape(b * h, 1, s), g, None,
            window if causal else None,
            delta.astype(jnp.float32).reshape(b * h, 1, s))
    return (dq.astype(jnp.float32), dk.astype(jnp.float32),
            dv.astype(jnp.float32))


def paged_attention_decode(q, k_pages, v_pages, page_table, lengths):
    """Single-token decode attention over a PAGED KV cache (one layer).

    The serving analog of :func:`~horovod_tpu.parallel.ring_attention.
    dense_attention` (vLLM's PagedAttention read side): each sequence's
    K/V live scattered across fixed-size pages of a shared pool
    (serve/kv_cache.py) and ``page_table`` names the pages in order.
    This is the XLA formulation — gather the pages into a contiguous
    (B, P*page, h_kv, D) view, then run the one-row attention math.
    The gather is layout-only (no arithmetic), so the numerics are
    EXACTLY dense_attention's row: same 1/sqrt(D) multiply, same
    NEG_INF fill, same f32 softmax, same p.astype(v.dtype) before the
    output contraction. When the gathered extent (pages * page_size)
    equals the padded forward length, the decode logits are bit-equal
    to the forward row at that position — the invariant
    tests/test_serving.py pins (see docs/serving.md "Numerics").

    q:          (B, 1, H, D) — the new token's query.
    k_pages:    (P, page, h_kv, D) — this layer's key-page pool.
    v_pages:    (P, page, h_kv, D) — this layer's value-page pool.
    page_table: (B, pages_per_seq) int32 — page ids per sequence, in
                order; unused slots point at page 0 (the null page).
    lengths:    (B,) int32 — visible tokens per sequence INCLUDING the
                one just written (so the mask is ``pos < lengths``).

    Returns (B, 1, H, D) in q.dtype.
    """
    from ..parallel.ring_attention import gqa_group
    b = q.shape[0]
    k = k_pages[page_table].reshape(b, -1, k_pages.shape[2],
                                    k_pages.shape[3])
    v = v_pages[page_table].reshape(b, -1, v_pages.shape[2],
                                    v_pages.shape[3])
    rep = gqa_group(q.shape[2], k.shape[2], v.shape[2])
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    d = q.shape[3]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    idx = jax.lax.broadcasted_iota(jnp.int32, s.shape, 3)
    s = jnp.where(idx < lengths[:, None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # Contract without the singleton q dim: the (B,H,K)x(B,K,H,D) form
    # lowers to the same per-row dot the full (Q,K) gemm uses, which the
    # 4-dim q=1 einsum does not (it differs by ~1 ulp on CPU).
    out = jnp.einsum("bhk,bkhd->bhd", p[:, :, 0].astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out[:, None].astype(q.dtype)
