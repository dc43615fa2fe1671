"""Mamba-2 sequence mixer (state-space duality, arXiv:2405.21060) — the
mixer of a layer whose :class:`~horovod_tpu.models.transformer.LayerSpec`
says ``mixer="mamba2"``, as ``models/moe.py`` is the FFN of a sparse one.

One layer, ``h`` the normed layer input (B, L, d_model), ``H`` heads of
``P`` features, state size ``N``, one group (B and C are shared by all
heads)::

    [z (H P) | xBC (H P + 2 N) | dt (H)] = h in_proj
    xBC = silu(causal depthwise conv1d(xBC, kernel d_conv) + conv_b)
    x (H, P), B (N), C (N) = split(xBC)
    dt = softplus(dt + dt_bias);  A = -exp(A_log)
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T          S in R^{P x N} a head
    y_t = S_t C_t + D x_t
    out = rmsnorm(y * silu(z)) * norm  @ out_proj       norm over all H P

The recurrence runs in its chunked form (:func:`ssd_chunked`): inside a
chunk of ``chunk`` positions the outputs are one masked matrix product
per head, ``(C B^T * decay) (dt x)``, and only the chunk's end state is
carried on. A head's ``(chunk, chunk)`` decay block is recomputed where
it is needed and never kept for the whole sequence: the chunks are
scanned ``block_chunks`` at a time under ``jax.checkpoint``, so the
backward holds one block's decay matrices (heads x block_chunks x chunk x
chunk floats) and the states between blocks, not ``L / chunk`` of them
(1 GiB a layer at 16,384 positions, and as much again for their
gradient). Cumulative sums of ``dt A``, the decays and the carried state
are float32 whatever ``dtype`` is; the matrix products take their
operands in ``dtype`` and accumulate in float32, like every other matmul
of the model.

The convolution with its bias and SiLU is the kernel pair of
``ops/causal_conv.py`` (:func:`conv_silu`), which reads xBC in place in
the in-projection's output; where the shapes are not whole lane tiles and
time blocks, or off the TPU outside the interpreter, it is
:func:`causal_conv1d` and ``jax.nn.silu`` as XLA ops.

Device scopes: ``hvd_ssm`` around ``hvd_ssm_in_proj``, ``hvd_ssm_conv``
(with the kernels, ``hvd_conv_fwd`` and ``hvd_conv_bwd`` inside it),
``hvd_ssm_scan`` (softplus, decays, intra-chunk products, chunk states,
the recurrence over chunks, state -> output, the D skip),
``hvd_ssm_norm`` and ``hvd_ssm_out_proj``.

Training only: there is no single-token state update, so the decode and
serve paths refuse a Mamba layer (they take one kind of layer); sequence
parallelism would have to hand the state from shard to shard and is
refused here.
"""

import dataclasses
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import causal_conv

# Chunks a step of the scan's loop takes, i.e. whose decay blocks are live
# at once (see the module docstring). Swept on the v5e at the published
# shapes (64 heads of 64, state 128, chunk 256, 16,384 positions; forward /
# gradient ms a layer, PERF.md section 6 PR 31): 2: 3.14 / 6.63, 4: 2.77 /
# 6.07, 8: 2.59 / 6.08, 16: 3.35 / 7.26. Fewer make the loop's trip count
# the cost, more the (heads, block, chunk, chunk) fusions' working set:
# 128 MiB of float32 at 8.
BLOCK_CHUNKS = 8


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    """What ``TransformerConfig.ssm_cfg`` hands the mixer; the defaults
    live there (``ssm_*``, ``norm_eps``)."""
    d_model: int
    n_heads: int
    head_dim: int
    d_state: int
    d_conv: int
    chunk: int
    norm_eps: float
    dtype: Any
    param_dtype: Any
    interpret: bool = False     # the kernels in the Pallas interpreter

    @property
    def d_inner(self):
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.d_state

    def conv_fused(self, length):
        """Whether the convolution of ``length`` positions runs as the
        kernel pair: read off the shapes (:func:`conv_silu`)."""
        return causal_conv.takes(self.conv_dim, length, self.d_conv,
                                 self.d_inner, self.interpret)


def init_ssm_params(key, cfg):
    """Mamba-2's initialisation: ``A_log = log(1..H)`` by head,
    ``dt_bias`` the inverse softplus of dt drawn log-uniform in [1e-3,
    1e-1], ``D = 1``, the convolution as torch's Conv1d draws it
    (uniform within 1 / sqrt(d_conv)), the projections normal over
    sqrt(fan in) like the model's other matrices."""
    pd, d, di, h = cfg.param_dtype, cfg.d_model, cfg.d_inner, cfg.n_heads
    k = jax.random.split(key, 5)
    bound = 1.0 / math.sqrt(cfg.d_conv)
    dt = jnp.exp(jax.random.uniform(k[2], (h,), jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "in_proj": jax.random.normal(k[0], (d, di + cfg.conv_dim + h), pd)
        / math.sqrt(d),
        "conv_w": jax.random.uniform(k[1], (cfg.d_conv, cfg.conv_dim), pd,
                                     -bound, bound),
        "conv_b": jax.random.uniform(k[4], (cfg.conv_dim,), pd, -bound,
                                     bound),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)
                         ).astype(pd),
        "D": jnp.ones((h,), pd),
        "norm": jnp.ones((di,), pd),
        "out_proj": jax.random.normal(k[3], (di, d), pd) / math.sqrt(di),
    }


def ssm_specs():
    """PartitionSpecs of :func:`init_ssm_params`: every leaf replicated
    (one group's B and C serve all heads and the gated norm runs over all
    of them, so a layer is whole on its chip)."""
    from jax.sharding import PartitionSpec as P
    return {name: P() for name in ("in_proj", "conv_w", "conv_b", "dt_bias",
                                   "A_log", "D", "norm", "out_proj")}


def causal_conv1d(x, w, b):
    """Depthwise causal convolution: ``y[t, c] = b[c] + sum_k w[k, c]
    x[t - (K - 1) + k, c]`` with zeros before the sequence. x: (B, L, C),
    w: (K, C), b: (C,) or ``None`` (no bias: the sum starts at the first
    tap's product and no zeros are built); float32 out. The padded copy
    stays in x's type (half the bytes of a float32 one at bf16); the taps
    are widened as they are read."""
    k, l = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    y = None if b is None else b.astype(jnp.float32)
    for i in range(k):
        tap = w[i] * lax.slice_in_dim(xp, i, i + l, axis=1).astype(
            jnp.float32)
        y = tap if y is None else y + tap
    return y


def conv_silu(x, w, b, interpret=False, offset=0):
    """``silu(causal_conv1d(x[..., offset:], w, b))`` in x's type, the
    channels being w's: the kernel pair of ``ops/causal_conv.py`` where
    it takes the shapes (:func:`causal_conv.takes`), read in place at that
    lane offset; otherwise the slice and the XLA form above."""
    channels = w.shape[1]
    if causal_conv.takes(channels, x.shape[1], w.shape[0], offset,
                         interpret):
        return causal_conv.conv_silu(x, w, b, offset=offset,
                                     interpret=interpret)
    x = x[..., offset:offset + channels]
    return jax.nn.silu(causal_conv1d(x, w, b)).astype(x.dtype)


def _ssd_block(state, blk, a, d):
    """``block_chunks`` chunks of the scan. state: (B, H, P, N) float32,
    the state before the block's first position. ``blk``: x (B, C, Q, H P)
    — the heads side by side, as the projection leaves them: a minor
    dimension of P = 64 would fill half the lanes of every array that
    lives outside the loop — and bm, cm (B, C, Q, N) in the activation
    type, dt (B, C, Q, H) float32. Returns ``(state after the block, y (B,
    C, Q, H P))``, the skip ``d x`` added where ``d`` is given."""
    x, dt, bm, cm = blk
    f32, dtype = jnp.float32, x.dtype
    q, h = x.shape[2], dt.shape[-1]
    x = x.reshape(x.shape[:3] + (h, x.shape[3] // h))
    # cumulative log-decay inside each chunk, heads before positions so
    # that the (Q, Q) blocks have positions on both minor dimensions
    cum = jnp.cumsum(dt * a, axis=2).transpose(0, 1, 3, 2)   # (B, C, H, Q)
    xdt = (x.astype(f32) * dt[..., None]).astype(dtype)
    # inside a chunk: y_i = sum_{j <= i} (C_i . B_j) exp(cum_i - cum_j)
    # dt_j x_j. Above the diagonal the exponent is positive and may
    # overflow: it is masked BEFORE the exp, so neither the value nor its
    # gradient sees an inf.
    seg = cum[..., :, None] - cum[..., None, :]           # (B, C, H, Q, Q)
    keep = jnp.tril(jnp.ones((q, q), bool))
    decay = jnp.exp(jnp.where(keep, seg, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", cm, bm, preferred_element_type=f32)
    y = jnp.einsum("bchij,bcjhp->bcihp",
                   (cb[:, :, None] * decay).astype(dtype), xdt,
                   preferred_element_type=f32)
    # each chunk's own end state: sum_j exp(cum_end - cum_j) dt_j x_j B_j^T
    to_end = jnp.exp(cum[..., -1:] - cum).transpose(0, 1, 3, 2)
    own = jnp.einsum("bcjhp,bcjn->bchpn",
                     (xdt.astype(f32) * to_end[..., None]).astype(dtype),
                     bm, preferred_element_type=f32)
    # the recurrence over the block's chunks
    chunk_decay = jnp.exp(cum[..., -1])                         # (B, C, H)
    before = []
    for c in range(x.shape[1]):
        before.append(state)
        state = chunk_decay[:, c, :, None, None] * state + own[:, c]
    before = jnp.stack(before, axis=1)                 # (B, C, H, P, N)
    # what the state before the chunk adds: exp(cum_i) S C_i
    carried = jnp.einsum("bcin,bchpn->bcihp", cm, before.astype(dtype),
                         preferred_element_type=f32)
    y = y + carried * jnp.exp(cum).transpose(0, 1, 3, 2)[..., None]
    if d is not None:
        y = y + d[:, None] * x.astype(f32)
    return state, y.astype(dtype).reshape(y.shape[:3] + (-1,))


def ssd_chunked(x, dt, a, bm, cm, chunk, block_chunks=BLOCK_CHUNKS, d=None):
    """The selective state-space recurrence in its chunked form.

    x: (B, L, H, P), bm / cm: (B, L, N) in the activation type; dt: (B, L,
    H) float32 (after softplus), a: (H,) float32 (negative), d: (H,)
    float32 or None. Returns ``(y (B, L, H, P) in x's type, the state
    after position L - 1 (B, H, P, N) float32)`` of ``S_t = exp(dt_t a)
    S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t (+ d x_t)``. Any L: the tail is padded with ``dt = 0``, which neither
    decays nor feeds the state. A decay between two positions of a chunk
    is the exp of a DIFFERENCE of the float32 cumulative sum of ``dt a``,
    so its exponent carries an ulp of the chunk's total log-decay (1e-4 at
    -1,000) as absolute error.
    """
    b, l, h, p = x.shape
    n = bm.shape[-1]
    q = min(chunk, l)
    kb = max(1, min(block_chunks, -(-l // q)))
    pad = -l % (q * kb)
    x = x.reshape(b, l, h * p)
    if pad:
        x, dt, bm, cm = (jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),)
                                 * (t.ndim - 2)) for t in (x, dt, bm, cm))
    nb = (l + pad) // (q * kb)

    def blocks(t):
        return jnp.moveaxis(t.reshape((b, nb, kb, q) + t.shape[2:]), 1, 0)

    @jax.checkpoint
    def body(state, blk):
        return _ssd_block(state, blk, a, d)

    state, y = lax.scan(body, jnp.zeros((b, h, p, n), jnp.float32),
                        tuple(map(blocks, (x, dt, bm, cm))))
    y = jnp.moveaxis(y, 0, 1).reshape(b, l + pad, h, p)
    return y[:, :l], state


def mamba2_mixer(params, h, cfg):
    """The mixer of one Mamba-2 layer. h: the normed layer input (B, L,
    d_model) -> ``(out (B, L, d_model) float32, state_rms (H,))``:
    ``state_rms`` is each head's root mean square of the final state
    ``S_L`` over the batch and the state's P x N entries, float32 (for the
    step's aux)."""
    dtype, f32 = cfg.dtype, jnp.float32
    hn, p, n, di = cfg.n_heads, cfg.head_dim, cfg.d_state, cfg.d_inner
    b, l, _ = h.shape
    with jax.named_scope("hvd_ssm"):
        with jax.named_scope("hvd_ssm_in_proj"):
            w = params["in_proj"].astype(dtype)
            zx = jnp.einsum("bld,de->ble", h, w[:, :di + cfg.conv_dim],
                            preferred_element_type=f32).astype(dtype)
            # the step sizes stay float32: they are summed over a chunk
            dt = jnp.einsum("bld,dh->blh", h, w[:, di + cfg.conv_dim:],
                            preferred_element_type=f32)
            z = zx[..., :di]
        with jax.named_scope("hvd_ssm_conv"):
            xbc = conv_silu(zx, params["conv_w"], params["conv_b"],
                            cfg.interpret, offset=di)
        with jax.named_scope("hvd_ssm_scan"):
            x = xbc[..., :di].reshape(b, l, hn, p)
            dt = jax.nn.softplus(dt + params["dt_bias"].astype(f32))
            y, state = ssd_chunked(
                x, dt, -jnp.exp(params["A_log"].astype(f32)),
                xbc[..., di:di + n], xbc[..., di + n:], cfg.chunk,
                d=params["D"].astype(f32))
            state_rms = jnp.sqrt(jnp.mean(jnp.square(state),
                                          axis=(0, 2, 3)))
        with jax.named_scope("hvd_ssm_norm"):
            g = y.reshape(b, l, di).astype(f32) * jax.nn.silu(z.astype(f32))
            g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                              + cfg.norm_eps)
            g = g.astype(dtype) * params["norm"].astype(dtype)
        with jax.named_scope("hvd_ssm_out_proj"):
            out = jnp.einsum("ble,ed->bld", g,
                             params["out_proj"].astype(dtype),
                             preferred_element_type=f32)
    return out, state_rms
