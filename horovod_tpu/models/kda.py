"""Kimi Delta Attention (KDA, arXiv:2510.26692) — the mixer of a layer whose
:class:`~horovod_tpu.models.transformer.LayerSpec` says ``mixer="kda"``: a
gated delta rule whose state decays by key channel, as ``models/ssm.py`` is
the mixer of a Mamba-2 layer.

One layer, ``h`` the normed layer input (B, L, d_model), ``H`` heads of
``D`` features for keys and values alike::

    q = l2norm(silu(conv(h wq)))   k = l2norm(silu(conv(h wk)))
    v = silu(conv(h wv))                           conv: causal, depthwise
    g = -exp(A_log) * softplus((h w_fa) w_fb + dt_bias)   (H, D) float32,
                                        the log-decay of each key channel
    beta = sigmoid(h w_b)                                  (H,)
    S' = Diag(exp(g_t)) S_{t-1}
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T       S in R^{D x D} a head
    o_t = S_t^T q_t / sqrt(D)
    out = (rmsnorm_D(o_t) * norm * sigmoid((h w_ga) w_gb)) wo

The recurrence runs in its chunked form, in one of two forms that the
head size chooses (one algorithm, no flag): a head of whole 128-lane
tiles — every published KDA model's — runs the Pallas kernel pair of
``ops/kda_scan.py`` (:func:`~horovod_tpu.ops.kda_scan.kda_scan`), which
keeps the state and a chunk's intermediates in VMEM; any other head size
(the tests' toy models) runs :func:`kda_chunked`, the same arithmetic in
XLA ops, which is also the kernels' oracle. With ``G``
the cumulative sum of ``g`` inside a chunk of ``C`` positions and ``S_0``
the state before it, the chunk's ``C`` rank-1 updates are one triangular
system (the WY form of the delta rule)::

    A_ij = (k_i * exp(G_i - G_j)) . k_j              j < i
    P_ij = (q_i * exp(G_i - G_j)) . k_j / sqrt(D)    j <= i
    T = (I + Diag(beta) A)^-1                        unit lower triangular
    w = T (beta * k * exp(G)),   u = T (beta * v)
    n = u - w S_0                                    the chunk's new values
    o = (q * exp(G)) S_0 / sqrt(D) + P n
    S_C = Diag(exp(G_C)) S_0 + (k * exp(G_C - G))^T n

``exp(G_i - G_j)`` does not factor into ``exp(G_i) exp(-G_j)`` over a
chunk: a channel that decays hard (``g`` of -20 a position) overflows
``exp(-G_j)`` within a few positions. So a chunk is cut into sub-chunks
of :data:`SUB_CHUNK` (8) positions. Between a row's sub-chunk and an
earlier one both factors are taken relative to the row's sub-chunk's start
— each is then an exp of something <= 0 — and the product is a matmul;
inside a sub-chunk (the diagonal blocks) the ``(SUB_CHUNK, SUB_CHUNK, D)``
decays are written out and summed over ``D``. Every exponent is masked before the
exp, so neither a value nor a gradient sees an inf.

``T`` is a solve in float32: the diagonal sub-chunks by forward
substitution, row by row, merged pairwise up to the chunk (the inverse of
``[[a, 0], [c, b]]`` is ``[[a^-1, 0], [-b^-1 c a^-1, b^-1]]``). A Neumann
series would be a few matmuls and is not used: repeated tokens give keys
that are nearly equal, entries of ``A`` near 1, and powers of ``A`` that
cancel badly.

Cumulative sums, decays, the solve and the carried state are float32
whatever ``dtype`` is; the matrix products take their operands in
``dtype`` and accumulate in float32, like every other matmul of the
model. :func:`kda_chunked` scans the chunks :data:`BLOCK_CHUNKS` at a
time under ``jax.checkpoint`` (one block's ``A``, ``P``, ``T`` are live,
not a layer's) and its state goes through HBM once a chunk; the kernels
cut a chunk of 64 their own way (ops/kda_scan.py) and write only ``o``,
the final state and, for the backward, the state before each chunk.

Device scopes: ``hvd_kda`` around ``hvd_kda_in_proj`` (q, k, v, the two
low-rank pairs, beta), ``hvd_kda_conv``, ``hvd_kda_scan`` (l2norm,
softplus and decays, the chunk's products and solve, the recurrence over
chunks — with the kernels, ``hvd_kda_fwd`` and ``hvd_kda_bwd`` inside it),
``hvd_kda_norm`` and ``hvd_kda_out_proj``.

Training only: there is no single-token state update, so the decode and
serve paths refuse a KDA layer; sequence parallelism would have to hand
the state from shard to shard and is refused here.
"""

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from ..ops import kda_scan
from ..utils.logging import get_logger
from .ssm import causal_conv1d

# Positions of a chunk and of a sub-chunk (see the module docstring) and the
# chunks a step of the scan's loop takes in the XLA form: the
# implementation's, no key of a published configuration. Swept on the v5e
# at the published
# shapes (32 heads of 128, 16,384 positions; chunk, sub-chunk, chunks a step:
# forward / gradient ms a layer, PERF.md section 6 PR 33): 64, 16, 8: 22.9 /
# 96.4; 64, 8, 8: 22.2 / 92.0; 64, 8, 4: 22.3 / 74.6; 64, 4, 8: 23.6 / 95.6;
# 128, 8, 4: 28.3 / 118.2; 32, 8, 8: 20.7 / 64.9; 32, 8, 4 (taken): 19.4 /
# 61.8; 32, 16, 8: 23.2 / 72.1; 32, 8, 16: 19.2 / 82.0; 16, 8, 16: 22.7 /
# 70.2. A larger chunk pays for its (chunk, chunk) system, a smaller
# sub-chunk for its copies of k, more positions a step for the working set
# of the backward, fewer for the loop's trip count.
CHUNK = 32
SUB_CHUNK = 8
BLOCK_CHUNKS = 4


@dataclasses.dataclass(frozen=True)
class KDAConfig:
    """What ``TransformerConfig.kda_cfg`` hands the mixer; the defaults
    live there (``kda_*``, ``norm_eps``)."""
    d_model: int
    n_heads: int
    head_dim: int
    d_conv: int
    norm_eps: float
    dtype: Any
    param_dtype: Any
    interpret: bool = False     # the kernels in the Pallas interpreter

    @property
    def d_inner(self):
        return self.n_heads * self.head_dim

    @property
    def fused(self):
        """Whether the recurrence runs as the kernel pair: read off the
        head size alone."""
        return kda_scan.takes(self.head_dim)


@functools.lru_cache(maxsize=None)
def _say_xla_form(head_dim):
    get_logger("horovod_tpu.models.kda").warning(
        "KDA layers of head size %d run the recurrence as XLA ops: the "
        "kernels take a head of whole 128-lane tiles", head_dim)


def init_kda_params(key, cfg):
    """``A_log`` log-uniform in [1, 16] by head and ``dt_bias`` the inverse
    softplus of dt drawn log-uniform in [1e-3, 1e-1] (Mamba-2's, by key
    channel), the convolutions as torch's Conv1d draws them without a
    bias, the projections normal over sqrt(fan in) like the model's other
    matrices; the low rank of the two pairs is the head size."""
    pd, d, di, hd = cfg.param_dtype, cfg.d_model, cfg.d_inner, cfg.head_dim
    k = jax.random.split(key, 12)
    bound = 1.0 / math.sqrt(cfg.d_conv)

    def dense(key, shape):
        return jax.random.normal(key, shape, pd) / math.sqrt(shape[0])

    dt = jnp.exp(jax.random.uniform(k[9], (di,), jnp.float32)
                 * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    return {
        "wq": dense(k[0], (d, di)), "wk": dense(k[1], (d, di)),
        "wv": dense(k[2], (d, di)),
        "conv_w": jax.random.uniform(k[3], (3, cfg.d_conv, di), pd,
                                     -bound, bound),
        "w_fa": dense(k[4], (d, hd)), "w_fb": dense(k[5], (hd, di)),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pd),
        "A_log": jnp.log(jnp.exp(jax.random.uniform(
            k[10], (cfg.n_heads,), jnp.float32) * math.log(16.0))
        ).astype(pd),
        "w_ga": dense(k[6], (d, hd)), "w_gb": dense(k[7], (hd, di)),
        "w_b": dense(k[8], (d, cfg.n_heads)),
        "norm": jnp.ones((hd,), pd),
        "wo": dense(k[11], (di, d)),
    }


def kda_specs():
    """PartitionSpecs of :func:`init_kda_params`: every leaf replicated (a
    layer whole on its chip)."""
    from jax.sharding import PartitionSpec as P
    return {name: P() for name in (
        "wq", "wk", "wv", "conv_w", "w_fa", "w_fb", "dt_bias", "A_log",
        "w_ga", "w_gb", "w_b", "norm", "wo")}


def _unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` (..., n, n) strictly lower triangular,
    float32, ``n`` a power of two times at most :data:`SUB_CHUNK`: forward
    substitution row by row up to SUB_CHUNK, pairwise merges above."""
    n = a.shape[-1]
    if n <= SUB_CHUNK:
        # the matrices side by side on the minor dimension: a row of the
        # inverse is then elementwise work on whole vector registers (with
        # the 16 columns there, seven lanes of eight would idle, and
        # ``t.at[i].add`` is a scatter)
        at = jnp.moveaxis(a.reshape(-1, n, n), 0, 2)         # (n, n, M)
        eye = jnp.eye(n, dtype=a.dtype)[:, :, None]
        rows = [jnp.broadcast_to(eye[0], at.shape[1:])]
        for i in range(1, n):
            # row i: e_i - sum_{j < i} a_ij (row j)
            rows.append(eye[i] - jnp.sum(
                at[i, :i, None] * jnp.stack(rows), axis=0))
        return jnp.moveaxis(jnp.stack(rows), 2, 0).reshape(a.shape)
    m = n // 2
    t11 = _unit_lower_inverse(a[..., :m, :m])
    t22 = _unit_lower_inverse(a[..., m:, m:])
    hi = lax.Precision.HIGHEST
    t21 = -jnp.matmul(jnp.matmul(t22, a[..., m:, :m], precision=hi), t11,
                      precision=hi)
    top = jnp.concatenate([t11, jnp.zeros_like(t21)], axis=-1)
    return jnp.concatenate([top, jnp.concatenate([t21, t22], axis=-1)],
                           axis=-2)


def _chunk_products(q, k, gc):
    """``(A, P)`` of the module docstring for every chunk of a block, both
    (..., C, C) float32 and zero where they are not defined (``A`` on and
    above the diagonal, ``P`` above it). q (scaled), k: (..., C, D) in the
    activation type; gc: (..., C, D) float32, the cumulative log-decay
    inside each chunk."""
    f32, dtype = jnp.float32, q.dtype
    c, d = q.shape[-2:]
    r = min(SUB_CHUNK, c)
    s = c // r
    lead = q.shape[:-2]

    def sub(x):
        return x.reshape(lead + (s, r, d))

    qs, ks = sub(q).astype(f32), sub(k).astype(f32)          # (s, r, d)
    gs = sub(gc)
    # the cumulative log-decay at each sub-chunk's start (0 at the chunk's)
    ref = jnp.concatenate([jnp.zeros_like(gs[..., :1, -1, :]),
                           gs[..., :-1, -1, :]], axis=-2)    # (s, d)
    rel = gs - ref[..., None, :]                             # <= 0
    # inside a sub-chunk: the decays written out, masked before the exp;
    # both sums read the one (s, r, r, d) product k_j * decay
    keep = jnp.tril(jnp.ones((r, r), bool))
    k_decayed = ks[..., None, :, :] * jnp.exp(jnp.where(
        keep[..., None], rel[..., :, None, :] - rel[..., None, :, :],
        -jnp.inf))
    diag = jnp.stack([jnp.sum(x[..., :, None, :] * k_decayed, axis=-1)
                      for x in (qs, ks)], axis=-4)           # (2, s, r, r)
    # against earlier sub-chunks: rows relative to their sub-chunk's
    # start, columns relative to the same point, each exponent <= 0
    rows = (jnp.stack([qs, ks], axis=-4)
            * jnp.exp(rel)[..., None, :, :, :]).astype(dtype)
    before = (jnp.arange(c) // r)[None, :] < jnp.arange(s)[:, None]
    cols = (k.astype(f32)[..., None, :, :] * jnp.exp(jnp.where(
        before[..., None], ref[..., :, None, :] - gc[..., None, :, :],
        -jnp.inf))).astype(dtype)                            # (s, c, d)
    off = jnp.einsum("...xsid,...smd->...xsim", rows, cols,
                     preferred_element_type=f32)             # (2, s, r, c)
    # the diagonal blocks into their places
    eye = jnp.eye(s, dtype=f32)
    both = off.reshape(lead + (2, c, c)) + (
        diag[..., :, :, None, :] * eye[:, None, :, None]).reshape(
            lead + (2, c, c))
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    return (jnp.where(strict, both[..., 1, :, :], 0.0),
            both[..., 0, :, :])


def _kda_block(state, blk, scale, prepare):
    """``block_chunks`` chunks of the recurrence. state: (B, H, D, D)
    float32, the state before the block's first position. ``blk``: q, k, v
    (B, N, C, H D) — the heads side by side, as the projections leave them
    — in the activation type, g (B, N, C, H D) and beta (B, N, C, H)
    float32, or what ``prepare`` makes those five of (see
    :func:`kda_chunked`), and ``live`` (B, N, C, 1), 0 on padded positions,
    or None. Returns ``(state after the block, o (B, N, C, H D))``."""
    *blk, live = blk
    q, k, v, g, beta = prepare(*blk) if prepare else blk
    if live is not None:    # a padded position neither decays nor feeds
        g, beta = g * live, beta * live
    f32, dtype = jnp.float32, q.dtype
    b, n, c, _ = q.shape
    h = beta.shape[-1]

    def heads(x):  # (B, N, C, H D) -> (B, N, H, C, D)
        return x.reshape(b, n, c, h, -1).transpose(0, 1, 3, 2, 4)

    q, k, v, g = map(heads, (q, k, v, g))
    beta = beta.transpose(0, 1, 3, 2)[..., None]             # (B, N, H, C, 1)
    gc = jnp.cumsum(g, axis=-2)
    qs = (q.astype(f32) * scale).astype(dtype)
    a, p = _chunk_products(qs, k, gc)
    t = _unit_lower_inverse(beta * a).astype(dtype)
    decay = jnp.exp(gc)
    kf = k.astype(f32)
    w = jnp.matmul(t, (beta * kf * decay).astype(dtype),
                   preferred_element_type=f32).astype(dtype)
    u = jnp.matmul(t, (beta * v.astype(f32)).astype(dtype),
                   preferred_element_type=f32)
    q_in = (qs.astype(f32) * decay).astype(dtype)
    to_end = jnp.exp(gc[..., -1:, :] - gc)
    k_end = jnp.swapaxes((kf * to_end).astype(dtype), -1, -2)  # (.., D, C)
    end_decay = decay[..., -1, :, None]                      # (B, N, H, D, 1)
    p = p.astype(dtype)
    out = []
    for i in range(n):
        s_in = state.astype(dtype)
        new = (u[:, i] - jnp.matmul(w[:, i], s_in,
                                    preferred_element_type=f32)
               ).astype(dtype)
        out.append(jnp.matmul(q_in[:, i], s_in, preferred_element_type=f32)
                   + jnp.matmul(p[:, i], new, preferred_element_type=f32))
        state = end_decay[:, i] * state + jnp.matmul(
            k_end[:, i], new, preferred_element_type=f32)
    o = jnp.stack(out, axis=1).astype(dtype)                 # (B, N, H, C, D)
    return state, o.transpose(0, 1, 3, 2, 4).reshape(b, n, c, -1)


def kda_chunked(q, k, v, g, beta, chunk, block_chunks=BLOCK_CHUNKS,
                prepare=None):
    """The gated delta rule in its chunked form.

    q, k, v: (B, L, H, D) in the activation type (q and k normalised); g:
    (B, L, H, D) float32, <= 0; beta: (B, L, H) float32. Returns ``(o (B,
    L, H, D) in q's type, the state after position L - 1 (B, H, D, D)
    float32)`` of ``S_t = Diag(exp(g_t)) S_{t-1} + beta_t k_t (v_t -
    (Diag(exp(g_t)) S_{t-1})^T k_t)^T``, ``o_t = S_t^T q_t / sqrt(D)``.
    Any L: a padded tail neither decays nor feeds the state.

    ``prepare(q, k, v, g, beta)``, where given, makes the five of what is
    handed in, one block of ``block_chunks`` chunks at a time inside the
    scan's loop, on arrays (B, N, C, H D) / (B, N, C, H): the mixer hands
    in what its projections left (in the activation type) and normalises
    q and k and forms the float32 log-decay there, so that no float32 (B,
    L, H D) array of it lives outside the loop (256 MiB each at 16,384
    positions, and as much again for its gradient).
    """
    b, l, h, d = q.shape
    c = min(chunk, l)
    subs = c // SUB_CHUNK
    if c > SUB_CHUNK and (c % SUB_CHUNK or subs & (subs - 1)):
        raise ValueError(f"a KDA chunk over {SUB_CHUNK} positions is a "
                         f"power of two times {SUB_CHUNK}, got {c}")
    kb = max(1, min(block_chunks, -(-l // c)))
    pad = -l % (c * kb)
    flat = [t.reshape(b, l, -1) for t in (q, k, v, g, beta)]
    if pad:
        flat = [jnp.pad(t, ((0, 0), (0, pad), (0, 0))) for t in flat]
    nb = (l + pad) // (c * kb)

    def blocks(t):
        return jnp.moveaxis(t.reshape((b, nb, kb, c) + t.shape[2:]), 1, 0)

    live = blocks(jnp.broadcast_to(
        (jnp.arange(l + pad) < l).astype(jnp.float32)[None, :, None],
        (b, l + pad, 1))) if pad else None

    @jax.checkpoint
    def body(state, blk):
        return _kda_block(state, blk, 1.0 / math.sqrt(d), prepare)

    state, o = lax.scan(body, jnp.zeros((b, h, d, d), jnp.float32),
                        tuple(map(blocks, flat)) + (live,))
    o = jnp.moveaxis(o, 0, 1).reshape(b, l + pad, h, d)
    return o[:, :l], state


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def kda_mixer(params, h, cfg):
    """The mixer of one KDA layer. h: the normed layer input (B, L,
    d_model) -> ``(out (B, L, d_model) float32, state_rms (H,))``:
    ``state_rms`` is each head's root mean square of the final state
    ``S_L`` over the batch and the state's D x D entries, float32 (for the
    step's aux)."""
    dtype, f32 = cfg.dtype, jnp.float32
    hn, hd = cfg.n_heads, cfg.head_dim
    b, l, _ = h.shape

    def proj(x, name):
        return jnp.einsum("bld,de->ble", x, params[name].astype(dtype),
                          preferred_element_type=f32)

    def prepare(q, k, v, f, beta):
        """One block of the scan: q and k normalised by head, the log-
        decay and beta in float32 from what the projections left."""
        def by_head(x):
            return x.reshape(x.shape[:-1] + (hn, hd))

        q, k = (_l2norm(by_head(x)).astype(dtype).reshape(x.shape)
                for x in (q, k))
        g = -jnp.exp(params["A_log"].astype(f32))[:, None] * by_head(
            jax.nn.softplus(f.astype(f32) + params["dt_bias"].astype(f32)))
        return q, k, v, g.reshape(f.shape), jax.nn.sigmoid(beta)

    with jax.named_scope("hvd_kda"):
        with jax.named_scope("hvd_kda_in_proj"):
            qkv = [proj(h, name).astype(dtype)
                   for name in ("wq", "wk", "wv")]
            f = proj(proj(h, "w_fa").astype(dtype), "w_fb").astype(dtype)
            gate = proj(proj(h, "w_ga").astype(dtype), "w_gb").astype(dtype)
            # beta stays float32: it enters a solve
            beta = proj(h, "w_b")
        with jax.named_scope("hvd_kda_conv"):
            q, k, v = (jax.nn.silu(causal_conv1d(
                x, params["conv_w"][i], jnp.zeros((), f32))).astype(dtype)
                for i, x in enumerate(qkv))
        with jax.named_scope("hvd_kda_scan"):
            if cfg.fused:
                o, state = kda_scan.kda_scan(
                    q, k, v, f, beta, params["A_log"], params["dt_bias"],
                    interpret=cfg.interpret)
                o = o.reshape(b, l, hn, hd)
            else:
                _say_xla_form(hd)
                o, state = kda_chunked(
                    *(x.reshape(b, l, hn, hd) for x in (q, k, v, f)), beta,
                    CHUNK, prepare=prepare)
            state_rms = jnp.sqrt(jnp.mean(jnp.square(state),
                                          axis=(0, 2, 3)))
        with jax.named_scope("hvd_kda_norm"):
            o = o.astype(f32)
            o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + cfg.norm_eps)
            o = (o * params["norm"].astype(f32)).reshape(b, l, hn * hd) \
                * jax.nn.sigmoid(gate.astype(f32))
        with jax.named_scope("hvd_kda_out_proj"):
            out = proj(o.astype(dtype), "wo")
    return out, state_rms
