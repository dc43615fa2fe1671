"""The gated delta rule's recurrence (models/kda.py) as a Pallas kernel
pair: a forward kernel and a backward kernel under one ``jax.custom_vjp``
that keep a head's ``(D, D)`` float32 state in VMEM across the chunks of a
sequence and make everything between a chunk's inputs and its outputs —
l2norm, softplus and log-decay, the cumulative sum, the decays, ``A``,
``P``, the inverse ``T``, ``w``, ``u``, ``n`` — on the chip. q, k, v and
the decay's input are read as the projections leave them, ``(B, L, H D)``
with a head on one 128-lane tile; ``o`` is written once.

A chunk is :data:`CHUNK` = 64 positions = 8 sub-chunks of 8. Inside the
kernel a chunk's rows are PERMUTED (one exact matmul with a 0 / 1 matrix a
grid step, all heads at once): row ``8 i + s`` holds position ``8 s + i``,
so that the eight rows of one vector register are position ``i`` of the
eight sub-chunks. The sub-chunks' diagonal blocks — where ``exp(G_i -
G_j)`` is written out, every exponent <= 0 — are then elementwise work on
whole registers and one lane reduction an entry, for all eight sub-chunks
at once, and the forward substitution of the eight 8 x 8 systems is
elementwise too. Above the sub-chunk the chunk is cut in halves three
times (16, 32, 64 positions): the block below the diagonal of a pair of
halves factors as ``(x_i exp(G_i - m)) . (k_j exp(m - G_j))`` with ``m``
the cumulative log-decay at the end of the left half — both exponents <=
0, taken as ``min(., 0)`` before the exp, so a hard decay underflows to
the 0 its product is and nothing overflows — one matmul a level over the
whole chunk, masked to the level's blocks. ``T`` merges pairwise up the
same levels (the inverse of ``[[a, 0], [c, b]]`` is ``[[a^-1, 0], [-b^-1
c a^-1, b^-1]]``) — a solve, not a series: at 16 positions with the
sub-chunks' inverses applied row block by row block on the vector unit
(exact float32, no matmul), at 32 as two float32 matmuls at the highest
precision, and at 64 not in ``T`` at all but in the products that use it
(``w`` and ``u`` of the lower half are ``T22 (x2 - L21 (T11 x1))``, two
more matmuls with operands in the activation type, as the XLA form's
chunks of 32 meet through ``w S`` in that type).

A grid step takes :data:`HEADS` heads. One head's chunk is traced once a
process into a jaxpr (:func:`_chunk_jaxpr`) and a grid step evaluates it
for its heads equation by equation in turn (:func:`_side_by_side`), so
that the kernel's program has the heads' independent chains side by side
for the scheduler.

The arithmetic is the XLA form's (``kda.kda_chunked``): cumulative sums,
decays, the diagonal blocks, the solve, the carried state and the states
saved for the backward in float32; matmul operands rounded to the
activation type where that form rounds them, float32 accumulation.

The backward kernel sweeps the chunks in reverse with ``dS`` in VMEM. A
chunk's cotangents are ``jax.vjp`` of the same chunk function the forward
kernel runs, traced into the kernel from the chunk's inputs and the state
before it (saved by the forward, one a chunk): the recompute and the
transposed products are the kernel's own code, nothing is derived by hand
twice.

Device scopes: ``hvd_kda_fwd`` and ``hvd_kda_bwd`` directly around the two
``pallas_call``s.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.extend.core import Literal

from .flash_attention import _named_pallas_call

CHUNK = 64          # positions a grid step takes: 8 sub-chunks of SUB
SUB = 8             # a float32 register's sublanes
LEVELS = (16, 32, 64)
_HI = lax.Precision.HIGHEST
# Heads a grid step takes and the VMEM a kernel may use. Swept on the v5e at
# the published shapes (32 heads of 128, 16,384 positions; heads a step:
# forward / forward + gradient ms, PERF.md section 6 PR 34): 2: 9.59 / 37.08;
# 4: 6.33 / 29.99; 8 (taken): 5.24 / 27.43; 16: 4.83 / 27.03. The backward
# kernel keeps a step's residuals of all its heads: 25 MiB at 8 heads, over
# the 16 MiB a kernel gets unasked.
HEADS = 8
VMEM_LIMIT = 64 * 2**20


def takes(head_dim):
    """Whether the kernels run a head of this size: a head is whole
    128-lane tiles."""
    return head_dim % 128 == 0


# --------------------------------------------------------------- constants

@functools.lru_cache(maxsize=None)
def _constants():
    """The 0 / 1 matrices of a chunk, in the permuted row order (row ``8 i
    + s`` = position ``8 s + i``), all exact in bfloat16:

    perm (C, C): ``perm @ x`` permutes natural rows.
    cum (C, C): ``cum @ g`` = the cumulative sum of g over positions.
    pick (32, 16): from the cumulative sum's last 16 rows (positions 6
        and 7 of every sub-chunk) the eight rows — one a sub-chunk — of
        each level's ``m``, the cumulative sum at the end of the left
        half of the sub-chunk's block (rows 8 l .. 8 l + 7 for level l).
    sign (3, C, 1): +1 on a level's right-half rows, -1 on its left.
    below (3, C, C): 1 where the row is in the right and the column in
        the left half of one block of the level.
    place (SUB, SUB, C): ``place[j][s, 8 j + s] = 1`` — where the entry
        (i, j) of sub-chunk s's diagonal block sits in row block i.
    """
    c = CHUNK
    r = np.arange(c)
    pos = (r % SUB) * SUB + r // SUB
    perm = np.zeros((c, c), np.float32)
    perm[r, pos] = 1.0
    cum = (pos[None, :] <= pos[:, None]).astype(np.float32)
    pick = np.zeros((4 * SUB, 2 * SUB), np.float32)
    sign, below = [], []
    for lvl, n in enumerate(LEVELS):
        # the left half's last position is position 7 of a sub-chunk
        sub = (np.arange(SUB) * SUB // n * n + n // 2 - 1) // SUB
        pick[lvl * SUB + np.arange(SUB), SUB + sub] = 1.0
        right = pos % n >= n // 2
        sign.append(np.where(right, 1.0, -1.0)[:, None])
        below.append(((pos[:, None] // n == pos[None, :] // n)
                      & right[:, None] & ~right[None, :]))
    place = np.zeros((SUB, SUB, c), np.float32)
    for j in range(SUB):
        place[j, np.arange(SUB), SUB * j + np.arange(SUB)] = 1.0
    return (perm, cum, pick, np.stack(sign).astype(np.float32),
            np.stack(below).astype(np.float32), place)


# ------------------------------------------------------ exact 0 / 1 matmuls

def _split3(x):
    """float32 -> three bfloat16 pieces that sum to it exactly."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _select_rows(m, x):
    """``m @ x`` for a 0 / 1 matrix m (bfloat16) in float32 arithmetic:
    three single-pass products of the exact pieces of x (one where x is
    bfloat16 already), where the highest precision would make six and
    split the constant each time."""
    def mm(piece):
        return jnp.dot(m, piece, preferred_element_type=jnp.float32)

    if x.dtype == jnp.bfloat16:
        return mm(x)
    hi, mid, lo = _split3(x.astype(jnp.float32))
    return (mm(hi) + mm(mid)) + mm(lo)


@jax.custom_vjp
def _select(m, mt, x):
    """:func:`_select_rows` whose cotangent is as exact (autodiff through
    the pieces would round it to bfloat16); ``mt`` is m transposed."""
    return _select_rows(m, x)


def _select_fwd(m, mt, x):
    return _select_rows(m, x), (m, mt)


def _select_bwd(res, ct):
    m, mt = res
    return jnp.zeros_like(m), jnp.zeros_like(mt), _select_rows(mt, ct)


_select.defvjp(_select_fwd, _select_bwd)


# ------------------------------------------------------------- one chunk

def _nt(a, b, precision=None):
    """a @ b.T, float32 accumulation."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           precision=precision,
                           preferred_element_type=jnp.float32)


def _chunk(q, k, v, f, beta, a, dt, st, live, consts):
    """One head's chunk, rows in the permuted order. q, k, v, f: (C, D) as
    the projections left them (the activation type); beta: (C, 1) float32
    logits; a = -exp(A_log) and dt = dt_bias: (1, D) float32; st: (D, D)
    float32, the state before the chunk TRANSPOSED (values by key
    channels, so that the decay runs along the lanes); live: (C, 1), 0 on
    padded positions, or None. Returns ``(o (C, D) in the activation
    type, the state after the chunk)``."""
    cum, cum_t, pick, pick_t, sign, below, place = consts
    f32, dtype = jnp.float32, q.dtype
    c, d = q.shape
    scale = 1.0 / math.sqrt(d)

    def l2norm(x):
        x = x.astype(f32)
        return (x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                              + 1e-6)).astype(dtype)

    def mm(x, y, precision=None):
        return jnp.dot(x, y, precision=precision,
                       preferred_element_type=f32)

    qs = (l2norm(q).astype(f32) * scale).astype(dtype).astype(f32)
    kf = l2norm(k).astype(f32)
    g = a * jax.nn.softplus(f.astype(f32) + dt)
    b = jax.nn.sigmoid(beta)
    if live is not None:      # a padded position neither decays nor feeds
        g, b = g * live, b * live
    gc = _select(cum, cum_t, g)
    mids = _select(pick, pick_t, gc[c - 2 * SUB:])           # (32, D)

    # ---- the sub-chunks' diagonal blocks, entry (i, j) of all eight at
    # once: rows 8 i .. 8 i + 7 are position i of each sub-chunk
    def rows(x, i):
        return x[SUB * i:SUB * (i + 1)]

    a_ij, p_ij = {}, {}
    for i in range(SUB):
        p_ij[i, i] = jnp.sum(rows(qs, i) * rows(kf, i), axis=-1,
                             keepdims=True)
        for j in range(i):
            kd = rows(kf, j) * jnp.exp(jnp.minimum(
                rows(gc, i) - rows(gc, j), 0.0))
            a_ij[i, j] = jnp.sum(rows(kf, i) * kd, axis=-1, keepdims=True)
            p_ij[i, j] = jnp.sum(rows(qs, i) * kd, axis=-1, keepdims=True)
    # forward substitution, row by row: t0 = (I + b A)^-1 of every
    # sub-chunk, entry (i, j) a column over the sub-chunks
    t_ij = {}
    for i in range(SUB):
        bi = rows(b, i)
        for j in range(i):
            acc = a_ij[i, j]
            for m in range(j + 1, i):
                acc = acc + a_ij[i, m] * t_ij[m, j]
            t_ij[i, j] = -bi * acc

    def blocks(entries, diagonal):
        """(C, C): the entries into their places, a row block at a time."""
        out = []
        for i in range(SUB):
            row = diagonal(i) * place[i]
            for j in range(i):
                row = row + entries[i, j] * place[j]
            out.append(row)
        return jnp.concatenate(out, axis=0)

    def t0_times(x, transposed=False):
        """``t0 @ x`` (``t0.T @ x``): t0 couples the rows of one
        sub-chunk only, so a row block of the product is a few row
        blocks of x scaled by t0's columns — exact float32, no matmul."""
        out = []
        for i in range(SUB):
            row = rows(x, i)
            for j in (range(i + 1, SUB) if transposed else range(i)):
                row = row + (t_ij[j, i] if transposed
                             else t_ij[i, j]) * rows(x, j)
            out.append(row)
        return jnp.concatenate(out, axis=0)

    t = blocks(t_ij, lambda i: 1.0)
    p = blocks(p_ij, lambda i: p_ij[i, i])

    # ---- halves of 16, 32, 64 positions
    for lvl in range(len(LEVELS)):
        m = jnp.concatenate([rows(mids, lvl)] * SUB, axis=0)
        e = jnp.exp(jnp.minimum(sign[lvl] * (gc - m), 0.0))
        xk = (kf * e).astype(dtype)
        xq = (qs * e).astype(dtype)
        lower = b * (_nt(xk, xk) * below[lvl])               # b A, level
        p = p + _nt(xq, xk) * below[lvl]
        if lvl == 0:      # t0 on both sides: the right one transposed
            half = t0_times(lower).T
            t = t - t0_times(half, transposed=True).T
        elif lvl == 1:
            half = mm(t, lower, _HI)
            t = t - mm(half, t, _HI)

    # ---- the chunk against the state. t holds the inverse by halves of
    # 32; the last level reaches w and u through their own products: the
    # lower half's rows are t22 (x2 - lower21 (t11 x1))
    td = t.astype(dtype)
    decay = jnp.exp(gc)
    x = jnp.concatenate([(b * kf * decay).astype(dtype),
                         (b * v.astype(f32)).astype(dtype)], axis=1)
    q_in = (qs * decay).astype(dtype)
    g_end = gc[c - 1:c]                       # the last position's row
    k_end = (kf * jnp.exp(g_end - gc)).astype(dtype)
    s_in = st.astype(dtype)
    y = mm(td, x)
    part = mm(lower.astype(dtype), y.astype(dtype)).astype(dtype)
    y = y - mm(td, part)
    w, u = y[:, :d].astype(dtype), y[:, d:]
    new = (u - _nt(w, s_in)).astype(dtype)
    o = _nt(q_in, s_in) + mm(p.astype(dtype), new)
    st = jnp.exp(g_end) * st + mm(new.T, k_end)
    return o.astype(dtype), st


@functools.lru_cache(maxsize=None)
def _chunk_jaxpr(dtype, d, live, backward, precision):
    """:func:`_chunk` of one head — or, ``backward``, its ``jax.vjp``
    pulled back — traced ONCE a process for a type, head size and
    default matmul precision (the trace reads it): ``f(q, k, v, f, beta,
    a, dt, st[, do, dst], *consts[, live])`` -> ``(o, st)`` or the eight
    cotangents. A grid step's heads and a model's layers evaluate the
    same jaxpr; tracing the vjp of eight heads anew for every kernel of
    every layer took 32 s of a job's start (PERF.md section 6 PR 34)."""
    del precision
    f32 = jnp.float32
    wide, col = (CHUNK, d), (CHUNK, 1)
    head = [(wide, dtype)] * 4 + [(col, f32), ((1, d), f32), ((1, d), f32),
                                  ((d, d), f32)]
    if backward:
        head += [(wide, dtype), ((d, d), f32)]
    consts = [(x.shape, x.dtype) for x in _const_inputs()[0][1:]]
    shapes = head + consts + ([(col, f32)] if live else [])

    def run(*args):
        x, rest = args[:8], args[len(head):]
        consts, row_mask = rest[:N_CONSTS - 1], (rest[-1] if live else None)
        fn = functools.partial(_chunk, live=row_mask, consts=consts)
        if not backward:
            return fn(*x)
        return jax.vjp(fn, *x)[1](tuple(args[8:len(head)]))

    return jax.make_jaxpr(run)(*(jax.ShapeDtypeStruct(shape, t)
                                 for shape, t in shapes))


def _side_by_side(closed, heads, shared):
    """Evaluate one head's jaxpr for every head of a grid step, equation
    by equation in turn: the heads' chains are independent, and one
    chunk's chain — a long line of dependent matmuls, reductions and
    transposes — emitted head after head leaves the units waiting on it
    (13.6 ms a forward at the published shapes; 6.3 side by side at four
    heads, 5.2 at eight). ``heads``: each head's own arguments;
    ``shared``: the arguments after them. Returns each head's outputs."""
    jaxpr = closed.jaxpr
    envs = [dict(zip(jaxpr.constvars + jaxpr.invars,
                     (*closed.consts, *own, *shared))) for own in heads]
    for eqn in jaxpr.eqns:
        for env in envs:
            # per head: a call primitive's sub-function is used up by bind
            subfuns, params = eqn.primitive.get_bind_params(eqn.params)
            out = eqn.primitive.bind(*subfuns, *(
                v.val if isinstance(v, Literal) else env[v]
                for v in eqn.invars), **params)
            env.update(zip(eqn.outvars, out if eqn.primitive.multiple_results
                           else [out]))
    return [[v.val if isinstance(v, Literal) else env[v]
             for v in jaxpr.outvars] for env in envs]


def _heads(q, k, v, f, beta, a, dt, st, cts, live, consts):
    """:func:`_chunk` for the heads of a grid step, side by side. q, k,
    v, f: (C, hb D); beta: (C, hb); a, dt: hb rows (1, D); st: (hb, D,
    D); consts and live as :func:`_chunk` takes them. ``cts`` None:
    returns ``(o (C, hb D), the states after the chunk (hb, D, D))``;
    ``cts = (do (C, hb D), dst (hb, D, D))``: the cotangents ``(dq, dk,
    dv, df (C, hb D), dbeta (C, hb), da, ddt (1, hb D), dst (hb, D,
    D))``."""
    hb, d = st.shape[:2]
    lane = lax.broadcasted_iota(jnp.int32, beta.shape, 1)
    heads = []
    for h in range(hb):
        sl = slice(h * d, (h + 1) * d)
        column = jnp.sum(jnp.where(lane == h, beta, 0.0), axis=-1,
                         keepdims=True)
        heads.append([q[:, sl], k[:, sl], v[:, sl], f[:, sl], column, a[h],
                      dt[h], st[h]]
                     + ([cts[0][:, sl], cts[1][h]] if cts else []))
    closed = _chunk_jaxpr(q.dtype, d, live is not None, cts is not None,
                          jax.config.jax_default_matmul_precision)
    outs = _side_by_side(closed, heads,
                         (*consts, *([] if live is None else [live])))

    def wide(i):
        return jnp.concatenate([out[i] for out in outs], axis=1)

    if cts is None:
        return wide(0), jnp.stack([out[1] for out in outs])
    dbeta = sum(out[4] * (lane[:1] == h).astype(jnp.float32)
                for h, out in enumerate(outs))
    return (wide(0), wide(1), wide(2), wide(3), dbeta, wide(5), wide(6),
            jnp.stack([out[7] for out in outs]))


# ----------------------------------------------------------- the kernels

N_CONSTS = 8


def _load_consts(refs):
    perm, *consts = (r[...] for r in refs)
    return perm, consts


def _permuted(perm, x):
    """The rows of a (C, n) block into the kernel's order (or, with perm
    transposed, back), exactly."""
    return _select_rows(perm, x).astype(x.dtype)


def _live_rows(length, padded, chunk):
    """(C, 1) float32: 0 on the rows of the chunk-th chunk past
    ``length``, or None where no chunk has such rows."""
    if padded == length:
        return None
    r = lax.broadcasted_iota(jnp.int32, (CHUNK, 1), 0)
    pos = (r % SUB) * SUB + r // SUB + chunk * CHUNK
    return (pos < length).astype(jnp.float32)


def _rows(a_ref, dt_ref, hb, d):
    """The two parameter rows by head, (1, D) each, read off the refs (a
    lane slice of a loaded one-row value has no layout to broadcast
    from)."""
    return tuple([ref[:, h * d:(h + 1) * d] for h in range(hb)]
                 for ref in (a_ref, dt_ref))


def _fwd_kernel(*refs, hb, d, length, padded, save):
    q_ref, k_ref, v_ref, f_ref, beta_ref, a_ref, dt_ref = refs[:7]
    perm, consts = _load_consts(refs[7:7 + N_CONSTS])
    outs = refs[7 + N_CONSTS:]
    o_ref, end_ref = outs[:2]
    st_ref = outs[-1]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        st_ref[...] = jnp.zeros_like(st_ref)

    if save:        # the state BEFORE this chunk, for the backward
        outs[2][0, 0] = st_ref[...]
    q, k, v, f = (_permuted(perm, r[0]) for r in (q_ref, k_ref, v_ref,
                                                   f_ref))
    o, st = _heads(q, k, v, f, beta_ref[0, 0], *_rows(a_ref, dt_ref, hb, d),
                   st_ref[...], None, _live_rows(length, padded, c), consts)
    st_ref[...] = st
    o_ref[0] = _permuted(perm.T, o)

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        end_ref[0] = st_ref[...]


def _bwd_kernel(*refs, hb, d, length, padded):
    (q_ref, k_ref, v_ref, f_ref, beta_ref, a_ref, dt_ref, st_ref, do_ref,
     dend_ref) = refs[:10]
    perm, consts = _load_consts(refs[10:10 + N_CONSTS])
    (dq_ref, dk_ref, dv_ref, df_ref, dbeta_ref, da_ref, ddt_ref,
     dst_ref) = refs[10 + N_CONSTS:]
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _():
        dst_ref[...] = dend_ref[0]
        da_ref[...] = jnp.zeros_like(da_ref)
        ddt_ref[...] = jnp.zeros_like(ddt_ref)

    q, k, v, f, do = (_permuted(perm, r[0]) for r in (
        q_ref, k_ref, v_ref, f_ref, do_ref))
    # the reversed sweep: this step's chunk is the (n - 1 - c)-th
    live = _live_rows(length, padded, pl.num_programs(2) - 1 - c)
    dq, dk, dv, df, dbeta, da, ddt, dst = _heads(
        q, k, v, f, beta_ref[0, 0], *_rows(a_ref, dt_ref, hb, d),
        st_ref[0, 0], (do, dst_ref[...]), live, consts)
    dst_ref[...] = dst
    for ref, x in zip((dq_ref, dk_ref, dv_ref, df_ref), (dq, dk, dv, df)):
        ref[0] = _permuted(perm.T, x)
    dbeta_ref[0, 0] = dbeta
    da_ref[0] += da
    ddt_ref[0] += ddt


def _const_inputs():
    perm, cum, pick, sign, below, place = _constants()
    bf16 = jnp.bfloat16
    arrays = (jnp.asarray(perm, bf16), jnp.asarray(cum, bf16),
              jnp.asarray(cum.T, bf16), jnp.asarray(pick, bf16),
              jnp.asarray(pick.T, bf16), jnp.asarray(sign),
              jnp.asarray(below), jnp.asarray(place))
    specs = [pl.BlockSpec(x.shape, lambda *_, n=x.ndim: (0,) * n)
             for x in arrays]
    return arrays, specs


def _layout(q, beta, hb, reverse):
    """What the two calls share: ``(B, H, D, chunks)``, the block specs
    of a (B, L, H D) array by chunk, of the beta block, of a parameter
    row, of the (B, H, D, D) state and of the states by chunk — the chunk
    axis runs backwards for the backward kernel — and the rest of a
    ``pallas_call``'s arguments."""
    b, padded, hd = q.shape
    h = beta.shape[1] * hb
    d, n = hd // h, padded // CHUNK

    def at(c):
        return n - 1 - c if reverse else c

    wide = pl.BlockSpec((1, CHUNK, hb * d), lambda b, g, c: (b, at(c), g))
    beta = pl.BlockSpec((1, 1, CHUNK, hb), lambda b, g, c: (b, g, at(c), 0))
    row = pl.BlockSpec((1, hb * d), lambda b, g, c: (0, g))
    state = pl.BlockSpec((1, hb, d, d), lambda b, g, c: (b, g, 0, 0))
    saved = pl.BlockSpec((1, 1, hb, d, d),
                         lambda b, g, c: (b, at(c), g, 0, 0))
    rest = dict(
        grid=(b, h // hb, n),
        scratch_shapes=[pltpu.VMEM((hb, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT))
    return (b, h, d, n), (wide, beta, row, state, saved), rest


# jitted: a model's KDA layers share one lowering of each kernel (a
# kernel's jaxpr into Mosaic's dialect took 3 - 10 s a call site on the
# chip's host: 118 s of a job's start un-shared, PERF.md section 6 PR 34)
@functools.partial(jax.jit,
                   static_argnames=("length", "hb", "interpret", "save"))
def _forward(q, k, v, f, beta, a, dt, *, length, hb, interpret, save):
    (b, h, d, n), (wide, beta_s, row, state, saved), rest = _layout(
        q, beta, hb, False)
    consts, const_specs = _const_inputs()
    out_shape = [jax.ShapeDtypeStruct(q.shape, q.dtype),
                 jax.ShapeDtypeStruct((b, h, d, d), jnp.float32)]
    out_specs = [wide, state]
    if save:
        out_shape.append(jax.ShapeDtypeStruct((b, n, h, d, d),
                                              jnp.float32))
        out_specs.append(saved)
    return _named_pallas_call(
        "hvd_kda_fwd",
        functools.partial(_fwd_kernel, hb=hb, d=d, length=length,
                          padded=q.shape[1], save=save),
        in_specs=[wide] * 4 + [beta_s, row, row] + const_specs,
        out_specs=out_specs, out_shape=out_shape, interpret=interpret,
        **rest)(q, k, v, f, beta, a, dt, *consts)


@functools.partial(jax.jit, static_argnames=("length", "hb", "interpret"))
def _backward(q, k, v, f, beta, a, dt, states, do, dend, *, length, hb,
              interpret):
    (b, h, d, n), (wide, beta_s, row, state, saved), rest = _layout(
        q, beta, hb, True)
    consts, const_specs = _const_inputs()
    grow = pl.BlockSpec((1, 1, hb * d), lambda b, g, c: (b, 0, g))
    wide_out = jax.ShapeDtypeStruct(q.shape, q.dtype)
    rows_out = jax.ShapeDtypeStruct((b, 1, h * d), jnp.float32)
    return _named_pallas_call(
        "hvd_kda_bwd",
        functools.partial(_bwd_kernel, hb=hb, d=d, length=length,
                          padded=q.shape[1]),
        in_specs=([wide] * 4 + [beta_s, row, row, saved, wide, state]
                  + const_specs),
        out_specs=[wide] * 4 + [beta_s, grow, grow],
        out_shape=[wide_out] * 4 + [
            jax.ShapeDtypeStruct(beta.shape, jnp.float32), rows_out,
            rows_out],
        interpret=interpret,
        **rest)(q, k, v, f, beta, a, dt, states, do, dend, *consts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _scan(q, k, v, f, beta, a, dt, length, hb, interpret):
    return _forward(q, k, v, f, beta, a, dt, length=length, hb=hb,
                    interpret=interpret, save=False)


def _scan_fwd(q, k, v, f, beta, a, dt, length, hb, interpret):
    o, end, states = _forward(q, k, v, f, beta, a, dt, length=length,
                              hb=hb, interpret=interpret, save=True)
    return (o, end), (q, k, v, f, beta, a, dt, states)


def _scan_bwd(length, hb, interpret, res, cts):
    q, k, v, f, beta, a, dt, states = res
    do, dend = cts
    *grads, da, ddt = _backward(q, k, v, f, beta, a, dt, states, do, dend,
                                length=length, hb=hb, interpret=interpret)
    return (*grads, jnp.sum(da, axis=0), jnp.sum(ddt, axis=0))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _beta_blocks(beta, hb, inverse=False):
    """beta (B, L, H) -> (B, H / hb, L, hb) with each chunk's rows in the
    kernels' order (row ``8 i + s`` = position ``8 s + i``), and back."""
    if inverse:
        b, g, l, _ = beta.shape
        x = beta.reshape(b, g, l // CHUNK, SUB, SUB, hb)
        return x.transpose(0, 2, 4, 3, 1, 5).reshape(b, l, g * hb)
    b, l, h = beta.shape
    x = beta.reshape(b, l // CHUNK, SUB, SUB, h // hb, hb)
    return x.transpose(0, 4, 1, 3, 2, 5).reshape(b, h // hb, l, hb)


def kda_scan(q, k, v, f, beta, a_log, dt_bias, interpret=False):
    """The gated delta rule from what the projections left.

    q, k, v, f: (B, L, H D) in the activation type, the heads side by
    side — q and k NOT yet normalised, f the decay's input; beta: (B, L,
    H) float32 logits; a_log: (H,), dt_bias: (H D,). With ``q, k =
    l2norm(q), l2norm(k)`` by head, ``g = -exp(a_log) softplus(f +
    dt_bias)`` and ``sigmoid(beta)`` this is ``kda.kda_chunked``: returns
    ``(o (B, L, H D) in q's type, the state after position L - 1 (B, H,
    D, D) float32)``. D is a multiple of 128 (:func:`takes`); any L (a
    padded tail neither decays nor feeds the state).
    """
    b, l, hd = q.shape
    h = beta.shape[-1]
    hb = math.gcd(h, HEADS)
    pad = -l % CHUNK
    flat = [q, k, v, f]
    beta = beta.astype(jnp.float32)
    if pad:
        flat = [jnp.pad(x, ((0, 0), (0, pad), (0, 0))) for x in flat]
        beta = jnp.pad(beta, ((0, 0), (0, pad), (0, 0)))
    a = jnp.repeat(-jnp.exp(a_log.astype(jnp.float32)), hd // h)[None]
    dt = dt_bias.astype(jnp.float32)[None]
    o, end = _scan(*flat, _beta_blocks(beta, hb), a, dt, l, hb, interpret)
    return o[:, :l], jnp.swapaxes(end, -1, -2)
