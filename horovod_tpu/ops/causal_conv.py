"""The depthwise causal convolution of the Mamba-2 and KDA mixers together
with its bias and SiLU — ``silu(causal_conv1d(x, w, b)).astype(x.dtype)``
(models/ssm.py) — as a Pallas kernel pair under one ``jax.custom_vjp``
whose residuals are x, w and b alone.

The forward kernel reads x once in its own type, widens it to float32 in
VMEM, adds the bias and the K taps in float32 in the XLA form's order
(``b + w[0] x[t - K + 1] + ... + w[K - 1] x[t]``), applies SiLU in float32
and writes y once in x's type. The backward kernel recomputes that
pre-activation, forms ``g = dy silu'(pre)`` in float32, writes ``dx[t] =
sum_k w[k] g[t + K - 1 - k]`` once in x's type, and sums ``dw[k] = sum_t
x[t - K + 1 + k] g[t]`` and ``db = sum_t g[t]`` in float32 VMEM across
the time blocks: x and the cotangent are each read once, with no pass a
tap over HBM.

Grid: (batch, channel blocks of whole 128-lane tiles, time blocks). A time
block needs the K - 1 rows before it: a second BlockSpec of :data:`HALO`
rows on x reads them (zeros before position 0). The backward needs g on
the K - 1 rows after a block too: it walks the time blocks in reverse and
keeps the first HALO rows of g of the block it did last. Inside a grid
step a ``lax.fori_loop`` takes :data:`ROWS` rows at a time, so the
kernels' bodies do not grow with the block or the model.

x may be a lane slice of a wider array (Mamba-2's xBC lies at lane 4,096
of the in-projection's output): the index maps read the slice in place,
and the gradient comes back as the wide array's, zero outside the slice.

Device scopes: ``hvd_conv_fwd`` and ``hvd_conv_bwd`` directly around the
two ``pallas_call``s.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _named_pallas_call

HALO = 16           # rows before a time block the kernels read: a bf16 tile
# Channel blocks (the widest that tiles the channels and their offset
# first), rows of a grid step and rows of a step of the loop inside one.
# Swept on the v5e at the two cells' shapes (bf16, 16,384 positions;
# forward / backward ms a call, PERF.md section 6), rows x lanes:
# Kimi's 4,096 channels: 32 x 512 0.54 / 0.89, 64 x 512 0.54 / 1.04,
# 128 x 512 0.62 / 1.31, 32 x 256 0.57 / 0.90, 64 x 256 (taken) 0.51 /
# 0.83, 128 x 256 0.52 / 0.99, 128 x 128 0.55 / 0.86; Granite's 4,352 at
# lane 4,096 (256 lanes at most; the backward with the gradient's pad to
# the 8,448-wide input): 32 rows 0.62 / 1.63, 64 (taken) 0.56 / 1.55, 128
# 0.57 / 1.71. Fewer rows pay more of the loop's own steps; more rows are
# slower too (likely a spill of the vector registers); 1,024 - 4,096 rows
# a grid step differ by under 0.1 ms.
LANES = (256, 128)
TIME_BLOCK = 2048
ROWS = 64
VMEM_LIMIT = 64 * 2**20


def _lanes(channels, offset):
    """The channel block: the widest of :data:`LANES` that tiles both
    the channels and their offset, or None."""
    for lanes in LANES:
        if channels % lanes == 0 and offset % lanes == 0:
            return lanes
    return None


def _blocks(length, time_block):
    """``(rows of a grid step, rows of a loop step)``, or None where the
    length is not whole time blocks of whole loop steps."""
    block = min(time_block, length)
    rows = min(ROWS, block)
    if length % block or block % rows or rows % HALO:
        return None
    return block, rows


def takes(channels, length, d_conv, offset=0, interpret=False,
          time_block=TIME_BLOCK):
    """Whether the kernels run a convolution of ``channels`` channels at
    lane ``offset`` over ``length`` positions: whole 128-lane tiles, whole
    time blocks, at most HALO + 1 taps, on a TPU or in the interpreter."""
    return (_lanes(channels, offset) is not None
            and _blocks(length, time_block) is not None
            and 1 <= d_conv <= HALO + 1
            and (interpret or jax.default_backend() == "tpu"))


# ----------------------------------------------------------- the kernels

def _rows_at(ref, r0, o, rows):
    """Rows ``r0 + o .. r0 + o + rows`` of a float32 VMEM ref, r0 a
    multiple of 8 and o static: the chip loads whole 8-row tiles only, so
    the rows off the tiling are an aligned load turned by o % 8 rows."""
    base, shift = r0 + o - o % 8, o % 8
    if not shift:
        return ref[pl.ds(base, rows), :]
    win = ref[pl.ds(base, rows + 8), :]
    return pltpu.roll(win, rows + 8 - shift, 0)[:rows]


def _pre(buf, w_ref, b_ref, r0, k, rows):
    """``b + sum_i w[i] x[t - K + 1 + i]`` (float32, the XLA form's order)
    for the rows ``r0 .. r0 + rows`` of a block whose row 0 is row HALO of
    ``buf``; also each tap's rows of x."""
    xs = [_rows_at(buf, r0, HALO - k + 1 + i, rows) for i in range(k)]
    acc = None if b_ref is None else b_ref[...]
    for i, x in enumerate(xs):
        tap = w_ref[pl.ds(i, 1), :] * x
        acc = tap if acc is None else acc + tap
    return acc, xs


def _fold(x):
    """(rows, lanes) -> (8, lanes): the sum of the 8-row groups, on whole
    vector registers."""
    return x.reshape(x.shape[0] // 8, 8, x.shape[1]).sum(axis=0)


def _fwd_kernel(*refs, k, rows, bias):
    x_ref, halo_ref, w_ref = refs[:3]
    b_ref = refs[3] if bias else None
    y_ref, buf = refs[3 + bias:]
    f32 = jnp.float32
    buf[pl.ds(0, HALO), :] = jnp.where(pl.program_id(2) > 0,
                                       halo_ref[0].astype(f32), 0.0)

    def step(i, carry):
        r0 = pl.multiple_of(i * rows, rows)
        buf[pl.ds(r0 + HALO, rows), :] = x_ref[0, pl.ds(r0, rows), :].astype(
            f32)
        pre, _ = _pre(buf, w_ref, b_ref, r0, k, rows)
        y_ref[0, pl.ds(r0, rows), :] = jax.nn.silu(pre).astype(y_ref.dtype)
        return carry

    lax.fori_loop(0, x_ref.shape[1] // rows, step, 0)


def _bwd_kernel(*refs, k, rows, bias):
    x_ref, halo_ref, dy_ref, w_ref = refs[:4]
    b_ref = refs[4] if bias else None
    outs = refs[4 + bias:]
    dx_ref, dw_ref = outs[:2]
    db_ref = outs[2] if bias else None
    xbuf, gbuf, dw_acc, db_acc = outs[2 + bias:]
    f32 = jnp.float32
    block, lanes = x_ref.shape[1:]
    n = block // rows
    t, last = pl.program_id(2), pl.num_programs(2) - 1

    @pl.when(t == 0)        # the last time block: nothing comes after it
    def _():
        gbuf[pl.ds(block, HALO), :] = jnp.zeros((HALO, lanes), f32)
        dw_acc[...] = jnp.zeros_like(dw_acc)
        db_acc[...] = jnp.zeros_like(db_acc)

    # the reversed sweep: the first time block is the last grid step
    xbuf[pl.ds(0, HALO), :] = jnp.where(t < last,
                                        halo_ref[0].astype(f32), 0.0)

    def widen(i, carry):
        r0 = pl.multiple_of(i * rows, rows)
        xbuf[pl.ds(r0 + HALO, rows), :] = x_ref[0, pl.ds(r0, rows), :
                                                ].astype(f32)
        return carry

    lax.fori_loop(0, n, widen, 0)

    def step(j, acc):
        # rows from the block's end: g after them is in gbuf already
        r0 = pl.multiple_of((n - 1 - j) * rows, rows)
        pre, xs = _pre(xbuf, w_ref, b_ref, r0, k, rows)
        sig = jax.nn.sigmoid(pre)
        dy = dy_ref[0, pl.ds(r0, rows), :].astype(f32)
        g = dy * sig + dy * pre * (sig * (1.0 - sig))
        gbuf[pl.ds(r0, rows), :] = g
        dx = None
        for i in range(k):
            tap = w_ref[pl.ds(i, 1), :] * _rows_at(gbuf, r0, k - 1 - i, rows)
            dx = tap if dx is None else dx + tap
        dx_ref[0, pl.ds(r0, rows), :] = dx.astype(dx_ref.dtype)
        dws, db = acc
        return (tuple(s + _fold(x * g) for s, x in zip(dws, xs)),
                db + _fold(g))

    zero = jnp.zeros((8, lanes), f32)
    dws, db = lax.fori_loop(0, n, step, ((zero,) * k, zero))
    for i in range(k):
        dw_acc[i] += dws[i]
    db_acc[...] += db
    # g's first rows, for the block before this one
    gbuf[pl.ds(block, HALO), :] = gbuf[pl.ds(0, HALO), :]

    @pl.when(t == last)
    def _():
        dw_ref[0] = jnp.sum(dw_acc[...], axis=1)
        if bias:
            db_ref[0] = jnp.sum(db_acc[...], axis=0, keepdims=True)


# ------------------------------------------------------------ the calls

def _layout(x, w, offset, time_block, reverse):
    """What the two calls share: the blocks, the grid, the specs of x, of
    the rows before an x block, of an output-shaped block, of w and of b."""
    b, length, _ = x.shape
    k, channels = w.shape
    lanes = _lanes(channels, offset)
    block, rows = _blocks(length, time_block)
    n, lane0 = length // block, offset // lanes

    def at(t):
        return n - 1 - t if reverse else t

    x_spec = pl.BlockSpec((1, block, lanes),
                          lambda i, c, t: (i, at(t), c + lane0))
    halo = pl.BlockSpec(
        (1, HALO, lanes),
        lambda i, c, t: (i, jnp.maximum(at(t) * (block // HALO) - 1, 0),
                         c + lane0))
    out = pl.BlockSpec((1, block, lanes), lambda i, c, t: (i, at(t), c))
    w_spec = pl.BlockSpec((k, lanes), lambda i, c, t: (0, c))
    b_spec = pl.BlockSpec((1, lanes), lambda i, c, t: (0, c))
    grid = (b, channels // lanes, n)
    return (k, block, rows, lanes), (x_spec, halo, out, w_spec, b_spec), grid


# jitted: a model's layers share one lowering of each kernel
@functools.partial(jax.jit, static_argnames=(
    "offset", "time_block", "interpret"))
def _forward(x, w, b, *, offset, time_block, interpret):
    (k, block, rows, lanes), (x_spec, halo, out, w_spec, b_spec), grid = \
        _layout(x, w, offset, time_block, False)
    bias = b is not None
    return _named_pallas_call(
        "hvd_conv_fwd",
        functools.partial(_fwd_kernel, k=k, rows=rows, bias=bias),
        grid=grid,
        in_specs=[x_spec, halo, w_spec] + [b_spec] * bias,
        out_specs=out,
        out_shape=jax.ShapeDtypeStruct(x.shape[:2] + w.shape[1:], x.dtype),
        scratch_shapes=[pltpu.VMEM((HALO + block, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3,
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x, x, w, *([b] if bias else []))


@functools.partial(jax.jit, static_argnames=(
    "offset", "time_block", "interpret"))
def _backward(x, w, b, dy, *, offset, time_block, interpret):
    (k, block, rows, lanes), (x_spec, halo, out, w_spec, b_spec), grid = \
        _layout(x, w, offset, time_block, True)
    bias = b is not None
    batch, channels = x.shape[0], w.shape[1]
    f32 = jnp.float32
    out_specs = [out, pl.BlockSpec((1, k, lanes), lambda i, c, t: (i, 0, c))]
    out_shape = [jax.ShapeDtypeStruct(dy.shape, x.dtype),
                 jax.ShapeDtypeStruct((batch, k, channels), f32)]
    if bias:
        out_specs.append(pl.BlockSpec((1, 1, lanes),
                                      lambda i, c, t: (i, 0, c)))
        out_shape.append(jax.ShapeDtypeStruct((batch, 1, channels), f32))
    return _named_pallas_call(
        "hvd_conv_bwd",
        functools.partial(_bwd_kernel, k=k, rows=rows, bias=bias),
        grid=grid,
        in_specs=[x_spec, halo, out, w_spec] + [b_spec] * bias,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((HALO + block, lanes), f32),
                        pltpu.VMEM((block + HALO, lanes), f32),
                        pltpu.VMEM((k, 8, lanes), f32),
                        pltpu.VMEM((8, lanes), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(x, x, dy, w, *([b] if bias else []))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv(x, w, b, offset, time_block, interpret):
    return _forward(x, w, b, offset=offset, time_block=time_block,
                    interpret=interpret)


def _conv_fwd(x, w, b, offset, time_block, interpret):
    return _conv(x, w, b, offset, time_block, interpret), (x, w, b)


def _conv_bwd(offset, time_block, interpret, res, dy):
    x, w, b = res
    dx, dw, *db = _backward(x, w, b, dy, offset=offset,
                            time_block=time_block, interpret=interpret)
    width, channels = x.shape[-1], w.shape[1]
    if channels != width:
        dx = jnp.pad(dx, ((0, 0), (0, 0),
                          (offset, width - offset - channels)))
    return (dx, jnp.sum(dw, axis=0),
            jnp.sum(db[0], axis=0) if db else None)


_conv.defvjp(_conv_fwd, _conv_bwd)


def conv_silu(x, w, b=None, *, offset=0, interpret=False,
              time_block=TIME_BLOCK):
    """``silu(causal_conv1d(x[..., offset:offset + channels], w,
    b)).astype(x.dtype)`` as the kernel pair, the channels being w's. x:
    (B, L, width) in the activation type; w: (K, channels); b:
    (channels,) or None (no bias). Differentiable in x, w and b; the
    caller asks :func:`takes` first."""
    f32 = jnp.float32
    return _conv(x, w.astype(f32),
                 None if b is None else b.astype(f32).reshape(1, -1),
                 offset, time_block, interpret)
