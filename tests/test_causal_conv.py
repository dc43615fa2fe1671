"""The depthwise causal convolution with its bias and SiLU as a Pallas kernel
pair (ops/causal_conv.py), in the interpreter on the CPU, against the XLA
form it replaces, ``silu(ssm.causal_conv1d(x, w, b)).astype(x.dtype)``:

- forward and ``jax.vjp`` (dx, dw, db) with and without a bias, 3 and 4
  taps, bf16 and float32 x, several time blocks of several loop steps,
  several channel blocks, and Mamba-2's case: a 4,352-channel slice at lane
  4,096 of a wider array, read in place;
- a Mamba-2 mixer through the kernels against the same mixer in XLA ops;
- the shapes choose the form: a length that is not whole time blocks,
  channels or an offset that are not whole lane tiles, the CPU outside the
  interpreter take the XLA form;
- ``hvd_conv_fused_calls`` counts the calls that took the kernels in the
  benchmark's Granite, Kimi and LFM2 models (one a Mamba-2 layer, three a
  KDA layer, none for the short convolution).
"""

import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import cells
from horovod_tpu.models import ssm
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import causal_conv

# (batch, length, width, offset, channels, time block): four time blocks
# of four loop steps over three channel blocks; two time blocks of two
# loop steps over Mamba-2's slice of its in-projection's output (17 blocks
# of 256 lanes)
LAYOUTS = {"blocks": (2, 1024, 384, 0, 384, 256),
           "granite_slice": (1, 256, 8448, 4096, 4352, 128)}


def xla_form(x, w, b, offset, channels):
    x = x[..., offset:offset + channels]
    return jax.nn.silu(ssm.causal_conv1d(x, w, b)).astype(x.dtype)


def inputs(layout, k, bias, dtype):
    b_, l, width, offset, channels, _ = LAYOUTS[layout]
    ks = jax.random.split(jax.random.PRNGKey(k + 10 * bias), 4)
    x = jax.random.normal(ks[0], (b_, l, width)).astype(dtype)
    w = jax.random.uniform(ks[1], (k, channels), minval=-0.5, maxval=0.5)
    b = jax.random.uniform(ks[2], (channels,), minval=-0.5,
                           maxval=0.5) if bias else None
    dy = jax.random.normal(ks[3], (b_, l, channels)).astype(dtype)
    return x, w, b, dy


def largest(a):
    return float(np.max(np.abs(np.asarray(a, np.float64))))


def diff(a, b):
    return np.abs(np.asarray(a, np.float64) - np.asarray(b, np.float64))


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [3, 4])
@pytest.mark.parametrize("bias", [True, False])
def test_kernels_are_the_xla_form(layout, dtype, k, bias):
    """y, dx, dw and db against the XLA form's: float32 to its rounding;
    in bf16 y and dx to one rounding of the float32 arithmetic (the XLA
    form rounds each tap's share of dx to bf16 before it sums them, the
    kernel sums in float32 and rounds once), dw and db (float32 in both)
    to the order of their sums."""
    *_, offset, channels, block = LAYOUTS[layout]
    x, w, b, dy = inputs(layout, k, bias, dtype)
    assert causal_conv.takes(channels, x.shape[1], k, offset,
                             interpret=True, time_block=block)

    def kernel(x, w, b):
        return causal_conv.conv_silu(x, w, b, offset=offset,
                                     interpret=True, time_block=block)

    y, vjp = jax.vjp(kernel, x, w, b)
    dx, dw, db = vjp(dy)
    y_x, vjp_x = jax.vjp(lambda *a: xla_form(*a, offset, channels), x, w, b)
    dx_x, dw_x, db_x = vjp_x(dy)
    # the XLA form in float32 throughout: the arithmetic both round
    f32 = jnp.float32
    y_f, vjp_f = jax.vjp(lambda *a: xla_form(*a, offset, channels),
                         x.astype(f32), w, b)
    dx_f, _, _ = vjp_f(dy.astype(f32))

    assert y.dtype == dtype and y.shape == y_x.shape
    assert dx.dtype == dtype and dx.shape == x.shape
    if dtype == jnp.float32:
        assert diff(y, y_x).max() <= 1e-6 * largest(y_x)
        assert diff(dx, dx_x).max() <= 1e-6 * largest(dx_x)
    else:
        # one rounding to bf16: half a unit in the last place (2^-9 of
        # the value), and a float32 ulp of room
        assert (diff(y, y_f) <= 2.0 ** -8 * np.abs(np.asarray(y_f))
                + 1e-6 * largest(y_f)).all()
        assert (diff(dx, dx_f) <= 2.0 ** -8 * np.abs(np.asarray(dx_f))
                + 1e-6 * largest(dx_f)).all()
        assert diff(y, y_x).max() <= 2.0 ** -7 * largest(y_x)
        assert diff(dx, dx_x).max() <= 2.0 ** -6 * largest(dx_x)
    # the wide array's gradient is zero outside the slice
    outside = np.asarray(dx, np.float64)
    outside = np.concatenate([outside[..., :offset],
                              outside[..., offset + channels:]], axis=-1)
    assert not outside.any()
    assert dw.dtype == f32 and dw.shape == w.shape
    assert diff(dw, dw_x).max() <= 1e-5 * largest(dw_x)
    if bias:
        assert db.shape == b.shape
        assert diff(db, db_x).max() <= 1e-5 * largest(db_x)
    else:
        assert db is None


def test_mamba2_mixer_through_the_kernels_is_the_xla_form():
    """A Mamba-2 layer whose xBC (256 channels) sits at lane 128 of its
    in-projection's output: the mixer's output and the gradient of every
    leaf and of its input, kernels (interpreted) against XLA ops."""
    cfg = ssm.SSMConfig(d_model=64, n_heads=2, head_dim=64, d_state=64,
                        d_conv=4, chunk=32, norm_eps=1e-5,
                        dtype=jnp.float32, param_dtype=jnp.float32)
    fused = dataclasses.replace(cfg, interpret=True)
    assert fused.conv_fused(128) and not cfg.conv_fused(128)
    params = ssm.init_ssm_params(jax.random.PRNGKey(3), cfg)
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 128, 64))

    def loss(c):
        def f(p, h):
            out, rms = ssm.mamba2_mixer(p, h, c)
            return jnp.sum(jnp.sin(out)) + jnp.sum(rms)
        return jax.value_and_grad(f, argnums=(0, 1))

    (want, want_g), (got, got_g) = (loss(c)(params, h) for c in (cfg, fused))
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        assert diff(g, w).max() <= 1e-5 * largest(w)


@pytest.mark.parametrize("channels, length, d_conv, offset, interpret, want", [
    (4352, 16384, 4, 4096, True, True),     # Granite's xBC
    (4096, 16384, 4, 0, True, True),        # one of Kimi's q, k, v
    (4096, 16384, 4, 0, False, False),      # the CPU, not interpreted
    (4096, 3000, 4, 0, True, False),        # not whole time blocks
    (4096, 100, 4, 0, True, False),         # one block, not whole tiles
    (200, 16384, 4, 0, True, False),        # channels off the lane tiles
    (4352, 16384, 4, 4000, True, False),    # an offset off the lane tiles
    (4096, 16384, 18, 0, True, False),      # more taps than the halo holds
])
def test_the_shapes_choose_the_form(channels, length, d_conv, offset,
                                    interpret, want):
    assert causal_conv.takes(channels, length, d_conv, offset,
                             interpret) == want


@pytest.mark.parametrize("length, fused", [(128, True), (100, False)])
def test_conv_silu_takes_the_kernels_where_the_shapes_allow(length, fused):
    """``ssm.conv_silu`` runs the kernels (``hvd_conv_fwd`` in the traced
    program) at a length of whole loop steps and the XLA form at one that
    is not, with the same result."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, length, 256))
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 128))
    b = jax.random.normal(jax.random.PRNGKey(2), (128,))
    text = str(jax.make_jaxpr(
        lambda x: ssm.conv_silu(x, w, b, True, offset=128))(x))
    assert ("hvd_conv_fwd" in text) == fused
    got = ssm.conv_silu(x, w, b, True, offset=128)
    want = xla_form(x, w, b, 128, 128)
    assert diff(got, want).max() <= 1e-6 * largest(want)


@pytest.mark.parametrize("cell, calls", [
    ("granite4h-micro_s16k", 9),     # nine Mamba-2 layers, one each
    ("kimi-linear_s16k", 12),        # four KDA layers, q, k and v each
    ("lfm2-24b-a2b_s16k", 0),        # the short convolution stays XLA ops
])
def test_the_gauge_counts_the_calls_that_take_the_kernels(cell, calls):
    """The benchmark's reduced-depth models at their cells' shapes,
    traced with interpreted kernels (nothing runs)."""
    import horovod_tpu as hvd
    cell = cells.load_cell(cell)
    mode = importlib.import_module("benchmark.modes." + cell["cell"]["mode"])
    cfg = mode.model_config(cell, interpret=True)
    params = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tokens = jax.ShapeDtypeStruct((1, cell["traffic"]["seq_len"]),
                                  jnp.int32)
    jax.eval_shape(lambda p, t: tfm.trunk_with_stats(p, t, cfg), params,
                   tokens)
    snap = hvd.metrics_snapshot()
    assert snap["hvd_conv_fused_calls"]["values"][""] == calls
