"""The dense gated SiLU FFN (models/transformer.py ``_gated_ffn``) against
autodiff of the plain three-einsum form it replaced: the same outputs and
the same four gradients, whole and with the backward in token slices,
alone and inside a model; the gate cotangents' one rounding
(``_cotangent_once``); and the trace-time gauge that says how many layers
took it.
"""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import transformer as tfm

D, FF = 64, 256


def plain(h, w1, w3, w2):
    """``_mlp_block_stats``' gated branch as it was written before the
    ``custom_vjp``."""
    u = jax.nn.silu(jnp.einsum(
        "bsd,df->bsf", h, w1, preferred_element_type=jnp.float32))
    u = u * jnp.einsum("bsd,df->bsf", h, w3,
                       preferred_element_type=jnp.float32)
    return jnp.einsum("bsf,fd->bsd", u.astype(w1.dtype), w2,
                      preferred_element_type=jnp.float32)


def operands(dtype, shape=(2, 512)):
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    h = jax.random.normal(k[0], shape + (D,))
    w1 = (jax.random.normal(k[1], (D, FF)) / 8).astype(dtype)
    w3 = (jax.random.normal(k[2], (D, FF)) / 8).astype(dtype)
    w2 = (jax.random.normal(k[3], (FF, D)) / 16).astype(dtype)
    ct = jax.random.normal(k[4], shape + (D,))
    return (h, w1, w3, w2), ct


def rel(got, want):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def out_and_grads(fn, args, ct):
    out, vjp = jax.vjp(fn, *args)
    return (out,) + vjp(ct)


@pytest.mark.parametrize("slices", [1, 2, 4],
                         ids=["whole", "2-slices", "4-slices"])
def test_float32_is_autodiff_of_the_plain_form(slices):
    args, ct = operands(jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = out_and_grads(
            functools.partial(tfm._gated_ffn, slices=slices), args, ct)
        want = out_and_grads(plain, args, ct)
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert rel(g, w) < 1e-6


@pytest.mark.parametrize("slices", [1, 2], ids=["whole", "2-slices"])
def test_bfloat16_is_within_rounding_of_a_float32_reference(slices):
    args, ct = operands(jnp.bfloat16)
    got = out_and_grads(
        functools.partial(tfm._gated_ffn, slices=slices), args, ct)
    auto = out_and_grads(plain, args, ct)
    # float32 throughout, from the values the bf16 run starts from
    exact = tuple(a.astype(jnp.float32) for a in args)
    with jax.default_matmul_precision("highest"):
        want = out_and_grads(plain, exact, ct)
    for g, a, w in zip(got, auto, want):
        assert g.dtype == a.dtype
        # one bf16 rounding of h, act, du, the gate's cotangents: 2^-8
        # each, and no further from the reference than autodiff's own
        # backward is
        assert rel(g, w) < 2e-2
        assert rel(g, w) < 2 * rel(a, w) + 1e-3


@pytest.mark.parametrize("dt", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_cotangent_is_rounded_to_the_activations_type_once(dt):
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 128))
    ct = jax.random.normal(jax.random.PRNGKey(1), (8, 128))
    out, vjp = jax.vjp(lambda a: tfm._cotangent_once(a, dt), x)
    got, = vjp(ct)
    assert out.dtype == got.dtype == jnp.float32
    np.testing.assert_array_equal(out, x)
    np.testing.assert_array_equal(got, ct.astype(dt).astype(jnp.float32))
    # forward only (serving): nothing of it is left in the program
    text = jax.jit(lambda a: tfm._cotangent_once(a, dt) * 2).lower(
        x).as_text()
    assert "optimization_barrier" not in text


def test_slices_follow_the_array_the_layer_kinds_and_the_tiles():
    dense = types.SimpleNamespace(has_sparse=False)
    sparse = types.SimpleNamespace(has_sparse=True)
    # the two cells that run it: 512 MiB in a model of dense FFNs, 768 MiB
    # in a model of one dense layer and four sparse ones
    assert tfm._gate_slices(16384, 8192, dense) == 2
    assert tfm._gate_slices(16384, 12288, sparse) == 1
    # the other shapes the rule was compiled on (PERF.md section 6 PR 32)
    assert tfm._gate_slices(32768, 8192, dense) == 4
    assert tfm._gate_slices(32768, 12288, sparse) == 1
    # under the bytes: whole
    assert tfm._gate_slices(8192, 8192, dense) == 1
    # 3,000 tokens divide into no multiple of 128 rows
    assert tfm._gate_slices(3000, 2 ** 16, dense) == 1
    assert tfm._gate_slices(3072, 2 ** 16, dense) == 4


def test_a_model_says_whether_it_has_sparse_layers():
    assert not make_cfg().has_sparse
    assert make_cfg(moe_layers=(1,)).has_sparse
    spec = tfm.LayerSpec(n_heads=4, window=None, rope=None, mlp="sparse")
    assert make_cfg(layers=(spec, spec), head_size=8).has_sparse


def make_cfg(**kw):
    base = dict(vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
                max_seq=256, positional="rope", mlp_gated=True,
                dtype=jnp.float32, attention_impl="dense")
    return tfm.TransformerConfig(**dict(base, **kw))


def plain_block(p, x, cfg, axes):
    h = tfm._rmsnorm(x, p["ln2"], cfg.norm_eps)
    out = plain(h, *(p[k].astype(cfg.dtype) for k in ("w1", "w3", "w2")))
    return tfm._residual(x, out, cfg), jnp.zeros((), jnp.float32), None


@pytest.mark.parametrize("remat", [False, True], ids=["keep", "remat"])
@pytest.mark.parametrize("slice_bytes", [2 ** 30, 2 ** 14],
                         ids=["whole", "sliced"])
def test_model_gradients_are_those_of_the_plain_block(monkeypatch, remat,
                                                      slice_bytes):
    cfg = make_cfg(remat=remat)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (2, 256), 0, 64)

    def loss_and_grads():
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                lambda p: tfm.loss_fn(p, tok, jnp.roll(tok, -1, 1), cfg)))(
                    params)

    monkeypatch.setattr(tfm, "_GATE_SLICE_BYTES", slice_bytes)
    assert tfm._gate_slices(2 * 256, 64, cfg) == (4 if slice_bytes < 2 ** 30
                                                 else 1)
    loss, grads = loss_and_grads()
    monkeypatch.setattr(tfm, "_mlp_block_stats", plain_block)
    want_loss, want = loss_and_grads()
    assert abs(float(loss) - float(want_loss)) < 1e-6
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want)):
        assert rel(g, w) < 1e-5


@pytest.mark.parametrize("gated, layers", [(True, 2), (False, 0)],
                         ids=["gated", "gelu"])
def test_gauge_counts_the_layers_of_the_model_traced_last(gated, layers):
    cfg = make_cfg(mlp_gated=gated, remat=True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((1, 64), jnp.int32)
    metrics.FFN_GATED_LAYERS.set(7)  # whatever an earlier trace left
    jax.jit(jax.grad(lambda p: tfm.loss_fn(p, tok, tok, cfg))).lower(params)
    snap = metrics.snapshot()["hvd_ffn_gated_layers"]
    assert snap["type"] == "gauge"
    assert snap["values"][""] == layers
