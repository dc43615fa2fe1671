"""A model that mixes Mamba-2 layers with NoPE attention layers, with a tied
and scaled embedding (PR 31): the program (models/ssm.py, the mixer name in
models/transformer.py ``LayerSpec``) against the plain reference
(tests/reference_granite.py, whose copy the benchmark carries), at toy
widths on the CPU, float32, seeded random weights.

- the whole model, loss and the gradient of every leaf, for the published
  9 : 1 pattern cut to a few layers and for sequence lengths that are and
  are not a multiple of the chunk;
- the chunked scan against the sequential recurrence where ``dt A`` is
  large and the decays underflow;
- the causal convolution against a shifted-sum closed form;
- attention at a stated scale against ``dense_attention``;
- the tied head: one leaf whose gradient is the sum of both uses;
- the share tied to the model: the eight vocabulary slices' logits side by
  side are the uncut model's, and a token of slice k embeds as in it;
- the two copies of the reference agree; serve / decode / pipeline refuse a
  Mamba-2 layer; the compiled step carries ``ssm_state_rms`` out.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import keystr, tree_flatten_with_path

import reference_granite as ref
from horovod_tpu.models import ssm
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import dense_attention

HERE = os.path.dirname(os.path.abspath(__file__))
H, P, N = 4, 8, 16     # SSM heads, features a head, state size
ARCH = {"attention_multiplier": 0.2, "embedding_multiplier": 3.0,
        "residual_multiplier": 0.5, "logits_scaling": 2.0,
        "rms_norm_eps": 1e-5,
        "mamba": {"n_heads": H, "d_head": P, "d_state": N}}
# the published period, shortened: Mamba-2 layers around one attention layer
PATTERN = ("mamba", "mamba", "attention", "mamba")


def make_cfg(types=PATTERN, vocab=64, **kw):
    layers = tuple(tfm.LayerSpec(
        n_heads=4, mixer="mamba2" if t == "mamba" else "attention")
        for t in types)
    base = dict(
        vocab_size=vocab, d_model=32, n_heads=4, n_kv_heads=2, head_size=8,
        n_layers=len(types), d_ff=64, max_seq=256, positional="rope",
        layers=layers, mlp_gated=True, attention_scale=0.2,
        embedding_multiplier=3.0, residual_multiplier=0.5,
        logits_scaling=2.0, tie_embeddings=True, norm_eps=1e-5,
        ssm_heads=H, ssm_head_dim=P, ssm_state=N, ssm_conv=4, ssm_chunk=16,
        dtype=jnp.float32, attention_impl="dense",
        flash_interpret=True)
    return tfm.TransformerConfig(**dict(base, **kw))


def batch(vocab=64, shape=(2, 96)):
    tok = jax.random.randint(jax.random.PRNGKey(1), shape, 0, vocab)
    return tok, jnp.roll(tok, -1, 1)


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) <= tol * (np.max(np.abs(b)) + 1e-12)


# the loop takes ssm.BLOCK_CHUNKS = 8 chunks of 16 a step: 256 is two
# whole blocks, 160 and 150 a whole one and a part (padded with dt = 0, 150
# inside a chunk too); 50 and 23 are ragged inside one block; 8 is shorter
# than a chunk; remat + flash + a chunked loss is the cell's path
@pytest.mark.parametrize("seq, kw", [
    (256, {}), (160, {}), (50, {}), (23, {}), (8, {}),
    (192, dict(remat=True, attention_impl="flash", loss_chunk=32)),
    (150, dict(types=("mamba",) * 5 + ("attention",) + ("mamba",) * 4))],
    ids=["s256", "s160", "s50", "s23", "s8", "s192-remat-flash",
         "one-period"])
def test_model_matches_the_reference_on_loss_and_every_gradient(seq, kw):
    cfg = make_cfg(**kw)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params
    tok, tgt = batch(shape=(2, seq))
    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_and_stats(p, tok, tgt, cfg),
            has_aux=True))(params)
    (want, states), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, ARCH), has_aux=True))(params)
    assert abs(float(loss) - float(want)) < 1e-5
    assert close(stats["ssm_state_rms"], states)
    assert stats["ssm_state_rms"].shape == (
        sum(l.mixer == "mamba2" for l in cfg.layers), H)
    flat = tree_flatten_with_path(grads)[0]
    for (path, got), exp in zip(flat, jax.tree.leaves(want_grads)):
        assert close(got, exp, 1e-4), keystr(path)


def _scan_inputs(l, dt_scale):
    k = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(k[0], (2, l, H, P))
    bm = jax.random.normal(k[1], (2, l, N))
    cm = jax.random.normal(k[2], (2, l, N))
    dt = dt_scale * jax.nn.softplus(jax.random.normal(k[3], (2, l, H)))
    a = -jnp.exp(jax.random.normal(k[4], (H,)))
    return x, dt, a, bm, cm


@pytest.mark.parametrize("dt_scale", [0.05, 1.0, 40.0, 400.0])
def test_chunked_scan_is_the_sequential_recurrence(dt_scale):
    """Up to dt A of several hundred a step: exp(-400) is 0 in float32,
    whole chunks forget their past, and neither the outputs, the final
    state nor any gradient is NaN. (At dt = 400 the gradients of dt and a
    are sums of terms of 1e9 that cancel, in either form: they are held
    to be finite, not equal.)"""
    args = _scan_inputs(70, dt_scale)

    def chunked(*a):
        return ssm.ssd_chunked(*a, chunk=16, block_chunks=2)

    def total(f):
        def g(*a):
            y, s = f(*a)
            return jnp.sum(y * jnp.cos(y)) + jnp.sum(s)
        return g

    with jax.default_matmul_precision("highest"):
        y, s = jax.jit(chunked)(*args)
        grads = jax.jit(jax.grad(total(chunked), argnums=(0, 1, 2, 3, 4)))(
            *args)
        want_y, want_s = jax.jit(ref.recurrence)(*args)
        want_grads = jax.jit(jax.grad(total(ref.recurrence),
                                      argnums=(0, 1, 2, 3, 4)))(*args)
    # the chunked form takes decays from DIFFERENCES of a float32
    # cumulative sum: the exponent is off by an ulp of the chunk's total
    # log-decay (1e-4 at -1,000), which is the tolerance past dt A = 1
    tol = 1e-4 if dt_scale <= 1.0 else 2e-3
    assert close(y, want_y, tol) and close(s, want_s, tol)
    for i, (got, exp) in enumerate(zip(grads, want_grads)):
        assert np.all(np.isfinite(got))
        if dt_scale < 400 or i not in (1, 2):
            assert close(got, exp, tol)


def test_causal_convolution_is_the_shifted_sum():
    k = jax.random.split(jax.random.PRNGKey(5), 3)
    x = jax.random.normal(k[0], (2, 11, 6))
    w = jax.random.normal(k[1], (4, 6))
    b = jax.random.normal(k[2], (6,))
    got = np.asarray(ssm.causal_conv1d(x, w, b))
    x, w = np.asarray(x, np.float64), np.asarray(w, np.float64)
    want = np.zeros_like(x) + np.asarray(b, np.float64)
    for t in range(11):
        for j in range(4):          # the tap j positions back
            if t - j >= 0:
                want[:, t] += w[3 - j] * x[:, t - j]
    np.testing.assert_allclose(got, want, atol=1e-5)
    # causal: the output at t does not see t + 1
    bumped = np.asarray(ssm.causal_conv1d(
        jnp.asarray(x).at[:, 7].add(1.0), jnp.asarray(w), b))
    np.testing.assert_array_equal(bumped[:, :7], got[:, :7])


@pytest.mark.parametrize("scale", [None, 0.015625, 0.3])
def test_flash_attention_at_a_stated_scale(scale):
    """Heads of 64, four query heads a kv head, forward and gradients,
    against dense_attention at the same scale; None is 1 / sqrt(64)."""
    k = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(k[0], (1, 128, 4, 64))
    kk = jax.random.normal(k[1], (1, 128, 1, 64))
    v = jax.random.normal(k[2], (1, 128, 1, 64))

    def flash(q, kk, v):
        return flash_attention(q, kk, v, True, 64, True, None, scale)

    def dense(q, kk, v):
        return dense_attention(q, kk, v, causal=True, scale=scale)

    np.testing.assert_allclose(flash(q, kk, v), dense(q, kk, v), atol=1e-4)
    got = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), (0, 1, 2))(
        q, kk, v)
    want = jax.grad(lambda *a: jnp.sum(jnp.sin(dense(*a))), (0, 1, 2))(
        q, kk, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-4)
    if scale is None:
        np.testing.assert_allclose(
            flash(q, kk, v), dense_attention(q, kk, v, causal=True,
                                             scale=0.125), atol=2e-5)


def test_tied_head_is_one_leaf_with_the_gradient_of_both_uses():
    cfg = make_cfg(types=("mamba", "attention"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert "lm_head" not in params
    assert "lm_head" not in tfm.param_specs(cfg)
    tok, tgt = batch()
    tied = jax.grad(lambda p: tfm.loss_fn(p, tok, tgt, cfg))(params)
    # the same model with the head held apart, at the same values
    untied_cfg = make_cfg(types=("mamba", "attention"),
                          tie_embeddings=False)
    untied = dict(params, lm_head=params["embed"].T)
    assert float(tfm.loss_fn(untied, tok, tgt, untied_cfg)) == pytest.approx(
        float(tfm.loss_fn(params, tok, tgt, cfg)), abs=1e-6)
    apart = jax.grad(lambda p: tfm.loss_fn(p, tok, tgt, untied_cfg))(untied)
    assert float(jnp.max(jnp.abs(apart["lm_head"]))) > 0
    assert float(jnp.max(jnp.abs(apart["embed"]))) > 0
    np.testing.assert_allclose(
        tied["embed"], apart["embed"] + apart["lm_head"].T, atol=1e-6)


def test_vocabulary_slices_side_by_side_are_the_uncut_model():
    """The cut the benchmark makes: rows k V/8 .. (k+1) V/8 of the tied
    embedding. A token of slice k embeds as in the uncut model, so the
    trunk is the same, and the eight slices' logits laid side by side are
    the uncut reference's."""
    vocab, ways = 64, 8
    rows = vocab // ways
    cfg = make_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(2), (2, 32), 0, vocab)
    want = ref.logits(params, tok, ARCH)
    share_cfg = make_cfg(vocab=rows)
    sides = []
    for k in range(ways):
        share = dict(params, embed=params["embed"][k * rows:(k + 1) * rows])
        # tokens of this slice, numbered within it
        ids = tok % rows
        whole = jax.tree.map(lambda a: a, params)
        x_share = tfm.embed_tokens(share, ids, share_cfg,
                                   tfm.ShardAxes(None, None, None))
        x_whole = tfm.embed_tokens(whole, ids + k * rows, cfg,
                                   tfm.ShardAxes(None, None, None))
        np.testing.assert_array_equal(x_share, x_whole)
        # the trunk of the uncut model under this slice's head
        with jax.default_matmul_precision("highest"):
            x, _ = tfm.trunk_with_aux(params, tok, cfg)
            sides.append(tfm._head(share, x, share_cfg))
    np.testing.assert_allclose(jnp.concatenate(sides, -1), want, atol=2e-5)


def test_the_two_copies_of_the_reference_agree():
    """tests/reference_granite.py is the benchmark's
    benchmark/lib/reference_granite.py: the same source, the same loss."""
    path = os.path.join(HERE, os.pardir, "benchmark", "lib",
                        "reference_granite.py")
    with open(path, "rb") as a, open(ref.__file__, "rb") as b:
        assert a.read() == b.read()
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    cfg = make_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch()
    a, sa = jax.jit(lambda p: ref.loss(p, tok, tgt, ARCH))(params)
    b, sb = jax.jit(lambda p: other.loss(p, tok, tgt, ARCH))(params)
    assert float(a) == float(b)
    np.testing.assert_array_equal(sa, sb)


@pytest.mark.parametrize("path", ["decode", "serve", "pipeline", "sp"])
def test_a_mamba_layer_is_refused_where_it_cannot_run(path):
    cfg = make_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch(shape=(2, 32))
    if path == "sp":
        with pytest.raises(ValueError, match="sequence parallelism"):
            tfm._ssm_block(params["layers"][0], jnp.zeros((1, 16, 32)), cfg,
                           tfm.ShardAxes(dp=None, sp="sp", tp=None))
        return
    with pytest.raises(ValueError, match="Mamba-2 layers"):
        if path == "decode":
            tfm.init_cache(cfg, 1, 16)
        elif path == "serve":
            from horovod_tpu.serve import engine
            engine.ServeEngine(params, cfg, num_pages=4, page_size=8)
            raise AssertionError("the engine took a Mamba-2 layer")
        else:
            tfm.pipeline_loss_fn(params, tok, tgt, cfg, num_microbatches=1)


def test_what_only_the_training_forward_applies_is_refused_elsewhere():
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=1, d_ff=64, max_seq=32,
                                residual_multiplier=0.22)
    with pytest.raises(ValueError, match="training forward only"):
        tfm.init_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="ssm_heads"):
        make_cfg(ssm_heads=0)
    with pytest.raises(ValueError, match="mixer"):
        make_cfg(types=("mamba",), layers=(tfm.LayerSpec(4, mixer="rnn"),))


def test_compiled_step_carries_the_state_statistic_out():
    """The normal path: hvd.compiled_train_step with has_aux, compiled
    steps only, one cache miss, and ``ssm_state_rms`` fed to the
    hvd_ssm_state_rms family."""
    import optax

    import horovod_tpu as hvd
    hvd.init()
    cfg = make_cfg(remat=True, loss_chunk=32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    step = hvd.compiled_train_step(
        lambda p, a, b: tfm.loss_and_stats(p, a, b, cfg),
        hvd.DistributedOptimizer(optax.adamw(3e-3)), has_aux=True)
    opt_state = step.init(params)
    tok, tgt = batch(shape=(hvd.size(), 64))
    losses = []
    for _ in range(3):
        params, opt_state, loss, aux = step(params, opt_state, tok, tgt)
        losses.append(float(loss))
        hvd.metrics.record_ssm_state(jax.device_get(aux))
    assert step.compiled_steps == 3 and step.fallback_steps == 0
    assert step.cache_misses == 1
    assert losses[2] < losses[0]
    assert aux["ssm_state_rms"].shape == (3, H)
    values = hvd.metrics_snapshot()["hvd_ssm_state_rms"]["values"]
    assert len(values) == 3 and all(v > 0 for v in values.values())
    # the gauge is the layer's: the heads' mean of squares, rooted
    by_layer = np.sqrt(np.mean(np.square(np.asarray(
        aux["ssm_state_rms"], np.float64)), axis=-1))
    np.testing.assert_allclose(sorted(values.values()), sorted(by_layer),
                               rtol=1e-6)
