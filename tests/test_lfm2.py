"""LFM2-24B-A2B as one chip's share (PR 37): doubly-gated short-convolution
layers, grouped-query attention with an RMS norm on every q and k head
before the rotation, and sigmoid-routed sparse FFNs without a shared
expert in one model — the program (models/sconv.py, models/transformer.py
``qk_norm``, models/moe.py ``router="sigmoid"`` with ``shared_d_ff=0``)
against the plain reference (tests/reference_lfm2.py, whose copy the
benchmark carries), at toy widths on the CPU, float32, seeded random
weights.
"""

import dataclasses
import importlib.util
import math
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import keystr, tree_flatten_with_path

import reference_lfm2 as ref
from horovod_tpu.models import moe, sconv, ssm
from horovod_tpu.models import transformer as tfm

HERE = os.path.dirname(os.path.abspath(__file__))
E, K = 16, 4          # routed experts, experts per token
HD = 16               # head size
#: published layers 1-9: conv + dense, then (attention, conv x 3) x 2
KINDS = ("sconv", "attention", "sconv", "sconv", "sconv",
         "attention", "sconv", "sconv", "sconv")
ROPE = tfm.RopeSpec(theta=1e6)


def make_cfg(held=(0, 2), kinds=KINDS, **kw):
    """The published pattern at toy widths: a leading dense layer, then
    sparse ones; three short convolutions to one QK-normed GQA layer."""
    layers = tuple(
        tfm.LayerSpec(4, mixer=kind, mlp="dense" if i == 0 else "sparse",
                      rope=ROPE if kind == "attention" else None)
        for i, kind in enumerate(kinds))
    base = dict(
        vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2, head_size=HD,
        n_layers=len(kinds), d_ff=128, max_seq=64, dtype=jnp.float32,
        attention_impl="dense", flash_interpret=True, positional="rope",
        loss_chunk=32, layers=layers, mlp_gated=True, norm_eps=1e-5,
        qk_norm=True, tie_embeddings=True, sconv_kernel=3,
        moe_num_experts=E, moe_top_k=K, moe_d_ff=32, moe_shared_d_ff=0,
        moe_routed_scale=1.0, moe_experts_held=held, moe_router="sigmoid")
    return tfm.TransformerConfig(**dict(base, **kw))


def make_arch(held=(0, 2)):
    return {"rms_norm_eps": 1e-5, "rope_theta": 1e6,
            "moe": {"top_k": K, "routed_scale": 1.0,
                    "experts_held": list(held)}}


def init(cfg, seed=0):
    """Seeded weights with norms and a bias that matter: the per-head norm
    weights away from one, a router bias that changes the choice."""
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    for i, layer in enumerate(params["layers"]):
        for name in ("q_norm", "k_norm"):
            if name in layer:
                layer[name] = 1 + 0.3 * jax.random.normal(
                    jax.random.PRNGKey(10 * i + len(name)), (HD,))
        if "moe" in layer:
            layer["moe"]["router_bias"] = 0.3 * jax.random.normal(
                jax.random.PRNGKey(7 + i), (E,))
    return params


def batch(vocab=256, shape=(2, 64)):
    tok = jax.random.randint(jax.random.PRNGKey(1), shape, 0, vocab)
    return tok, jnp.roll(tok, -1, 1)


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "Q_BLOCK", 32)


def rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def mixer_inputs(seq, channels, taps=3):
    cfg = sconv.SConvConfig(d_model=channels, d_conv=taps,
                            dtype=jnp.float32, param_dtype=jnp.float32)
    p = sconv.init_sconv_params(jax.random.PRNGKey(channels + seq), cfg)
    h = jax.random.normal(jax.random.PRNGKey(seq), (2, seq, channels))
    return cfg, p, h


@pytest.mark.parametrize("seq, channels, taps", [
    (64, 32, 3), (2, 16, 3), (33, 128, 3), (16, 8, 4)])
def test_the_mixer_is_three_shifted_products(seq, channels, taps):
    """``(C * conv(B * u)) w_out`` against the reference's shifted products
    written out, values and the gradient of the input and of every leaf;
    one sequence shorter than the kernel, one odd length, a fourth tap."""
    cfg, p, h = mixer_inputs(seq, channels, taps)
    w = jax.random.normal(jax.random.PRNGKey(3), (2, seq, channels))

    def both(fn):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda p, h: jnp.sum(fn(p, h) * w), argnums=(0, 1))(p, h)

    got, (gp, gh) = both(lambda p, h: sconv.sconv_mixer(p, h, cfg))
    want, (wp, wh) = both(ref.conv_mixer)
    assert p["w_in"].shape == (channels, 3 * channels)
    assert p["conv_w"].shape == (taps, channels)
    assert abs(float(got) - float(want)) < 1e-4 * (1 + abs(float(want)))
    assert rel_err(gh, wh) < 1e-5
    for name in ("w_in", "conv_w", "w_out"):
        assert rel_err(gp[name], wp[name]) < 1e-5, name


def test_the_mixer_is_causal():
    """Changing position t changes no output before t, and changes
    outputs t .. t + 2 (the kernel's reach) and none after."""
    cfg, p, h = mixer_inputs(32, 16)
    t = 11
    base = sconv.sconv_mixer(p, h, cfg)
    moved = sconv.sconv_mixer(p, h.at[:, t].add(1.0), cfg)
    changed = np.asarray(jnp.any(base != moved, axis=(0, 2)))
    assert not changed[:t].any() and changed[t:t + 3].all()
    assert not changed[t + 3:].any()


def test_a_convolution_without_bias_builds_no_zeros():
    """``causal_conv1d(x, w, None)`` is the zero-bias call in every bit,
    and its program adds the three taps' products and nothing to them."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 24))
    w = jax.random.normal(jax.random.PRNGKey(1), (3, 24))
    np.testing.assert_array_equal(
        ssm.causal_conv1d(x, w, None),
        ssm.causal_conv1d(x, w, jnp.zeros((24,))))
    text = str(jax.make_jaxpr(lambda x, w: ssm.causal_conv1d(x, w, None))(
        x, w))
    with_bias = str(jax.make_jaxpr(ssm.causal_conv1d)(x, w, jnp.zeros(24)))
    assert text.count(" add ") == 2 and with_bias.count(" add ") == 3


def attention_layer(qk_norm, impl="dense"):
    cfg = make_cfg(kinds=("attention",), qk_norm=qk_norm,
                   attention_impl=impl, max_seq=256)
    p = init(cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 256, 64))
    return cfg, p, x


def test_qk_norm_and_rotation_through_the_flash_kernels():
    """An attention layer with the per-head norm, through the interpreted
    flash kernels, against the reference's dense attention (norm, then
    rotation, 4 query heads on 2 kv heads): the mixer's output and the
    gradient of every leaf, the two norm weights among them."""
    cfg, p, x = attention_layer(True, "flash")
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)
    w = jax.random.normal(jax.random.PRNGKey(4), x.shape)

    def got_fn(p):
        return jnp.sum((tfm._attention_block(
            p, x, cfg, axes, cfg.layers[0]) - x) * w)

    def want_fn(p):
        h = ref._rmsnorm(x, p["ln1"], 1e-5)
        return jnp.sum(ref._attn_mixer(p, h, make_arch()) * w)

    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(got_fn)(p)
        want, want_g = jax.value_and_grad(want_fn)(p)
    assert abs(float(got) - float(want)) < 1e-4 * (1 + abs(float(want)))
    for name in ("wq", "wkv", "wo", "q_norm", "k_norm", "ln1"):
        assert rel_err(got_g[name], want_g[name]) < 2e-5, name


def test_without_the_field_the_block_is_what_it_was():
    """``qk_norm`` off: no norm leaves, and the block's output is the
    projection -> rotation -> attention -> wo chain written out from the
    block's own parts, in every bit."""
    cfg, p, x = attention_layer(False)
    assert "q_norm" not in p and "k_norm" not in p
    axes, spec = tfm.ShardAxes(dp=None, sp=None, tp=None), cfg.layers[0]
    got = tfm._attention_block(p, x, cfg, axes, spec)
    h = tfm._pre_norm(x, p["ln1"], cfg)
    q, k, v = tfm._qkv_proj(p, h, cfg)
    pos = jnp.arange(x.shape[1])
    q, k = (tfm._rope_spec(t, pos, spec.rope) for t in (q, k))
    a = tfm._attend(q, k, v, None, cfg, axes)
    out = jnp.einsum("bshx,hxd->bsd", a, p["wo"].astype(cfg.dtype),
                     preferred_element_type=jnp.float32)
    np.testing.assert_array_equal(got, tfm._residual(x, out, cfg))
    # and with it on, the output differs
    cfg_n, p_n, _ = attention_layer(True)
    assert rel_err(tfm._attention_block(
        p_n, x, cfg_n, axes, spec), got) > 1e-3


def test_sigmoid_router_top_4_without_a_shared_expert():
    """``router="sigmoid"``, ``top_k=4``, ``shared_d_ff=0``: no ``shared``
    leaf, and the layer is the masked dense sum of ``s / sum(chosen s)``
    weighted experts, the bias in the choice only."""
    cfg = make_cfg(held=(0, E)).moe_cfg
    p = moe.init_moe_params(jax.random.PRNGKey(3), cfg)
    assert "shared" not in p and p["router_bias"].shape == (E,)
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (E,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    got, stats = moe.moe_dropless(p, x, cfg)
    assert float(stats["expert_load"].sum()) == 2 * 64 * K
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ p["w_router"])
        _, chosen = jax.lax.top_k(s + p["router_bias"], K)
        picked = jnp.take_along_axis(s, chosen, -1)
        gates = picked / picked.sum(-1, keepdims=True)
        want = 0
        for e in range(E):
            w_e = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
            want = want + w_e[..., None] * ref._ffn(
                x, {n: p[n][e] for n in ("w1", "w3", "w2")})
    assert rel_err(got, want) < 1e-5
    # the reference adds the family's 1e-6 to the sum: inside rounding
    assert rel_err(ref.sparse(p, x, make_arch((0, E))["moe"])[0],
                   want) < 1e-5


@pytest.mark.parametrize("case", ["share", "uncut", "share_flash_remat"])
def test_model_against_the_reference_loss_and_every_gradient(case):
    """Loss, the per-expert assignment counts and d loss / d leaf for
    every leaf: the nine-layer pattern as the share the benchmark cell
    holds (experts 0-1 of 16 = 1/8); its first five layers (the dense
    layer and one period) uncut, and as the share through the flash
    kernels under remat."""
    held = (0, E) if case == "uncut" else (0, 2)
    kinds = KINDS if case == "share" else KINDS[:5]
    cfg, arch = make_cfg(held, kinds), make_arch(held)
    if case == "share_flash_remat":
        cfg = dataclasses.replace(cfg, attention_impl="flash", remat=True)
    params = init(cfg)
    tok, tgt = batch()
    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_and_stats(p, tok, tgt, cfg),
            has_aux=True))(params)
    (want, aux), want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, arch), has_aux=True))(params)
    assert abs(float(got) - float(want)) < 2e-5
    assert stats["expert_load"].shape == (len(kinds) - 1, held[1])
    np.testing.assert_array_equal(stats["expert_load"], aux["load"])
    assert "lm_head" not in params
    leaves = tree_flatten_with_path(got_g)[0]
    kinds = {keystr(path).split("]", 2)[-1] for path, _ in leaves}
    assert {"['sconv']['w_in']", "['sconv']['conv_w']",
            "['sconv']['w_out']", "['q_norm']", "['k_norm']", "['wkv']",
            "['w3']", "['moe']['w_router']", "['moe']['router_bias']",
            "['moe']['w3']"} <= kinds
    assert not any("shared" in kind for kind in kinds)
    for (path, g), w in zip(leaves, jax.tree.leaves(want_g)):
        if "router_bias" in keystr(path):
            assert not g.any() and not w.any()
            continue
        assert rel_err(g, w) < 3e-4, keystr(path)


def test_eight_expert_shares_add_up_to_the_uncut_layer():
    """16 experts cut 8 ways under the sigmoid router with a bias: the 8
    shares' routed parts are the uncut sparse layer, which is the
    reference's — there is no shared expert to count once — and every
    assignment is taken by exactly one share."""
    whole_cfg = make_cfg(held=(0, E)).moe_cfg
    p = moe.init_moe_params(jax.random.PRNGKey(3), whole_cfg)
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (E,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = moe.moe_dropless(p, x, whole_cfg)
        want, _ = ref.sparse(p, x, make_arch((0, E))["moe"])
        total, taken = 0, 0
        for i in range(8):
            cfg = dataclasses.replace(whole_cfg, experts_held=(2 * i, 2))
            part = {n: (v if n in ("w_router", "router_bias")
                        else v[2 * i:2 * i + 2]) for n, v in p.items()}
            y, stats = moe.moe_dropless(part, x, cfg)
            total, taken = total + y, taken + stats["expert_load"].sum()
            np.testing.assert_array_equal(
                stats["expert_load"],
                whole_stats["expert_load"][2 * i:2 * i + 2])
    assert float(taken) == 2 * 64 * K
    assert rel_err(total, whole) < 1e-5
    assert rel_err(whole, want) < 1e-5


@pytest.mark.parametrize("depth, total", [(9, 832_652_032),
                                          (8, 740_235_968)])
def test_the_cell_s_parameter_count(depth, total):
    """The configuration's arithmetic (ISSUE 37) at the published widths,
    counted from the shapes alone: a conv mixer 16,783,360, an attention
    mixer 10,485,888, a sparse conv layer 92,416,064, the dense layer
    89,139,200; nine layers 832,652,032, the eight that stand
    740,235,968."""
    layers = tuple(
        tfm.LayerSpec(32, mixer=kind, mlp="dense" if i == 0 else "sparse",
                      rope=ROPE if kind == "attention" else None)
        for i, kind in enumerate(KINDS[:depth]))
    cfg = tfm.TransformerConfig(
        vocab_size=8192, d_model=2048, n_heads=32, n_kv_heads=8,
        head_size=64, n_layers=depth, d_ff=11776, positional="rope",
        layers=layers, mlp_gated=True, qk_norm=True, tie_embeddings=True,
        moe_num_experts=64, moe_top_k=4, moe_d_ff=1536,
        moe_experts_held=(0, 8), moe_router="sigmoid")
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))

    def count(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    first, attn, conv = (shapes["layers"][i] for i in (0, 1, 2))
    assert count(first["sconv"]) == 16_783_360
    assert count(first) == 89_139_200
    assert count({n: attn[n] for n in ("wq", "wkv", "wo", "q_norm",
                                       "k_norm")}) == 10_485_888
    assert count(attn) == 86_118_592 and count(conv) == 92_416_064
    assert count(shapes) == total


def test_every_new_scope_is_in_the_step_s_hlo():
    """``hvd_sconv`` around its three parts and ``hvd_qk_norm`` beside
    (never under) ``hvd_attn_proj``, forward and backward, the flash
    kernels under ``hvd_attn_full``; none of the names is a step-region
    label."""
    from horovod_tpu.diag.xla_trace import phase_of_op_name
    cfg = make_cfg(kinds=("sconv", "attention"), attention_impl="flash",
                   remat=True)
    params = init(cfg)
    tok, tgt = batch()

    def step(p, a, b):
        with jax.named_scope("hvd_forward"):
            loss, bwd = jax.vjp(lambda q: tfm.loss_fn(q, a, b, cfg), p)
        with jax.named_scope("hvd_backward"):
            (g,) = bwd(jnp.ones_like(loss))
        return loss, g

    text = jax.jit(step).lower(params, tok, tgt).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*hvd_[^"]*)"', text))
    paths = {p for p in paths if "hvd_forward" in p}
    backward = {p for p in paths if "hvd_backward" in p}
    parts = ("hvd_sconv_in_proj", "hvd_sconv_gate", "hvd_sconv_out_proj")
    for region in (paths - backward, backward):
        for name in parts:
            assert any(re.search(rf"hvd_sconv\)?/{name}/", p)
                       for p in region), name
        assert any(re.search(r"hvd_qk_norm\)?/", p) for p in region)
    assert not any("hvd_attn_proj" in p and "hvd_qk_norm" in p
                   for p in paths)
    for kernel in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"):
        assert any(re.search(rf"hvd_attn_full/{kernel}", p) for p in paths)
    assert any("hvd_moe_route" in p for p in paths)
    for name in parts + ("hvd_sconv", "hvd_qk_norm"):
        assert phase_of_op_name(f"jit(f)/{name}/x") is None


def test_the_two_copies_of_the_reference_agree():
    """tests/reference_lfm2.py is the benchmark's
    benchmark/lib/reference_lfm2.py: the same source, the same loss."""
    path = os.path.join(HERE, os.pardir, "benchmark", "lib",
                        "reference_lfm2.py")
    with open(path, "rb") as a, open(ref.__file__, "rb") as b:
        assert a.read() == b.read()
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    other.Q_BLOCK = ref.Q_BLOCK
    cfg = make_cfg()
    params = init(cfg)
    tok, tgt = batch()
    a, sa = jax.jit(lambda p: ref.loss(p, tok, tgt, make_arch()))(params)
    b, sb = jax.jit(lambda p: other.loss(p, tok, tgt, make_arch()))(params)
    assert float(a) == float(b)
    np.testing.assert_array_equal(sa["load"], sb["load"])


def test_a_configuration_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="'sconv'"):
        make_cfg(kinds=("conv",))
    # one kind of layer with the norm: trained, not decoded
    plain = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_kv_heads=1, n_layers=1,
        d_ff=64, positional="rope", qk_norm=True)
    assert "q_norm" in tfm.init_params(
        jax.random.PRNGKey(0), plain)["layers"][0]
    with pytest.raises(ValueError, match="per-head QK norm"):
        tfm.init_cache(plain, 1, 8)


def test_compiled_step_trains_the_pattern():
    """The normal path: hvd.compiled_train_step with has_aux, compiled
    steps only, one cache miss; the routing counters fed to hvd_moe_*,
    hvd_sconv_layers counting the model traced last, the bias unmoved."""
    import optax

    import horovod_tpu as hvd
    hvd.init()
    cfg = make_cfg(remat=True)
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
        aux = jax.device_get(aux)
        hvd.metrics.record_moe_routing(aux)
    assert step.compiled_steps == 3 and step.fallback_steps == 0
    assert step.cache_misses == 1
    assert losses[2] < losses[0]
    assert aux["expert_load"].shape == (8, 2)
    snap = hvd.metrics_snapshot()
    assert snap["hvd_sconv_layers"]["values"][""] == 7
    assert snap["hvd_kda_layers"]["values"][""] == 0
    for layer in params["layers"][1:]:
        assert not np.asarray(layer["moe"]["router_bias"]).any()
