"""Kimi-Linear as one chip's share (PR 33): KDA layers, a latent-attention
layer without positions and sigmoid-routed sparse FFNs in one model — the
program (models/kda.py, models/transformer.py ``_mla_block``, models/moe.py
``router="sigmoid"``, ops/flash_attention.py with q / k of one head size
and v of another) against the plain reference (tests/reference_kimi_linear
.py, whose copy the benchmark carries), at toy widths on the CPU, float32,
seeded random weights.
"""

import dataclasses
import functools
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.tree_util import keystr, tree_flatten_with_path

import reference_kimi_linear as ref
from horovod_tpu.models import kda, moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import dense_attention

HERE = os.path.dirname(os.path.abspath(__file__))
E, K = 16, 4          # routed experts, experts per token
H, D = 2, 16          # KDA heads, their size
KINDS = ("kda", "kda", "kda", "mla", "kda")


def make_cfg(held=(0, 4), kinds=KINDS, **kw):
    """The published pattern at toy widths: a leading dense layer, then
    sparse ones, KDA x 3 to one latent-attention layer."""
    layers = tuple(tfm.LayerSpec(2, mixer=kind,
                                 mlp="dense" if i == 0 else "sparse")
                   for i, kind in enumerate(kinds))
    base = dict(
        vocab_size=256, d_model=64, n_heads=2, head_size=24,
        n_layers=len(kinds), d_ff=128, max_seq=64, dtype=jnp.float32,
        attention_impl="dense", flash_interpret=True, positional="rope",
        loss_chunk=32, layers=layers, mlp_gated=True, norm_eps=1e-5,
        kda_heads=H, kda_head_dim=D, mla_kv_rank=32,
        mla_qk_nope=16, mla_qk_shared=8, mla_v_dim=16,
        moe_num_experts=E, moe_top_k=K, moe_d_ff=32, moe_shared_d_ff=32,
        moe_routed_scale=2.446, moe_experts_held=held,
        moe_router="sigmoid")
    return tfm.TransformerConfig(**dict(base, **kw))


def make_arch(held=(0, 4)):
    return {"rms_norm_eps": 1e-5, "kda": {"n_heads": H, "head_dim": D},
            "mla": {"kv_rank": 32, "qk_nope": 16},
            "moe": {"top_k": K, "routed_scale": 2.446,
                    "experts_held": list(held)}}


def batch(vocab=256, shape=(2, 64)):
    tok = jax.random.randint(jax.random.PRNGKey(1), shape, 0, vocab)
    return tok, jnp.roll(tok, -1, 1)


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    monkeypatch.setattr(ref, "Q_BLOCK", 32)
    monkeypatch.setattr(ref, "SCAN_BLOCK", 16)


def rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def recurrence_inputs(l, decay, seed=0):
    """q, k normalised, v, log-decays down to ``-decay`` a position, beta;
    (2, l, H, D)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k, v = (jax.random.normal(key, (2, l, H, D)) for key in ks[:3])
    q, k = (x / jnp.linalg.norm(x, axis=-1, keepdims=True) for x in (q, k))
    g = -decay * jax.random.uniform(ks[3], (2, l, H, D))
    if decay > 1:   # half the channels hardly decay, the others hard
        g = jnp.where(jnp.arange(D) % 2 == 0, g, 0.01 * g)
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (2, l, H)))


@pytest.mark.parametrize("seq, chunk, decay", [
    (64, 16, 1.0), (128, 64, 1.0), (96, 32, 1.0), (100, 32, 1.0),
    (128, 64, 20.0)])
def test_chunked_kda_is_the_sequential_recurrence(seq, chunk, decay):
    """Outputs, the final state and the gradient of every input: one
    sub-chunk a chunk, four of them, a length that is padded, and log-
    decays of -20 a position (exp(-G) would overflow within five
    positions of a chunk: everything stays finite and agrees)."""
    args = recurrence_inputs(seq, decay)

    def both(fn):
        def f(*a):
            o, s = fn(*a)
            return jnp.sum(o * jnp.cos(o)) + jnp.sum(s * s), (o, s)
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.value_and_grad(
                f, argnums=(0, 1, 2, 3, 4), has_aux=True))(*args)

    (_, (o, s)), grads = both(lambda *a: kda.kda_chunked(
        *a, chunk=chunk, block_chunks=2))
    (_, (o_w, s_w)), want = both(ref.recurrence)
    assert bool(jnp.isfinite(o).all()) and bool(jnp.isfinite(s).all())
    assert rel_err(o, o_w) < 2e-5 and rel_err(s, s_w) < 2e-5
    for name, g, w in zip("qkvgb", grads, want):
        assert bool(jnp.isfinite(g).all()), name
        assert rel_err(g, w) < 1e-4, name


def kernel_inputs(l, dtype, decay, beta):
    """What the projections leave at 2 heads of 128: q, k (not yet
    normalised), v, the decay's input (1, l, 256) in ``dtype``; beta's
    logits (1, l, 2); A_log (2,), dt_bias (256,). ``decay`` > 1: half the
    channels decay by up to -20 a position, the others hardly; ``beta``:
    None, or the value sigmoid(logits) takes everywhere."""
    ks = jax.random.split(jax.random.PRNGKey(l), 6)
    q, k, v, f = (jax.random.normal(key, (1, l, 256)) for key in ks[:4])
    a_log = jnp.log(jnp.array([3.0, 1.5]))
    dt_bias = 0.1 * jax.random.normal(ks[4], (256,))
    if decay > 1:
        f = jnp.where(jnp.arange(256) % 2 == 0,
                      5.0 * jax.random.uniform(ks[3], (1, l, 256)), -6.0)
        a_log, dt_bias = jnp.log(jnp.array([4.0, 4.0])), 0.0 * dt_bias
    logits = (jax.random.normal(ks[5], (1, l, 2)) if beta is None
              else jnp.full((1, l, 2), 1e4 if beta else -1e4, jnp.float32))
    return tuple(x.astype(dtype) for x in (q, k, v, f)) + (
        logits, a_log, dt_bias)


def prepared(q, k, v, f, logits, a_log, dt_bias):
    """What ``kda_mixer``'s ``prepare`` makes of them, (1, l, 2, 128): q
    and k normalised, the log-decay, sigmoid(logits)."""
    q, k, v, f = (x.reshape(x.shape[:2] + (2, 128)) for x in (q, k, v, f))
    q, k = (kda._l2norm(x).astype(x.dtype) for x in (q, k))
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
        f.astype(jnp.float32) + dt_bias.reshape(2, 128))
    return q, k, v, g, jax.nn.sigmoid(logits)


@functools.lru_cache(maxsize=None)
def value_and_grads(form, seq):
    """``(o, final state)`` and the gradient of every input, jitted once
    a form and length (the interpreted kernels take seconds to compile):
    the Pallas kernel pair, ``kda_chunked``, or the sequential
    recurrence (values only)."""
    from horovod_tpu.ops import kda_scan

    def run(*a):
        if form == "kernels":
            return kda_scan.kda_scan(*a, interpret=True)
        o, s = kda.kda_chunked(*prepared(*a),
                               chunk=kda.CHUNK if seq > 32 else 8)
        return o.reshape(1, seq, 256), s

    def f(*a):
        o, s = run(*a)
        o = o.astype(jnp.float32)
        return jnp.sum(o * jnp.cos(o)) + jnp.sum(s * s), (o, s)

    if form == "sequential":
        return jax.jit(lambda *a: ref.recurrence(*(
            x.astype(jnp.float32) for x in prepared(*a))))
    return jax.jit(jax.value_and_grad(f, argnums=tuple(range(7)),
                                      has_aux=True))


@pytest.mark.parametrize("seq, decay, beta, dtype", [
    (128, 1.0, None, "float32"), (72, 1.0, None, "float32"),
    (24, 1.0, None, "float32"), (128, 20.0, None, "float32"),
    (128, 1.0, 0, "float32"), (128, 1.0, 1, "float32"),
    (128, 1.0, None, "bfloat16"), (128, 20.0, None, "bfloat16"),
    (128, 1.0, 0, "bfloat16"), (128, 1.0, 1, "bfloat16")])
def test_kda_kernels_are_the_xla_form(seq, decay, beta, dtype):
    """The Pallas kernel pair (interpreted) against ``kda_chunked`` on the
    same inputs — ``o``, the final state and the gradient of EVERY input
    (q, k, v, the decay's input, beta's logits, A_log, dt_bias) — and,
    for values, against the sequential recurrence: whole chunks, a padded
    tail, less than a chunk, log-decays of -20 a position (nothing inf or
    nan), beta at 0 and at 1; float32 at the chunked form's own
    tolerances, bfloat16 at 8 / 16 of its eps (the two forms round at the
    same places and cut their chunks differently)."""
    args = kernel_inputs(seq, jnp.dtype(dtype), decay, beta)
    tol_v, tol_g = (2e-5, 1e-4) if dtype == "float32" else (2 ** -5, 2 ** -4)
    with jax.default_matmul_precision("highest"):
        (_, (o, s)), grads = value_and_grads("kernels", seq)(*args)
        (_, (o_w, s_w)), want = value_and_grads("xla", seq)(*args)
        o_r, s_r = value_and_grads("sequential", seq)(*args)
    assert o.shape == (1, seq, 256) and s.shape == (1, 2, 128, 128)
    assert rel_err(o, o_w) < tol_v and rel_err(s, s_w) < tol_v
    assert rel_err(o, o_r.reshape(o.shape)) < tol_v
    assert rel_err(s, s_r) < tol_v
    for name, g, w in zip(("q", "k", "v", "f", "beta", "A_log", "dt_bias"),
                          grads, want):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        if beta is not None and name == "beta":
            assert not g.any() and not w.any()   # sigmoid is flat there
            continue
        assert rel_err(g, w) < tol_g, name


@pytest.mark.parametrize("head_dim, fused", [(16, 0), (128, 2)])
def test_the_head_size_chooses_the_form(head_dim, fused, caplog):
    """A head of 16 runs the XLA form and says so once in the log; a
    head of 128 runs the kernels (``hvd_kda_fwd`` in the traced program)
    — ``hvd_kda_fused_layers`` reads 0 / the KDA layers, and no
    configuration key, flag or environment variable enters."""
    import horovod_tpu as hvd
    cfg = make_cfg(kinds=("kda", "mla", "kda"), kda_heads=1,
                   kda_head_dim=head_dim)
    assert cfg.kda_cfg.fused == bool(fused)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    tok, tgt = batch()
    kda._say_xla_form.cache_clear()
    from horovod_tpu.utils.logging import get_logger
    logger = get_logger("horovod_tpu.models.kda")   # does not propagate
    logger.addHandler(caplog.handler)
    try:
        text = str(jax.make_jaxpr(
            lambda p: tfm.loss_and_stats(p, tok, tgt, cfg))(shapes))
    finally:
        logger.removeHandler(caplog.handler)
    said = [r.getMessage() for r in caplog.records
            if "run the recurrence as XLA ops" in r.getMessage()]
    assert ("hvd_kda_fwd" in text) == bool(fused)
    assert len(said) == (0 if fused else 1)
    assert all("head size 16" in line for line in said)
    snap = hvd.metrics_snapshot()
    assert snap["hvd_kda_layers"]["values"][""] == 2
    assert snap["hvd_kda_fused_layers"]["values"][""] == fused


def test_a_chunk_is_whole_sub_chunks():
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_chunked(*recurrence_inputs(48, 1.0), chunk=48)


@pytest.mark.parametrize("h_kv", [4, 2])
def test_flash_attention_with_a_head_size_of_its_own_for_v(h_kv):
    """q, k of 192 beside v of 128 (interpreted kernels), forward and dQ /
    dK / dV against dense attention, two blocks a sequence; and the
    kernels every other caller uses: with v cut to its first 64 columns
    the output is the equal-size call's first 64 columns in every bit."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 192))
    k = jax.random.normal(ks[1], (1, 256, h_kv, 192))
    v = jax.random.normal(ks[2], (1, 256, h_kv, 128))
    w = jax.random.normal(ks[3], (1, 256, 4, 128))

    def run(fn):
        def f(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out * w), out
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(f, argnums=(0, 1, 2),
                                      has_aux=True)(q, k, v)

    (_, out), grads = run(lambda q, k, v: flash_attention(
        q, k, v, True, 128, True))
    (_, want), want_g = run(lambda q, k, v: dense_attention(
        q, k, v, causal=True))
    assert out.shape == (1, 256, 4, 128)
    assert rel_err(out, want) < 1e-5
    for g, wg in zip(grads, want_g):
        assert g.shape == wg.shape and rel_err(g, wg) < 1e-5
    k128 = k[..., :128]
    equal = flash_attention(q[..., :128], k128, v, True, 128, True)
    cut = flash_attention(q[..., :128], k128, v[..., :64], True, 128, True)
    np.testing.assert_array_equal(cut, equal[..., :64])


def test_a_bias_changes_the_choice_and_not_the_weights():
    """The sigmoid router: ``chosen = top_k(s + b)``, ``w = scale * s /
    sum(chosen s)`` — with a bias that pushes expert 3 into every token's
    choice the layer is the masked dense sum under exactly that formula,
    and differs from the unbiased layer."""
    cfg = make_cfg(held=(0, E)).moe_cfg
    p = moe.init_moe_params(jax.random.PRNGKey(3), cfg)
    assert p["router_bias"].shape == (E,) and not p["router_bias"].any()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    plain, _ = moe.moe_dropless(p, x, cfg)
    biased = dict(p, router_bias=p["router_bias"].at[3].set(10.0))
    got, stats = moe.moe_dropless(biased, x, cfg)
    assert float(stats["expert_load"][3]) == 2 * 64
    with jax.default_matmul_precision("highest"):
        s = jax.nn.sigmoid(x @ p["w_router"])
        _, chosen = jax.lax.top_k(s.at[..., 3].add(10.0), K)
        picked = jnp.take_along_axis(s, chosen, -1)
        gates = 2.446 * picked / picked.sum(-1, keepdims=True)
        want = ref._ffn(x, p["shared"])
        for e in range(E):
            w_e = jnp.sum(jnp.where(chosen == e, gates, 0.0), -1)
            want = want + w_e[..., None] * ref._ffn(
                x, {n: p[n][e] for n in ("w1", "w3", "w2")})
    assert rel_err(got, want) < 1e-5
    assert rel_err(plain, want) > 1e-2
    # the bias gets no gradient
    grad = jax.grad(lambda b: moe.moe_dropless(
        dict(p, router_bias=b), x, cfg)[0].sum())(biased["router_bias"])
    assert not grad.any()


@pytest.mark.parametrize("case", ["share", "uncut", "share_flash_remat"])
def test_model_against_the_reference_loss_and_every_gradient(case):
    """Loss, the per-expert assignment counts, the KDA layers' final-state
    rms by head and d loss / d leaf for every leaf: the five-layer
    pattern as the share the benchmark cell holds (experts 0-3 of 16),
    uncut, and the share through the flash kernels under remat."""
    held = (0, E) if case == "uncut" else (0, 4)
    cfg, arch = make_cfg(held), make_arch(held)
    if case == "share_flash_remat":
        cfg = dataclasses.replace(cfg, attention_impl="flash", remat=True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    # a bias that matters: the reference must use it in the choice only
    params["layers"][1]["moe"]["router_bias"] = 0.3 * jax.random.normal(
        jax.random.PRNGKey(7), (E,))
    tok, tgt = batch()
    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_and_stats(p, tok, tgt, cfg),
            has_aux=True))(params)
    (want, aux), want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, arch), has_aux=True))(params)
    assert abs(float(got) - float(want)) < 2e-5
    np.testing.assert_array_equal(stats["expert_load"], aux["load"])
    assert stats["kda_state_rms"].shape == (4, H)
    np.testing.assert_allclose(stats["kda_state_rms"], aux["rms"],
                               rtol=1e-4)
    leaves = tree_flatten_with_path(got_g)[0]
    kinds = {keystr(path).split("]", 2)[-1] for path, _ in leaves}
    assert {"['kda']['conv_w']", "['kda']['w_fb']", "['kda']['A_log']",
            "['mla']['w_kvb']", "['mla']['kv_norm']", "['w3']",
            "['moe']['w_router']", "['moe']['router_bias']",
            "['moe']['shared']['w2']"} <= kinds
    for (path, g), w in zip(leaves, jax.tree.leaves(want_g)):
        if "router_bias" in keystr(path):
            assert not g.any() and not w.any()
            continue
        assert rel_err(g, w) < 3e-4, keystr(path)


def test_expert_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """16 experts cut 4 ways under the sigmoid router with a bias: the 4
    shares' routed parts plus the shared expert counted once are the
    uncut sparse layer, which is the reference's, and every assignment is
    taken by exactly one share."""
    whole_cfg = make_cfg(held=(0, E)).moe_cfg
    p = moe.init_moe_params(jax.random.PRNGKey(3), whole_cfg)
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(5), (E,))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = moe.moe_dropless(p, x, whole_cfg)
        want, _ = ref._sparse(p, x, make_arch((0, E))["moe"])
        shared = ref._ffn(x, p["shared"])  # what every chip computes alike
        total, taken = 0, 0
        for i in range(4):
            cfg = dataclasses.replace(whole_cfg, experts_held=(4 * i, 4))
            part = {n: (v if n in ("w_router", "router_bias", "shared")
                        else v[4 * i:4 * i + 4]) for n, v in p.items()}
            y, stats = moe.moe_dropless(part, x, cfg)
            total, taken = total + y, taken + stats["expert_load"].sum()
            np.testing.assert_array_equal(
                stats["expert_load"],
                whole_stats["expert_load"][4 * i:4 * i + 4])
    assert float(taken) == 2 * 64 * K
    assert rel_err(total - 3 * shared, whole) < 1e-5
    assert rel_err(whole, want) < 1e-5


def test_the_cell_s_parameter_count():
    """The configuration's arithmetic (ISSUE 33): a KDA mixer 39,514,272
    parameters, the latent-attention mixer 29,114,880, at the published
    widths — counted from the shapes alone."""
    layers = (tfm.LayerSpec(32, mixer="kda"), tfm.LayerSpec(32, mixer="mla"))
    cfg = tfm.TransformerConfig(
        vocab_size=128, d_model=2304, n_heads=32, head_size=192,
        n_layers=2, d_ff=128, positional="rope", layers=layers,
        mlp_gated=True, kda_heads=32)
    shapes = jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                            jax.random.PRNGKey(0))

    def count(tree):
        return sum(math.prod(x.shape) for x in jax.tree.leaves(tree))

    assert count(shapes["layers"][0]["kda"]) == 39_514_272
    assert count(shapes["layers"][1]["mla"]) == 29_114_880


@pytest.mark.parametrize("head_dim", [16, 128])
def test_every_new_scope_is_in_the_step_s_hlo(head_dim):
    """``hvd_kda`` around its five parts, ``hvd_mla_proj`` and the
    latent-attention layer's kernels under ``hvd_attn_full``, forward and
    backward; none of the names is a step-region label. With a head of
    128 ``hvd_kda_scan`` holds the forward kernel (in the forward and in
    remat's second one) and the backward kernel, a ``custom_vjp``'s."""
    import re

    from horovod_tpu.diag.xla_trace import phase_of_op_name
    cfg = make_cfg(kinds=("kda", "mla"), attention_impl="flash",
                   remat=True, kda_heads=1 if head_dim == 128 else H,
                   kda_head_dim=head_dim)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch()

    def step(p, a, b):
        with jax.named_scope("hvd_forward"):
            loss, bwd = jax.vjp(lambda q: tfm.loss_fn(q, a, b, cfg), p)
        with jax.named_scope("hvd_backward"):
            (g,) = bwd(jnp.ones_like(loss))
        return loss, g

    text = jax.jit(step).lower(params, tok, tgt).as_text(debug_info=True)
    paths = set(re.findall(r'loc\("([^"]*hvd_[^"]*)"', text))
    parts = ("hvd_kda_in_proj", "hvd_kda_conv", "hvd_kda_scan",
             "hvd_kda_norm", "hvd_kda_out_proj")
    paths = {p for p in paths if "hvd_forward" in p}
    backward = {p for p in paths if "hvd_backward" in p}
    for region in (paths - backward, backward):
        for name in parts:
            assert any(re.search(rf"hvd_kda\)?/{name}/", p)
                       for p in region), name
        assert any(re.search(r"hvd_mla_proj\)?/", p) for p in region)
    for kernel in ("hvd_flash_fwd", "hvd_flash_dq", "hvd_flash_dkv"):
        assert any(re.search(rf"hvd_attn_full/{kernel}", p) for p in paths)
    if head_dim == 128:
        # each kernel's call is jitted (one lowering a model): the call
        # site carries the scopes, the callee the kernel's own name, and
        # XLA joins them (tests/test_flash_v5e_compile.py reads the
        # compiled op_name)
        for region, calls in ((paths - backward, ("_forward",)),
                              (backward, ("_forward", "_backward"))):
            for call in calls:
                assert any(re.search(rf"hvd_kda_scan/jit\({call}\)$", p)
                           for p in region), call
        inside = set(re.findall(r'loc\("(hvd_kda_(?:fwd|bwd)/[^"]*)"', text))
        for kernel in ("hvd_kda_fwd", "hvd_kda_bwd"):
            assert any(p.startswith(f"{kernel}/{kernel}/") for p in inside)
    assert any("hvd_moe_route" in p for p in paths)
    for name in parts + ("hvd_kda", "hvd_mla_proj"):
        assert phase_of_op_name(f"jit(f)/{name}/x") is None


def test_the_two_copies_of_the_reference_agree():
    """tests/reference_kimi_linear.py is the benchmark's
    benchmark/lib/reference_kimi_linear.py: the same source, the same
    loss."""
    path = os.path.join(HERE, os.pardir, "benchmark", "lib",
                        "reference_kimi_linear.py")
    with open(path, "rb") as a, open(ref.__file__, "rb") as b:
        assert a.read() == b.read()
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    other.Q_BLOCK, other.SCAN_BLOCK = ref.Q_BLOCK, ref.SCAN_BLOCK
    cfg = make_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch()
    a, sa = jax.jit(lambda p: ref.loss(p, tok, tgt, make_arch()))(params)
    b, sb = jax.jit(lambda p: other.loss(p, tok, tgt, make_arch()))(params)
    assert float(a) == float(b)
    np.testing.assert_array_equal(sa["rms"], sb["rms"])


@pytest.mark.parametrize("path", ["decode", "serve", "pipeline", "sp"])
@pytest.mark.parametrize("mixer, named", [
    ("kda", "KDA layers"), ("mla", "unequal qk / v head sizes"),
    ("sconv", "short-convolution layers"),
    ("qk_norm", "per-head QK norm")])
def test_a_layer_is_refused_where_it_cannot_run(mixer, named, path):
    """Decode, the serving engine, the pipeline stages and sequence
    parallelism say which layer they cannot run (``qk_norm``: an
    attention layer with the per-head norm on q and k)."""
    if mixer == "qk_norm":
        layers = (tfm.LayerSpec(2, rope=tfm.RopeSpec()),
                  tfm.LayerSpec(2, mixer="kda"))
        cfg = make_cfg(layers=layers, n_layers=2, qk_norm=True)
    else:
        cfg = make_cfg(kinds=(mixer, "kda"))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch(shape=(2, 32))
    if path == "sp":
        block = {"kda": tfm._kda_block, "mla": tfm._mla_block,
                 "sconv": tfm._sconv_block,
                 "qk_norm": tfm._attention_block}[mixer]
        with pytest.raises(ValueError, match="sequence"):
            block(params["layers"][0], jnp.zeros((1, 16, 64)), cfg,
                  tfm.ShardAxes(dp=None, sp="sp", tp=None),
                  *((cfg.layers[0],) if mixer == "qk_norm" else ()))
        return
    with pytest.raises(ValueError, match=named):
        if path == "decode":
            tfm.init_cache(cfg, 1, 16)
        elif path == "serve":
            from horovod_tpu.serve import engine
            engine.ServeEngine(params, cfg, num_pages=4, page_size=8)
            raise AssertionError(f"the engine took a {mixer} layer")
        else:
            tfm.pipeline_loss_fn(params, tok, tgt, cfg, num_microbatches=1)


def test_a_configuration_that_cannot_be_is_refused():
    with pytest.raises(ValueError, match="kda_heads"):
        make_cfg(kda_heads=0)
    with pytest.raises(ValueError, match="neither positions"):
        make_cfg(layers=tuple(
            tfm.LayerSpec(2, mixer="mla", rope=tfm.RopeSpec())
            for _ in KINDS))


def test_compiled_step_carries_both_statistics_out():
    """The normal path: hvd.compiled_train_step with has_aux, compiled
    steps only, one cache miss; ``kda_state_rms`` fed to the
    hvd_kda_state_rms family, the routing counters to hvd_moe_*, and
    hvd_kda_layers counting the model traced last."""
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
        hvd.metrics.record_kda_state(aux)
        hvd.metrics.record_moe_routing(aux)
    assert step.compiled_steps == 3 and step.fallback_steps == 0
    assert step.cache_misses == 1
    assert losses[2] < losses[0]
    assert aux["kda_state_rms"].shape == (4, H)
    assert aux["expert_load"].shape == (4, 4)
    snap = hvd.metrics_snapshot()
    assert snap["hvd_kda_layers"]["values"][""] == 4
    values = snap["hvd_kda_state_rms"]["values"]
    by_layer = np.sqrt(np.mean(np.square(np.asarray(
        aux["kda_state_rms"], np.float64)), axis=-1))
    np.testing.assert_allclose(sorted(values.values()), sorted(by_layer),
                               rtol=1e-6)
    # the balancing bias starts at zero and no step moves it
    for layer in params["layers"][1:]:
        assert not np.asarray(layer["moe"]["router_bias"]).any()
