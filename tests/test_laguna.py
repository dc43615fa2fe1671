"""A model whose layers differ, held as one chip's share (PR 27): the
program (models/transformer.py per-layer description, models/moe.py
``moe_dropless``) against the plain reference (tests/reference_laguna.py,
whose copy the benchmark carries), at toy widths on the CPU, float32,
seeded random weights.

- the whole model, loss and the gradient of every leaf, as a share and
  uncut;
- the share tied to the model: the head shares' ``a wo`` add up to the
  uncut attention layer, the expert shares plus the shared expert counted
  once add up to the uncut sparse layer;
- dropless routing against the masked dense reference under a skewed
  router (several chunks of rows, an expert with no token), also with
  NaN in every row a grouped matmul leaves unwritten; the groups ending
  at a chunk's last live row against the padded form, bit for bit;
- the routing gauges after a compiled step;
- YaRN / partial rotary against the closed form;
- the two copies of the reference agree.
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

import reference_laguna as ref
from horovod_tpu.models import moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops.grouped_matmul import grouped_matmul

HERE = os.path.dirname(os.path.abspath(__file__))

ROPE_FULL = {"rope_theta": 500000, "rope_type": "yarn", "factor": 8,
             "original_max_position_embeddings": 32, "beta_slow": 1,
             "beta_fast": 32, "attention_factor": 1.2,
             "partial_rotary_factor": 0.5}
ROPE_SLIDING = {"rope_type": "default", "rope_theta": 10000,
                "partial_rotary_factor": 1}
FULL = tfm.RopeSpec(theta=5e5, rotary_dim=8, yarn_factor=8,
                    yarn_original_max_seq=32, attention_factor=1.2)
SLIDING = tfm.RopeSpec()
E, K = 16, 4          # routed experts, experts per token


def make_cfg(heads=(2, 3), kv_heads=1, held=(0, 4), vocab=256, **kw):
    """Full + dense, sliding + sparse, full + sparse: every kind of layer
    the configuration has, once. ``heads`` = (full, sliding) query
    heads."""
    full, sliding = heads
    layers = (tfm.LayerSpec(full, None, FULL, "dense"),
              tfm.LayerSpec(sliding, 16, SLIDING, "sparse"),
              tfm.LayerSpec(full, None, FULL, "sparse"))
    base = dict(
        vocab_size=vocab, d_model=64, n_heads=full, n_kv_heads=kv_heads,
        head_size=16, n_layers=3, d_ff=128, max_seq=64,
        dtype=jnp.float32, attention_impl="dense", flash_interpret=True,
        positional="rope", loss_chunk=32, layers=layers, attn_gate=True,
        mlp_gated=True, moe_num_experts=E, moe_top_k=K, moe_d_ff=32,
        moe_shared_d_ff=32, moe_routed_scale=2.5, moe_experts_held=held)
    return tfm.TransformerConfig(**dict(base, **kw))


def make_arch(held=(0, 4)):
    return {"layers": [{"window": None, "rope": ROPE_FULL},
                       {"window": 16, "rope": ROPE_SLIDING},
                       {"window": None, "rope": ROPE_FULL}],
            "moe": {"num_experts": E, "top_k": K, "routed_scale": 2.5,
                    "experts_held": list(held)}}


def batch(vocab=256, shape=(2, 64)):
    tok = jax.random.randint(jax.random.PRNGKey(1), shape, 0, vocab)
    return tok, jnp.roll(tok, -1, 1)


@pytest.fixture(autouse=True)
def small_reference_blocks(monkeypatch):
    # two query blocks, the window across them
    monkeypatch.setattr(ref, "Q_BLOCK", 32)


def rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.mark.parametrize("case", ["share", "uncut", "share_flash_remat"])
def test_model_against_the_reference_loss_and_every_gradient(case):
    """Loss, the per-expert assignment counts and d loss / d leaf for
    every leaf kind: as the share the benchmark cell holds (1 kv head,
    experts 0-3 of 16), uncut (4 kv heads, all 16 experts), and the share
    through the flash kernels under remat."""
    if case == "uncut":
        cfg, arch = make_cfg((8, 12), 4, (0, E)), make_arch((0, E))
    else:
        cfg, arch = make_cfg(), make_arch()
    if case == "share_flash_remat":
        cfg = dataclasses.replace(cfg, attention_impl="flash", remat=True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch()
    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.jit(jax.value_and_grad(
            lambda p: tfm.loss_and_stats(p, tok, tgt, cfg),
            has_aux=True))(params)
    (want, load), want_g = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, tok, tgt, arch), has_aux=True))(params)
    assert abs(float(got) - float(want)) < 2e-5
    np.testing.assert_array_equal(stats["expert_load"], load)
    assert stats["unrouted_tokens"].shape == (2,)
    leaves = tree_flatten_with_path(got_g)[0]
    kinds = {keystr(path).split("]", 2)[-1] for path, _ in leaves}
    assert {"['wg']", "['w3']", "['moe']['w_router']", "['moe']['w1']",
            "['moe']['shared']['w2']"} <= kinds
    for (path, g), w in zip(leaves, jax.tree.leaves(want_g)):
        assert rel_err(g, w) < 2e-4, keystr(path)


def test_head_shares_add_up_to_the_uncut_attention_layer():
    """8 kv heads with 2 query heads each, cut 8 ways: each share's
    ``x + (gate * a) wo`` less the residual is a partial sum over its
    heads, and the 8 add up to the uncut layer."""
    cfg = make_cfg((16, 16), 8)
    spec = tfm.LayerSpec(16, 16, SLIDING, "dense")
    p = tfm.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64))
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)
    whole = tfm._attention_block(p, x, cfg, axes, spec) - x
    share_cfg = make_cfg((2, 2), 1)
    total = 0
    for i in range(8):
        q = slice(2 * i, 2 * i + 2)
        part = dict(p, wq=p["wq"][:, q], wkv=p["wkv"][:, :, i:i + 1],
                    wg=p["wg"][q], wo=p["wo"][q])
        total = total + tfm._attention_block(
            part, x, share_cfg, axes, dataclasses.replace(spec, n_heads=2)
        ) - x
    assert rel_err(total, whole) < 1e-5
    # and the reference, given the uncut layer, says the same
    arch_layer = {"window": 16, "rope": ROPE_SLIDING}
    with jax.default_matmul_precision("highest"):
        want, _ = ref._layer(dict(p, w1=p["w1"] * 0, w3=p["w3"] * 0),
                             x, arch_layer, None)
    assert rel_err(whole, want - x) < 1e-5


def test_expert_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """16 experts cut 4 ways: the 4 shares' routed parts plus the shared
    expert counted once are the uncut sparse layer, and every assignment
    is taken by exactly one share."""
    whole_cfg = make_cfg(held=(0, E)).moe_cfg
    p = moe.init_moe_params(jax.random.PRNGKey(3), whole_cfg)
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 64, 64))
    whole, whole_stats = moe.moe_dropless(p, x, whole_cfg)
    routed, _ = moe.moe_dropless(
        {n: v for n, v in p.items() if n != "shared"}, x, whole_cfg)
    shared = whole - routed  # what every chip computes alike
    assert float(jnp.max(jnp.abs(shared))) > 0.1
    total, taken = 0, 0
    for i in range(4):
        cfg = dataclasses.replace(whole_cfg, experts_held=(4 * i, 4))
        part = {n: (v if n in ("w_router", "shared")
                    else v[4 * i:4 * i + 4]) for n, v in p.items()}
        y, stats = moe.moe_dropless(part, x, cfg)
        total, taken = total + y, taken + stats["expert_load"].sum()
        np.testing.assert_array_equal(
            stats["expert_load"],
            whole_stats["expert_load"][4 * i:4 * i + 4])
    assert float(taken) == 2 * 64 * K
    assert rel_err(total - 3 * shared, whole) < 1e-5


def nan_past_the_groups(out, sizes):
    """What a grouped matmul leaves in the rows past its last group:
    unwritten memory, here NaN in every one of them."""
    dead = jnp.arange(out.shape[0]) >= jnp.sum(sizes)
    return jnp.where(dead[:, None], jnp.nan, out)


def poisoned_grouped_matmul(sums):
    """``moe.grouped_matmul`` whose rows past the last group come back
    NaN, in the forward and in the rows' cotangent (the interpreter would
    hand back whatever it had there); ``sums`` collects each call's
    ``sum(group_sizes)``."""
    real = moe.grouped_matmul

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
    def gmm(lhs, rhs, sizes, out_dtype, interpret):
        return gmm_fwd(lhs, rhs, sizes, out_dtype, interpret)[0]

    def gmm_fwd(lhs, rhs, sizes, out_dtype, interpret):
        jax.debug.callback(lambda n: sums.append(int(n)), jnp.sum(sizes))
        out, vjp = jax.vjp(
            lambda a, b: real(a, b, sizes, out_dtype, interpret), lhs, rhs)
        return nan_past_the_groups(out, sizes), (vjp, sizes)

    def gmm_bwd(out_dtype, interpret, res, ct):
        vjp, sizes = res
        d_lhs, d_rhs = vjp(ct)
        return nan_past_the_groups(d_lhs, sizes), d_rhs, None

    gmm.defvjp(gmm_fwd, gmm_bwd)
    return gmm


def padded_grouped_matmul(lhs, rhs, sizes, out_dtype, interpret):
    """The form before the groups ended at the last live row: every row of
    the chunk in some group, the dead ones as the last group's tail."""
    sizes = sizes.at[-1].add(lhs.shape[0] - jnp.sum(sizes))
    return grouped_matmul(lhs, rhs, sizes, out_dtype, interpret)


def skewed_layer(skew):
    """A sparse layer with no shared expert and its input, the router
    pushed onto the held experts 0 and 2 by ``skew`` and away from 1."""
    cfg = dataclasses.replace(make_cfg().moe_cfg, shared_d_ff=0)
    p = moe.init_moe_params(jax.random.PRNGKey(5), cfg)
    x = jax.random.normal(jax.random.PRNGKey(6), (8, 128, 64))
    # a constant feature gives the router a bias to skew
    x = x.at[..., 0].set(4.0)
    bias = jnp.zeros((E,)).at[jnp.array([0, 2])].set(skew).at[1].set(
        -4 * skew)
    p["w_router"] = p["w_router"].at[0].add(bias)
    return cfg, p, x


@pytest.mark.parametrize("skew, poison", [
    pytest.param(0.0, False, id="0.0"), pytest.param(6.0, False, id="6.0"),
    pytest.param(0.0, True, id="0.0-poison"),
    pytest.param(6.0, True, id="6.0-poison")])
def test_dropless_routing_against_the_masked_dense_reference(
        skew, poison, monkeypatch):
    """Output, gradients and counts under a router pushed onto the held
    experts 0 and 2 (``skew``): more than one chunk of rows runs, and
    expert 1 is biased away until it gets no token; nothing is dropped.
    ``poison``: the grouped matmuls' rows past the chunk's last live
    assignment come back NaN, and nothing of them reaches a result; each
    call's groups end at that assignment."""
    cfg, p, x = skewed_layer(skew)
    sums = []
    if poison:
        monkeypatch.setattr(moe, "grouped_matmul",
                            poisoned_grouped_matmul(sums))
    moe_arch = make_arch()["moe"]

    def program(p, x):
        y, stats = moe.moe_dropless(p, x, cfg)
        return jnp.sum(y * jnp.cos(y)), stats

    def reference(p, x):
        y, load = ref._sparse(p, x, moe_arch)
        return jnp.sum(y * jnp.cos(y)), load

    with jax.default_matmul_precision("highest"):
        (got, stats), got_g = jax.jit(jax.value_and_grad(
            program, argnums=(0, 1), has_aux=True))(p, x)
        (want, load), want_g = jax.jit(jax.value_and_grad(
            reference, argnums=(0, 1), has_aux=True))(p, x)
    np.testing.assert_array_equal(stats["expert_load"], load)
    total = float(load.sum())
    assert total + float(stats["unrouted_tokens"]) >= 8 * 128  # no drop
    if skew:
        rows = moe.chunk_rows(8 * 128, cfg)
        assert total > rows, (total, rows)        # a second chunk ran
        assert float(load[1]) == 0                # an expert with no token
    assert abs(float(got) - float(want)) < 1e-4 * abs(float(want))
    for (path, g), w in zip(tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        assert rel_err(g, w) < 2e-4, keystr(path)
        assert bool(jnp.all(jnp.isfinite(g))), keystr(path)
    if poison:
        assert np.isfinite(float(got))
        rows = moe.chunk_rows(8 * 128, cfg)
        live = {min(rows, int(total) - lo)
                for lo in range(0, int(total), rows)}
        # forward, and the backward's recompute of each chunk: 3 matmuls
        assert set(sums) == live and len(sums) == 6 * len(live), sums
        assert min(sums) < rows


@pytest.mark.parametrize("skew", [0.0, 6.0], ids=["one-chunk", "two-chunks"])
def test_dropless_groups_ending_at_the_last_live_row_change_no_bit(
        skew, monkeypatch):
    """The groups ending at each chunk's last live assignment against the
    padded form (the dead rows as the last group's tail): the live rows'
    products are the same, and the padded form only ever added zero rows,
    so the output and every gradient agree in every bit."""
    cfg, p, x = skewed_layer(skew)

    def run():
        def f(p, x):
            y, stats = moe.moe_dropless(p, x, cfg)
            return jnp.sum(y * jnp.cos(y)), (y, stats["expert_load"])
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1),
                                          has_aux=True))(p, x)

    (_, (got, load)), got_g = run()
    monkeypatch.setattr(moe, "grouped_matmul", padded_grouped_matmul)
    (_, (want, _)), want_g = run()
    chunks = math.ceil(float(load.sum()) / moe.chunk_rows(8 * 128, cfg))
    assert chunks == (2 if skew else 1)
    np.testing.assert_array_equal(got, want)
    for (path, g), w in zip(tree_flatten_with_path(got_g)[0],
                            jax.tree.leaves(want_g)):
        np.testing.assert_array_equal(g, w, err_msg=keystr(path))


def test_yarn_and_partial_rotary_against_the_closed_form():
    """The published block of the full-attention layers: 64 of 128
    features rotated, theta 5e5, factor 128 over 8192 positions."""
    spec = tfm.RopeSpec(theta=500000.0, rotary_dim=64, yarn_factor=128.0,
                        yarn_original_max_seq=8192, yarn_beta_fast=32.0,
                        yarn_beta_slow=1.0,
                        attention_factor=1.4852030263919618)
    inv = tfm.rope_inv_freq(spec, 128)
    base = 500000.0 ** (-np.arange(32) / 32)

    def dim_of(rotations):  # the dim that turns `rotations` times in 8192
        return 64 * math.log(8192 / (rotations * 2 * math.pi)) \
            / (2 * math.log(500000.0))

    low, high = math.floor(dim_of(32)), math.ceil(dim_of(1))
    assert (low, high) == (9, 18)
    np.testing.assert_allclose(inv[:low + 1], base[:low + 1], rtol=1e-12)
    np.testing.assert_allclose(inv[high:], base[high:] / 128, rtol=1e-12)
    mid = (12 - low) / (high - low)
    np.testing.assert_allclose(
        inv[12], base[12] * (1 - mid) + base[12] / 128 * mid, rtol=1e-12)
    np.testing.assert_allclose(
        inv, ref.inv_freq({**ROPE_FULL, "factor": 128, "beta_fast": 32,
                           "original_max_position_embeddings": 8192},
                          128), rtol=1e-12)
    assert abs(0.1 * math.log(128) + 1 - spec.attention_factor) < 1e-12
    # the rotation itself: pairs (i, i + 32), the last 64 untouched
    x = jax.random.normal(jax.random.PRNGKey(7), (1, 5, 2, 128))
    pos = jnp.array([0, 1, 7, 100, 8191])
    out = tfm._rope_spec(x, pos, spec)
    np.testing.assert_array_equal(out[..., 64:], x[..., 64:])
    ang = np.asarray(pos, np.float64)[:, None] * inv[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    x1, x2 = np.asarray(x[..., :32]), np.asarray(x[..., 32:64])
    want = spec.attention_factor * np.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    np.testing.assert_allclose(out[..., :64], want, atol=2e-3)
    # position 0 only scales; the plain spec is _rope itself
    np.testing.assert_allclose(out[:, 0, :, :64],
                               spec.attention_factor * x[:, 0, :, :64],
                               rtol=1e-6)
    np.testing.assert_array_equal(tfm._rope_spec(x, pos, tfm.RopeSpec()),
                                  tfm._rope(x, pos))


def test_the_two_copies_of_the_reference_agree():
    """tests/reference_laguna.py is the benchmark's
    benchmark/lib/reference_laguna.py: the same source, the same loss."""
    path = os.path.join(HERE, os.pardir, "benchmark", "lib",
                        "reference_laguna.py")
    with open(path, encoding="utf-8") as a, \
            open(ref.__file__, encoding="utf-8") as b:
        assert a.read() == b.read()
    spec = importlib.util.spec_from_file_location("bench_reference", path)
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    other.Q_BLOCK = 32
    cfg = make_cfg()
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok, tgt = batch()
    a, la = jax.jit(lambda p: ref.loss(p, tok, tgt, make_arch()))(params)
    b, lb = jax.jit(lambda p: other.loss(p, tok, tgt, make_arch()))(params)
    assert float(a) == float(b)
    np.testing.assert_array_equal(la, lb)


def test_per_layer_config_is_refused_where_layers_must_be_alike():
    cfg = make_cfg()
    with pytest.raises(ValueError, match="one kind of layer"):
        tfm.init_cache(cfg, 1, 16)
    with pytest.raises(ValueError, match="describes 3 layers"):
        dataclasses.replace(cfg, n_layers=4)


def test_compiled_step_carries_the_routing_counters_out():
    """The normal path: hvd.compiled_train_step with has_aux, compiled
    steps only, one cache miss, and the counters fed to the hvd_moe_*
    families."""
    import optax

    import horovod_tpu as hvd
    hvd.init()
    cfg = make_cfg(vocab=512)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    step = hvd.compiled_train_step(
        lambda p, a, b: tfm.loss_and_stats(p, a, b, cfg),
        hvd.DistributedOptimizer(optax.adamw(3e-4)), has_aux=True)
    opt_state = step.init(params)
    tok, tgt = batch(512, (hvd.size(), 64))
    before = hvd.metrics_snapshot()
    losses = []
    for _ in range(3):
        params, opt_state, loss, aux = step(params, opt_state, tok, tgt)
        losses.append(float(loss))
        aux = jax.device_get(aux)
        hvd.metrics.record_moe_routing(aux)
    assert step.compiled_steps == 3 and step.fallback_steps == 0
    assert step.cache_misses == 1
    assert losses[2] < losses[0]
    assert aux["expert_load"].shape == (2, 4)
    after = hvd.metrics_snapshot()

    def total(snap, name):
        return sum(snap[name]["values"].values())

    routed = (total(after, "hvd_moe_routed_tokens_total")
              - total(before, "hvd_moe_routed_tokens_total"))
    # the step means its aux over the chips: per-chip assignments
    assert 0 < routed <= 3 * 2 * 64 * K
    assert total(after, "hvd_moe_unrouted_tokens_total") \
        > total(before, "hvd_moe_unrouted_tokens_total")
    assert total(after, "hvd_moe_load_max_over_mean") >= 1.0
    assert total(after, "hvd_moe_dropped_tokens_total") \
        == total(before, "hvd_moe_dropped_tokens_total")
    # the chunk of the layer traced last: a chip's 64 tokens
    rows = moe.chunk_rows(64, cfg.moe_cfg)
    assert total(after, "hvd_moe_chunk_rows") == rows
    # the last step's worst layer: its live rows over its chunks' rows
    live = [float(n) for n in np.asarray(aux["expert_load"]).sum(axis=1)]
    assert total(after, "hvd_moe_rows_live_share") == pytest.approx(min(
        n / (math.ceil(n / rows) * rows) for n in live))


def test_rows_live_share_takes_the_worst_layer_over_its_chunks():
    """hvd_moe_rows_live_share: live assignments over the rows of the
    chunks that ran, the lowest over the layers; a layer with no live
    assignment ran no chunk and is left out."""
    from horovod_tpu import metrics
    metrics.MOE_CHUNK_ROWS.set(512)
    metrics.record_moe_routing({
        "expert_load": [[100.0, 200.0], [300.0, 400.0], [0.0, 0.0]],
        "unrouted_tokens": [0.0, 0.0, 0.0]})
    share = metrics.snapshot()["hvd_moe_rows_live_share"]
    assert share["type"] == "gauge"
    # 300 of one chunk against 700 of two
    assert share["values"][""] == pytest.approx(300 / 512)
    metrics.record_moe_routing({"expert_load": [[512.0, 512.0]],
                                "unrouted_tokens": [0.0]})
    assert metrics.snapshot()["hvd_moe_rows_live_share"]["values"][""] == 1
