"""The per-leaf sharding spec (docs/performance.md "Composable
parallelism").

Contracts pinned here:

- every layout ``DistributedOptimizer`` can say — psum, ZeRO stage 1/2/3,
  a stage-0 DCN link, expert leaves — compiled through the ONE
  ``_spec_shard`` body of the step program lands where plain ``optax``
  on one device lands on the global-batch mean gradient;
- ``DistributedOptimizer`` builds a ``_ShardingSpec`` for every
  configuration, the compiled step takes ``exchange="auto"|"none"`` and
  nothing else, and the program the public path arrives at is the one
  ``benchmark/tools/fit.py`` compiles;
- the combinations compose: ``expert_keys + zero_stage=2`` (and ``+
  dcn_compression``) compiles into one donated program and trains within
  1e-7 of each component path over 10 steps.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import step_program

AXIS = "hvd"
N = 8


@pytest.fixture(autouse=True)
def _fresh_runtime():
    yield
    hvd.shutdown()


# ----------------------------------------------------------- dense harness

def _make_params(seed=0):
    rng = np.random.RandomState(seed)
    return {
        "w1": jnp.asarray(rng.randn(6, 13).astype(np.float32) * 0.3),
        "b1": jnp.zeros((13,), jnp.float32),
        "w2": jnp.asarray(rng.randn(13, 3).astype(np.float32) * 0.3),
        "b2": jnp.zeros((3,), jnp.float32),
    }


def _make_batch(seed=1):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(N * 4, 6).astype(np.float32)),
            jnp.asarray(rng.randn(N * 4, 3).astype(np.float32)))


def _loss_fn(params, x, y):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    p = h @ params["w2"] + params["b2"]
    return jnp.mean((p - y) ** 2)


def _run_compiled(opt, steps=5, seed=0, loss=_loss_fn, params=None):
    step = hvd.compiled_train_step(loss, opt, donate=False)
    params = _make_params(seed) if params is None else params
    state = step.init(params)
    if step._resident:  # stage 3: train on the flat stripe
        params = step.shard_params(params)
    X, Y = _make_batch()
    for _ in range(steps):
        params, state, _ = step(params, state, X, Y)
    assert step.fallback_steps == 0
    if step._resident:  # lossless full-precision gather back
        params = step.unshard_params(params)
    return params


def _shard_values(x):
    try:
        return [np.asarray(s.data) for s in x.addressable_shards]
    except AttributeError:
        return [np.asarray(x)]


def _max_delta(a, b):
    """Max abs elementwise difference over every leaf and every device
    shard (fake-replicated layouts differ per device — device 0 alone
    would under-check the expert and stripe leaves)."""
    worst = 0.0
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for va, vb in zip(la, lb):
        for sa, sb in zip(_shard_values(va), _shard_values(vb)):
            worst = max(worst, float(np.max(np.abs(sa - sb))))
    return worst


def _plain_reference(opt, steps=5, seed=0):
    """The oracle no layout can be: the same steps on ONE device, plain
    optax on the gradient of the global-batch mean loss."""
    params = _make_params(seed)
    X, Y = _make_batch()

    @jax.jit
    def step(p, s):
        upd, s = opt.update(jax.grad(_loss_fn)(p, X, Y), s, p)
        return optax.apply_updates(p, upd), s

    state = opt.init(params)
    for _ in range(steps):
        params, state = step(params, state)
    return params


# --------------------------------------------------------- moe harness

def _moe_cfg():
    return moe.MoEConfig(d_model=16, d_ff=32, num_experts=4, top_k=2,
                         capacity_factor=4.0, dtype=jnp.float32)


def _expert_params(cfg, mesh, seed=0):
    full = moe.init_moe_params(jax.random.PRNGKey(seed), cfg)
    e_loc = cfg.num_experts // mesh.shape["ep"]

    def shard_fn(p):
        i = lax.axis_index("ep") * e_loc
        return {"w_router": p["w_router"],
                "w1": lax.dynamic_slice_in_dim(p["w1"], i, e_loc, 0),
                "w2": lax.dynamic_slice_in_dim(p["w2"], i, e_loc, 0)}

    return jax.jit(jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), check_vma=False))(full)


def _moe_loss(cfg, ep_axis="ep"):
    def loss_fn(p, x, y):
        out, aux = moe.moe_layer(p, x, cfg, ep_axis=ep_axis)
        return jnp.mean((out - y) ** 2) + 0.01 * aux
    return loss_fn


def _run_moe(tx, cfg, steps=5, ep=True):
    loss = _moe_loss(cfg, ep_axis="ep" if ep else None)
    step = hvd.compiled_train_step(loss, tx, donate=False)
    params = (_expert_params(cfg, hvd.expert_mesh()) if ep
              else moe.init_moe_params(jax.random.PRNGKey(0), cfg))
    opt_state = step.init(params)
    for i in range(steps):
        kx, ky = jax.random.split(jax.random.PRNGKey(1 + i))
        x = jax.random.normal(kx, (16, 8, cfg.d_model), jnp.float32)
        y = jax.random.normal(ky, (16, 8, cfg.d_model), jnp.float32)
        params, opt_state, _ = step(params, opt_state, x, y)
    assert step.fallback_steps == 0
    return params


def _gather_experts(params, mesh, num_experts):
    """Reassemble full expert stacks from the fake-replicated per-device
    shards (device at ep index k holds experts [k*e_loc, (k+1)*e_loc))."""
    e_loc = num_experts // mesh.shape["ep"]

    def one(arr):
        if arr.shape[0] != e_loc:
            return np.asarray(arr)  # replicated leaf (router)
        by_dev = {s.device: np.asarray(s.data)
                  for s in arr.addressable_shards}
        return np.concatenate(
            [by_dev[mesh.devices[0, e]] for e in range(mesh.shape["ep"])],
            axis=0)

    return {k: one(v) for k, v in params.items()}


def _expert_runtime(monkeypatch):
    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_EXPERT_PARALLEL", "4")
    hvd.init()


def _moe_plain_reference(cfg, steps=5):
    """tests/test_moe.py::test_expert_parallel_matches_local's oracle,
    trained: every expert local on ONE device, plain sgd on the mean of
    the 8 shards' losses (the load-balance term is per shard, so the
    mean is taken over shard losses, not over one 128-token batch)."""
    loss = _moe_loss(cfg, ep_axis=None)
    params = moe.init_moe_params(jax.random.PRNGKey(0), cfg)
    opt = optax.sgd(0.05)

    def mean_loss(p, x, y):
        xs, ys = (a.reshape(N, -1, *a.shape[1:]) for a in (x, y))
        return jnp.mean(jax.vmap(loss, in_axes=(None, 0, 0))(p, xs, ys))

    @jax.jit
    def step(p, s, x, y):
        upd, s = opt.update(jax.grad(mean_loss)(p, x, y), s, p)
        return optax.apply_updates(p, upd), s

    state = opt.init(params)
    for i in range(steps):
        kx, ky = jax.random.split(jax.random.PRNGKey(1 + i))
        x = jax.random.normal(kx, (16, 8, cfg.d_model), jnp.float32)
        y = jax.random.normal(ky, (16, 8, cfg.d_model), jnp.float32)
        params, state = step(params, state, x, y)
    return params


# ------------------------------- every layout against the plain reference

_LAYOUTS = {
    # name: (DistributedOptimizer arguments, spec label)
    "psum": ({}, "psum"),
    "zero1": ({"reduce_scatter": True}, "zero1"),
    "zero2": ({"zero_stage": 2}, "zero2"),
    "zero3": ({"zero_stage": 3}, "zero3"),
    "dcn-bf16": ({"dcn_compression": "bf16", "dcn_local_size": 4},
                 "psum+dcn"),
    "dcn-int8": ({"dcn_compression": "int8", "dcn_local_size": 4},
                 "psum+dcn"),
    "moe": ({"expert_keys": ("w1", "w2")}, "psum+ep"),
}


@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_spec_layout_matches_plain_reference(monkeypatch, layout):
    """Five compiled steps of every layout against one device and plain
    optax. The lossless layouts compute the same fp32 arithmetic in a
    different summation order (8 shard means averaged by a psum or a
    reduce-scatter, against one 32-row mean): measured 3e-8 (psum and
    moe, sgd) to 9e-8 (the ZeRO stages, adam) on parameters of
    magnitude <= 0.82, i.e. under one fp32 ulp — held to 1e-6. The DCN
    cases are lossy by design (measured 1.2e-4 bf16, 1.0e-2 int8: adam
    turns a quantised near-zero gradient into a full lr-sized step) and
    are held to the bound
    test_zero_sharding.py::test_dcn_compressed_close_and_residual_carries
    puts on the hop itself: 2 % of the largest magnitude."""
    kwargs, label = _LAYOUTS[layout]
    if layout == "moe":
        _expert_runtime(monkeypatch)
        cfg = _moe_cfg()
        tx = hvd.DistributedOptimizer(optax.sgd(0.05), **kwargs)
        got = _gather_experts(_run_moe(tx, cfg), hvd.expert_mesh(),
                              cfg.num_experts)
        want = {k: np.asarray(v)
                for k, v in _moe_plain_reference(cfg).items()}
    else:
        hvd.init()
        base = optax.sgd(0.1) if layout == "psum" else optax.adam(1e-2)
        tx = hvd.DistributedOptimizer(base, **kwargs)
        got, want = _run_compiled(tx), _plain_reference(base)
    assert tx.update._hvd_spec.label == label
    bound = 1e-6
    if layout.startswith("dcn"):
        bound = 0.02 * max(float(np.max(np.abs(v))) for v in want.values())
    assert _max_delta(got, want) <= bound


# ------------------------------------------------- the one rule, pinned

def test_distributed_optimizer_always_builds_a_spec():
    """Every configuration's product carries ``_hvd_spec`` and is tagged
    ``"spec"``; the only other tag in the tree is the bare links'
    ``"inline"``."""
    model = {"model_keys": ("['wq']",)}
    for kwargs, label in [*_LAYOUTS.values(), (model, "psum+tp")]:
        tx = hvd.DistributedOptimizer(optax.sgd(0.1), **kwargs)
        assert tx.update._hvd_exchange == "spec"
        assert tx.update._hvd_spec.label == label
        assert tx.update._hvd_base is not None
    assert hvd.DistributedGradientTransform().update._hvd_exchange == "inline"
    # equal specs are one spec: two optimizers that say the same layout
    # share compiled programs
    a = hvd.DistributedOptimizer(optax.sgd(0.1)).update._hvd_spec
    b = hvd.DistributedOptimizer(optax.sgd(0.1)).update._hvd_spec
    assert a == b and hash(a) == hash(b)
    assert a != hvd.DistributedOptimizer(
        optax.sgd(0.1), zero_stage=1).update._hvd_spec


@pytest.mark.parametrize("value", ["psum", "reduce_scatter", "zero1",
                                   "zero2", "zero3", "moe", "spec"])
def test_compiled_step_rejects_removed_exchange_values(value):
    """The layout is an option of the optimizer, not of the step: the
    step takes 'auto' and 'none', the builder under it 'psum' (as the
    spec says) and 'none', and both name what they take."""
    with pytest.raises(ValueError, match="'auto'.*'none'"):
        hvd.compiled_train_step(_loss_fn, optax.sgd(0.1), exchange=value)
    if value != "psum":
        mesh = Mesh(np.array(jax.devices()[:1]), (AXIS,))
        with pytest.raises(ValueError, match="'psum'.*'none'"):
            step_program._build_step_program(
                mesh, _loss_fn, optax.sgd(0.1), 2, value, True, None,
                False, False, False)


def test_public_path_builds_the_program_the_fit_tools_compile(
        hvd_init, monkeypatch):
    """``hvd.DistributedOptimizer(adamw)`` -> ``hvd.compiled_train_step``
    fetches a program that lowers to the same text as the positional
    builder call of benchmark/tools/fit.py and fit_mode.py — so what
    the tools size is what benchmark/run.py runs."""
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.float32)
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, cfg, axes)

    adamw = optax.adamw(3e-4)
    built = []
    build = step_program._build_step_program

    def spy(*args):
        built.append(build(*args))
        return built[-1]

    monkeypatch.setattr(step_program, "_build_step_program", spy)
    step = hvd.compiled_train_step(loss_fn, hvd.DistributedOptimizer(adamw),
                                   donate=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    state = step.init(params)
    tok = jnp.zeros((2 * N, 16), jnp.int32)
    step(params, state, tok, tok)
    assert len(built) == 1 and step.fallback_steps == 0
    tools = build(hvd.mesh(), loss_fn, adamw, 2, "psum", True, None, False,
                  False, False)
    args = (params, state, tok, tok)
    assert built[0].lower(*args).as_text() == tools.lower(*args).as_text()


# ------------------------------------------------------- combinations

def test_moe_zero2_combo_parity_vs_components(monkeypatch):
    """expert_keys + zero_stage=2 compiles into one donated program and stays within 1e-7 of BOTH
    component paths over 10 steps: pure expert parallelism (unstriped)
    and pure zero2 (full experts, data parallel)."""
    _expert_runtime(monkeypatch)
    cfg = _moe_cfg()
    combo_tx = hvd.DistributedOptimizer(
        optax.sgd(0.05), expert_keys=("w1", "w2"), zero_stage=2)
    assert combo_tx.update._hvd_exchange == "spec"
    combo = _run_moe(combo_tx, cfg, steps=10)
    mesh = hvd.expert_mesh()
    combo_full = _gather_experts(combo, mesh, cfg.num_experts)

    moe_only = _run_moe(hvd.DistributedOptimizer(
        optax.sgd(0.05), expert_keys=("w1", "w2")), cfg, steps=10)
    assert _max_delta(combo, moe_only) <= 1e-7

    zero2_only = _run_moe(hvd.DistributedOptimizer(
        optax.sgd(0.05), zero_stage=2), cfg, steps=10, ep=False)
    zero2_full = {k: np.asarray(v) for k, v in zero2_only.items()}
    assert _max_delta(combo_full, zero2_full) <= 1e-7


def test_moe_zero2_dcn_combo_parity(monkeypatch):
    """The triple combination — expert_keys + zero_stage=2 +
    dcn_compression — trains within 1e-7 of its dcn-bearing component:
    expert_keys + dcn at stage 0 on the SAME mesh and expert layout. Same layout means the lossy
    staged hop quantizes bit-identical reduced gradients in both runs,
    so the only remaining difference is the ZeRO-2 striping — which
    must not perturb the exchange beyond float noise. (A cross-layout
    reference — e.g. data-parallel zero2+dcn with full experts — is NOT
    a valid 1e-7 target: bf16 rounding of values that differ at the
    1e-8 level diverges by a bf16 ulp.)"""
    _expert_runtime(monkeypatch)
    cfg = _moe_cfg()
    combo_tx = hvd.DistributedOptimizer(
        optax.sgd(0.05), expert_keys=("w1", "w2"), zero_stage=2,
        dcn_compression="bf16", dcn_local_size=2)
    assert combo_tx.update._hvd_exchange == "spec"
    combo = _run_moe(combo_tx, cfg, steps=10)

    moe_dcn_tx = hvd.DistributedOptimizer(
        optax.sgd(0.05), expert_keys=("w1", "w2"),
        dcn_compression="bf16", dcn_local_size=2)
    assert moe_dcn_tx.update._hvd_exchange == "spec"
    assert moe_dcn_tx.update._hvd_spec.dcn_link
    moe_dcn = _run_moe(moe_dcn_tx, cfg, steps=10)
    assert _max_delta(combo, moe_dcn) <= 1e-7


def test_moe_zero2_dcn_stateful_optimizer(monkeypatch):
    """Regression: a STATEFUL base optimizer (adam) under a multi-axis
    spec. ``step.init`` runs host-side, where the stripe-axis size used
    to fall back to the WORLD size (8) while the compiled program
    stripes over the data axis of the expert mesh (size 2) — the adam
    state and the DCN residual were laid out for 1/8 stripes against
    the program's 1/2 scatter (shape error at trace time, or a silent
    pytree-structure mismatch for the residual). Stateless sgd carries
    no per-element state, which is how every other combo test missed
    it. Striping must also stay invisible to adam: same spec at
    zero_stage=0 from the same init, within float noise."""
    _expert_runtime(monkeypatch)
    cfg = _moe_cfg()

    def run(zero_stage):
        tx = hvd.DistributedOptimizer(
            optax.adam(1e-2), expert_keys=("w1", "w2"),
            zero_stage=zero_stage, dcn_compression="bf16",
            dcn_local_size=2)
        assert tx.update._hvd_exchange == "spec"
        return _run_moe(tx, cfg, steps=5)

    assert _max_delta(run(2), run(0)) <= 1e-6


# ------------------------------------------- 3-D mesh: + model parallel

def test_model_parallel_3d_combo(monkeypatch):
    """The full composition on the 2x2x2 (data, expert, model) mesh: a
    TP dense trunk (models.transformer head-sharded attention,
    column/row FFN, vocab-parallel CE), an expert-parallel MoE FFN, and
    ZeRO-2 striping, in one compiled program with zero fallbacks — and
    the striping must not perturb training beyond float noise (same
    spec at zero_stage=0 from the same init)."""
    from horovod_tpu.models import transformer as tfm

    hvd.shutdown()
    monkeypatch.setenv("HOROVOD_EXPERT_PARALLEL", "2")
    monkeypatch.setenv("HOROVOD_MODEL_PARALLEL", "2")
    hvd.init()
    mesh = hvd.model_mesh()
    assert dict(mesh.shape) == {"hvd": 2, "ep": 2, "model": 2}

    cfg = tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64,
        max_seq=16, dtype=jnp.float32, positional="rope",
        attention_impl="dense", moe_layers=(1,), moe_num_experts=4,
        moe_top_k=2)
    axes = tfm.ShardAxes(dp=None, sp=None, tp="model", ep="ep")
    specs = tfm.param_specs(cfg, axes)
    model_keys = tfm.model_parallel_keys(cfg, axes)
    assert model_keys and all("['moe']" not in k for k in model_keys)
    full = tfm.init_params(jax.random.PRNGKey(0), cfg)

    # batch shards over data x expert, replicated over model
    batch_sharding = NamedSharding(mesh, P(("hvd", "ep")))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                           cfg.vocab_size), batch_sharding)
    targets = jax.device_put(jnp.roll(tokens, -1, axis=1), batch_sharding)

    def loss(p, t, y):
        return tfm.loss_fn(p, t, y, cfg, axes)

    def train(zero_stage):
        tx = hvd.DistributedOptimizer(
            optax.sgd(0.05),
            expert_keys=("['moe']['w1']", "['moe']['w2']"),
            model_keys=model_keys, zero_stage=zero_stage)
        assert tx.update._hvd_exchange == "spec"
        step = hvd.compiled_train_step(loss, tx, donate=False)
        p = tfm.slice_param_shards(full, specs, mesh)
        s = step.init(p)
        for _ in range(3):
            p, s, _ = step(p, s, tokens, targets)
        assert step.fallback_steps == 0
        return p

    assert _max_delta(train(2), train(0)) <= 5e-7
