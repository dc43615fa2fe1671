"""Bucketed backward/exchange overlap (ISSUE-18): the compiled step's
gradient exchange split into layer-ordered buckets — one psum call over
each bucket's leaves — inside the same donated XLA program.

Acceptance surface: because psum is a per-element reduction unaffected
by bucket boundaries, ANY bucket count is bit-identical to
HOROVOD_EXCHANGE_BUCKETS=1 (one psum call over all leaves)
with an elementwise optimizer like sgd, across the psum and zero2 tags;
the guard-enabled bucketed program matches the guard-off build bitwise
when no fault fires; the bucket count is part of the step-program cache
signature (two counts never share a program) and an elastic re-init
cold-starts the membership-scoped cache; xla_trace.summarize folds
hvd_exchange intervals against the compute-union into the ``exchange``
block whose hidden_frac feeds the ``hvd_exchange_hidden_frac`` gauge and
the autoscaler's min-fold policy signal.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.ops.collectives import exchange_bucket_plan


def _reinit(monkeypatch=None, **env):
    hvd.shutdown()
    if monkeypatch is not None:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    hvd.init()
    return hvd.state().engine


@pytest.fixture(autouse=True)
def _fresh_runtime():
    yield
    hvd.shutdown()


# ---------------------------------------------------------- tiny workload

def _loss_fn(params, x, y):
    h = jnp.tanh(x @ params["w1"] + params["b1"])
    pred = h @ params["w2"] + params["b2"]
    return jnp.mean((pred - y) ** 2)


def _make_params():
    rng = np.random.RandomState(0)
    return {
        "w1": jnp.asarray(rng.randn(4, 8) * 0.3, jnp.float32),
        "b1": jnp.zeros((8,), jnp.float32),
        "w2": jnp.asarray(rng.randn(8, 1) * 0.3, jnp.float32),
        "b2": jnp.zeros((1,), jnp.float32),
    }


def _make_batch(rows=16, seed=1):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(rows, 4), jnp.float32)
    y = jnp.asarray(rng.randn(rows, 1), jnp.float32)
    return x, y


def _run(step, params, steps=4):
    opt_state = step.init(params)
    losses = []
    for i in range(steps):
        x, y = _make_batch(seed=1 + i)
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    return params, losses


def _assert_tree_bitwise(got, want):
    for (kg, g), (kw, w) in zip(sorted(got.items()), sorted(want.items())):
        assert kg == kw
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=kg)


# -------------------------------------------------------------- the plan

def test_bucket_plan_identity_and_edge_cases():
    """buckets=1 is the identity plan in ORIGINAL leaf order — one psum
    call over the whole tree; empty and singleton trees degrade
    sanely."""
    leaves = [np.zeros((8,)), np.zeros((4, 4)), np.zeros((2,))]
    assert exchange_bucket_plan(leaves, 1) == ((0, 1, 2),)
    assert exchange_bucket_plan(leaves, 0) == ((0, 1, 2),)
    assert exchange_bucket_plan([], 4) == ()
    assert exchange_bucket_plan([np.zeros((3,))], 4) == ((0,),)


def test_bucket_plan_reverse_order_exact_partition():
    """buckets>1: the plan walks leaves in REVERSE index order (backprop
    finishes the last layer's gradient first), partitions every index
    exactly once, and clamps the bucket count to the leaf count."""
    leaves = [np.zeros((64,)), np.zeros((8,)), np.zeros((128,)),
              np.zeros((16,)), np.zeros((4,)), np.zeros((256,))]
    plan = exchange_bucket_plan(leaves, 3)
    assert len(plan) == 3
    flat = [i for b in plan for i in b]
    assert sorted(flat) == list(range(6))
    # reverse traversal: bucket k's indices all exceed bucket k+1's
    assert flat == sorted(flat, reverse=True)
    # more buckets than leaves: one singleton per leaf, still reversed
    plan = exchange_bucket_plan(leaves, 99)
    assert plan == ((5,), (4,), (3,), (2,), (1,), (0,))


def test_bucket_plan_balances_bytes():
    """One giant leaf cannot drag every small leaf into its bucket: the
    byte-share boundary closes a bucket once its share is reached."""
    leaves = [np.zeros((4,)), np.zeros((4,)), np.zeros((1024,))]
    plan = exchange_bucket_plan(leaves, 2)
    assert plan == ((2,), (1, 0))


# ------------------------------------------------------------ bit parity

def test_psum_bit_identity_across_bucket_counts():
    """sgd at buckets 3 and 8 vs the default one-call build:
    BIT-identical losses and params — psum is per-element, so bucket
    boundaries cannot change a single ulp."""
    _reinit()
    params = _make_params()
    want, losses_w = _run(
        hvd.compiled_train_step(_loss_fn, optax.sgd(0.05)), params)
    for buckets in (3, 8):
        step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05),
                                       exchange_buckets=buckets)
        assert step._resolve_buckets(hvd.state().config) == buckets
        got, losses_g = _run(step, params)
        assert losses_g == losses_w
        _assert_tree_bitwise(got, want)
        assert step.compiled_steps == 4 and step.fallback_steps == 0


def test_env_knob_resolves_and_nonpsum_pins_to_one(monkeypatch):
    """HOROVOD_EXCHANGE_BUCKETS feeds Config.from_env and the step's
    resolution; exchange='none' ignores it (nothing to bucket)."""
    _reinit(monkeypatch, HOROVOD_EXCHANGE_BUCKETS="4")
    cfg = hvd.state().config
    assert cfg.exchange_buckets == 4
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    assert step._resolve_buckets(cfg) == 4
    none_step = hvd.compiled_train_step(
        _loss_fn, optax.chain(hvd.DistributedGradientTransform(),
                              optax.sgd(0.05)), exchange="none")
    assert none_step._resolve_buckets(cfg) == 1


def test_zero2_bit_identity_across_bucket_counts():
    """zero2's bucketed pipelining rides the _ZeroCore chunk layout:
    stripe ORDER changes with the bucket count but the gathered full
    rows are bit-identical for an elementwise optimizer."""
    _reinit()
    params = _make_params()
    want, _ = _run(hvd.compiled_train_step(
        _loss_fn, hvd.DistributedOptimizer(optax.sgd(0.05), zero_stage=2)),
        params, steps=3)
    z = hvd.DistributedOptimizer(optax.sgd(0.05), zero_stage=2,
                                 exchange_buckets=4)
    step = hvd.compiled_train_step(_loss_fn, z)
    got, _ = _run(step, params, steps=3)
    _assert_tree_bitwise(got, want)
    assert step.compiled_steps == 3 and step.fallback_steps == 0


def test_guard_program_bitwise_with_buckets(monkeypatch):
    """HOROVOD_GUARD=1 at buckets=8: per-segment health rows fold in
    ORIGINAL leaf order, so the guarded bucketed program is bit-identical
    to the guard-off bucketed build when no fault fires."""
    _reinit()
    params = _make_params()
    want, _ = _run(hvd.compiled_train_step(_loss_fn, optax.sgd(0.05),
                                           exchange_buckets=8), params)
    _reinit(monkeypatch, HOROVOD_GUARD="1")
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05),
                                   exchange_buckets=8)
    got, _ = _run(step, params)
    _assert_tree_bitwise(got, want)
    verdict = step.finish()
    assert verdict["ok"] and step.compiled_steps == 4


# ------------------------------------------------------- cache discipline

def test_bucket_count_is_part_of_cache_signature():
    """Two step objects differing only in exchange_buckets compile two
    distinct programs — one miss each, hits thereafter; an unbucketed
    program can never be served where a bucketed one was requested."""
    eng = _reinit()
    params = _make_params()
    s1 = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05),
                                 exchange_buckets=1)
    s8 = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05),
                                 exchange_buckets=8)
    _run(s1, params, steps=2)
    _run(s8, params, steps=2)
    assert s1.cache_misses == 1 and s1.cache_hits == 1
    assert s8.cache_misses == 1 and s8.cache_hits == 1
    assert eng._step_cache.misses == 2 and eng._step_cache.hits == 2


def test_elastic_reinit_cold_starts_bucketed_cache():
    """Shrink to survivors mid-run: the bucketed program compiled for the
    dead membership can never be served again — first post-resize call
    is a miss on the new engine's membership-scoped cache."""
    eng = _reinit()
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05),
                                   exchange_buckets=8)
    _run(step, _make_params(), steps=3)
    assert eng._step_cache.misses == 1
    hvd.shutdown()
    hvd.init(comm=list(range(4)))
    eng2 = hvd.state().engine
    params = _make_params()
    opt_state = step.init(params)
    x, y = _make_batch()
    step(params, opt_state, x, y)
    assert eng2._step_cache.misses == 1 and eng2._step_cache.hits == 0


# ----------------------------------------------- trace fold + observability

_EXCHANGE_HLO = """
  %conv.1 = f32[4]{0} convolution(%a, %b), metadata={op_name="jit(step)/hvd_backward/conv"}
  %ar.2 = f32[4]{0} add(%c, %d), metadata={op_name="jit(step)/hvd_exchange_bucket0/psum/add"}
  %ar.3 = f32[4]{0} add(%e, %f), metadata={op_name="jit(step)/hvd_exchange_bucket1/psum/add"}
  %app.4 = f32[4]{0} add(%g, %h), metadata={op_name="jit(step)/hvd_optimizer/hvd_apply_bucket0/add"}
"""


def _exchange_capture():
    """Synthetic capture, as ``read_capture`` hands it on (plain lists,
    ns): backward compute 0-100us; exchange bucket A 50-110us (50us
    hidden under backward), exchange bucket B 200-240us (fully exposed)
    -> hidden_frac = 50/100. A lane per op stream, as the CPU backend
    has them (a TPU's XLA Ops line is serial)."""
    def lane(*ops):
        return {"ops": [[name, ts * 1000, dur * 1000, None]
                        for name, ts, dur in ops],
                "modules": [], "async": []}

    return {"lanes": {"cpu:0": lane(("conv.1", 0, 100), ("app.4", 300, 10)),
                      "cpu:1": lane(("ar.2", 50, 60), ("ar.3", 200, 40))},
            "host": [], "files": []}


def test_summarize_exchange_fold():
    """The nested hvd_exchange_bucket{k} scopes attribute to 'exchange'
    (prefix match), hvd_apply_bucket{k} under hvd_optimizer stays
    compute, and the interval fold reports the hidden fraction."""
    from horovod_tpu.diag.xla_trace import build_op_table, summarize

    s = summarize(_exchange_capture(), build_op_table(_EXCHANGE_HLO))
    assert s["phases"]["exchange"] == pytest.approx(100e-6)
    assert s["phases"]["backward"] == pytest.approx(100e-6)
    assert s["phases"]["optimizer"] == pytest.approx(10e-6)
    ex = s["exchange"]
    assert ex["exchange_s"] == pytest.approx(100e-6)
    assert ex["hidden_s"] == pytest.approx(50e-6)
    assert ex["hidden_frac"] == pytest.approx(0.5)


def test_tracer_exports_hidden_frac_gauge(monkeypatch, tmp_path):
    """StepTracer.stop() exports the fold as hvd_exchange_hidden_frac —
    the gauge the autoscaler signal and observability docs point at."""
    from horovod_tpu.diag import xla_trace
    from horovod_tpu.diag.xla_trace import StepTracer

    monkeypatch.setattr(jax.profiler, "start_trace", lambda d, **kw: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(xla_trace, "read_capture",
                        lambda d: _exchange_capture())
    tr = StepTracer(diag_dir=str(tmp_path))
    tr.arm(1)
    tr.tick()              # starts the window, creates last_dir
    tr.register_hlo(_EXCHANGE_HLO)
    tr.tick()              # closes the window -> reduce + export
    assert not tr.active and tr.captures == 1
    assert tr.last_summary["exchange"]["hidden_frac"] == pytest.approx(0.5)
    snap = hvd.metrics_snapshot()
    val = snap["hvd_exchange_hidden_frac"]["values"].get("", None)
    assert val == pytest.approx(0.5)


def test_policy_aggregates_exchange_hidden_worst_case():
    """aggregate_signals folds exchange_hidden_frac as the MIN across
    reporters (one exposed wire paces the gang); absent everywhere ->
    None, and rankless serve signals fold as neutral."""
    from horovod_tpu.elastic.policy import aggregate_signals

    assert aggregate_signals([])["exchange_hidden_frac"] is None
    sigs = [{"rank": 0, "exchange_hidden_frac": 0.8},
            {"rank": 1, "exchange_hidden_frac": 0.35},
            {"rank": 2}]
    assert aggregate_signals(sigs)["exchange_hidden_frac"] == \
        pytest.approx(0.35)
    assert aggregate_signals(
        [{"rank": 0}])["exchange_hidden_frac"] is None
