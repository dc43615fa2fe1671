"""Compiled hot loop (ISSUE-11): the single donated XLA step program.

Acceptance surface: compiled step bit-parity with the eager decomposition
(device AND host modes); DistributedOptimizer auto-decomposition and the
ZeRO-1 reduce-scatter mode agree with the allreduce math; steady-state
step-program cache hit rate >= 0.9 (one miss, then hits forever); the
guard-enabled program is numerically identical to the plain build when no
fault fires and its deferred verdict folds on finish(); an elastic
re-init over survivors cold-starts the membership-scoped cache; shape
churn past HOROVOD_STEP_PROGRAM_CHURN_LIMIT and HOROVOD_STEP_PROGRAM=0 /
HOROVOD_DEVICE_RESIDENT=0 fall back to the eager path with the right
``hvd_step_fallback_total`` reason.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd


def _reinit(monkeypatch=None, **env):
    hvd.shutdown()
    if monkeypatch is not None:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    hvd.init()
    return hvd.state().engine


@pytest.fixture(autouse=True)
def _fresh_runtime():
    """Config (step_program, device_resident, guard) is captured at
    init() from env — shut down after each test so the next one
    re-initializes against its own environment."""
    yield
    hvd.shutdown()


def _metric(name, key=""):
    return hvd.metrics_snapshot()[name]["values"].get(key, 0.0)


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


def _eager_reference(params, tx, steps=5, name="ref"):
    """The eager decomposition the compiled program must match: full-batch
    value_and_grad on host, engine exchange (identical data on every rank,
    so the average is a no-op numerically), optax apply."""
    opt_state = tx.init(params)
    losses = []
    for i in range(steps):
        x, y = _make_batch(seed=1 + i)
        loss, grads = jax.value_and_grad(_loss_fn)(params, x, y)
        grads = hvd.exchange_gradients(grads, average=True,
                                       name_prefix=f"{name}.{i}")
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        losses.append(float(loss))
    return params, losses


def _run_compiled(step, params, steps=5):
    opt_state = step.init(params)
    losses = []
    for i in range(steps):
        x, y = _make_batch(seed=1 + i)
        params, opt_state, loss = step(params, opt_state, x, y)
        losses.append(float(loss))
    return params, losses


def _assert_tree_close(got, want, rtol=2e-5):
    for (kg, g), (kw, w) in zip(sorted(got.items()), sorted(want.items())):
        assert kg == kw
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=rtol, atol=1e-6, err_msg=kg)


# ------------------------------------------------------------------ parity

def test_compiled_matches_eager_reference():
    """Device-mode compiled step vs the eager decomposition: same losses,
    same final params within float32 tolerance; every step compiled."""
    _reinit()
    params = _make_params()
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    assert step._exchange == "psum"
    got, losses_c = _run_compiled(step, params)
    want, losses_e = _eager_reference(params, optax.sgd(0.05))
    np.testing.assert_allclose(losses_c, losses_e, rtol=2e-5)
    _assert_tree_close(got, want)
    assert step.compiled_steps == 5 and step.fallback_steps == 0


def test_host_mode_falls_back_with_parity(monkeypatch):
    """HOROVOD_DEVICE_RESIDENT=0: the compiled path defers to the eager
    engine (reason host_mode) and still produces the same numbers."""
    _reinit()
    params = _make_params()
    want, _ = _run_compiled(hvd.compiled_train_step(_loss_fn,
                                                    optax.sgd(0.05)), params)
    _reinit(monkeypatch, HOROVOD_DEVICE_RESIDENT="0")
    before = _metric("hvd_step_fallback_total", 'reason="host_mode"')
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    got, _ = _run_compiled(step, params)
    assert step.fallback_steps == 5 and step.compiled_steps == 0
    assert _metric("hvd_step_fallback_total",
                   'reason="host_mode"') == before + 5
    _assert_tree_close(got, want)


def test_disabled_env_forces_fallback(monkeypatch):
    """HOROVOD_STEP_PROGRAM=0 wins over device-resident mode: every step
    runs eager with reason=disabled."""
    _reinit(monkeypatch, HOROVOD_STEP_PROGRAM="0")
    before = _metric("hvd_step_fallback_total", 'reason="disabled"')
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    _run_compiled(step, _make_params(), steps=3)
    assert step.fallback_steps == 3 and step.compiled_steps == 0
    assert _metric("hvd_step_fallback_total",
                   'reason="disabled"') == before + 3


# --------------------------------------------- optimizer integration modes

def test_distributed_optimizer_auto_decomposes():
    """DistributedOptimizer(chain) under exchange='auto': the step's
    in-graph psum replaces DistributedGradientTransform, only the base
    optimizer runs in the program — numbers match the eager reference."""
    _reinit()
    params = _make_params()
    dopt = hvd.DistributedOptimizer(optax.sgd(0.05))
    step = hvd.compiled_train_step(_loss_fn, dopt)
    assert step._exchange == "psum"
    got, _ = _run_compiled(step, params)
    want, _ = _eager_reference(params, optax.sgd(0.05), name="ref.dopt")
    _assert_tree_close(got, want)


def test_zero1_reduce_scatter_matches_allreduce_math():
    """DistributedOptimizer(reduce_scatter=True) compiles whole (the
    reduce-scatter IS the update transform) and, for a stateless-per-shard
    optimizer like sgd, agrees with the psum build."""
    _reinit()
    params = _make_params()
    z = hvd.DistributedOptimizer(optax.sgd(0.05), reduce_scatter=True)
    step_z = hvd.compiled_train_step(_loss_fn, z)
    assert step_z._exchange == "zero1"
    got, _ = _run_compiled(step_z, params, steps=3)
    want, _ = _run_compiled(hvd.compiled_train_step(_loss_fn,
                                                    optax.sgd(0.05)),
                            params, steps=3)
    _assert_tree_close(got, want)
    assert step_z.compiled_steps == 3 and step_z.fallback_steps == 0


def test_rejects_multisteps_and_hand_rolled_chain():
    """Shapes the builder cannot introspect fail loudly at construction:
    MultiSteps hides the inner transform; a hand-rolled chain around
    DistributedGradientTransform would exchange twice under auto (but is
    fine once the caller says exchange='none')."""
    _reinit()
    with pytest.raises(ValueError, match="MultiSteps"):
        hvd.compiled_train_step(_loss_fn, optax.MultiSteps(optax.sgd(0.05),
                                                           2))
    chained = optax.chain(hvd.DistributedGradientTransform(),
                          optax.sgd(0.05))
    with pytest.raises(ValueError, match="exchange"):
        hvd.compiled_train_step(_loss_fn, chained)
    step = hvd.compiled_train_step(_loss_fn, chained, exchange="none")
    assert step._exchange == "none"


# -------------------------------------------------------- cache discipline

def test_steady_state_cache_hit_rate():
    """12 same-shape steps: one miss (the first), hits forever after —
    hit rate >= 0.9, and the engine gauges mirror the object counters."""
    eng = _reinit()
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    _run_compiled(step, _make_params(), steps=12)
    assert step.cache_misses == 1 and step.cache_hits == 11
    assert step.cache_hit_rate >= 0.9
    assert eng._step_cache.misses == 1 and eng._step_cache.hits == 11
    assert _metric("hvd_step_program_cache_hits") == 11.0
    assert _metric("hvd_step_compiled_total") >= 12.0


def test_shape_churn_limit_falls_back(monkeypatch):
    """More distinct batch signatures than the churn limit: the extra
    shape runs eager (reason shape_churn) instead of compiling a third
    program — recompile storms cannot eat the hot loop."""
    _reinit(monkeypatch, HOROVOD_STEP_PROGRAM_CHURN_LIMIT="2")
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    params = _make_params()
    opt_state = step.init(params)
    before = _metric("hvd_step_fallback_total", 'reason="shape_churn"')
    for rows in (16, 24, 32):
        x, y = _make_batch(rows=rows)
        params, opt_state, _ = step(params, opt_state, x, y)
    assert step.compiled_steps == 2 and step.fallback_steps == 1
    assert _metric("hvd_step_fallback_total",
                   'reason="shape_churn"') == before + 1


def test_elastic_reinit_cold_starts_cache():
    """Shrink to survivors: the new engine's participants digest scopes
    the step-program cache, so the program compiled for the dead
    membership can never be served again."""
    eng = _reinit()
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    _run_compiled(step, _make_params(), steps=3)
    old_digest = eng._step_cache.participants_digest
    assert eng._step_cache.hits == 2
    hvd.shutdown()
    hvd.init(comm=list(range(4)))
    eng2 = hvd.state().engine
    assert eng2 is not eng
    assert eng2._step_cache.participants_digest != old_digest
    params = _make_params()
    opt_state = step.init(params)
    x, y = _make_batch()
    step(params, opt_state, x, y)
    # the step object rebound to the new engine: fresh signature set,
    # cold membership-scoped cache — first call is a miss again
    assert eng2._step_cache.misses == 1 and eng2._step_cache.hits == 0


# ------------------------------------------------------------------- guard

def test_guard_program_identical_without_fault(monkeypatch):
    """HOROVOD_GUARD=1: the health-matrix build with its in-graph skip
    gate produces BIT-IDENTICAL params when no fault fires, and finish()
    folds the deferred verdict (ok, action=apply)."""
    _reinit()
    plain, _ = _run_compiled(hvd.compiled_train_step(_loss_fn,
                                                     optax.sgd(0.05)),
                             _make_params(), steps=4)
    _reinit(monkeypatch, HOROVOD_GUARD="1")
    step = hvd.compiled_train_step(_loss_fn, optax.sgd(0.05))
    guarded, _ = _run_compiled(step, _make_params(), steps=4)
    for k in plain:
        assert np.array_equal(np.asarray(plain[k]), np.asarray(guarded[k])), k
    verdict = step.finish()
    assert verdict is not None and verdict["ok"]
    assert verdict["action"] == "apply"
    assert step.finish() is None  # backlog drained
