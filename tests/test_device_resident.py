"""Device-resident gradient exchange (ISSUE-5): zero-readback eager
allreduce through the in-graph unfuse wire program, the signature-keyed
wire-program cache, the paper-parity wire profiler, and the autotune
largest-message guard.

Acceptance surface: device-resident vs host-path allreduce results equal
within dtype tolerance; synchronize() waits on dispatch only (handles
resolve to jax device arrays with no in-flight record); steady-state
wire-cache hit rate >= 0.9; HOROVOD_DEVICE_RESIDENT=0 restores the exact
legacy numpy behavior; elastic aborts invalidate BOTH the response cache
and the wire-program cache (a stale compiled program for a dead
membership must never run).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu.autotune import ParameterManager
from horovod_tpu.config import Config
from horovod_tpu.exceptions import WorkerLostError
from horovod_tpu.ops.engine import WireProgramCache


def _reinit(monkeypatch=None, **env):
    hvd.shutdown()
    if monkeypatch is not None:
        for k, v in env.items():
            monkeypatch.setenv(k, v)
    hvd.init()
    return hvd.state().engine


CASES = [
    ("f32.avg", np.float32, (4, 3), True, None),
    ("f32.sum", np.float32, (17,), False, None),
    ("f64.avg", np.float64, (5, 2), True, None),
    ("i32.sum", np.int32, (6,), False, None),
    ("i32.avg", np.int32, (8,), True, None),  # floor-div averaging parity
    ("f32.comp", np.float32, (9,), True, hvd.Compression.fp16),
]


def test_device_resident_matches_host_path(hvd_init):
    """Same tensors through both paths: results equal within dtype
    tolerance (identical arithmetic order; the cast back from the wire
    dtype is the in-graph decompress)."""
    for tag, dtype, shape, avg, comp in CASES:
        data = (np.arange(np.prod(shape)) % 11 - 3).reshape(shape) \
            .astype(dtype)
        kwargs = {} if comp is None else {"compression": comp}
        host = hvd.allreduce(data, average=avg, name=f"dr.h.{tag}", **kwargs)
        dev = hvd.allreduce(data, average=avg, name=f"dr.d.{tag}",
                            to_host=False, **kwargs)
        assert isinstance(host, np.ndarray), tag
        assert isinstance(dev, jax.Array), tag
        got = np.asarray(dev)
        assert got.dtype == host.dtype, tag
        if np.issubdtype(dtype, np.floating):
            rtol = 1e-2 if comp is not None else \
                (1e-12 if dtype == np.float64 else 1e-6)
            np.testing.assert_allclose(got, host, rtol=rtol, atol=1e-6), tag
        else:
            np.testing.assert_array_equal(got, host), tag


@pytest.mark.parametrize("to_host", [True, False])
def test_bf16_average_is_a_float_mean(hvd_init, to_host):
    """numpy does not count bfloat16 among its floating types; an
    average that asked it floor-divided (2.5 -> 2.0). Both unfuse paths
    ask jnp."""
    data = jnp.asarray([2.5, -1.5, 0.75, 3.0], jnp.bfloat16)
    out = hvd.allreduce(np.asarray(data), average=True,
                        name=f"dr.bf16.avg.{to_host}", to_host=to_host)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(data, np.float32))


def test_device_resident_per_rank_divergent(hvd_init):
    """Divergent per-rank tensors (explicit rank submissions) through the
    device path sum correctly — the fused wire program sees every rank's
    row, exactly like the host path."""
    n = hvd.size()
    handles = {r: hvd.allreduce_async(np.full((6,), float(r), np.float32),
                                      average=False, name="dr.ranks", rank=r,
                                      to_host=False)
               for r in range(n)}
    expect = np.full((6,), sum(range(n)), np.float32)
    for r, h in handles.items():
        res = hvd.synchronize(h)
        val = res[r] if isinstance(res, dict) else res
        assert isinstance(val, jax.Array)
        np.testing.assert_allclose(np.asarray(val), expect)


def test_device_resident_completes_at_dispatch(hvd_init):
    """Zero-readback contract: after the cycle runs, the handle is
    already resolved (poll True, no in-flight record, no completion
    thread involvement) and the engine counted a device bucket."""
    eng = hvd.state().engine
    before = _device_buckets()
    h = hvd.allreduce_async(np.ones((32,), np.float32), name="dr.dispatch",
                            to_host=False)
    assert hvd.poll(h)
    assert not eng._inflight
    assert _device_buckets() == before + 1
    res = hvd.synchronize(h)
    val = next(iter(res.values())) if isinstance(res, dict) else res
    assert isinstance(val, jax.Array)


def _device_buckets():
    snap = hvd.metrics_snapshot()
    vals = snap["hvd_engine_device_resident_buckets_total"]["values"]
    return vals.get("", 0.0)


def test_device_resident_disabled_is_exact_legacy(monkeypatch):
    """HOROVOD_DEVICE_RESIDENT=0: to_host=False is ignored and the host
    path serves everything — numpy results, no device buckets."""
    _reinit(monkeypatch, HOROVOD_DEVICE_RESIDENT="0")
    before = _device_buckets()
    out = hvd.allreduce(np.arange(8, dtype=np.float32), name="dr.legacy",
                        to_host=False)
    assert isinstance(out, np.ndarray)
    assert _device_buckets() == before
    monkeypatch.delenv("HOROVOD_DEVICE_RESIDENT")
    _reinit()


def test_mixed_host_and_device_requests_one_cycle(hvd_init):
    """Host and device entries submitted together fuse into SEPARATE
    buckets (the device wire program carries the in-graph unfuse) and
    both resolve correctly."""
    hh = hvd.allreduce_async(np.full((12,), 2.0, np.float32),
                             name="dr.mix.host")
    hd = hvd.allreduce_async(np.full((12,), 3.0, np.float32),
                             name="dr.mix.dev", to_host=False)
    host = hvd.synchronize(hh)
    dev = hvd.synchronize(hd)
    hv = next(iter(host.values())) if isinstance(host, dict) else host
    dv = next(iter(dev.values())) if isinstance(dev, dict) else dev
    assert isinstance(hv, np.ndarray) and isinstance(dv, jax.Array)
    np.testing.assert_allclose(hv, np.full((12,), 2.0))
    np.testing.assert_allclose(np.asarray(dv), np.full((12,), 3.0))


def test_exchange_gradients_device_pytree(hvd_init):
    """hvd.exchange_gradients: whole pytree exchanged in one fused
    device-resident cycle; results are device arrays equal to the host
    exchange."""
    grads = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
             "b": np.ones((5,), np.float32)}
    dev = hvd.exchange_gradients(grads, name_prefix="dr.ex.dev")
    host = hvd.exchange_gradients(grads, to_host=True,
                                  name_prefix="dr.ex.host")
    for k in grads:
        assert isinstance(dev[k], jax.Array), k
        np.testing.assert_allclose(np.asarray(dev[k]), host[k], rtol=1e-6)


def test_wire_cache_steady_state_hit_rate(hvd_init):
    """Steady-state training loop shape: same tensor set every step maps
    onto one cached executable (power-of-two binned), so the hit rate
    crosses 0.9 and misses stop growing after the first step."""
    eng = hvd.state().engine
    base_h, base_m = eng._wire_cache.hits, eng._wire_cache.misses
    misses_after_first = None
    for s in range(12):
        handles = [hvd.allreduce_async(
            np.full((64,), float(s + i), np.float32),
            name=f"dr.loop.{i}", to_host=False) for i in range(3)]
        for h in handles:
            hvd.synchronize(h)
        if s == 0:
            misses_after_first = eng._wire_cache.misses
    hits = eng._wire_cache.hits - base_h
    misses = eng._wire_cache.misses - base_m
    assert hits / max(hits + misses, 1) >= 0.9, (hits, misses)
    assert eng._wire_cache.misses == misses_after_first  # no recompiles
    snap = hvd.metrics_snapshot()
    assert snap["hvd_engine_wire_cache_hits"]["values"][""] >= hits


def test_wire_cache_participants_digest_scopes_keys():
    """The digest is part of every key: identical signatures under
    different memberships are different programs — a stale executable
    can never serve a rebuilt session."""
    a = WireProgramCache("digest-a")
    b = WireProgramCache("digest-b")
    sig = ("psum", "float32", (8, 64), False)
    pa = a.get(sig, lambda: object())
    pb = b.get(sig, lambda: object())
    assert pa is not pb
    assert a.get(sig, lambda: object()) is pa  # same membership: hit
    assert a.hits == 1 and a.misses == 1


def test_elastic_abort_invalidates_both_caches(hvd_init):
    """Satellite: after a worker-loss abort, the response cache AND the
    wire-program cache are empty — nothing validated or compiled against
    the dead membership survives into recovery, and post-abort
    submissions fail fast."""
    eng = hvd.state().engine
    hvd.allreduce(np.ones((16,), np.float32), name="dr.abort.warm")
    hvd.allreduce(np.ones((16,), np.float32), name="dr.abort.warm")
    assert len(eng._wire_cache) > 0
    assert eng._response_cache.hits > 0 or eng._response_cache.misses > 0
    with eng._lock:
        eng._apply_abort_locked({"kind": "worker_lost", "lost_pids": [2],
                                 "epoch": 1})
    assert len(eng._wire_cache) == 0
    assert not eng._response_cache.lookup(_probe_request())
    with pytest.raises(WorkerLostError):
        hvd.allreduce(np.ones((16,), np.float32), name="dr.abort.after")
    # recovery: re-init builds a fresh engine with cold, freshly-scoped
    # caches
    hvd.shutdown()
    hvd.init()
    eng2 = hvd.state().engine
    assert eng2 is not eng
    assert len(eng2._wire_cache) == 0 and eng2._wire_cache.hits == 0
    out = hvd.allreduce(np.ones((16,), np.float32), name="dr.abort.fresh")
    np.testing.assert_allclose(out, np.ones((16,)))


def _probe_request():
    from horovod_tpu.ops.engine import ALLREDUCE, _Request
    return _Request(ALLREDUCE, 0, "dr.abort.warm",
                    np.ones((16,), np.float32), handle=0)


def test_wire_cache_fresh_after_membership_change(hvd_init):
    """Elastic-recovery shape: re-init over a survivor subset (the
    elastic runner's ``init(comm=survivors)`` path) gets a wire cache
    with a DIFFERENT participants digest — old keys are unreachable by
    construction — and starts cold."""
    eng8 = hvd.state().engine
    hvd.allreduce(np.ones((8,), np.float32), name="dr.mem.warm",
                  to_host=False)
    d8 = eng8._wire_cache.participants_digest
    assert len(eng8._wire_cache) > 0
    hvd.shutdown()
    hvd.init(comm=list(range(4)))
    try:
        eng4 = hvd.state().engine
        assert eng4._wire_cache.participants_digest != d8
        assert len(eng4._wire_cache) == 0 and eng4._wire_cache.hits == 0
        out = hvd.allreduce(np.ones((8,), np.float32), name="dr.mem.after",
                            to_host=False)
        np.testing.assert_allclose(np.asarray(out), np.ones((8,)))
    finally:
        hvd.shutdown()
        hvd.init()


def test_wire_profile_csv_dump(monkeypatch, tmp_path):
    """HOROVOD_WIRE_PROFILE=1: per-message-size wire latency lands in
    profiler.csv at shutdown (the fork's time_map_allreduce table), with
    power-of-two size bins — including device-resident buckets, which
    are only measured in this mode."""
    path = tmp_path / "profiler.csv"
    _reinit(monkeypatch, HOROVOD_WIRE_PROFILE="1",
            HOROVOD_WIRE_PROFILE_PATH=str(path))
    hvd.allreduce(np.ones((1000,), np.float32), name="dr.prof.host")
    hvd.allreduce(np.ones((1000,), np.float32), name="dr.prof.dev",
                  to_host=False)
    hvd.alltoall(np.arange(8, dtype=np.float32), name="dr.prof.a2a")
    hvd.shutdown()
    text = path.read_text()
    lines = text.strip().splitlines()
    assert lines[0] == "op,size_bin_bytes,count,mean_us,total_us"
    rows = [l.split(",") for l in lines[1:]]
    assert rows, text
    allreduce_bins = [int(r[1]) for r in rows if r[0] == "allreduce"]
    assert allreduce_bins
    for b in allreduce_bins:
        assert b > 0 and (b & (b - 1)) == 0, b  # power-of-two bins
    # alltoall spans feed the same histogram as allreduce/allgather
    # (dispatch span through engine._observe_wire, not just bytes)
    assert [r for r in rows if r[0] == "alltoall"], text
    monkeypatch.delenv("HOROVOD_WIRE_PROFILE")
    hvd.init()


def test_autotune_largest_message_guard(tmp_path):
    """Satellite: a candidate with a better overall score but WORSE
    measured goodput at the largest observed message size never becomes
    the incumbent; the rejection is recorded in the autotune CSV."""
    cfg = Config()
    cfg.autotune = True
    cfg.autotune_warmup_samples = 0
    cfg.autotune_steps_per_sample = 1
    cfg.autotune_bayes_opt_max_samples = 10
    cfg.autotune_log = str(tmp_path / "autotune.csv")
    pm = ParameterManager(cfg)
    # sample 1 (incumbent): 1 GiB/s at the 1 MiB bin
    pm.record_wire(1 << 20, 0.001)
    pm.record_bytes(1 << 20)
    incumbent = pm._best
    assert incumbent[0] > 0
    # sample 2: vastly higher overall score, but large-message goodput
    # collapsed (the batch-512 sweep signature)
    pm.record_wire(1 << 20, 1.0)
    pm.record_bytes(1 << 40)
    assert pm._best == incumbent  # guard held the incumbent
    assert pm._log_rows[-1][-2] == 1  # guard_rejected recorded
    # sample 3: higher score AND no large-message regression -> accepted
    pm.record_wire(1 << 20, 0.0009)
    pm.record_bytes(1 << 40)
    assert pm._best != incumbent
    assert pm._log_rows[-1][-2] == 0
    header = (tmp_path / "autotune.csv").read_text().splitlines()[0]
    assert "largest_msg_bytes" in header
    assert "guard_rejected" in header
    assert header.endswith("overlap_adjusted_bytes_per_sec")  # score last


def test_single_rank_world_device_resident():
    """World size 1: the device-resident contract (a device array the
    jitted apply can consume) holds through the identity path."""
    hvd.shutdown()
    hvd.init(num_ranks=1)
    out = hvd.allreduce(np.arange(4, dtype=np.float32), name="dr.one",
                        to_host=False)
    assert isinstance(out, jax.Array)
    np.testing.assert_allclose(np.asarray(out), np.arange(4))
    hvd.shutdown()
    hvd.init()
