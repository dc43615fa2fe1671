#!/usr/bin/env python
"""ResNet-50 synthetic training benchmark — the reference's parity vehicle.

Protocol parity (reference: examples/tensorflow_synthetic_benchmark.py:20-107):
ResNet-50, synthetic 224x224 data, SGD(0.01), untimed warmup (both jit
specializations must compile before timing), 10 iterations x 10 batches,
reporting images/sec per device as mean +- 1.96 sigma. Here the model is the
TPU-native flax ResNet v1.5 in bfloat16, data-parallel over every visible
chip via shard_map + hvd.DistributedOptimizer.

Beyond the reference protocol (round-2 perf story):
- per-chip batch sweep (32..512) — the headline number is the best
  batch, reported alongside the full sweep (the reference pins 32, sized
  for 2017 GPUs; a TPU chip needs a larger batch to fill the MXU);
- MFU — model FLOPs (XLA cost analysis of the compiled step, fallback to
  the analytic 3x forward estimate) / chip peak bf16 FLOPs, so the number
  says how much of the chip the framework actually uses.

Prints ONE JSON line:
  {"metric": "resnet50_img_sec_per_chip", "value": N, "unit": "img/sec",
   "vs_baseline": R, "batch_per_chip": B, "mfu_pct": M, "sweep": {...},
   "platform": P, "device_kind": K, "device_count": C}
A row that raises is recorded as {"failed": ...}; the JSON line still prints
with what the other rows measured, and the process then exits non-zero.
vs_baseline divides by 103.55 img/sec/device — the reference's only published
per-device absolute number (docs/benchmarks.rst:29-42: ResNet-101 synthetic,
`total images/sec: 1656.82` on 16 Pascal GPUs => 103.55/GPU).
"""

import json
import os
import sys
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, ".")

# bench's own timers are the deliverable; don't let the library's stats
# profiler drop a profiler.txt into the cwd (an explicit
# HOROVOD_PROFILER_PATH / HOROVOD_METRICS_DIR still wins).
os.environ.setdefault("HOROVOD_PROFILER_DISABLE", "1")

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu import diag as hvd_diag  # noqa: E402
from horovod_tpu import hardware as hvd_hardware  # noqa: E402
from horovod_tpu import metrics as hvd_metrics  # noqa: E402
from horovod_tpu.models import ResNet50  # noqa: E402

BASELINE_IMG_SEC_PER_DEVICE = 103.55

BATCH_CANDIDATES = (32, 64, 128, 256, 512)
NUM_ITERS = 10
SWEEP_ITERS = 2
BATCHES_PER_ITER = 10
IMAGE_SIZE = 224

# CI smoke mode (HOROVOD_BENCH_SMOKE=1): shrink the protocol so a CPU
# runner can prove the whole pipeline — sweep, timed loop, JSON line —
# end to end in seconds. Numbers from smoke runs are NOT comparable to
# the protocol (tiny images break the analytic-FLOPs constant too).
SMOKE = os.environ.get("HOROVOD_BENCH_SMOKE", "") not in ("", "0", "false")
if SMOKE:
    BATCH_CANDIDATES = (8,)
    NUM_ITERS = 2
    SWEEP_ITERS = 1
    BATCHES_PER_ITER = 2
    IMAGE_SIZE = 64

# Deferred-readback pipelining in the timed loop (docs/performance.md):
# how many program calls may be dispatched before blocking on the oldest
# result. Matches the eager engine's knob so one env var tunes both —
# including 0, the synchronous fallback (block on every call's result,
# the pre-pipeline timing).
PIPELINE_DEPTH = max(int(os.environ.get("HOROVOD_PIPELINE_DEPTH", "2")
                         or 2), 0)

# Input-data prefetch depth for the input-pipeline profile (matches the
# loader's env knob; 0 = synchronous fallback). HOROVOD_BENCH_INPUT_PIPELINE=1
# runs ONLY the input-pipeline measurement and emits its own JSON line —
# the CI data-pipeline smoke step (docs/data.md).
DATA_PREFETCH = max(int(os.environ.get("HOROVOD_DATA_PREFETCH", "2")
                        or 2), 0)
INPUT_PIPELINE_ONLY = os.environ.get(
    "HOROVOD_BENCH_INPUT_PIPELINE", "") not in ("", "0", "false")

# Device-resident hot loop (docs/performance.md): with
# HOROVOD_DEVICE_RESIDENT != 0 (the default, auto) the timed loop never
# fetches the loss to host — it paces itself on device readiness
# (block_until_ready) and defers every host fetch to the untimed drain,
# so the dispatch_readback cost is REMOVED from the hot loop rather than
# merely hidden behind in-flight calls (loop_readback_wait_ms ≈ 0).
# HOROVOD_DEVICE_RESIDENT=0 restores the legacy deferred-readback loop.
DEVICE_RESIDENT = os.environ.get(
    "HOROVOD_DEVICE_RESIDENT", "") not in ("0",)

# Bucketed backward/exchange overlap (docs/performance.md "Bucketed
# backward/exchange overlap"): the compiled profile runs with this tuned
# bucket count and A/Bs it against buckets=1 (one psum call over all
# leaves). 8 keeps margin above the CI overlap gate's 0.3 floor — the
# PR 13 lesson (moe chunks=4 sat at 0.31 against the same gate).
EXCHANGE_BUCKETS = max(
    int(os.environ.get("HOROVOD_EXCHANGE_BUCKETS", "8") or 8), 1)


def _async_host(x):
    """Start the device->host copy without blocking (readback then costs
    only the residual transfer at the sync point)."""
    x.copy_to_host_async()


# Rows that raised, in order. main() prints the JSON line with whatever
# the other rows measured and then exits non-zero if this is non-empty:
# a benchmark that lost a row never reads as a clean run.
FAILED_ROWS = []


def _row(name, fn):
    """Run one independent benchmark row. The row boundary is the one
    place that must keep running after a failure (the rows that did
    measure something still get printed), so it records the traceback,
    marks the row failed and lets main() turn that into the exit code."""
    import traceback
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 — reported, then exit != 0
        traceback.print_exc(file=sys.stderr)
        print(f"# row {name} FAILED: {type(e).__name__}: {e}",
              file=sys.stderr)
        FAILED_ROWS.append(name)
        return {"failed": f"{type(e).__name__}: {e}"}


# ResNet-50 @224: ~4.09 GFLOPs forward per image; training ~= 3x forward
# (fwd + 2x bwd). MFU uses this analytic model-FLOPs figure by convention
# (the scaling-book definition) — XLA's cost_analysis() counts post-fusion
# hardware ops, which is an HFU-flavored number and materially lower; it is
# reported alongside as hfu-style context when available.
ANALYTIC_TRAIN_FLOPS_PER_IMAGE = 3 * 4.09e9


def _peak_flops():
    """Per-chip peak from HOROVOD_PEAK_FLOPS or the shared table in
    horovod_tpu.hardware (the live hvd_step_mfu gauge divides by the same
    numbers); None on the CPU (mfu_pct is then null), an error for a chip
    the table does not list."""
    from horovod_tpu.config import Config
    peak = hvd_hardware.peak_flops_per_chip(Config.from_env())
    return peak or None


def build_step(model, tx, mesh):
    """One compiled program running BATCHES_PER_ITER train steps
    (lax.scan keeps per-dispatch host latency out of a device-throughput
    benchmark — the reference's sess.run amortizes the same way)."""

    def per_shard_iter(params, batch_stats, opt_state, images, labels):
        # batch_stats ride in sharded over 'hvd' with a leading device axis
        # (Horovod semantics: BN stats are per-replica, never reduced).
        bs = jax.tree.map(lambda x: x[0], batch_stats)

        def one_step(carry, _):
            params, bs, opt_state = carry

            def loss_fn(p):
                logits, mutated = model.apply(
                    {"params": p, "batch_stats": bs}, images,
                    train=True, mutable=["batch_stats"])
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean()
                return loss, mutated["batch_stats"]

            (loss, bs), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            return (params, bs, opt_state), loss

        (params, bs, opt_state), losses = jax.lax.scan(
            one_step, (params, bs, opt_state), None,
            length=BATCHES_PER_ITER)
        return params, jax.tree.map(lambda x: x[None], bs), opt_state, \
            losses[-1][None]

    # donate: training state is dead after each call, so XLA reuses its
    # buffers instead of holding two copies of the model in HBM.
    return jax.jit(jax.shard_map(
        per_shard_iter, mesh=mesh,
        in_specs=(P(), P("hvd"), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P("hvd"), P(), P("hvd")),
        check_vma=False), donate_argnums=(0, 1, 2))


def _setup(batch_per_chip, n, mesh, model, variables):
    """Fresh device-resident training state + data for one batch size."""
    batch = batch_per_chip * n
    params = variables["params"]
    batch_stats = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (n,) + x.shape), variables["batch_stats"])
    tx = hvd.DistributedOptimizer(optax.sgd(0.01), axis_name="hvd")
    opt_state = tx.init(params)
    step = build_step(model, tx, mesh)

    images = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(1),
                          (batch, IMAGE_SIZE, IMAGE_SIZE, 3), jnp.bfloat16),
        NamedSharding(mesh, P("hvd")))
    labels = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(2), (batch,), 0, 1000),
        NamedSharding(mesh, P("hvd")))
    batch_stats = jax.device_put(batch_stats, NamedSharding(mesh, P("hvd")))
    params = jax.device_put(params, NamedSharding(mesh, P()))
    opt_state = jax.device_put(opt_state, NamedSharding(mesh, P()))
    return step, params, batch_stats, opt_state, images, labels


def _warmup(step, state, images, labels):
    """Two untimed calls: the first traces with host-initialized avals,
    the second with the program's own outputs — both jit specializations
    must compile before timing. Returns the updated state."""
    for _ in range(2):
        *state, loss = step(*state, images, labels)
        float(np.asarray(loss)[0])
    return state


def _timed_iters(step, state, images, labels, iters, imgs_per_call):
    """The shared timed-iteration body (sweep points and the final
    protocol run MUST time identically or their numbers aren't
    comparable).

    Overlapped-communication pipeline: each call is dispatched without
    blocking, its loss's host copy starts at dispatch, and an iteration
    only blocks on the result from PIPELINE_DEPTH calls back — so the
    device->host readback rides behind the in-flight calls' compute
    instead of serializing with it.
    The first PIPELINE_DEPTH calls prime the pipeline untimed; each of
    the ``iters`` timed iterations then spans one dispatch plus one
    blocking readback, i.e. one steady-state step (the rate a real
    training loop, which never blocks per step, sustains). The tail
    drains untimed so bunched-ready results can't fabricate near-zero
    intervals.

    Device-resident mode (DEVICE_RESIDENT): the loop blocks only on
    *device* completion of the call PIPELINE_DEPTH back — timing still
    spans one honest steady-state step — and the host fetch never enters
    the loop at all, so the per-iteration blocked-readback wait is zero
    by construction (the fetch happens once, untimed, at the drain).

    Returns (img/sec samples, updated state, per-iteration
    blocked-readback seconds, per-iteration device-wait seconds)."""
    samples, waits, dev_waits = [], [], []
    pending = deque()
    done = []
    for _ in range(iters + PIPELINE_DEPTH):
        t0 = time.perf_counter()
        *state, loss = step(*state, images, labels)
        if not DEVICE_RESIDENT:
            _async_host(loss)
        pending.append(loss)
        if len(pending) > PIPELINE_DEPTH:
            tw = time.perf_counter()
            old = pending.popleft()
            if DEVICE_RESIDENT:
                jax.block_until_ready(old)  # paces the loop, no host fetch
                done.append(old)
                now = time.perf_counter()
                dev_waits.append(now - tw)
                waits.append(0.0)
            else:
                float(np.asarray(old)[0])
                now = time.perf_counter()
                waits.append(now - tw)
            samples.append(imgs_per_call / (now - t0))
    while pending:  # untimed pipeline drain
        done.append(pending.popleft())
    for loss in done:  # untimed host fetches (validates the results)
        float(np.asarray(loss)[0])
    return samples, state, waits, dev_waits


def _flight_attribution(flight, phase0, events0, loop_wall, iters):
    """Per-iteration phase breakdown and recorder self-cost over the
    timed measurement loop.

    The breakdown comes from the always-on flight recorder's phase
    accounting (docs/diagnostics.md): wire/readback/input seconds that
    accrued during the loop, with compute as the unattributed remainder
    of the loop's wall time. Under jitted shard_map steps the eager
    engine never enters the hot loop, so wire/readback/input legitimately
    read ~0 and compute carries the whole step — the field is most
    informative for eager-exchange runs.

    flight_overhead_frac is measured, not modeled: the per-event cost of
    a ring append (timed on a throwaway recorder, same code path) times
    the events the loop actually recorded, over the loop's wall time.
    Acceptance for the always-on default is < 1% steady state."""
    if flight is None or loop_wall <= 0 or iters <= 0:
        return None, 0.0
    p1 = flight.phase_totals()
    wire_s = max(p1["wire_s"] - phase0["wire_s"], 0.0)
    readback_s = max(p1["readback_s"] - phase0["readback_s"], 0.0)
    input_s = max(p1["input_s"] - phase0["input_s"], 0.0)
    compute_s = max(loop_wall - wire_s - readback_s - input_s, 0.0)
    per_iter = 1e3 / iters
    breakdown = {
        "compute_ms": round(compute_s * per_iter, 3),
        "wire_ms": round(wire_s * per_iter, 3),
        "readback_ms": round(readback_s * per_iter, 3),
        "input_ms": round(input_s * per_iter, 3),
    }
    probe = hvd_diag.FlightRecorder(capacity=256)
    n_probe = 2000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        probe.record("probe", name="bench.overhead", op="PROBE",
                     nbytes=0, dtype="f32")
    cost_per_event = (time.perf_counter() - t0) / n_probe
    events = max(flight.events_recorded - events0, 0)
    frac = min(events * cost_per_event / loop_wall, 1.0)
    return breakdown, round(frac, 6)


def _guard_attribution(loop_wall, iters):
    """Measured fraction of the loop's wall time the step-integrity
    guard's host-side work would cost (docs/robustness.md; acceptance:
    < 2% on the device-resident path).

    Like flight_overhead_frac, measured rather than modeled: the
    device-resident guard adds (a) one fused in-graph health reduction
    per bucket — part of the wire program, invisible to the host — and
    (b) per step, one deferred health-array fold plus the policy ladder
    (note_device_health + end_step). The probe times (b) on a throwaway
    monitor with a generous 8-bucket health row, then scales by the
    loop's iteration count."""
    if loop_wall <= 0 or iters <= 0:
        return 0.0
    import jax.numpy as jnp

    from horovod_tpu.config import Config
    from horovod_tpu.guard import GuardMonitor
    mon = GuardMonitor(Config())
    health = jnp.ones((8, 2), jnp.float32)
    names = [f"bench.guard.{i}" for i in range(8)]
    n_probe = 500
    t0 = time.perf_counter()
    for _ in range(n_probe):
        mon.note_device_health(names, health)
        mon.end_step()
    cost_per_step = (time.perf_counter() - t0) / n_probe
    return round(min(cost_per_step * iters / loop_wall, 1.0), 6)


def _trace_attribution(loop_wall, iters):
    """Measured fraction of the loop's wall time the step tracer costs
    when tracing is OFF (the shipped default): the per-step hook on the
    compiled path is one ``StepTracer.tick`` call that returns at its
    first check while nothing is armed. Timed on a throwaway tracer
    (same code path) and scaled by the loop's iteration count
    (acceptance: < 1% with tracing disabled)."""
    if loop_wall <= 0 or iters <= 0:
        return 0.0
    from horovod_tpu.diag.xla_trace import StepTracer
    probe = StepTracer(diag_dir=".")
    n_probe = 10000
    t0 = time.perf_counter()
    for _ in range(n_probe):
        probe.tick(owner=_trace_attribution)
    cost_per_step = (time.perf_counter() - t0) / n_probe
    return round(min(cost_per_step * iters / loop_wall, 1.0), 6)


def measure(batch_per_chip, n, mesh, model, variables, iters):
    """Sweep-point measurement: fresh setup + compile for this batch
    size, warmup, ``iters`` timed calls. Returns the img/sec samples.
    The FINAL protocol run lives in main() and reuses ONE compiled step
    across CI rounds and the block-timed measurement."""
    step, params, batch_stats, opt_state, images, labels = _setup(
        batch_per_chip, n, mesh, model, variables)
    state = _warmup(step, (params, batch_stats, opt_state), images, labels)
    samples, _, _, _ = _timed_iters(step, state, images, labels, iters,
                                    batch_per_chip * BATCHES_PER_ITER)
    return samples


def _dispatch_profile():
    """Decompose the per-dispatch host overhead of a null jitted call
    (round-4 verdict #8: quantify WHAT the fixed per-call cost is).
    Four measurements, min-of-5 each:

    - ``enqueue``: the jit call returning WITHOUT readback — Python
      dispatch + RPC enqueue cost;
    - ``readback_sync``: ``np.asarray`` of an already-computed device
      scalar with NO prior async copy — the pure device->host round-trip
      a blocking per-step fetch pays;
    - ``readback`` (deferred): the same fetch when the host copy was
      started at dispatch time (``copy_to_host_async``) and has had time
      to ride behind other work — the cost the pipelined timed loop
      actually pays at its sync points;
    - ``full``: call + sync readback, the barrier the OLD per-iteration
      timed loop paid (back-compat ``dispatch_overhead_ms``).

    ``overlap_efficiency`` = 1 - readback_deferred/readback_sync: the
    fraction of the readback round-trip the deferred path hides. This is
    the mechanism's ceiling; the reported JSON value is additionally
    bounded by the timed loop's actual blocked-readback waits (see
    main()), so it reflects achieved — not just achievable — overlap.

    The emitted dispatch_*_ms JSON fields carry the measured values; the
    block-timed path in main() amortizes the barrier to one fetch per
    block. None of them has been measured on the current chip (PERF.md)."""
    f = jax.jit(lambda x: x + 1.0)
    x = jnp.float32(0)
    float(np.asarray(f(x)))  # compile

    enq = []
    y = None
    for _ in range(5):
        t0 = time.perf_counter()
        y = f(x)
        enq.append(time.perf_counter() - t0)
    jax.block_until_ready(y)
    # readback: a FRESH completed array per timing (jax.Array caches its
    # numpy value after the first read, so re-reading one array would
    # measure a host cache hit, not the transfer)
    zs = [jax.block_until_ready(f(jnp.float32(i))) for i in range(5)]
    rb = []
    for z in zs:
        t0 = time.perf_counter()
        np.asarray(z)
        rb.append(time.perf_counter() - t0)
    full = []
    for _ in range(5):
        t0 = time.perf_counter()
        float(np.asarray(f(x)))
        full.append(time.perf_counter() - t0)
    # deferred readback: async host copies issued at dispatch; by the time
    # the loop syncs (after a ready-wait plus a settle bounded by the sync
    # RTT) the value is host-side and the fetch is a residual, not an RTT
    zs2 = [f(jnp.float32(i + 50)) for i in range(5)]
    for z in zs2:
        _async_host(z)
    jax.block_until_ready(zs2)
    time.sleep(min(max(min(rb), 1e-3) * 2.0, 0.25))
    deferred = []
    for z in zs2:
        t0 = time.perf_counter()
        np.asarray(z)
        deferred.append(time.perf_counter() - t0)
    sync_ms = min(rb) * 1e3
    deferred_ms = min(deferred) * 1e3
    if sync_ms > 0.05:  # below noise floor there is nothing to hide
        overlap_eff = max(0.0, min(1.0, 1.0 - deferred_ms / sync_ms))
    else:
        overlap_eff = 1.0
    return {"enqueue_ms": min(enq) * 1e3, "readback_ms": deferred_ms,
            "readback_sync_ms": sync_ms, "full_ms": min(full[1:]) * 1e3,
            "overlap_efficiency": overlap_eff}


def _input_pipeline_profile(depth):
    """Exposed input wait through ``hvd.data.DistributedDataset`` at one
    prefetch depth (docs/data.md). The source charges a fixed per-batch
    production cost (sleep standing in for decode/augment/storage I/O)
    and the loop a fixed consume cost (standing in for the dispatched
    device step): with prefetch on, production rides behind the consume
    window and the exposed wait collapses toward zero; the synchronous
    fallback (depth 0) pays the full production cost inside every step.
    ``data_wait_ms`` is the steady-state mean exposed wait per batch —
    the input analog of ``loop_readback_wait_ms``."""
    from horovod_tpu.data import DistributedDataset
    n_batches = 6 if SMOKE else 20
    batch = 8
    produce_s = 0.004
    consume_s = 0.004

    def fetch(idx):
        time.sleep(produce_s)
        return np.asarray(idx, np.float32)

    ds = DistributedDataset(fetch, batch, num_samples=n_batches * batch,
                            seed=0, rank=0, size=1, prefetch=depth)
    ds.take_wait()
    waits = []
    t0 = time.perf_counter()
    for _ in ds:
        time.sleep(consume_s)
        waits.append(ds.take_wait())
    elapsed = time.perf_counter() - t0
    ds.close()
    # the first batch has no consume window to hide behind — both modes
    # pay its production cost equally, so it stays out of steady state
    steady = waits[1:] or waits
    return {"prefetch_depth": depth,
            "data_wait_ms": round(float(np.mean(steady)) * 1e3, 3),
            "batches": len(waits),
            "batches_per_sec": round(len(waits) / elapsed, 2)}


def _eager_exchange_profile():
    """Steady-state eager gradient exchange through the engine: the same
    small pytree of tensors every step, like a training loop's gradient
    set. Measures the signature-keyed wire-program cache (steady state
    should hit one cached executable per bucket — ``wire_cache_hit_rate``
    >= 0.9 once warm) and, in device-resident mode, the per-step
    synchronize wait with zero readback (``eager_sync_wait_ms``). The
    legacy mode (HOROVOD_DEVICE_RESIDENT=0) runs the same protocol on
    the host-readback path so both appear in BENCH artifacts."""
    import horovod_tpu as hvd
    eng = hvd.state().engine
    # >= 0.9 hit rate needs >= 10 steady-state steps even when every
    # tensor compiles its own program (world size 1's identity tier).
    steps = 12 if SMOKE else 24
    shapes = [(1024,), (64, 32), (256,)]
    base_h, base_m = eng._wire_cache.hits, eng._wire_cache.misses
    sync_waits = []
    device_out = False
    for s in range(steps):
        handles = [hvd.allreduce_async(
            np.full(shape, float(s + i), np.float32),
            name=f"bench.exchange.{i}", to_host=not DEVICE_RESIDENT)
            for i, shape in enumerate(shapes)]
        t0 = time.perf_counter()
        results = [hvd.synchronize(h) for h in handles]
        sync_waits.append(time.perf_counter() - t0)
        device_out = device_out or any(
            isinstance(next(iter(r.values())) if isinstance(r, dict) else r,
                       jax.Array) for r in results)
    hits = eng._wire_cache.hits - base_h
    misses = eng._wire_cache.misses - base_m
    rate = hits / max(hits + misses, 1)
    # steady state excludes the first (compiling) step
    steady = sync_waits[1:] or sync_waits
    return {"wire_cache_hit_rate": round(rate, 4),
            "wire_cache_hits": hits,
            "wire_cache_misses": misses,
            "eager_sync_wait_ms": round(float(np.mean(steady)) * 1e3, 3),
            "device_resident_results": bool(device_out),
            "steps": steps}


def _overlap_microbench(mesh, n, out_base, buckets, trace_n=4):
    """Comm-bound overlap measurement the headline capture can't give us
    on every backend: the smoke-scale ResNet program emits so many device
    events on CPU that the profiler's event cap drops the collective ops
    and the exchange fold reads zero. This runs a deliberately
    params-heavy / compute-light MLP (exchange bytes ~ backward FLOPs) at
    ``buckets=1`` vs the tuned count and folds each side's trace, so the
    reported ``hidden_frac`` comes from a capture small enough to be
    complete. This is the acceptance measurement for the bucketed
    overlap (docs/performance.md "Bucketed backward/exchange overlap")."""
    depth, width = 8, 1024
    rows = 32 * n

    def loss_fn(p, x, y):
        h = x
        for i in range(depth):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    key = jax.random.PRNGKey(11)
    host = {f"w{i}": np.asarray(
        jax.random.normal(jax.random.fold_in(key, i),
                          (width, width), jnp.float32)) * 0.05
        for i in range(depth)}
    x = jax.device_put(
        jax.random.normal(jax.random.fold_in(key, 100),
                          (rows, width), jnp.float32),
        NamedSharding(mesh, P("hvd")))
    y = jax.device_put(jnp.zeros((rows, width), jnp.float32),
                       NamedSharding(mesh, P("hvd")))

    out = {"buckets": buckets, "depth": depth, "width": width}
    for tag, bk in (("base", 1), ("tuned", buckets)):
        step = hvd.compiled_train_step(
            loss_fn, optax.sgd(0.01),
            name=f"bench.overlap_micro.{tag}", exchange_buckets=bk)
        p = jax.device_put(host, NamedSharding(mesh, P()))
        o = jax.device_put(step.init(host), NamedSharding(mesh, P()))
        for _ in range(2):  # warmup/compile outside the capture
            p, o, ls = step(p, o, x, y)
        jax.block_until_ready(ls)
        ts = []
        tr = hvd.trace_steps(trace_n, out_dir=out_base)
        for _ in range(trace_n + 2):
            t0 = time.perf_counter()
            p, o, ls = step(p, o, x, y)
            jax.block_until_ready(ls)
            ts.append(time.perf_counter() - t0)
        if tr.active or tr.armed:
            tr.stop()
        ex = (tr.last_summary or {}).get("exchange")
        out[f"step_ms_{tag}"] = round(float(np.median(ts)) * 1e3, 3)
        out[f"hidden_frac_{tag}"] = (
            None if not ex else round(ex["hidden_frac"], 4))
        out[f"exchange_ms_{tag}"] = (
            None if not ex else round(ex["exchange_s"] * 1e3, 3))
    return out


def _compiled_step_profile(batch_per_chip, n, mesh, model, variables,
                           exchange_buckets=None):
    """The compiled hot loop (docs/performance.md "Compiled hot loop"):
    ``hvd.compiled_train_step`` fuses forward, backward, the fused
    in-graph gradient exchange, and the optimizer apply into ONE jitted,
    buffer-donated XLA program — per-STEP dispatch instead of the scan
    path's per-BLOCK amortization, so the measured ``python_overhead_ms``
    (wall time of one ``step()`` call returning unfetched device arrays)
    is exactly the steady-state per-step Python cost the acceptance
    bounds at < 1 ms. The loop paces itself on device readiness
    PIPELINE_DEPTH calls back and never fetches a value, so
    ``loop_readback_wait_ms`` is 0.0 by construction. Reported next to
    (not replacing) the eager/scan numbers, with the step-program cache
    hit rate — steady state is one compile then hits forever.

    ``exchange_buckets`` tunes the bucketed backward/exchange overlap
    (docs/performance.md "Bucketed backward/exchange overlap"): the
    profile runs at the tuned count, then A/Bs a fresh ``buckets=1``
    step (one psum call over all leaves) with the same blocked
    measurement protocol and reports both sides under ``overlap_ab`` —
    the with/without-overlap delta plus each side's trace-measured
    ``exchange_hidden_frac``."""
    # BN stats ride as frozen constants: the compiled-step API takes a
    # pure loss, and per-replica stats mutation is a no-op for a
    # synthetic throughput measurement (same images every step anyway).
    bs = variables["batch_stats"]

    def loss_fn(params, images, labels):
        logits, _ = model.apply({"params": params, "batch_stats": bs},
                                images, train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    buckets = (EXCHANGE_BUCKETS if exchange_buckets is None
               else max(int(exchange_buckets), 1))
    step = hvd.compiled_train_step(loss_fn, optax.sgd(0.01),
                                   name="bench.compiled",
                                   exchange_buckets=buckets)
    batch = batch_per_chip * n
    params = jax.device_put(variables["params"], NamedSharding(mesh, P()))
    opt_state = jax.device_put(step.init(variables["params"]),
                               NamedSharding(mesh, P()))
    images = jax.device_put(
        jax.random.normal(jax.random.PRNGKey(3),
                          (batch, IMAGE_SIZE, IMAGE_SIZE, 3), jnp.bfloat16),
        NamedSharding(mesh, P("hvd")))
    labels = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(4), (batch,), 0, 1000),
        NamedSharding(mesh, P("hvd")))
    # two untimed warmup calls: both jit specializations compile before
    # timing (donation consumes the inputs — always rebind the returns)
    for _ in range(2):
        params, opt_state, loss = step(params, opt_state, images, labels)
    jax.block_until_ready(loss)
    h0, m0 = step.cache_hits, step.cache_misses

    iters = max(NUM_ITERS * BATCHES_PER_ITER, 12)
    py_overheads, rates = [], []
    pending = deque()
    t_loop0 = time.perf_counter()
    for _ in range(iters + PIPELINE_DEPTH):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, images, labels)
        py_overheads.append(time.perf_counter() - t0)
        pending.append(loss)
        if len(pending) > PIPELINE_DEPTH:
            # device-completion pacing only — no host fetch in the loop
            jax.block_until_ready(pending.popleft())
            rates.append(batch_per_chip / (time.perf_counter() - t0))
    while pending:  # untimed drain
        jax.block_until_ready(pending.popleft())
    loop_wall = time.perf_counter() - t_loop0
    float(np.asarray(loss))  # untimed validation fetch

    hits = step.cache_hits - h0
    misses = step.cache_misses - m0
    hit_rate = hits / max(hits + misses, 1)
    mean, spread, sem, rejected = _robust_stats(rates)
    peak = _peak_flops()
    mfu = (None if peak is None
           else ANALYTIC_TRAIN_FLOPS_PER_IMAGE * mean / peak * 100.0)

    # Phase-attributed device trace of the same compiled step, captured
    # AFTER the timed loop so the lower/compile + capture cost stays out
    # of the measured numbers (docs/diagnostics.md "Seeing inside the
    # compiled step").
    import tempfile

    from horovod_tpu.config import Config
    out_base = Config.from_env().diag_dir or tempfile.mkdtemp(
        prefix="bench-xla-trace-")
    trace_n = 4
    phase_ms = stage_ms = hidden_frac = None
    tracer = hvd.trace_steps(trace_n, out_dir=out_base)
    # trace_n + 2 ticks: the first starts the capture, the next
    # trace_n close the window, one spare guarantees the stop fires
    # even if a tick is swallowed.
    for _ in range(trace_n + 2):
        params, opt_state, loss = step(params, opt_state, images, labels)
        jax.block_until_ready(loss)
    if tracer.active or tracer.armed:
        tracer.stop()
    summary = tracer.last_summary
    trace_dir = tracer.last_dir
    if summary:
        per = 1e3 / trace_n / max(summary["lanes"], 1)
        phase_ms = {p: round(v * per, 3)
                    for p, v in summary["phases"].items()}
        stage_ms = {s: round(v * per, 3)
                    for s, v in summary["stages"].items()}
        ex = summary.get("exchange")
        if ex:
            hidden_frac = round(ex["hidden_frac"], 4)

    # Overlap A/B (docs/performance.md "Bucketed backward/exchange
    # overlap"): same loss, same blocked per-step protocol on BOTH sides
    # — buckets=1 (one psum call over all leaves) vs the tuned
    # count — so the with/without-overlap delta is apples-to-apples even
    # though the headline loop above paces on PIPELINE_DEPTH. Each side
    # also traces its own exchange_hidden_frac.
    ab_iters = 8

    def _blocked_ms(st, p, o):
        for _ in range(2):
            p, o, ls = st(p, o, images, labels)
        jax.block_until_ready(ls)
        ts = []
        for _ in range(ab_iters):
            t0 = time.perf_counter()
            p, o, ls = st(p, o, images, labels)
            jax.block_until_ready(ls)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts)) * 1e3, p, o

    def _traced_hidden(st, p, o):
        tr = hvd.trace_steps(trace_n, out_dir=out_base)
        for _ in range(trace_n + 2):
            p, o, ls = st(p, o, images, labels)
            jax.block_until_ready(ls)
        if tr.active or tr.armed:
            tr.stop()
        ex = (tr.last_summary or {}).get("exchange")
        return (None if not ex
                else round(ex["hidden_frac"], 4)), p, o

    tuned_ms, params, opt_state = _blocked_ms(step, params, opt_state)
    step1 = hvd.compiled_train_step(loss_fn, optax.sgd(0.01),
                                    name="bench.compiled.b1",
                                    exchange_buckets=1)
    # fresh bindings from the still-live host pytree (the tuned
    # side's device buffers may have been donated away)
    p1 = jax.device_put(variables["params"], NamedSharding(mesh, P()))
    o1 = jax.device_put(step1.init(variables["params"]),
                        NamedSharding(mesh, P()))
    base_ms, p1, o1 = _blocked_ms(step1, p1, o1)
    base_hidden, p1, o1 = _traced_hidden(step1, p1, o1)
    overlap_ab = {
        "buckets_base": 1,
        "buckets_tuned": buckets,
        "step_ms_base": round(base_ms, 3),
        "step_ms_tuned": round(tuned_ms, 3),
        "speedup_pct": round(
            (base_ms - tuned_ms) / base_ms * 100.0, 2),
        "hidden_frac_base": base_hidden,
        "hidden_frac_tuned": hidden_frac,
    }

    # Comm-bound microbench: the interval-fold measurement the CI
    # overlap gate keys on. When the headline capture could not
    # attribute exchange time (event-capped trace on CPU backends),
    # its tuned-side hidden fraction stands in for the headline one.
    micro = _overlap_microbench(mesh, n, out_base, buckets)
    if hidden_frac is None:
        hidden_frac = micro.get("hidden_frac_tuned")

    return {
        "img_sec_per_chip": round(mean, 2),
        "spread": round(spread, 2),
        "samples": len(rates),
        "outliers_rejected": rejected,
        "mfu_pct": None if mfu is None else round(mfu, 2),
        # wall time of one step() dispatch returning device arrays — the
        # entire per-step Python cost of the compiled path (< 1 ms target)
        "python_overhead_ms": round(
            float(np.median(py_overheads)) * 1e3, 3),
        "step_program_cache_hit_rate": round(hit_rate, 4),
        "step_program_cache_hits": hits,
        "step_program_cache_misses": misses,
        "compiled_steps": step.compiled_steps,
        "fallback_steps": step.fallback_steps,
        # the loop never fetches to host; zero by construction (the
        # compiled analog of the device-resident scan loop's field)
        "loop_readback_wait_ms": 0.0,
        # deferred guard fold cost the compiled path would add per step
        # under HOROVOD_GUARD=1 (acceptance: < 2%)
        "guard_overhead_frac": _guard_attribution(loop_wall, len(rates)),
        # XLA device-trace phase attribution of this exact program:
        # device ms per step per lane inside each hvd_ named scope
        # (docs/diagnostics.md); None when the capture produced no
        # parseable device events on this backend
        "step_phase_breakdown": phase_ms,
        "wire_stage_ms": stage_ms,
        "xla_trace_dir": trace_dir,
        # bucketed backward/exchange overlap (HOROVOD_EXCHANGE_BUCKETS):
        # fraction of exchange device time hidden under compute in this
        # exact program's trace (CI overlap-smoke gate: >= 0.3), plus
        # the buckets=1-vs-tuned A/B the acceptance records
        "exchange_buckets": buckets,
        "exchange_hidden_frac": hidden_frac,
        "overlap_ab": overlap_ab,
        "overlap_microbench": micro,
        # idle-tracer per-step cost over this loop (tracing off default;
        # acceptance < 1%)
        "trace_overhead_frac": _trace_attribution(loop_wall, iters),
        "steps": iters,
    }


def _zero_profile(n, mesh):
    """ZeRO sharding + DCN-compression profile (docs/performance.md
    "ZeRO stages & DCN compression"): a small MLP trained at
    ``zero_stage=2`` with and without ``dcn_compression="int8"``,
    reporting (a) ``dcn_bytes_saved_frac`` — the measured DCN-stage wire
    reduction from the per-stage counters' delta across the compressed
    run, (b) ``dcn_loss_delta`` — final-loss gap vs the uncompressed
    trajectory (the error-feedback convergence claim), and (c)
    ``zero_memory`` — the per-device resident footprint split
    (params/grads/opt-state stripes vs the replicated full sizes) from
    the zero-3 stripe layout. Cheap by construction: D=256 two-layer
    MLP, 8 steps per run."""
    D, steps = 256, 8
    rng = np.random.RandomState(7)
    params0 = {
        "w1": jnp.asarray(rng.randn(D, D).astype(np.float32) * 0.05),
        "b1": jnp.zeros((D,), jnp.float32),
        "w2": jnp.asarray(rng.randn(D, 8).astype(np.float32) * 0.05),
        "b2": jnp.zeros((8,), jnp.float32),
    }
    X = jnp.asarray(rng.randn(n * 4, D).astype(np.float32))
    Y = jnp.asarray(rng.randn(n * 4, 8).astype(np.float32))

    def loss_fn(params, x, y):
        h = jnp.tanh(x @ params["w1"] + params["b1"])
        return jnp.mean((h @ params["w2"] + params["b2"] - y) ** 2)

    # staging needs an ICI group size that divides n; n//2 gives a real
    # two-stage split on any even world, n==1 degenerates to single-stage
    local = n // 2 if n >= 2 and n % 2 == 0 else 1

    def run(dcn):
        tx = hvd.DistributedOptimizer(
            optax.adam(1e-2), zero_stage=2, dcn_compression=dcn,
            dcn_local_size=local if dcn else 0)
        step = hvd.compiled_train_step(loss_fn, tx,
                                       name=f"bench.zero2.{dcn or 'raw'}")
        params, state = params0, step.init(params0)
        loss = None
        for _ in range(steps):
            params, state, loss = step(params, state, X, Y)
        return float(np.asarray(loss))

    def _stage(snap, family, stage):
        return snap.get(family, {}).get("values", {}).get(
            f'stage="{stage}"', 0.0)

    loss_raw = run("")
    before = hvd_metrics.snapshot()
    loss_c = run("int8")
    after = hvd_metrics.snapshot()
    wire = (_stage(after, "hvd_wire_stage_bytes_total", "dcn")
            - _stage(before, "hvd_wire_stage_bytes_total", "dcn"))
    raw = (_stage(after, "hvd_wire_stage_raw_bytes_total", "dcn")
           - _stage(before, "hvd_wire_stage_raw_bytes_total", "dcn"))
    saved = round(1.0 - wire / raw, 4) if raw else None

    # zero-3 resident footprint split: stripes are the per-device truth
    # (fake-replicated P(): logical shape == per-device shape)
    tx3 = hvd.DistributedOptimizer(optax.adam(1e-2), zero_stage=3)
    step3 = hvd.compiled_train_step(loss_fn, tx3, name="bench.zero3.mem")
    state3 = step3.init(params0)
    stripe = step3.shard_params(params0)
    full_params = sum(l.nbytes for l in jax.tree.leaves(params0))
    opt_stripe = sum(l.nbytes for l in jax.tree.leaves(state3.base)
                     if hasattr(l, "nbytes"))
    memory = {
        "world_size": n,
        "params_full_bytes": full_params,
        "params_stripe_bytes": int(stripe.nbytes),
        "grads_stripe_bytes": int(stripe.nbytes),
        "opt_state_stripe_bytes": int(opt_stripe),
        # params + grads + opt state: stripes vs the replicated layout
        # (replicated opt state would be this stripe on every rank) — the
        # acceptance's ~1/N claim, measured from the real buffers
        "resident_frac_of_replicated": round(
            (2 * int(stripe.nbytes) + opt_stripe)
            / max(2 * full_params + opt_stripe * n, 1), 4),
    }
    return {
        "zero_stage": 2,
        "dcn_local_size": local,
        "dcn_bytes_saved_frac": saved,
        "dcn_loss_delta": round(abs(loss_c - loss_raw), 6),
        "loss_uncompressed": round(loss_raw, 6),
        "loss_compressed": round(loss_c, 6),
        "zero_memory": memory,
        "steps": steps,
    }


def _robust_stats(samples):
    """Stats after MAD outlier rejection (5-sigma-equivalent): the
    driver host occasionally steals a whole scheduling quantum from one
    iteration, and a single such outlier at 10 samples previously blew
    the 1.96-sigma interval to +-46% of the mean (round-4 verdict #5).

    Returns (mean, spread, sem, rejected): ``spread`` is the reference
    protocol's 1.96*std per-sample interval (printed for parity);
    ``sem`` is the 1.96*std/sqrt(n) standard error of the MEAN — the
    quantity more samples actually shrink, so it is what the
    repeat-until-tight loop and the JSON's ci_pct target."""
    a = np.asarray(samples, dtype=np.float64)
    med = np.median(a)
    mad = np.median(np.abs(a - med))
    if mad > 0:
        keep = a[np.abs(a - med) <= 5.0 * 1.4826 * mad]
    else:
        keep = a
    mean = float(np.mean(keep))
    spread = float(1.96 * np.std(keep))
    sem = spread / max(len(keep), 1) ** 0.5
    return mean, spread, sem, len(a) - len(keep)


CI_TARGET_PCT = 3.0     # repeat final measurement until 1.96 sigma <= 3%
MAX_MEASURE_ROUNDS = 1 if SMOKE else 4  # at most this many NUM_ITERS rounds


def main():
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()
    # Input-pipeline profile: exposed input wait at the configured
    # prefetch depth vs the synchronous fallback, so data stalls are
    # visible in the JSON next to the comm/dispatch numbers.
    pipe = _input_pipeline_profile(DATA_PREFETCH)
    pipe_sync = _input_pipeline_profile(0)
    print(f"# input pipeline: {pipe['data_wait_ms']:.2f} ms/batch exposed "
          f"wait at prefetch depth {DATA_PREFETCH} "
          f"(synchronous {pipe_sync['data_wait_ms']:.2f} ms)",
          file=sys.stderr)
    if INPUT_PIPELINE_ONLY:
        print(json.dumps({
            "metric": "input_pipeline_wait",
            "value": pipe["data_wait_ms"],
            "unit": "ms/batch",
            "data_wait_ms": pipe["data_wait_ms"],
            "data_wait_sync_ms": pipe_sync["data_wait_ms"],
            "prefetch_depth": DATA_PREFETCH,
            "input_pipeline": {"prefetch": pipe, "sync": pipe_sync},
            "metrics": hvd_metrics.compact_snapshot(),
            **hvd_hardware.device_info(),
        }))
        hvd.shutdown()
        return
    profile = _dispatch_profile()
    exchange = _eager_exchange_profile()
    # Per-call host overhead the timed loop pays: device-resident mode
    # never fetches in the loop, so only the enqueue cost remains; with
    # the (legacy) pipeline on, async enqueue plus the deferred readback
    # residual; in synchronous fallback mode (HOROVOD_PIPELINE_DEPTH=0)
    # the loop blocks on every call, so the full dispatch+readback
    # barrier — the pre-pipeline accounting — is what device-side rates
    # must back out.
    if DEVICE_RESIDENT:
        overhead = profile["enqueue_ms"] / 1e3
    else:
        overhead = (profile["full_ms"] if PIPELINE_DEPTH == 0 else
                    profile["enqueue_ms"] + profile["readback_ms"]) / 1e3

    model = ResNet50(num_classes=1000, dtype=jnp.bfloat16)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, IMAGE_SIZE, IMAGE_SIZE, 3),
                                    jnp.bfloat16),
                           train=True)
    # Master copy lives on the HOST: each measure() transfers fresh device
    # buffers, so the step's donated (hence deleted) arrays can never alias
    # the template reused by the next sweep point.
    variables = jax.tree.map(np.asarray, variables)

    # Batch sweep: short runs pick the throughput-optimal per-chip batch.
    sweep = {}
    for b in BATCH_CANDIDATES:
        try:
            img_secs = measure(b, n, mesh, model, variables, SWEEP_ITERS)
        except jax.errors.JaxRuntimeError as e:
            # the one tolerated failure: a batch too large for the
            # chip's memory is a sweep result, anything else is a bug
            if "RESOURCE_EXHAUSTED" not in str(e):
                raise
            print(f"# batch {b}: out of device memory", file=sys.stderr)
            sweep[str(b)] = None
            continue
        sweep[str(b)] = round(float(np.mean(img_secs)), 1)
        print(f"# sweep batch {b}: {sweep[str(b)]} img/s/chip",
              file=sys.stderr)
    usable = {int(b): v for b, v in sweep.items() if v is not None}
    if not usable:
        raise RuntimeError(
            f"no batch in {BATCH_CANDIDATES} fits this chip's memory")
    # Smallest batch within 2% of the sweep max: the short sweep runs
    # carry a few-% noise, and the larger batch costs HBM headroom and
    # per-iteration variance for no real throughput gain on a tie.
    cutoff = 0.98 * max(usable.values())
    best_batch = min(b for b, v in usable.items() if v >= cutoff)

    # Full protocol run at the winning batch. One _setup/compile serves
    # every extra CI round AND the block-timed run (donation chains the
    # training state through all of them — re-setup would pay a full
    # fresh jit compile per round). Measurement health (round-4 verdict
    # #5): MAD outlier rejection, then repeat (bounded) until the
    # standard error of the mean is within CI_TARGET_PCT; the JSON
    # carries ci_pct (+ ci_degraded when the target was unattainable).
    step, params, batch_stats, opt_state, images, labels = _setup(
        best_batch, n, mesh, model, variables)
    cost = step.lower(params, batch_stats, opt_state, images,
                      labels).compile().cost_analysis()
    flops = float(cost.get("flops", 0.0)) or None
    batch_imgs = best_batch * BATCHES_PER_ITER
    state = _warmup(step, (params, batch_stats, opt_state), images, labels)
    samples = []
    loop_waits = []
    loop_dev_waits = []
    rounds = 0
    flight = hvd_diag.get()
    flight_phase0 = flight.phase_totals() if flight is not None else None
    flight_events0 = flight.events_recorded if flight is not None else 0
    t_loop0 = time.perf_counter()
    while True:
        more, state, waits, dwaits = _timed_iters(step, state, images,
                                                  labels, NUM_ITERS,
                                                  batch_imgs)
        samples += more
        loop_waits += waits
        loop_dev_waits += dwaits
        rounds += 1
        mean, spread, sem, rejected = _robust_stats(samples)
        if sem <= CI_TARGET_PCT / 100.0 * mean \
                or rounds >= MAX_MEASURE_ROUNDS:
            break
        print(f"# CI {sem / mean * 100:.1f}% > {CI_TARGET_PCT}% after "
              f"{len(samples)} samples; measuring another round",
              file=sys.stderr)
    loop_wall = time.perf_counter() - t_loop0
    ci_pct = sem / mean * 100.0 if mean else 0.0
    ci_degraded = ci_pct > CI_TARGET_PCT
    step_phase_breakdown, flight_overhead_frac = _flight_attribution(
        flight, flight_phase0, flight_events0, loop_wall, len(samples))
    guard_overhead_frac = _guard_attribution(loop_wall, len(samples))
    # Achieved overlap: the profile's deferred-vs-sync ratio measures the
    # async-copy MECHANISM under ideal settle time; the timed loop's
    # actual blocked-readback waits measure what the pipeline DELIVERED.
    # Report the lower of the two so overlap_efficiency can't claim
    # hiding the loop never achieved (sync fallback: waits ~= the sync
    # RTT, efficiency ~0 as it should be).
    overlap_eff = profile["overlap_efficiency"]
    sync_ms = profile["readback_sync_ms"]
    if loop_waits and sync_ms > 0.05:
        wait_ms = float(np.mean(loop_waits)) * 1e3
        overlap_eff = min(overlap_eff,
                          max(0.0, 1.0 - min(wait_ms, sync_ms) / sync_ms))
    # Device-side throughput: the same samples with the measured
    # per-dispatch host overhead removed from each iteration's wall time
    # (protocol `value` stays raw for reference parity).
    dev_secs = [batch_imgs / max(batch_imgs / s - overhead, 1e-9)
                for s in samples]
    dev_mean, _, _, _ = _robust_stats(dev_secs)
    # Block-timed rate: barrier paid once across NUM_ITERS program calls
    # (the sustained-training view; see _dispatch_profile). Reuses the
    # same compiled step and current state.
    t0 = time.perf_counter()
    for _ in range(NUM_ITERS):
        *state, loss = step(*state, images, labels)
    float(np.asarray(loss)[0])  # one barrier for the whole block
    block_rate = batch_imgs * NUM_ITERS / (time.perf_counter() - t0)

    # Compiled hot loop at the same winning batch: per-step dispatch of
    # the single donated program, reported side by side with the
    # eager/scan numbers (docs/performance.md "Compiled hot loop"). In
    # legacy host mode every call would fall back to the eager
    # decomposition — nothing this profile measures — so it is skipped.
    compiled = {"skipped": "host mode (HOROVOD_DEVICE_RESIDENT=0): "
                           "the compiled path falls back per step"}
    if DEVICE_RESIDENT:
        compiled = _row("compiled_step", lambda: _compiled_step_profile(
            best_batch, n, mesh, model, variables,
            exchange_buckets=EXCHANGE_BUCKETS))
    if "img_sec_per_chip" in compiled:
        print(f"# compiled step: {compiled['img_sec_per_chip']:.1f} "
              f"img/s/chip, python overhead "
              f"{compiled['python_overhead_ms']:.3f} ms/step, cache hit "
              f"rate {compiled['step_program_cache_hit_rate']:.2f}, MFU "
              f"{compiled['mfu_pct']}%, guard frac "
              f"{compiled['guard_overhead_frac']}, exchange hidden frac "
              f"{compiled['exchange_hidden_frac']} "
              f"(buckets={compiled['exchange_buckets']})", file=sys.stderr)
        micro = compiled.get("overlap_microbench")
        if micro:
            print(f"# overlap microbench: hidden frac "
                  f"{micro['hidden_frac_base']} -> "
                  f"{micro['hidden_frac_tuned']} at "
                  f"{micro['buckets']} buckets, step "
                  f"{micro['step_ms_base']} -> {micro['step_ms_tuned']} ms",
                  file=sys.stderr)

    # ZeRO/DCN profile (docs/performance.md "ZeRO stages & DCN
    # compression"): wire savings, EF-convergence delta, 1/N footprint.
    zero = _row("zero_profile", lambda: _zero_profile(n, mesh))
    if "zero_memory" in zero:
        print(f"# zero2/dcn: saved frac {zero['dcn_bytes_saved_frac']}, "
              f"loss delta {zero['dcn_loss_delta']}, resident frac "
              f"{zero['zero_memory']['resident_frac_of_replicated']}",
              file=sys.stderr)

    peak = _peak_flops()
    mfu = hfu = None
    if peak:
        # MFU: analytic model FLOPs per image x achieved img/s, per chip
        # (device-side rate: the number describes the chip, not the rig)
        mfu = ANALYTIC_TRAIN_FLOPS_PER_IMAGE * dev_mean / peak * 100.0
        if flops:
            # XLA-counted (post-fusion) flops of the whole n-chip program
            hfu = (flops / n) * (dev_mean / batch_imgs) / peak * 100.0

    print(f"# Img/sec per chip: {mean:.1f} +-{spread:.1f} "
          f"(sem-ci {ci_pct:.1f}%, {rejected} outlier(s) rejected, "
          f"{len(samples)} samples) at batch {best_batch} (device-side "
          f"{dev_mean:.1f}, block-timed {block_rate:.1f}; total on {n} "
          f"chip(s): {mean * n:.1f}), MFU "
          f"{mfu if mfu is None else round(mfu, 1)}%, dispatch "
          f"enqueue/readback/full = {profile['enqueue_ms']:.1f}/"
          f"{profile['readback_ms']:.1f}/{profile['full_ms']:.1f} ms "
          f"(sync readback {profile['readback_sync_ms']:.1f} ms, overlap "
          f"eff {overlap_eff:.2f}, pipeline depth "
          f"{PIPELINE_DEPTH}, device-resident {DEVICE_RESIDENT}, wire "
          f"cache hit rate {exchange['wire_cache_hit_rate']:.2f})",
          file=sys.stderr)

    import bench_transformer

    # Flagship transformer row (reduced iters) so one JSON line carries
    # both model families — see bench_transformer.py for the full
    # protocol. TPU-only: the d2048 config is pointless on a CPU smoke run.
    if jax.devices()[0].platform == "tpu":
        transformer = _row(
            "transformer", lambda: bench_transformer.run_benchmark(
                bench_transformer.parse_args(["--iters", "4"])))
    else:
        transformer = {
            "skipped": f"non-TPU backend "
                       f"({jax.devices()[0].platform}); run "
                       f"bench_transformer.py on a chip for this row"}

    # Continuous-batching serving row (docs/serving.md): the paged-KV
    # decode engine at 8 concurrent streams on the current FLAT mesh —
    # it must run before the MoE row re-factorizes the runtime onto the
    # 2-D expert mesh. Reports TTFT/per-token latency percentiles,
    # tokens/sec, and the decode program-cache hit rate the CI
    # serve-smoke gate asserts (>= 0.9, zero fallbacks). CPU-capable by
    # design, like the MoE smoke.
    if DEVICE_RESIDENT and 8 % hvd.size() == 0:
        serve = _row("serve", lambda: bench_transformer.run_serve_benchmark(
            bench_transformer.parse_args(["--serve"]))["serve"])
    else:
        serve = {"skipped": "needs the device-resident path and a world "
                            "size dividing the 8 serve kv heads"}

    # Expert-parallel MoE row (docs/performance.md "Expert-parallel
    # MoE"): re-inits the runtime onto the 2-D (data, expert) mesh and
    # drives the chunked-alltoall MoE step through the same donated
    # step-program machinery. CPU-capable by design — the CI moe-smoke
    # gate asserts its overlap and cache numbers on the 8-device virtual
    # mesh. Device-resident only: in host mode every compiled call would
    # fall back, which is nothing this row measures.
    if DEVICE_RESIDENT and hvd.size() % 2 == 0:
        ep = 4 if hvd.size() % 4 == 0 else 2
        moe = _row("moe", lambda: bench_transformer.run_moe_benchmark(
            bench_transformer.parse_args(
                ["--moe", "--iters", "4",
                 "--expert-parallel", str(ep)]))["moe"])
    else:
        moe = {"skipped": "needs an even device count and the "
                          "device-resident path for the 2-D expert mesh"}

    # Composable-parallelism row (docs/performance.md "Composable
    # parallelism"): re-inits onto the 3-D 2x2x2 (data, expert, model)
    # mesh — after the MoE row, whose 2-D factorization it supersedes —
    # and trains the TP + expert-MoE + ZeRO-2 transformer through ONE
    # donated spec-driven step program. The CI mesh3d-smoke gate asserts
    # its cache-hit/fallback/parity numbers on the 8-device virtual mesh.
    if DEVICE_RESIDENT and hvd.size() % 8 == 0:
        mesh3d = _row(
            "mesh3d", lambda: bench_transformer.run_mesh3d_benchmark(
                bench_transformer.parse_args(
                    ["--mesh3d", "--iters", "4"]))["mesh3d"])
    else:
        mesh3d = {"skipped": "needs a device count divisible by 8 and "
                             "the device-resident path for the 2x2x2 "
                             "(data, expert, model) mesh"}

    # Pod-scale control-plane scaling row (docs/controlplane.md): a
    # shrunken simrank curve — real coordinators over a live in-process
    # KV server, no devices — so the JSON tracks negotiation
    # rounds/sec, tree speedup over the star, and the graduated static
    # round's O(1) root reads alongside the training numbers. The full
    # published curve (worlds up to 1024) is CONTROL_r*.json.
    from horovod_tpu.controlplane import simrank as _simrank
    control_plane = _row("control_plane", lambda: _simrank.scaling_curve(
        worlds=(8, 64) if SMOKE else (8, 64, 256),
        fanout=8 if SMOKE else 32))

    print(json.dumps({
        "metric": "resnet50_img_sec_per_chip",
        "value": round(mean, 2),
        "unit": "img/sec",
        "vs_baseline": round(mean / BASELINE_IMG_SEC_PER_DEVICE, 3),
        "batch_per_chip": best_batch,
        "ci_pct": round(ci_pct, 2),
        "ci_degraded": ci_degraded,
        "samples": len(samples),
        "outliers_rejected": rejected,
        "img_sec_device_side": round(dev_mean, 2),
        "img_sec_block_timed": round(block_rate, 2),
        # full sync dispatch+readback barrier (what the pre-pipeline loop
        # paid per call)
        "dispatch_overhead_ms": round(profile["full_ms"], 2),
        "dispatch_enqueue_ms": round(profile["enqueue_ms"], 2),
        # readback at the pipelined loop's sync point (deferred: the host
        # copy was started at dispatch) vs the raw blocking round-trip
        "dispatch_readback_ms": round(profile["readback_ms"], 2),
        "dispatch_readback_sync_ms": round(profile["readback_sync_ms"], 2),
        "overlap_efficiency": round(overlap_eff, 4),
        "pipeline_inflight_depth": PIPELINE_DEPTH,
        # device-resident hot loop (docs/performance.md): True means the
        # timed loop never fetched the loss to host — readback is removed
        # from the hot loop, not merely deferred, so
        # loop_readback_wait_ms is 0 by construction and
        # loop_device_wait_ms carries the device-completion pacing wait
        "device_resident": DEVICE_RESIDENT,
        "loop_readback_wait_ms": round(
            float(np.mean(loop_waits)) * 1e3, 2) if loop_waits else None,
        "loop_device_wait_ms": round(
            float(np.mean(loop_dev_waits)) * 1e3, 2)
        if loop_dev_waits else None,
        # signature-keyed wire-program cache, steady-state eager exchange
        # (engine.WireProgramCache; >= 0.9 means one cached executable
        # per bucket shape and ~zero recompiles)
        "wire_cache_hit_rate": exchange["wire_cache_hit_rate"],
        "eager_exchange": exchange,
        # compiled hot loop (hvd.compiled_train_step): per-step dispatch
        # of the single donated XLA program — python_overhead_ms is the
        # whole per-step Python cost (< 1 ms acceptance), hit rate >= 0.9
        # means one compile per loop shape
        "compiled_step": compiled,
        "step_program_cache_hit_rate":
            compiled.get("step_program_cache_hit_rate"),
        # bucketed backward/exchange overlap (HOROVOD_EXCHANGE_BUCKETS;
        # docs/performance.md): fraction of exchange device time hidden
        # under compute in the compiled step's trace — the CI
        # overlap-smoke gate asserts >= 0.3
        "exchange_hidden_frac": compiled.get("exchange_hidden_frac"),
        # ZeRO sharding + DCN compression profile: the active default
        # stage, measured DCN wire saving, EF-convergence loss delta,
        # and the per-device stripe footprint split
        "zero_stage": zero.get("zero_stage", 0),
        "dcn_bytes_saved_frac": zero.get("dcn_bytes_saved_frac"),
        "zero_memory": zero.get("zero_memory"),
        "zero_profile": zero,
        # input pipeline (docs/data.md): exposed per-batch input wait at
        # the configured prefetch depth vs the synchronous fallback
        "data_wait_ms": pipe["data_wait_ms"],
        "data_wait_sync_ms": pipe_sync["data_wait_ms"],
        "prefetch_depth": DATA_PREFETCH,
        "input_pipeline": {"prefetch": pipe, "sync": pipe_sync},
        # Per-step phase attribution (docs/diagnostics.md): the compiled
        # path's XLA device-trace breakdown (forward/backward/exchange/
        # optimizer/guard device ms per step per lane) when available,
        # else the flight recorder's host-side view (compute/wire/
        # readback/input ms per timed iteration).
        "step_phase_breakdown": (compiled.get("step_phase_breakdown")
                                 if isinstance(compiled, dict) else None)
        or step_phase_breakdown,
        "flight_step_phase_breakdown": step_phase_breakdown,
        "flight_overhead_frac": flight_overhead_frac,
        # Step-integrity guard self-cost (docs/robustness.md): measured
        # per-step host-side guard work over the loop's wall time
        # (acceptance: < 2% on the device-resident path).
        "guard_overhead_frac": guard_overhead_frac,
        # Idle step-tracer cost over the measurement loop (the per-step
        # tick hook with tracing off; acceptance: < 1%).
        "trace_overhead_frac": (compiled.get("trace_overhead_frac")
                                if isinstance(compiled, dict)
                                and "trace_overhead_frac" in compiled
                                else _trace_attribution(loop_wall,
                                                        len(samples))),
        "mfu_pct": None if mfu is None else round(mfu, 2),
        # mfu as a fraction — the compiled hot loop's number when it ran
        # (the path the live hvd_step_mfu gauge watches), else the
        # eager/scan loop's; None when the chip peak is unknown and
        # HOROVOD_PEAK_FLOPS is unset.
        "mfu": (round(compiled["mfu_pct"] / 100.0, 4)
                if isinstance(compiled, dict)
                and isinstance(compiled.get("mfu_pct"), (int, float))
                else None if mfu is None else round(mfu / 100.0, 4)),
        "xla_counted_fu_pct": None if hfu is None else round(hfu, 2),
        "sweep": sweep,
        "transformer": transformer,
        # Expert-parallel MoE scenario: tokens/sec on the 2-D (data,
        # expert) mesh, dispatch/combine alltoall ms/step, the chunked
        # pipeline's overlap fraction (alltoall_hidden_frac), and the
        # capacity-router drop fraction — docs/performance.md
        # "Expert-parallel MoE".
        "moe": moe,
        # Composable parallelism on the 3-D (data, expert, model) mesh:
        # TP trunk + expert MoE + ZeRO-2 in one donated program, with
        # the striped-vs-unstriped parity delta and program-cache
        # numbers — docs/performance.md "Composable parallelism".
        "mesh3d": mesh3d,
        # Continuous-batching serving scenario: TTFT/per-token latency
        # percentiles, tokens/sec at 8 streams, decode program-cache hit
        # rate and fallback count — docs/serving.md.
        "serve": serve,
        # Control-plane scaling: simulated-rank negotiation throughput
        # star vs tree vs graduated, with the acceptance block
        # (tree speedup, O(1) graduated reads, bit-identity, demotion
        # on membership change) — docs/controlplane.md.
        "control_plane": control_plane,
        # Runtime-metrics snapshot (non-zero series only): comm counters,
        # engine cycle health, step telemetry — docs/observability.md.
        "metrics": hvd_metrics.compact_snapshot(),
        "failed_rows": FAILED_ROWS,
        **hvd_hardware.device_info(),
    }))
    hvd.shutdown()
    if FAILED_ROWS:
        sys.exit(f"bench.py: rows failed: {', '.join(FAILED_ROWS)}")


if __name__ == "__main__":
    main()
