#!/usr/bin/env python
"""Eager (op-at-a-time) data-plane throughput benchmark.

Round-1 VERDICT weak #3: the eager engine's host-numpy -> device -> psum ->
numpy round-trip is the path the torch/TF surfaces and the autotuner live
on, and nothing measured it. This benchmark reproduces the reference's
motivating workload — many small gradient tensors submitted op-at-a-time
(the reason its fusion buffer exists, fusion_buffer_manager.{h,cc}) — and
reports wire bytes/sec with fusion and the response cache toggled, plus the
fused-vs-unfused speedup the fusion system is supposed to buy.

Usage: python bench_eager.py   (the mesh is whatever hvd.init() sees)
       python bench_eager.py --cpu-devices 8   (virtual CPU mesh, on request)
       python bench_eager.py --multihost 2   (real CPU processes through the
launcher: per-cycle control-plane latency and MB/s with the steady-state
epoch-token bypass on vs off — the cost the reference's response-cache
bitvector sync eliminates, response_cache.cc:304-390)
Emits one JSON line:
  {"metric": "eager_allreduce_mbytes_sec", "value": N, "unit": "MB/s",
   "vs_baseline": fused_over_unfused_speedup, "configs": {...},
   "platform": P, "device_kind": K, "device_count": C}
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def run_eager_bench(num_tensors=128, elems=1024, repeats=5,
                    fusion_threshold=None, cache_capacity=None):
    """Submit ``num_tensors`` float32 tensors of ``elems`` elements on every
    rank, synchronize all, repeated ``repeats`` times after one warmup
    round. Returns aggregate wire MB/s (payload bytes x ranks / wall time).
    """
    import numpy as np

    import horovod_tpu as hvd

    if fusion_threshold is not None:
        os.environ["HOROVOD_FUSION_THRESHOLD"] = str(fusion_threshold)
    else:
        os.environ.pop("HOROVOD_FUSION_THRESHOLD", None)
    if cache_capacity is not None:
        os.environ["HOROVOD_CACHE_CAPACITY"] = str(cache_capacity)
    else:
        os.environ.pop("HOROVOD_CACHE_CAPACITY", None)
    hvd.shutdown()
    hvd.init()
    n = hvd.size()
    data = [np.random.RandomState(i).randn(elems).astype(np.float32)
            for i in range(num_tensors)]
    nbytes_round = num_tensors * elems * 4 * n

    def one_round(tag):
        handles = []
        for i, t in enumerate(data):
            handles.append(hvd.allreduce_async(
                t, average=False, name=f"eb.{tag}.{i}"))
        for h in handles:
            hvd.synchronize(h)

    one_round("warm")  # compile the wire programs outside the timing
    t0 = time.perf_counter()
    for r in range(repeats):
        one_round(f"r{r}")
    dt = time.perf_counter() - t0
    hvd.shutdown()
    return nbytes_round * repeats / dt / 1e6


def run_broadcast_bench(num_tensors=16, elems=262144, repeats=5):
    """broadcast_parameters-style workload: root fans a model's tensors out
    to every rank. Reports payload MB/s (payload = one tensor copy per
    round, the quantity a user's checkpoint-restore broadcast moves)."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init()
    data = [np.random.RandomState(i).randn(elems).astype(np.float32)
            for i in range(num_tensors)]
    nbytes_round = num_tensors * elems * 4

    def one_round(tag):
        handles = [hvd.broadcast_async(t, 0, name=f"bb.{tag}.{i}")
                   for i, t in enumerate(data)]
        for h in handles:
            hvd.synchronize(h)

    one_round("warm")
    t0 = time.perf_counter()
    for r in range(repeats):
        one_round(f"r{r}")
    dt = time.perf_counter() - t0
    hvd.shutdown()
    return nbytes_round * repeats / dt / 1e6


def _mh_worker_phase(tag, num_tensors, elems, steps):
    """One steady-state measurement phase inside a launcher worker: submit
    num_tensors small allreduces per step, synchronize all, repeat.
    Returns (cycle_latency_ms, mbytes_sec, publish_bytes)."""
    import numpy as np

    import horovod_tpu as hvd

    hvd.init()
    n = hvd.size()
    data = [np.random.RandomState(i).randn(elems).astype(np.float32)
            for i in range(num_tensors)]

    def one_step(s):
        handles = [hvd.allreduce_async(t, average=False,
                                       name=f"mh.{tag}.{i}")
                   for i, t in enumerate(data)]
        for h in handles:
            hvd.synchronize(h)

    one_step("warm")
    t0 = time.perf_counter()
    for s in range(steps):
        one_step(s)
    dt = time.perf_counter() - t0
    st = hvd.state().stats
    publish_bytes = sum(sz * cnt for sz, (cnt, _)
                        in st.histogram("gather").items())
    hvd.shutdown()
    return (dt / steps * 1e3,
            num_tensors * elems * 4 * n * steps / dt / 1e6,
            publish_bytes)


def _mh_worker(num_tensors, elems, steps):
    """Worker body: measure with the epoch-token bypass disabled, then
    enabled, and (process 0) print one JSON line."""
    import horovod_tpu as hvd

    os.environ["HOROVOD_COORDINATOR_BYPASS_DISABLE"] = "1"
    lat_off, mbs_off, pub_off = _mh_worker_phase("off", num_tensors, elems,
                                                 steps)
    os.environ.pop("HOROVOD_COORDINATOR_BYPASS_DISABLE")
    lat_on, mbs_on, pub_on = _mh_worker_phase("on", num_tensors, elems,
                                              steps)
    import jax

    from horovod_tpu.hardware import device_info
    if jax.process_index() == 0:
        print(json.dumps({
            "metric": "eager_multihost_cycle_ms",
            "value": round(lat_on, 2),
            "unit": "ms/step",
            "vs_baseline": round(lat_off / max(lat_on, 1e-9), 3),
            "configs": {
                "bypass_off": {"cycle_ms": round(lat_off, 2),
                               "mbytes_sec": round(mbs_off, 2),
                               "publish_bytes": pub_off},
                "bypass_on": {"cycle_ms": round(lat_on, 2),
                              "mbytes_sec": round(mbs_on, 2),
                              "publish_bytes": pub_on},
            },
            "num_tensors": num_tensors,
            "processes": jax.process_count(),
            **device_info(),
        }))
    del hvd


def _mh_launch(nproc, num_tensors, elems, steps):
    from horovod_tpu.run.run import launch
    env = dict(os.environ)
    # A control-plane measurement, explicitly on the CPU: a chip belongs
    # to one process, so N children must never reach for it.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = ""  # one CPU device per process
    env.setdefault("HOROVOD_PROFILER_DISABLE", "1")
    rc = launch(nproc, [sys.executable, os.path.abspath(__file__),
                        "--mh-worker", "--tensors", str(num_tensors),
                        "--elems", str(elems), "--steps", str(steps)],
                start_timeout=120, env=env)
    if rc != 0:
        sys.exit(rc)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multihost", type=int, default=0, metavar="N",
                    help="run the control-plane benchmark across N real "
                         "processes via the launcher")
    ap.add_argument("--mh-worker", action="store_true",
                    help=argparse.SUPPRESS)  # internal: launcher child
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="run on an N-device virtual CPU mesh instead of "
                         "the devices hvd.init() finds")
    ap.add_argument("--tensors", type=int, default=200)
    ap.add_argument("--elems", type=int, default=1024)
    ap.add_argument("--steps", type=int, default=10)
    args = ap.parse_args()
    if args.mh_worker:
        # the launcher parent (_mh_launch) pinned JAX_PLATFORMS=cpu
        _mh_worker(args.tensors, args.elems, args.steps)
        return
    if args.multihost:
        _mh_launch(args.multihost, args.tensors, args.elems, args.steps)
        return
    if args.cpu_devices:
        from horovod_tpu.utils.devices import force_host_device_count
        force_host_device_count(args.cpu_devices)
    configs = {
        "fused_cached": dict(fusion_threshold=64 * 1024 * 1024,
                             cache_capacity=1024),
        "fused_nocache": dict(fusion_threshold=64 * 1024 * 1024,
                              cache_capacity=0),
        "unfused_cached": dict(fusion_threshold=1, cache_capacity=1024),
        "unfused_nocache": dict(fusion_threshold=1, cache_capacity=0),
    }
    results = {}
    for name, cfg in configs.items():
        results[name] = round(run_eager_bench(**cfg), 2)
        print(f"# {name}: {results[name]} MB/s", file=sys.stderr)
    results["broadcast"] = round(run_broadcast_bench(), 2)
    print(f"# broadcast: {results['broadcast']} MB/s payload",
          file=sys.stderr)
    speedup = (results["fused_cached"] / results["unfused_nocache"]
               if results["unfused_nocache"] else 0.0)
    from horovod_tpu.hardware import device_info
    print(json.dumps({
        "metric": "eager_allreduce_mbytes_sec",
        "value": results["fused_cached"],
        "unit": "MB/s",
        "vs_baseline": round(speedup, 3),
        "configs": results,
        **device_info(),
    }))


if __name__ == "__main__":
    main()
