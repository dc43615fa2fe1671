#!/usr/bin/env python
"""Weak-scaling harness: tracks the reference's headline metric.

The reference's published numbers are *scaling efficiencies* — 90% for
Inception V3/ResNet-101 and 68% for VGG-16 at 512 GPUs (reference:
README.rst:65-72, docs/benchmarks.rst:8-13), measured by growing the job
with a fixed per-device batch (weak scaling) and dividing achieved
throughput by perfect-linear throughput. BASELINE.md's north star is >= 90%
on a v5p-256. This harness produces that number continuously: it runs the
same shard_map + DistributedOptimizer train step on 1, 2, 4, ... N devices
at a fixed per-chip batch and reports

    efficiency(n) = (imgs_per_sec(n) / n) / imgs_per_sec(1) * 100

On real TPU slices the number is meaningful against the >= 90% target. On
the virtual-CPU test mesh all "devices" share the host's cores, so absolute
efficiency is compute-bound noise — but the harness still tracks framework
regressions (a collective suddenly serializing shows up as a cliff), which
is why tests run it at tiny sizes.

Usage:  python bench_scaling.py                  # every chip of the host
        python bench_scaling.py --cpu-devices 8  # virtual CPU mesh, on request
Emits one JSON line:
  {"metric": "weak_scaling_efficiency", "value": E, "unit": "%",
   "vs_baseline": E/90, "per_n": {...}, "devices": N,
   "platform": P, "device_kind": K, "device_count": C}
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def run_weak_scaling(batch_per_chip=64, hidden=1024, depth=4, steps=8,
                     warmup=2, max_devices=None, repeats=1):
    """Returns {n: imgs_per_sec_total} for n = 1, 2, 4, ... and the
    efficiency dict. Small dense model by default: the harness measures the
    framework's data plane (gradient allreduce scaling), not conv kernels.

    ``repeats``: measurement passes per device count; the MEDIAN is kept
    (one descheduled pass on a shared host would otherwise poison the
    1-device baseline every other efficiency divides by).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    total = max_devices or len(jax.devices())
    sizes = []
    n = 1
    while n <= total:
        sizes.append(n)
        n *= 2

    throughput = {}
    for n in sizes:
        hvd.shutdown()
        hvd.init(num_ranks=n)
        mesh = hvd.mesh()
        model_dims = [hidden] * depth
        rng = np.random.RandomState(0)
        params = {}
        prev = 784
        for i, h in enumerate(model_dims + [10]):
            params[f"w{i}"] = jnp.asarray(
                rng.randn(prev, h).astype(np.float32) * 0.05)
            params[f"b{i}"] = jnp.zeros((h,), jnp.float32)
            prev = h
        tx = hvd.DistributedOptimizer(optax.sgd(0.01))
        opt_state = tx.init(params)

        def per_shard(params, opt_state, xb, yb):
            def loss_fn(p):
                x = xb
                for i in range(len(model_dims) + 1):
                    x = x @ p[f"w{i}"] + p[f"b{i}"]
                    if i < len(model_dims):
                        x = jax.nn.relu(x)
                return optax.softmax_cross_entropy_with_integer_labels(
                    x, yb).mean()

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = jax.jit(jax.shard_map(
            per_shard, mesh=mesh, in_specs=(P(), P(), P("hvd"), P("hvd")),
            out_specs=(P(), P(), P()), check_vma=False),
            donate_argnums=(0, 1))

        batch = batch_per_chip * n
        X = jax.device_put(
            jnp.asarray(rng.randn(batch, 784).astype(np.float32)),
            NamedSharding(mesh, P("hvd")))
        Y = jax.device_put(
            jnp.asarray(rng.randint(0, 10, (batch,))),
            NamedSharding(mesh, P("hvd")))
        for _ in range(warmup):
            params, opt_state, loss = step(params, opt_state, X, Y)
            float(np.asarray(loss))
        samples = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            for _ in range(steps):
                params, opt_state, loss = step(params, opt_state, X, Y)
            float(np.asarray(loss))
            samples.append(batch * steps / (time.perf_counter() - t0))
        throughput[n] = float(np.median(samples))
        hvd.shutdown()

    base = throughput[sizes[0]]
    efficiency = {n: (throughput[n] / n) / base * 100.0 for n in sizes}
    return throughput, efficiency


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="run on an N-device virtual CPU mesh instead of "
                         "the chips (framework-regression smoke only)")
    args = ap.parse_args(argv)
    if args.cpu_devices:
        from horovod_tpu.utils.devices import force_host_device_count
        force_host_device_count(args.cpu_devices)
    import jax

    from horovod_tpu.hardware import device_info
    if len(jax.devices()) < 2:
        # one device has no scaling curve, and switching to the CPU
        # unasked would print a CPU number where a chip's was expected
        sys.exit(f"bench_scaling.py: found {device_info()}; a scaling "
                 "curve needs >= 2 devices (--cpu-devices N runs the "
                 "virtual CPU mesh)")
    env_int = lambda k, d: int(os.environ.get(k, d))
    throughput, efficiency = run_weak_scaling(
        batch_per_chip=env_int("HOROVOD_SCALING_BATCH", 64),
        hidden=env_int("HOROVOD_SCALING_HIDDEN", 1024),
        depth=env_int("HOROVOD_SCALING_DEPTH", 4),
        steps=env_int("HOROVOD_SCALING_STEPS", 8),
        warmup=env_int("HOROVOD_SCALING_WARMUP", 2),
        max_devices=args.cpu_devices or None,
        repeats=env_int("HOROVOD_SCALING_REPEATS", 1))
    top = max(efficiency)
    for n in sorted(throughput):
        print(f"# n={n}: {throughput[n]:.0f} img/s total, "
              f"efficiency {efficiency[n]:.1f}%", file=sys.stderr)
    print(json.dumps({
        "metric": "weak_scaling_efficiency",
        "value": round(efficiency[top], 2),
        "unit": "%",
        "vs_baseline": round(efficiency[top] / 90.0, 3),
        "per_n": {str(n): round(efficiency[n], 2) for n in efficiency},
        "devices": top,
        **device_info(),
    }))


if __name__ == "__main__":
    main()
