#!/usr/bin/env python
"""Does the system still start on the chip? The quickest end-to-end proof.

Trains the flagship TransformerLM (bench_transformer.py's defaults: d_model
2048, 8 layers, 16 q / 4 kv heads, d_ff 8192, vocab 32768, seq 4096,
per-chip batch 4, bf16, RoPE, flash attention, chunked cross entropy, adamw)
for a few steps through the entry points a user calls — ``hvd.init`` ->
``hvd.broadcast_parameters`` -> ``hvd.DistributedOptimizer`` ->
``hvd.compiled_train_step`` -> ``step.init`` -> steps -> ``hvd.shutdown`` —
in ONE process on every chip ``jax.devices()`` shows, with weights and data
made from a seed, and checks what came out:

- the Pallas flash kernels, compiled (never interpreted), agree with dense
  attention on a small input, forward and backward;
- ``psum(axis_index)`` over the ``hvd`` axis equals n(n-1)/2;
- the loss is finite at every step and lower at the last than the first;
- every step ran the one compiled, buffer-donating program: no fallback
  step, exactly one step-program cache miss, donation on and taking effect;
- the parameters live on every device of ``hvd.mesh()``;
- per-device peak memory is reported, and even across devices (10 %).

Any failed check, and any exception, is a non-zero exit. It refuses to run
(non-zero, no result line) unless ``jax.devices()[0].platform == "tpu"``;
``--cpu-rehearsal N`` is the explicit way to walk the same path on an
N-device virtual CPU mesh at a toy width with interpreted kernels, and says
so in its output. On success the last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

The step time it prints is a smoke observation, not a benchmark.
"""

import argparse
import importlib.metadata
import json
import os
import sys
import time


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--steps", type=int, default=6,
                    help="train steps, the first of which compiles "
                         "(default 6; at least 5)")
    ap.add_argument("--layers", type=int, default=8,
                    help="transformer depth (default 8, the flagship's; "
                         "the width is never cut on a chip)")
    ap.add_argument("--cpu-rehearsal", type=int, default=0, metavar="N",
                    help="NOT a chip run: rehearse on an N-device virtual "
                         "CPU mesh at a toy width, kernels interpreted")
    args = ap.parse_args(argv)
    if args.steps < 5:
        ap.error("--steps must be at least 5")
    return args


def main(argv=None):
    args = parse_args(argv)
    rehearsal = bool(args.cpu_rehearsal)
    t_start = time.perf_counter()
    if rehearsal:
        from horovod_tpu.utils.devices import force_host_device_count
        force_host_device_count(args.cpu_rehearsal)

    import jax
    import jax.numpy as jnp
    import jaxlib
    import numpy as np
    import optax
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import native
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.ops.flash_attention import flash_attention
    from horovod_tpu.parallel.ring_attention import dense_attention
    from horovod_tpu.runtime import compile_cache_dir

    # hvd.init() comes first, as in any user program: it places the
    # compile cache and (under the launcher) joins the job, both of which
    # must happen before the first backend is opened.
    hvd.init()
    dev0 = jax.devices()[0]
    if rehearsal:
        print("platform=cpu REHEARSAL (toy width, interpreted kernels; "
              "says nothing about the chip)")
    elif dev0.platform != "tpu":
        print(f"chip_smoke: REFUSED: jax.devices()[0].platform is "
              f"{dev0.platform!r}, not 'tpu' — this script proves the "
              "chip path and never runs on anything else "
              "(--cpu-rehearsal N rehearses on the CPU)", file=sys.stderr)
        hvd.shutdown()
        return 2
    n = hvd.size()
    mesh = hvd.mesh()
    replicated = NamedSharding(mesh, P())
    cache_dir = compile_cache_dir()

    def cache_entries():
        return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0

    cache_at_start = cache_entries()
    print(f"platform={dev0.platform} device_kind={dev0.device_kind!r} "
          f"device_count={len(jax.devices())} hvd.size={n} "
          f"processes={jax.process_count()}")
    print(f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
          f"libtpu={importlib.metadata.version('libtpu')} "
          f"python={sys.version.split()[0]}")
    print("runtime: " + " | ".join(
        dev0.client.platform_version.strip().splitlines()))
    plane = ("native " + native._LIB_PATH if native.available()
             else "pure-Python (no native library)")
    placed = (", placed by JAX_COMPILATION_CACHE_DIR"
              if os.environ.get("JAX_COMPILATION_CACHE_DIR") else "")
    print(f"control plane: {plane}")
    print(f"compile cache: {cache_dir} ({cache_at_start} entries at "
          f"start{placed})")
    init_s = time.perf_counter() - t_start

    if rehearsal:
        cfg = tfm.TransformerConfig(
            vocab_size=512, d_model=128, n_heads=4, n_kv_heads=1,
            n_layers=min(args.layers, 2), d_ff=512, max_seq=256,
            dtype=jnp.bfloat16, positional="rope", attention_impl="flash",
            flash_interpret=True, loss_chunk=128)
        batch_per_chip = 2
    else:
        cfg = tfm.TransformerConfig(
            vocab_size=32768, d_model=2048, n_heads=16, n_kv_heads=4,
            n_layers=args.layers, d_ff=8192, max_seq=4096,
            dtype=jnp.bfloat16, positional="rope", attention_impl="flash",
            flash_interpret=False, loss_chunk=512)
        batch_per_chip = 4
    failures = []

    def check(ok, what):
        print(f"  [{'ok' if ok else 'FAIL'}] {what}")
        if not ok:
            failures.append(what)

    # ---------------------------------------------------------- collective
    total = jax.jit(jax.shard_map(
        lambda: lax.psum(lax.axis_index("hvd"), "hvd"), mesh=mesh,
        in_specs=(), out_specs=P()))()
    check(int(total) == n * (n - 1) // 2,
          f"shard_map psum(axis_index) over 'hvd' = {int(total)}, "
          f"expected n(n-1)/2 = {n * (n - 1) // 2}")

    # ------------------------------------------------------------- kernels
    # The three static kernels (forward, dQ, dK/dV) at the flagship's tile
    # — block 512 x head_dim 128, GQA group 4, bf16 — on an input small
    # enough for dense attention to referee: 2 x 2 blocks.
    t0 = time.perf_counter()
    kd = cfg.head_dim
    ks = 2 * min(512, cfg.max_seq // 2)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (1, ks, 8, kd), jnp.bfloat16)
    k = jax.random.normal(kk, (1, ks, 2, kd), jnp.bfloat16)
    v = jax.random.normal(kv, (1, ks, 2, kd), jnp.bfloat16)

    def flash_loss(q, k, v):
        out = flash_attention(q, k, v, True, ks // 2, cfg.flash_interpret)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    def dense_loss(q, k, v):
        out = dense_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(jnp.float32) ** 2), out

    flash_vg = jax.jit(jax.value_and_grad(flash_loss, argnums=(0, 1, 2),
                                          has_aux=True))
    (_, out_f), grads_f = flash_vg(q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    (_, out_d), grads_d = jax.jit(jax.value_and_grad(
        dense_loss, argnums=(0, 1, 2), has_aux=True))(*f32)

    def rel_err(a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-6))

    # bf16 inputs and outputs against an f32 reference: 2^-8 relative
    # rounding on each, a few of them accumulated
    errs = [rel_err(out_f, out_d)] + [rel_err(a, b)
                                      for a, b in zip(grads_f, grads_d)]
    check(all(np.isfinite(errs)) and max(errs) < 3e-2,
          "flash kernels vs dense attention, max relative error "
          f"out/dq/dk/dv = {'/'.join(f'{e:.1e}' for e in errs)} (< 3e-2)")
    if not rehearsal:
        mosaic_calls = flash_vg.lower(q, k, v).as_text().count(
            "tpu_custom_call")
        check(mosaic_calls == 3 and cfg.flash_interpret is False,
              f"flash kernels compiled through Mosaic ({mosaic_calls} "
              "tpu_custom_call in the lowered fwd+bwd, expected 3), "
              f"flash_interpret={cfg.flash_interpret}")
    kernels_s = time.perf_counter() - t0

    # -------------------------------------------------------------- set-up
    # Weights from a seed, born replicated on every device of the mesh
    # (nothing lands on device 0 only), then through the broadcast a real
    # job starts with.
    t0 = time.perf_counter()
    params = jax.jit(lambda key: tfm.init_params(key, cfg),
                     out_shardings=replicated)(jax.random.PRNGKey(0))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    params = jax.device_put(hvd.broadcast_parameters(params), replicated)
    params_s = time.perf_counter() - t0

    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, cfg, axes)

    tx = hvd.DistributedOptimizer(optax.adamw(3e-4))
    step = hvd.compiled_train_step(loss_fn, tx, name="chip_smoke")
    opt_state = jax.jit(step.init, out_shardings=replicated)(params)

    batch = batch_per_chip * n
    rng = np.random.default_rng(1)
    tokens = rng.integers(0, cfg.vocab_size, (batch, cfg.max_seq),
                          dtype=np.int32)
    sharded = NamedSharding(mesh, P("hvd"))
    targets = jax.device_put(np.roll(tokens, -1, axis=1), sharded)
    tokens = jax.device_put(tokens, sharded)
    print(f"model: {n_params / 1e6:.1f}M params, d_model {cfg.d_model}, "
          f"{cfg.n_layers} layers, {cfg.n_heads} q / {cfg.n_kv_heads} kv "
          f"heads (head_dim {cfg.head_dim}), d_ff {cfg.d_ff}, vocab "
          f"{cfg.vocab_size}, seq {cfg.max_seq}, batch {batch_per_chip}/chip "
          f"= {batch} global, {jnp.dtype(cfg.dtype).name}, "
          f"attention={cfg.attention_impl}, loss_chunk {cfg.loss_chunk}")

    # --------------------------------------------------------------- steps
    first_in = jax.tree.leaves(params)[0]
    losses, step_s = [], []
    for i in range(args.steps):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready((params, opt_state, loss))
        step_s.append(time.perf_counter() - t0)
        losses.append(float(loss))
        print(f"step {i}: loss {losses[-1]:.4f}  {step_s[-1]:.3f} s"
              f"{'  (trace + compile + run)' if i == 0 else ''}")
    steady = step_s[1:]

    print("checks:")
    check(all(np.isfinite(losses)), "loss finite at every step")
    check(losses[-1] < losses[0],
          f"loss fell: {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(step.fallback_steps == 0 and step.compiled_steps == args.steps,
          f"compiled_steps={step.compiled_steps} of {args.steps}, "
          f"fallback_steps={step.fallback_steps}")
    check(step.cache_misses == 1 and step.cache_hits == args.steps - 1,
          f"step-program cache: {step.cache_misses} miss, "
          f"{step.cache_hits} hits (expected 1 and {args.steps - 1})")
    if rehearsal:
        # the donation policy is "accelerators only"; on the CPU it is off
        print(f"  [--] donation resolved {step.donates} (CPU policy)")
    else:
        check(step.donates is True and first_in.is_deleted(),
              f"donation resolved on={step.donates}, the first step's "
              f"input buffer was consumed={first_in.is_deleted()}")
    on_all = all(leaf.sharding.device_set == set(mesh.devices.flat)
                 for leaf in jax.tree.leaves(params))
    check(on_all, f"every parameter leaf lives on all {n} mesh devices")
    if not rehearsal:  # the CPU backend keeps no memory statistics
        stats = [d.memory_stats() for d in jax.local_devices()]
        peaks = [st["peak_bytes_in_use"] for st in stats]
        spread = (max(peaks) - min(peaks)) / max(peaks)
        check(n == 1 or spread <= 0.10,
              "per-device memory_stats() peak_bytes_in_use GiB = "
              f"{[round(p / 2**30, 2) for p in peaks]} of bytes_limit "
              f"{stats[0]['bytes_limit'] / 2**30:.2f} "
              f"(spread {spread:.1%}, limit 10%)")

    cache_at_end = cache_entries()
    print(f"set-up seconds: init {init_s:.1f}, kernel check {kernels_s:.1f}, "
          f"params+broadcast {params_s:.1f}, first step (trace + compile + "
          f"run) {step_s[0]:.1f}; compile cache {cache_at_start} -> "
          f"{cache_at_end} entries ({cache_at_end - cache_at_start} "
          "compiled and written by this run, the rest read back)")
    print(f"steady step time: median {np.median(steady):.3f} s, min "
          f"{min(steady):.3f} s over {len(steady)} steps — smoke "
          "observation, not a benchmark")
    print(f"total wall {time.perf_counter() - t_start:.1f} s")
    hvd.shutdown()
    if failures:
        print("chip_smoke: FAILED:\n  " + "\n  ".join(failures),
              file=sys.stderr)
        return 1
    sys.stdout.flush()
    result = {"ok": True, "device": {"platform": dev0.platform,
                                     "kind": dev0.device_kind,
                                     "count": len(jax.devices())}}
    if rehearsal:
        result["rehearsal"] = True
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
