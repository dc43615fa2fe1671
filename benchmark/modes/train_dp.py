"""Mode ``train_dp``: the compiled data-parallel train step, driven the way a
user drives it.

``hvd.init()`` -> parameters from ``--seed`` under ``jit`` with replicated
``out_shardings`` -> ``hvd.broadcast_parameters`` ->
``hvd.DistributedOptimizer(optax.adamw)`` -> ``hvd.compiled_train_step`` with
``tfm.loss_fn`` -> batches from ``hvd.data.DistributedDataset`` (callable
seeded source, ``sharding=P("hvd")``, prefetch on) -> a loop that enqueues
step *i* and then reads back the loss of step *i-1*, as a logging trainer
does. One process drives all the cell's chips.

From the program this file takes the entry points above, the step's public
counters (``compiled_steps``, ``fallback_steps``, ``cache_hits``,
``cache_misses``), ``hvd.metrics_snapshot()``, the ``hvd_*`` named scopes and
the parameter tree's names. Everything else — traffic, clock, trace
reduction, FLOP counts, the reference and the comparison that decides
``correct`` — is the benchmark's own (``benchmark/lib``).
"""

import contextlib
import glob
import math
import os
import shutil
import statistics
import time


def model_config(cell, interpret):
    """The ``TransformerConfig`` a cell runs: the configuration file's
    published keys through its ``maps_to`` table, its ``fixed`` choices,
    and the cell file's run-time choices. No size is set here."""
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm
    conf, run, traffic = cell["config"], cell["cell"], cell["traffic"]
    kw = {field: conf[key] for field, key in conf["maps_to"].items()}
    kw.update(conf.get("fixed", {}))
    kw.setdefault("max_seq", traffic["seq_len"])
    return tfm.TransformerConfig(
        dtype=jnp.dtype(run["dtype"]), param_dtype=jnp.dtype(
            run["param_dtype"]), attention_impl=run["attention_impl"],
        flash_interpret=interpret, loss_chunk=run["loss_chunk"],
        remat=run["remat"], **kw)


def base_optimizer(opt):
    import optax
    if opt["name"] != "adamw":
        raise SystemExit(f"train_dp: optimizer {opt['name']!r} is not "
                         "known here (adamw)")
    return optax.adamw(opt["learning_rate"])


def flops_shape(cfg, traffic):
    """The sizes ``benchmark/lib/flops.py`` counts work from."""
    return {"d_model": cfg.d_model, "head_dim": cfg.head_dim,
            "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads or cfg.n_heads,
            "d_ff": cfg.d_ff, "n_layers": cfg.n_layers,
            "vocab_size": cfg.vocab_size, "seq_len": traffic["seq_len"],
            "window": cfg.attention_window}


def apply_tiny(cell, tiny):
    """``--cpu-rehearsal <preset>``: the same cell at toy sizes, for walking
    the harness on the CPU. Never a measurement. A preset size replaces the
    published key its field maps to, where the configuration has one."""
    conf = dict(cell["config"])
    sizes = dict(tiny["model"], max_seq=tiny["traffic"]["seq_len"])
    for field, value in sizes.items():
        if field in conf["maps_to"]:
            conf[conf["maps_to"][field]] = value
    traffic = dict(cell["traffic"], seq_len=tiny["traffic"]["seq_len"],
                   global_batch=tiny["traffic"]["batch_per_chip"]
                   * cell["chips"])
    run = dict(cell["cell"], loss_chunk=tiny["traffic"]["loss_chunk"])
    return dict(cell, config=conf, traffic=traffic, cell=run)


class Spans:
    """Host-clock spans around the harness's own calls into the program;
    inside the traced window each is also a ``TraceAnnotation`` so that
    the device's idle gaps can be laid against them."""

    def __init__(self):
        import jax
        self.rows, self.phase, self.annotate = [], "setup", False
        self._annotation = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name):
        note = (self._annotation(f"bench_{name}")
                if self.annotate else contextlib.nullcontext())
        t0 = time.perf_counter()
        with note:
            yield
        self.rows.append((self.phase, name, t0, time.perf_counter()))


class CompileWatch:
    """Counts jax's trace/lower/compile events; none may fall inside the
    measured window."""

    def __init__(self):
        import jax
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_, **__):
        if name.startswith("/jax/core/compile/"):
            self.count += 1


def counter_totals(snapshot):
    """``{family: total}`` of a ``hvd.metrics_snapshot()``: counters and
    gauges summed over their label series, histograms by their count."""
    out = {}
    for name, fam in snapshot.items():
        total = 0.0
        for v in fam["values"].values():
            total += v["count"] if isinstance(v, dict) else v
        out[name] = total
    return out


def step_executable(marker="hvd_forward"):
    """The compiled train step as the runtime holds it: the live
    executable whose HLO carries the program's ``hvd_forward`` scope.
    Found through jax, not through the program's internals, so a refactor
    of how the step is built cannot hide it. Returns ``(hlo_text,
    CompiledMemoryStats)`` or ``(None, None)``."""
    import jax
    best = (None, None)
    for exe in jax.devices()[0].client.live_executables():
        try:
            text = exe.hlo_modules()[0].to_string()
        except Exception:  # noqa: BLE001 - an executable without HLO
            continue
        if marker in text and (best[0] is None or len(text) > len(best[0])):
            best = (text, exe.get_compiled_memory_stats())
    return best


def replicas_identical(params, mesh):
    """True when every device's replica of every parameter is the same
    bits: elementwise max over the mesh equals elementwise min."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P
    if mesh.size == 1:
        return True
    axis = mesh.axis_names[0]

    def per_shard(p):
        same = []
        for leaf in jax.tree.leaves(p):
            bits = lax.bitcast_convert_type(leaf, jnp.uint32)
            same.append(jnp.all(lax.pmax(bits, axis)
                                == lax.pmin(bits, axis)))
        return jnp.all(jnp.stack(same))

    fn = jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=(P(),),
                               out_specs=P(), check_vma=False))
    return bool(fn(params))


def sampled_leaves(cfg):
    """Three places to hold the first update against the reference's
    gradient: the first layer's q (or fused qkv) projection — the far end
    of backprop —, a middle layer's first MLP matrix, and the LM head.
    ``(path into the tree, column slice compared)``."""
    attn = "wq" if (cfg.n_kv_heads and cfg.n_kv_heads != cfg.n_heads) \
        else "wqkv"
    return [(("layers", 0, attn), None),
            (("layers", cfg.n_layers // 2, "w1"), 2048),
            (("lm_head",), 512)]


def _cut(leaf, cols):
    return leaf if cols is None else leaf[..., :cols]


def reference_check(cfg, cell, seed, source, loss0, p1, tol):
    """Checks (a) and (b): the step-0 loss against the plain reference on
    the first global batch, and the sign of the first update on the
    sampled leaves against the reference's global-batch gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.lib import reference
    from horovod_tpu.models import transformer as tfm
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("ref",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("ref"))
    p0 = jax.jit(lambda k: tfm.init_params(k, cfg), out_shardings=rep)(
        jax.random.PRNGKey(seed))
    arch = {"positional": cfg.positional,
            "attention_window": cfg.attention_window}
    leaves = sampled_leaves(cfg)
    paths = [p for p, _ in leaves]
    gb, seq = cell["traffic"]["global_batch"], cell["traffic"]["seq_len"]
    tokens, targets = source(range(gb))
    per_dev = max(1, min(gb // len(devices), 4096 // seq))
    micro = per_dev * len(devices)
    fn = jax.jit(lambda p, a, b: reference.loss_and_grads(
        p, a, b, arch, paths))
    loss, grads = 0.0, None
    for i in range(0, gb, micro):
        a = jax.device_put(tokens[i:i + micro], split)
        b = jax.device_put(targets[i:i + micro], split)
        l_mb, g_mb = fn(p0, a, b)
        w = micro / gb
        loss += w * float(l_mb)
        g_mb = [w * _cut(g, c) for g, (_, c) in zip(g_mb, leaves)]
        grads = g_mb if grads is None else [x + y for x, y
                                            in zip(grads, g_mb)]
    out = {"loss0": loss0, "reference_loss0": loss,
           "loss0_abs_err": abs(loss0 - loss), "sign_agreement": {}}
    ok = out["loss0_abs_err"] <= tol["loss0_abs"]
    for (path, cols), g, after in zip(leaves, grads, p1):
        g = np.asarray(g, np.float32)
        before = np.asarray(_cut(reference.get_leaf(p0, path), cols))
        delta = after - before
        big = np.abs(g) > np.median(np.abs(g))
        agree = float(np.mean(np.sign(delta[big]) == -np.sign(g[big])))
        out["sign_agreement"]["/".join(map(str, path))] = agree
        ok = ok and agree >= tol["sign_agreement_min"]
    return ok, out


def run(cell, args, t_start):
    """One run of one cell. Returns the result dict ``run.py`` prints."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.lib import cells, data, flops, reference, trace_reduce
    from horovod_tpu.models import transformer as tfm

    # the benchmark's own small programs (slices, the reference) are
    # cached too, whatever their compile time
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spans, watch = Spans(), CompileWatch()
    rehearsal = bool(args.cpu_rehearsal)
    if rehearsal:
        cell = apply_tiny(cell, cells.load_json(args.cpu_rehearsal))
    # libtpu's own start takes 5.6-13 s and varies by seconds from run to
    # run on one machine; nothing in the repository can change it, so it is
    # timed apart (runtime_start_s) and left out of setup_s. The compile
    # cache is placed by the environment (run.py), so opening the backend
    # before hvd.init() changes nothing hvd.init() does.
    with spans.span("runtime_start"):
        devices = jax.devices()
    with spans.span("hvd_init"):
        hvd.init()
    dev0 = devices[0]
    if dev0.platform != ("cpu" if rehearsal else "tpu"):
        raise SystemExit(
            f"benchmark: jax.devices()[0].platform is {dev0.platform!r}: "
            "a cell is measured on a TPU and nowhere else "
            "(--cpu-rehearsal <preset> walks the harness on the CPU)")
    if len(devices) != cell["chips"] or hvd.size() != cell["chips"]:
        raise SystemExit(
            f"benchmark: cell {cell['name']} asks for {cell['chips']} "
            f"chip(s), jax shows {len(devices)}, hvd.size() is "
            f"{hvd.size()}")
    n, mesh = hvd.size(), hvd.mesh()
    replicated = NamedSharding(mesh, P())
    cfg = model_config(cell, interpret=rehearsal)
    run_cfg, traffic = cell["cell"], cell["traffic"]
    gb, seq = traffic["global_batch"], traffic["seq_len"]
    if gb % n:
        raise SystemExit(f"benchmark: global batch {gb} does not divide "
                         f"over {n} chips")
    source = data.make_source(traffic, args.seed, cfg.vocab_size)

    # ------------------------------------------------------------ set-up
    with spans.span("params_init"):
        params = jax.jit(lambda k: tfm.init_params(k, cfg),
                         out_shardings=replicated)(
                             jax.random.PRNGKey(args.seed))
        jax.block_until_ready(params)
    with spans.span("params_broadcast"):
        params = jax.device_put(hvd.broadcast_parameters(params),
                                replicated)
        jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    axes = tfm.ShardAxes(dp=None, sp=None, tp=None)

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, cfg, axes)

    tx = hvd.DistributedOptimizer(base_optimizer(run_cfg["optimizer"]))
    step = hvd.compiled_train_step(loss_fn, tx, name="benchmark")
    with spans.span("opt_init"):
        opt_state = jax.jit(step.init, out_shardings=replicated)(params)
        jax.block_until_ready(opt_state)
    ds = hvd.data.DistributedDataset(
        source, batch_size=gb, num_samples=gb * 4096, seed=args.seed,
        shuffle=False, sharding=NamedSharding(mesh, P(mesh.axis_names[0])))
    batches = iter(ds)
    leaves = sampled_leaves(cfg)
    losses = []

    with spans.span("first_batch"):
        batch = next(batches)
    first_ok = bool(np.array_equal(np.asarray(batch[0]),
                                   source(range(gb))[0]))
    with spans.span("first_step"):
        params, opt_state, loss = step(params, opt_state, *batch)
        losses.append(float(loss))
    with spans.span("snapshot_leaves"):
        p1 = [np.asarray(_cut(reference.get_leaf(params, path), cols))
              for path, cols in leaves]
    with spans.span("warmup_steps"):
        for _ in range(run_cfg["warmup_steps"] - 1):
            batch = next(batches)
            params, opt_state, loss = step(params, opt_state, *batch)
            losses.append(float(loss))

    # ------------------------------------------------------------ window
    def one_step(prev):
        nonlocal params, opt_state
        with spans.span("next_batch"):
            batch = next(batches)
        with spans.span("dispatch"):
            params, opt_state, loss = step(params, opt_state, *batch)
        if prev is not None:
            with spans.span("loss_readback"):
                losses.append(float(prev))
        return loss

    spans.phase = "window"
    counters0 = counter_totals(hvd.metrics_snapshot())
    compiles0, prev, steps = watch.count, None, 0
    t_w0 = time.perf_counter()
    while True:
        prev = one_step(prev)
        steps += 1
        if time.perf_counter() - t_w0 >= args.seconds:
            break
    with spans.span("loss_readback"):
        losses.append(float(prev))
        jax.block_until_ready((params, opt_state))
    t_w1 = time.perf_counter()
    compiles_in_window = watch.count - compiles0
    counters1 = counter_totals(hvd.metrics_snapshot())
    window_losses = losses[-steps:]
    mem = [d.memory_stats() or {} for d in jax.local_devices()]

    # ------------------------------------------------------ traced window
    trace, trace_err = None, None
    if args.trace:
        spans.phase, spans.annotate = "traced", True
        tdir = os.path.join(cells.ROOT, ".bench_out", f"trace-{cell['name']}")
        shutil.rmtree(tdir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(tdir, profiler_options=opts)
        prev = None
        for i in range(run_cfg["traced_steps"]):
            with jax.profiler.StepTraceAnnotation("bench_step", step_num=i):
                prev = one_step(prev)
        with spans.span("loss_readback"):
            losses.append(float(prev))
            jax.block_until_ready((params, opt_state))
        jax.profiler.stop_trace()
        spans.annotate = False
    spans.phase = "post"
    hlo, memstats = step_executable()
    if args.trace:
        try:
            path = glob.glob(os.path.join(
                tdir, "plugins", "profile", "*", "*.xplane.pb"))[0]
            trace = trace_reduce.reduce_trace(
                trace_reduce.read_xplane(path), trace_reduce.scope_map(hlo))
            if args.dump_dir:
                os.makedirs(args.dump_dir, exist_ok=True)
                shutil.copy(path, os.path.join(
                    args.dump_dir, f"{cell['name']}.xplane.pb"))
        except Exception as e:  # noqa: BLE001 - a run without a trace
            trace_err = repr(e)
        shutil.rmtree(tdir, ignore_errors=True)
    if args.dump_dir and hlo:
        os.makedirs(args.dump_dir, exist_ok=True)
        with open(os.path.join(args.dump_dir, f"{cell['name']}.hlo.txt"),
                  "w", encoding="utf-8") as f:
            f.write(hlo)

    # ------------------------------------------------------------ checks
    checks = {"first_batch_is_sample_0": first_ok}
    counts = {"compiled_steps": step.compiled_steps,
              "fallback_steps": step.fallback_steps,
              "cache_misses": step.cache_misses,
              "cache_hits": step.cache_hits, "donates": step.donates,
              "compiles_in_window": compiles_in_window}
    total_steps = len(losses)
    checks["counters"] = (
        step.compiled_steps == total_steps and step.fallback_steps == 0
        and step.cache_misses == 1 and compiles_in_window == 0)
    kernels = (hlo or "").count('custom_call_target="tpu_custom_call"')
    need = cfg.n_layers * run_cfg.get("min_flash_calls_per_layer", 2)
    checks["flash_kernels_compiled"] = rehearsal or (
        kernels >= need and cfg.flash_interpret is False)
    checks["loss_finite"] = bool(np.all(np.isfinite(losses)))
    band = run_cfg["loss_band"]
    lo, hi = band["steps"]
    band_mean = (statistics.fmean(losses[lo:hi + 1])
                 if total_steps > hi else None)
    checks["loss_band"] = rehearsal or (
        band_mean is not None and band["low"] <= band_mean <= band["high"])
    with spans.span("replica_check"):
        checks["replicas_identical"] = replicas_identical(params, mesh)
    ds.close()
    del params, opt_state, batch, prev, loss, step, tx
    jax.clear_caches()
    with spans.span("reference_check"):
        try:
            checks["reference"], ref = reference_check(
                cfg, cell, args.seed, source, losses[0], p1,
                run_cfg["tolerances"])
        except Exception as e:  # noqa: BLE001 - a reference that cannot run
            checks["reference"], ref = False, {"error": repr(e)[:2000]}
    with spans.span("hvd_shutdown"):
        hvd.shutdown()

    # ------------------------------------------------------------ result
    reserved = max(m.get("peak_bytes_in_use", 0)
                   + m.get("peak_bytes_reserved", 0) for m in mem)
    program = (memstats.argument_size_in_bytes + memstats.temp_size_in_bytes
               + memstats.output_size_in_bytes
               - memstats.alias_size_in_bytes) if memstats else 0
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": reserved}
    shape = flops_shape(cfg, traffic)
    ctx = {"spans": spans.rows, "steps": steps, "trace": trace,
           "counters": {k: counters1[k] - counters0.get(k, 0.0)
                        for k in counters1},
           "shape": shape, "seqs_per_chip": gb // n, "notes": {},
           "peaks": None if rehearsal else flops.peaks_for(dev0.device_kind)}
    if trace and trace["devices"]:
        device["busy_s"] = trace_reduce.mean_over_devices(
            trace, lambda _, d: d["busy_ns"] * 1e-9)
        device["window_s"] = trace_reduce.mean_over_devices(
            trace, lambda _, d: d["window_ns"] * 1e-9)
    runtime_start = sum(e - s for _, name, s, e in spans.rows
                        if name == "runtime_start")
    failed = (sum(1 for x in window_losses if not math.isfinite(x))
              + counts["fallback_steps"])
    return {
        "correct": all(checks.values()), "attempted": steps,
        "failed": failed, "device": device, "ctx": ctx,
        "end_to_end": {
            "tokens_per_s_per_chip": steps * gb * seq / (t_w1 - t_w0) / n,
            "peak_hbm_gib": reserved / 2.0 ** 30,
            "setup_s": t_w0 - t_start - runtime_start},
        "breakdown": trace_reduce.breakdown(trace) if trace else None,
        "checks": checks,
        "detail": {
            "counts": counts, "reference": ref, "n_params": n_params,
            "loss_band_mean": band_mean, "losses": losses,
            "tpu_custom_calls": kernels, "trace_error": trace_err,
            "window_s": t_w1 - t_w0, "memory_stats": mem,
            "step_memory_analysis_bytes": program,
            "required_flops_per_token": flops.required_flops_per_token(
                shape),
            "attention_flops_share": flops.attention_share(shape),
            "setup_spans_s": {name: e - s for phase, name, s, e
                              in spans.rows if phase == "setup"},
            "post_spans_s": {name: e - s for phase, name, s, e
                             in spans.rows if phase == "post"},
            "total_s": time.perf_counter() - t_start},
    }
