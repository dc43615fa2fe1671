"""Mode ``train_hybrid``: the compiled train step of a model that mixes
Mamba-2 layers with attention layers (``granite-4.0-h-micro``), driven the
way a user drives it — the path of ``train_dp`` and ``train_share``:

``hvd.init()`` -> ``tfm.init_params`` from ``--seed`` ->
``hvd.broadcast_parameters`` -> ``hvd.DistributedOptimizer(optax.adamw)`` ->
``hvd.compiled_train_step(loss_fn, has_aux=True)`` with
``tfm.loss_and_stats`` -> batches from ``hvd.data.DistributedDataset`` -> a
loop that enqueues step *i* and then reads back the loss and the layers'
final-state statistic of step *i-1*.

What differs from ``train_share`` (not edited; its optimizer is taken as
it is): the configuration file's published keys (``layer_types``, the
``mamba_*`` sizes, the four multipliers, ``tie_word_embeddings``,
``position_embedding_type``) become a per-layer ``TransformerConfig`` whose
layers name their mixer; the reference is ``reference_granite`` (the
sequential recurrence); the step's aux carries ``ssm_state_rms`` out, which
a sixth check holds to the reference's own final states; and work is
counted per layer kind by ``benchmark/lib/work_hybrid.py``, registered
here.

From the program this file takes what ``train_dp`` takes, plus
``tfm.LayerSpec``, the step's aux and ``hvd.metrics.record_ssm_state``.
"""

import glob
import math
import os
import re
import shutil
import statistics
import time

from benchmark.lib import layer_metrics, work_hybrid
from benchmark.modes.train_dp import (CompileWatch, Spans, counter_totals,
                                      replicas_identical, step_executable)
from benchmark.modes.train_share import base_optimizer  # noqa: F401

work_hybrid.register(layer_metrics)

#: the loss carries the layers' state statistic out of the step
LOSS_HAS_AUX = True


def model_config(cell, interpret):
    """The ``TransformerConfig`` a cell runs, from the configuration
    file's published keys and the cell file's run-time choices. No size is
    set here."""
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm
    conf, run, traffic = cell["config"], cell["cell"], cell["traffic"]
    if conf["position_embedding_type"] != "nope" \
            or conf["mamba_n_groups"] != 1 or conf["num_local_experts"]:
        raise SystemExit("train_hybrid: a configuration with positions, "
                         "several SSM groups or routed experts is not "
                         "known here")
    n, h = conf["num_hidden_layers"], conf["num_attention_heads"]
    layers = tuple(
        tfm.LayerSpec(n_heads=h, mixer="mamba2" if kind == "mamba"
                      else "attention")
        for kind in conf["layer_types"][:n])
    return tfm.TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=h, n_kv_heads=conf["num_key_value_heads"],
        head_size=conf["hidden_size"] // h, n_layers=n,
        d_ff=conf["shared_intermediate_size"], max_seq=traffic["seq_len"],
        positional="rope", layers=layers, mlp_gated=True,
        attention_scale=conf["attention_multiplier"],
        embedding_multiplier=float(conf["embedding_multiplier"]),
        residual_multiplier=conf["residual_multiplier"],
        logits_scaling=float(conf["logits_scaling"]),
        tie_embeddings=conf["tie_word_embeddings"],
        norm_eps=conf["rms_norm_eps"], ssm_heads=conf["mamba_n_heads"],
        ssm_head_dim=conf["mamba_d_head"], ssm_state=conf["mamba_d_state"],
        ssm_conv=conf["mamba_d_conv"], ssm_chunk=conf["mamba_chunk_size"],
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        attention_impl=run["attention_impl"], flash_interpret=interpret,
        loss_chunk=run["loss_chunk"], remat=run["remat"])


def reference_arch(cell):
    """What ``reference_granite`` needs beside the parameters, from the
    configuration file alone."""
    conf = cell["config"]
    return {key: conf[key] for key in (
        "attention_multiplier", "embedding_multiplier",
        "residual_multiplier", "logits_scaling", "rms_norm_eps")} | {
        "mamba": {"n_heads": conf["mamba_n_heads"],
                  "d_head": conf["mamba_d_head"],
                  "d_state": conf["mamba_d_state"]}}


def work_shape(cell):
    """The sizes ``work_hybrid`` counts from, from the configuration file
    alone."""
    conf = cell["config"]
    h = conf["num_attention_heads"]
    return {
        "d_model": conf["hidden_size"], "vocab_size": conf["vocab_size"],
        "seq_len": cell["traffic"]["seq_len"],
        "d_ff": conf["shared_intermediate_size"], "mlp_matrices": 3,
        "head_dim": conf["hidden_size"] // h, "n_heads": h,
        "n_kv_heads": conf["num_key_value_heads"],
        "layers": conf["layer_types"][:conf["num_hidden_layers"]],
        "mamba": {"n_heads": conf["mamba_n_heads"],
                  "d_head": conf["mamba_d_head"],
                  "d_state": conf["mamba_d_state"],
                  "chunk": conf["mamba_chunk_size"]}}


def apply_tiny(cell, tiny):
    """``--cpu-rehearsal <preset>``: the same cell at toy sizes, for
    walking the harness on the CPU. Never a measurement. The preset's
    ``config`` block replaces published keys of the same name, its
    ``cell`` block run-time choices (float32 activations, so that the
    reference's checks mean at toy widths what they mean at real ones)."""
    conf = dict(cell["config"], **tiny["config"])
    traffic = dict(cell["traffic"], seq_len=tiny["traffic"]["seq_len"],
                   global_batch=tiny["traffic"]["batch_per_chip"]
                   * cell["chips"])
    run = dict(cell["cell"], **tiny.get("cell", {}),
               loss_chunk=tiny["traffic"]["loss_chunk"])
    return dict(cell, config=conf, traffic=traffic, cell=run)


def sampled_leaves(cfg):
    """Where the first update is held against the reference's gradient:
    the first layer's input projection (the far end of backprop, through
    every scan), a middle Mamba-2 layer's convolution and output
    projection, the attention layer's q projection, the last layer's MLP,
    the tied embedding. ``(path into the tree, rows compared, columns
    compared)``."""
    mamba = [i for i, l in enumerate(cfg.layers) if l.mixer == "mamba2"]
    attn = [i for i, l in enumerate(cfg.layers) if l.mixer == "attention"]
    mid = mamba[len(mamba) // 2]
    return [(("layers", mamba[0], "ssm", "in_proj"), None, None),
            (("layers", mid, "ssm", "conv_w"), None, None),
            (("layers", mid, "ssm", "out_proj"), None, None),
            (("layers", attn[0], "wq"), None, None),
            (("layers", cfg.n_layers - 1, "w1"), None, 2048),
            (("embed",), 512, None)]


def _cut(leaf, rows, cols):
    return leaf[:rows, ..., :cols]


def reference_reading(cfg, cell, seed, source, loss_and_grads=None):
    """What the plain reference reads on the first global batch of
    ``seed``: ``{"loss", "rms" (Mamba-2 layers, heads), "grads" and
    "before" of the sampled leaves, cut as they are compared}``.
    ``loss_and_grads(p0, tokens, targets, arch, paths)`` takes the place
    of ``reference_granite.loss_and_grads`` where a reading in a lower
    precision is wanted (``benchmark/tools/precision_control.py``)."""
    import jax
    import numpy as np

    from benchmark.lib import reference_granite as reference
    from horovod_tpu.models import transformer as tfm
    p0 = jax.jit(lambda k: tfm.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    arch = reference_arch(cell)
    leaves = sampled_leaves(cfg)
    paths = [p for p, _, _ in leaves]
    gb = cell["traffic"]["global_batch"]
    tokens, targets = source(range(gb))
    fn = jax.jit(lambda p, a, b: (loss_and_grads
                                  or reference.loss_and_grads)(
        p, a, b, arch, paths))
    loss, rms, grads = 0.0, 0.0, None
    for i in range(gb):  # one sequence at a time: the state is 2 MiB a head
        (l_mb, rms_mb), g_mb = fn(p0, tokens[i:i + 1], targets[i:i + 1])
        loss += float(l_mb) / gb
        # the program's statistic is over the batch: mean of squares
        rms = rms + np.asarray(rms_mb, np.float64) ** 2 / gb
        g_mb = [_cut(g, r, c) / gb for g, (_, r, c) in zip(g_mb, leaves)]
        grads = g_mb if grads is None else [x + y for x, y
                                            in zip(grads, g_mb)]
    return {"loss": loss, "rms": np.sqrt(rms),
            "grads": [np.asarray(g, np.float32) for g in grads],
            "before": [np.asarray(_cut(reference.get_leaf(p0, path), r, c))
                       for path, r, c in leaves]}


def compare_with_reference(cfg, want, loss0, rms0, p1, tol):
    """Checks (a), (b) and the sixth, of ``(step-0 loss, step-0
    ssm_state_rms, the sampled leaves after the first update)`` against a
    :func:`reference_reading`: the loss, the sign of the first update on
    the sampled leaves against the reference's global-batch gradient, and
    the final state's rms of every head of every Mamba-2 layer against
    the reference's sequential recurrence (by head: a state kept in too
    few bits stops decaying in the slow heads only, which a layer's one
    number dilutes). Returns ``(ok, what was compared)``."""
    import numpy as np
    rms, rms0 = want["rms"], np.asarray(rms0, np.float64)
    rel = np.abs(rms0 - rms) / rms                        # (layers, heads)

    def by_layer(r):
        return np.sqrt(np.mean(r * r, axis=-1))

    out = {"loss0": loss0, "reference_loss0": want["loss"],
           "loss0_abs_err": abs(loss0 - want["loss"]), "sign_agreement": {},
           "ssm_state_rms_by_layer": by_layer(rms0).tolist(),
           "reference_ssm_state_rms_by_layer": by_layer(rms).tolist(),
           "ssm_state_rms_rel_err_by_layer": (
               np.abs(by_layer(rms0) - by_layer(rms))
               / by_layer(rms)).tolist(),
           "ssm_state_rms_rel_err_max_by_layer": rel.max(-1).tolist(),
           "ssm_state_rms_rel_err_max": float(rel.max())}
    ok = (out["loss0_abs_err"] <= tol["loss0_abs"]
          and out["ssm_state_rms_rel_err_max"]
          <= tol["ssm_state_rms_rel_max"])
    for (path, _, _), g, before, after in zip(
            sampled_leaves(cfg), want["grads"], want["before"], p1):
        delta = after - before
        big = np.abs(g) > np.median(np.abs(g))
        agree = float(np.mean(np.sign(delta[big]) == -np.sign(g[big])))
        out["sign_agreement"]["/".join(map(str, path))] = agree
        ok = ok and agree >= tol["sign_agreement_min"]
    return ok, out


def reference_check(cfg, cell, seed, source, loss0, rms0, p1, tol):
    """What the timed path produced on the first batch against the plain
    reference on the same batch."""
    return compare_with_reference(
        cfg, reference_reading(cfg, cell, seed, source), loss0, rms0, p1,
        tol)


def run(cell, args, t_start):
    """One run of one cell. Returns the result dict ``run.py`` prints."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.lib import (cells, data, flops, reference_granite,
                               trace_reduce)
    from horovod_tpu.models import transformer as tfm

    if not hasattr(tfm.TransformerConfig, "ssm_heads"):
        raise SystemExit(
            f"benchmark: cell {cell['name']} needs a TransformerConfig "
            "whose layers name their mixer (LayerSpec.mixer, the ssm_* "
            "sizes); this program has none")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spans, watch = Spans(), CompileWatch()
    rehearsal = bool(args.cpu_rehearsal)
    if rehearsal:
        cell = apply_tiny(cell, cells.load_json(args.cpu_rehearsal))
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
        return tfm.loss_and_stats(p, tokens, targets, cfg, axes)

    tx = hvd.DistributedOptimizer(base_optimizer(run_cfg["optimizer"]))
    step = hvd.compiled_train_step(loss_fn, tx, has_aux=True,
                                   name="benchmark")
    with spans.span("opt_init"):
        opt_state = jax.jit(step.init, out_shardings=replicated)(params)
        jax.block_until_ready(opt_state)
    ds = hvd.data.DistributedDataset(
        source, batch_size=gb, num_samples=gb * 4096, seed=args.seed,
        shuffle=False, sharding=NamedSharding(mesh, P(mesh.axis_names[0])))
    batches = iter(ds)
    leaves = sampled_leaves(cfg)
    losses, states = [], []

    def read_back(loss, aux):
        """The loss and the layers' final-state rms of a finished step,
        as a logging trainer reads them; the statistic also goes to the
        program's own ``hvd_ssm_state_rms`` family."""
        losses.append(float(loss))
        aux = jax.device_get(aux)
        hvd.metrics.record_ssm_state(aux)
        states.append(np.asarray(aux["ssm_state_rms"], np.float64))

    with spans.span("first_batch"):
        batch = next(batches)
    first_ok = bool(np.array_equal(np.asarray(batch[0]),
                                   source(range(gb))[0]))
    with spans.span("first_step"):
        params, opt_state, loss, aux = step(params, opt_state, *batch)
        read_back(loss, aux)
    with spans.span("snapshot_leaves"):
        p1 = [np.asarray(_cut(reference_granite.get_leaf(params, path),
                              rows, cols)) for path, rows, cols in leaves]
    with spans.span("warmup_steps"):
        for _ in range(run_cfg["warmup_steps"] - 1):
            batch = next(batches)
            params, opt_state, loss, aux = step(params, opt_state, *batch)
            read_back(loss, aux)

    # ------------------------------------------------------------ window
    def one_step(prev):
        nonlocal params, opt_state
        with spans.span("next_batch"):
            batch = next(batches)
        with spans.span("dispatch"):
            params, opt_state, loss, aux = step(params, opt_state, *batch)
        if prev is not None:
            with spans.span("loss_readback"):
                read_back(*prev)
        return loss, aux

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
        read_back(*prev)
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
            read_back(*prev)
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
    # the attention layers' kernels, by the names the program gives them
    kernels = len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*hvd_flash_', hlo or ""))
    attention_layers = sum(1 for l in cfg.layers if l.mixer == "attention")
    need = attention_layers * run_cfg.get("min_flash_calls_per_layer", 2)
    checks["flash_kernels_compiled"] = rehearsal or (
        kernels >= need and cfg.flash_interpret is False)
    checks["loss_finite"] = bool(np.all(np.isfinite(losses))
                                 and np.all(np.isfinite(states)))
    band = run_cfg["loss_band"]
    lo, hi = band["steps"]
    band_mean = (statistics.fmean(losses[lo:hi + 1])
                 if total_steps > hi else None)
    checks["loss_band"] = rehearsal or (
        band_mean is not None and band["low"] <= band_mean <= band["high"])
    with spans.span("replica_check"):
        checks["replicas_identical"] = replicas_identical(params, mesh)
    ds.close()
    del params, opt_state, batch, prev, loss, aux, step, tx
    jax.clear_caches()
    with spans.span("reference_check"):
        try:
            checks["reference"], ref = reference_check(
                cfg, cell, args.seed, source, losses[0], states[0], p1,
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
    shape = work_shape(cell)
    tokens_per_chip = gb // n * seq
    need_flops, per_layer = work_hybrid.required_flops_per_token(shape)
    ctx = {"spans": spans.rows, "steps": steps, "trace": trace,
           "counters": {k: counters1[k] - counters0.get(k, 0.0)
                        for k in counters1},
           "work": {"shape": shape, "remat": bool(run_cfg["remat"]),
                    "tokens_per_chip": tokens_per_chip,
                    "seqs_per_chip": gb // n,
                    "required_flops_per_token": need_flops},
           "notes": {},
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
            "ssm_state_rms_by_step_and_layer": [
                np.sqrt(np.mean(s * s, axis=-1)).tolist() for s in states],
            "flash_custom_calls": kernels, "trace_error": trace_err,
            "window_s": t_w1 - t_w0, "memory_stats": mem,
            "step_memory_analysis_bytes": program,
            "required_flops_per_token": need_flops,
            "forward_flops_per_token_by_layer": per_layer,
            "setup_spans_s": {name: e - s for phase, name, s, e
                              in spans.rows if phase == "setup"},
            "post_spans_s": {name: e - s for phase, name, s, e
                             in spans.rows if phase == "post"},
            "total_s": time.perf_counter() - t_start},
    }
