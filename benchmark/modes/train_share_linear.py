"""Mode ``train_share_linear``: the compiled train step of ONE CHIP'S SHARE
of a model that mixes KDA (gated delta rule) layers with latent-attention
layers and routes its sparse FFNs by a sigmoid router
(``kimi-linear-48b-a3b``), driven the way a user drives it — the path of
``train_dp``, ``train_share`` and ``train_hybrid``:

``hvd.init()`` -> ``tfm.init_params`` from ``--seed`` ->
``hvd.broadcast_parameters`` -> ``hvd.DistributedOptimizer(optax.adamw)`` ->
``hvd.compiled_train_step(loss_fn, has_aux=True)`` with
``tfm.loss_and_stats`` -> batches from ``hvd.data.DistributedDataset`` -> a
loop that enqueues step *i* and then reads back the loss, the routing
counters and the KDA layers' final-state statistic of step *i-1*.

What differs from the two modes it borrows from (neither is edited): the
configuration file's published keys (``linear_attn_config``, the MLA
sizes, the router's keys) become a per-layer ``TransformerConfig`` whose
layers name their mixer; the reference is ``reference_kimi_linear`` (the
sequential recurrence); the step's aux carries BOTH ``expert_load``
(``train_share``'s sixth check, (f)) and ``kda_state_rms``
(``train_hybrid``'s, here (g)); work is counted per layer kind by
``benchmark/lib/work_linear.py``, registered here.

Taken from the other modes as they are: ``Spans``, ``CompileWatch``,
``counter_totals``, ``replicas_identical``, ``step_executable``
(``train_dp``), ``base_optimizer`` and ``apply_tiny`` (``train_share``),
``_cut`` (``train_hybrid``). Their ``run`` could not be: each builds its
configuration, its read-back and its reference check from names of its
own module, with no argument to hand it others (PERF.md section 7), so the
loop is written out here once more.
"""

import glob
import math
import os
import re
import shutil
import statistics
import time

from benchmark.lib import layer_metrics, work_linear
from benchmark.modes.train_dp import (CompileWatch, Spans, counter_totals,
                                      replicas_identical, step_executable)
from benchmark.modes.train_hybrid import _cut
from benchmark.modes.train_share import apply_tiny, base_optimizer

work_linear.register(layer_metrics)

#: the loss carries routing counters and the state statistic out of the step
LOSS_HAS_AUX = True


def _layer_kinds(conf):
    """``[(mixer, mlp)]`` of the layers held, from the published lists
    (1-indexed) and ``first_k_dense_replace``."""
    lin = conf["linear_attn_config"]
    kinds = []
    for i in range(1, conf["num_hidden_layers"] + 1):
        if i in lin["kda_layers"]:
            mixer = "kda"
        elif i in lin["full_attn_layers"]:
            mixer = "mla"
        else:
            raise SystemExit(f"train_share_linear: layer {i} is in neither "
                             "list of linear_attn_config")
        kinds.append((mixer, "dense" if i <= conf["first_k_dense_replace"]
                      else "sparse"))
    return kinds


def model_config(cell, interpret):
    """The ``TransformerConfig`` a cell runs, from the configuration
    file's published keys and the cell file's run-time choices. The counts
    in the file are what this chip holds; the router keeps its published
    width (``published.num_experts``). No size is set here."""
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm
    conf, run, traffic = cell["config"], cell["cell"], cell["traffic"]
    if (not conf["mla_use_nope"] or conf["q_lora_rank"] is not None
            or conf["moe_router_activation_func"] != "sigmoid"
            or not conf["moe_renormalize"] or conf["moe_layer_freq"] != 1
            or (conf["num_expert_group"], conf["topk_group"]) != (1, 1)
            or conf["num_nextn_predict_layers"]
            or conf["tie_word_embeddings"]):
        raise SystemExit(
            "train_share_linear: a configuration with positions in its "
            "latent attention, a compressed q, another router, expert "
            "groups, prediction layers or a tied head is not known here")
    lin, n, h = (conf["linear_attn_config"], conf["num_hidden_layers"],
                 conf["num_attention_heads"])
    layers = tuple(tfm.LayerSpec(n_heads=h, mixer=mixer, mlp=mlp)
                   for mixer, mlp in _layer_kinds(conf))
    return tfm.TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=h, head_size=conf["qk_nope_head_dim"]
        + conf["qk_rope_head_dim"], n_layers=n,
        d_ff=conf["intermediate_size"], max_seq=traffic["seq_len"],
        positional="rope", layers=layers, mlp_gated=True,
        norm_eps=conf["rms_norm_eps"], kda_heads=lin["num_heads"],
        kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        mla_kv_rank=conf["kv_lora_rank"],
        mla_qk_nope=conf["qk_nope_head_dim"],
        mla_qk_shared=conf["qk_rope_head_dim"],
        mla_v_dim=conf["v_head_dim"],
        moe_num_experts=conf["published"]["num_experts"],
        moe_top_k=conf["num_experts_per_token"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_shared_d_ff=conf["moe_intermediate_size"]
        * conf["num_shared_experts"],
        moe_routed_scale=float(conf["routed_scaling_factor"]),
        moe_experts_held=(conf["deployment"]["first_expert_held"],
                          conf["num_experts"]),
        moe_router="sigmoid", dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        attention_impl=run["attention_impl"], flash_interpret=interpret,
        loss_chunk=run["loss_chunk"], remat=run["remat"])


def reference_arch(cell):
    """What ``reference_kimi_linear`` needs beside the parameters, from the
    configuration file alone."""
    conf = cell["config"]
    lin = conf["linear_attn_config"]
    return {"rms_norm_eps": conf["rms_norm_eps"],
            "kda": {"n_heads": lin["num_heads"],
                    "head_dim": lin["head_dim"]},
            "mla": {"kv_rank": conf["kv_lora_rank"],
                    "qk_nope": conf["qk_nope_head_dim"]},
            "moe": {"top_k": conf["num_experts_per_token"],
                    "routed_scale": conf["routed_scaling_factor"],
                    "experts_held": [
                        conf["deployment"]["first_expert_held"],
                        conf["num_experts"]]}}


def work_shape(cell):
    """The sizes ``work_linear`` counts from, from the configuration file
    alone."""
    conf = cell["config"]
    lin = conf["linear_attn_config"]
    return {
        "d_model": conf["hidden_size"], "vocab_size": conf["vocab_size"],
        "seq_len": cell["traffic"]["seq_len"],
        "d_ff": conf["intermediate_size"], "mlp_matrices": 3,
        "layers": [{"mixer": mixer, "mlp": mlp}
                   for mixer, mlp in _layer_kinds(conf)],
        "kda": {"n_heads": lin["num_heads"], "head_dim": lin["head_dim"]},
        "mla": {"n_heads": conf["num_attention_heads"],
                "qk_dim": conf["qk_nope_head_dim"]
                + conf["qk_rope_head_dim"],
                "v_dim": conf["v_head_dim"],
                "kv_rank": conf["kv_lora_rank"],
                "qk_shared": conf["qk_rope_head_dim"]},
        "experts": {"router_width": conf["published"]["num_experts"],
                    "held": conf["num_experts"],
                    "width": conf["moe_intermediate_size"],
                    "shared_width": conf["moe_intermediate_size"]
                    * conf["num_shared_experts"],
                    "matrices": 3}}


def sampled_leaves(cfg):
    """Where the first update is held against the reference's gradient:
    the first layer's KDA q projection and the wide half of its decay's
    low-rank pair (the far end of backprop, through every recurrence), a
    later KDA layer's convolutions and output projection, the latent-
    attention layer's expansion and q projection, the held experts' first
    matrices and the router of the first sparse layer, the embedding.
    ``(path into the tree, rows compared, columns compared)``. ALL the
    held experts' matrices, not one expert's: an expert that the seed
    sends 59 of 131,072 assignments has a gradient of 59 rows, one flipped
    assignment turns 1 % of its signs (PERF.md section 6 PR 33), and the
    seed decides which expert that is; pooled, the elements above the
    median |g| are the well-fed experts'."""
    kda = [i for i, l in enumerate(cfg.layers) if l.mixer == "kda"]
    mla = [i for i, l in enumerate(cfg.layers) if l.mixer == "mla"]
    sparse = [i for i, l in enumerate(cfg.layers) if l.mlp == "sparse"]
    return [(("layers", kda[0], "kda", "wq"), None, None),
            (("layers", kda[0], "kda", "w_fb"), None, None),
            (("layers", kda[2], "kda", "conv_w"), None, None),
            (("layers", kda[2], "kda", "wo"), None, None),
            (("layers", mla[0], "mla", "w_kvb"), None, None),
            (("layers", mla[0], "mla", "wq"), None, None),
            (("layers", sparse[0], "moe", "w1"), None, None),
            (("layers", sparse[0], "moe", "w_router"), None, None),
            (("embed",), 512, None)]


def reference_reading(cfg, cell, seed, source, loss_and_grads=None):
    """What the plain reference reads on the first global batch of
    ``seed``: ``{"loss", "rms" (KDA layers, heads), "load" (sparse layers,
    experts held), "grads" and "before" of the sampled leaves, cut as they
    are compared}``. ``loss_and_grads(p0, tokens, targets, arch, paths)``
    takes the place of ``reference_kimi_linear.loss_and_grads`` where a
    reading in a lower precision is wanted
    (``benchmark/tools/precision_control_linear.py``)."""
    import jax
    import numpy as np

    from benchmark.lib import reference_kimi_linear as reference
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
    loss, rms, load, grads = 0.0, 0.0, 0.0, None
    for i in range(gb):  # one sequence at a time: the state is 2 MiB a head
        (l_mb, aux), g_mb = fn(p0, tokens[i:i + 1], targets[i:i + 1])
        loss += float(l_mb) / gb
        # the program's statistic is over the batch: mean of squares
        rms = rms + np.asarray(aux["rms"], np.float64) ** 2 / gb
        load = load + np.asarray(aux["load"], np.float64)
        g_mb = [_cut(g, r, c) / gb for g, (_, r, c) in zip(g_mb, leaves)]
        grads = g_mb if grads is None else [x + y for x, y
                                            in zip(grads, g_mb)]
    return {"loss": loss, "rms": np.sqrt(rms), "load": load,
            "grads": [np.asarray(g, np.float32) for g in grads],
            "before": [np.asarray(_cut(reference.get_leaf(p0, path), r, c))
                       for path, r, c in leaves]}


def compare_with_reference(cfg, want, loss0, rms0, load0, p1, tol):
    """Checks (a), (b), (f) and (g) of ``(step-0 loss, step-0
    kda_state_rms, step-0 expert_load, the sampled leaves after the first
    update)`` against a :func:`reference_reading`: the loss; the sign of
    the first update on the sampled leaves against the reference's
    global-batch gradient; the share of the first batch's assignments to
    held experts that sit on another expert than in the reference's own
    top-k; the final state's rms of every head of every KDA layer against
    the reference's sequential recurrence (by head, as ``train_hybrid``
    has it). Returns ``(ok, what was compared)``, the limits missed among
    it (``limits_missed``)."""
    import numpy as np
    rms, rms0 = want["rms"], np.asarray(rms0, np.float64)
    rel = np.abs(rms0 - rms) / rms                        # (layers, heads)
    load = want["load"]
    moved = float(np.abs(np.asarray(load0, np.float64) - load).sum() / 2)
    out = {"loss0": loss0, "reference_loss0": want["loss"],
           "loss0_abs_err": abs(loss0 - want["loss"]), "sign_agreement": {},
           "kda_state_rms_rel_err_max_by_layer": rel.max(-1).tolist(),
           "kda_state_rms_rel_err_max": float(rel.max()),
           "expert_load": np.asarray(load0).tolist(),
           "reference_expert_load": load.tolist(),
           "assignments_moved": moved,
           "assignments_moved_share": moved / max(load.sum(), 1.0)}
    missed = [name for name, value in (
        ("loss0_abs", out["loss0_abs_err"]),
        ("kda_state_rms_rel_max", out["kda_state_rms_rel_err_max"]),
        ("assignments_moved_share_max", out["assignments_moved_share"]))
        if not value <= tol[name]]
    for (path, _, _), g, before, after in zip(
            sampled_leaves(cfg), want["grads"], want["before"], p1):
        delta = after - before
        big = np.abs(g) > np.median(np.abs(g))
        agree = float(np.mean(np.sign(delta[big]) == -np.sign(g[big])))
        name = "/".join(map(str, path))
        out["sign_agreement"][name] = agree
        if not agree >= tol.get("sign_agreement_min_by_leaf", {}).get(
                name, tol["sign_agreement_min"]):
            missed.append(f"sign_agreement_min:{name}")
    return not missed, dict(out, limits_missed=missed)


def reference_check(cfg, cell, seed, source, loss0, rms0, load0, p1, tol):
    """What the timed path produced on the first batch against the plain
    reference on the same batch."""
    return compare_with_reference(
        cfg, reference_reading(cfg, cell, seed, source), loss0, rms0,
        load0, p1, tol)


def run(cell, args, t_start):
    """One run of one cell. Returns the result dict ``run.py`` prints."""
    from horovod_tpu.models import transformer as tfm
    if not hasattr(tfm.TransformerConfig, "kda_heads"):
        raise SystemExit(
            f"benchmark: cell {cell['name']} needs a TransformerConfig "
            "with KDA and latent-attention layers and a sigmoid router "
            "(LayerSpec.mixer 'kda' / 'mla', the kda_* / mla_* sizes, "
            "moe_router); this program has none")
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.lib import (cells, data, flops, reference_kimi_linear,
                               trace_reduce)

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
    losses, loads, states = [], [], []

    def read_back(loss, aux):
        """The loss, the routing counters and the KDA layers' final-state
        rms of a finished step, as a logging trainer reads them; both also
        go to the program's own ``hvd_moe_*`` / ``hvd_kda_state_rms``
        families."""
        losses.append(float(loss))
        aux = jax.device_get(aux)
        hvd.metrics.record_moe_routing(aux)
        hvd.metrics.record_kda_state(aux)
        # the step means its aux over the chips; loads are per chip
        loads.append(np.asarray(aux["expert_load"], np.float64))
        states.append(np.asarray(aux["kda_state_rms"], np.float64))

    with spans.span("first_batch"):
        batch = next(batches)
    first_ok = bool(np.array_equal(np.asarray(batch[0]),
                                   source(range(gb))[0]))
    with spans.span("first_step"):
        params, opt_state, loss, aux = step(params, opt_state, *batch)
        read_back(loss, aux)
    with spans.span("snapshot_leaves"):
        p1 = [np.asarray(_cut(reference_kimi_linear.get_leaf(params, path),
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
    window_losses, window_loads = losses[-steps:], loads[-steps:]
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
    # the grouped matmuls are Mosaic calls too: the attention kernels are
    # told apart by the names the program gives them
    kernels = len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*hvd_flash_', hlo or ""))
    attention_layers = sum(1 for l in cfg.layers if l.mixer == "mla")
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
                cfg, cell, args.seed, source, losses[0], states[0],
                loads[0] * n, p1, run_cfg["tolerances"])
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
    per_token = (float(np.mean([l.sum(-1).mean() for l in window_loads]))
                 / tokens_per_chip)
    need_flops, per_layer = work_linear.required_flops_per_token(
        shape, per_token)
    ctx = {"spans": spans.rows, "steps": steps, "trace": trace,
           "counters": {k: counters1[k] - counters0.get(k, 0.0)
                        for k in counters1},
           "routing": [l.tolist() for l in window_loads],
           "work": {"shape": shape, "remat": bool(run_cfg["remat"]),
                    "assignments_per_token": per_token,
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
            "kda_state_rms_by_step_and_layer": [
                np.sqrt(np.mean(s * s, axis=-1)).tolist()
                for s in states],
            "flash_custom_calls": kernels, "trace_error": trace_err,
            "window_s": t_w1 - t_w0, "memory_stats": mem,
            "step_memory_analysis_bytes": program,
            "required_flops_per_token": need_flops,
            "forward_flops_per_token_by_layer": per_layer,
            "assignments_per_token_per_sparse_layer": per_token,
            "assignments_by_step_and_layer": [
                l.sum(-1).tolist() for l in loads],
            "setup_spans_s": {name: e - s for phase, name, s, e
                              in spans.rows if phase == "setup"},
            "post_spans_s": {name: e - s for phase, name, s, e
                             in spans.rows if phase == "post"},
            "total_s": time.perf_counter() - t_start},
    }
