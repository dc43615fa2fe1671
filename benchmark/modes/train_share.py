"""Mode ``train_share``: the compiled train step of ONE CHIP'S SHARE of a
model whose layers differ (two kinds of attention layer, dense and sparse
FFNs), driven the way a user drives it — the path of ``train_dp``:

``hvd.init()`` -> ``tfm.init_params`` from ``--seed`` ->
``hvd.broadcast_parameters`` -> ``hvd.DistributedOptimizer(optax.adamw)`` ->
``hvd.compiled_train_step(loss_fn, has_aux=True)`` with
``tfm.loss_and_stats`` -> batches from ``hvd.data.DistributedDataset`` -> a
loop that enqueues step *i* and then reads back the loss and the routing
counters of step *i-1*.

What differs from ``train_dp`` (which reads ONE ``maps_to`` table into a
homogeneous configuration and is not edited): the configuration file's
published keys (``layer_types``, ``num_attention_heads_per_layer``,
``rope_parameters``, ``mlp_layer_types``, the expert keys) become a
per-layer ``TransformerConfig`` whose head, expert and vocabulary counts
are the share this chip holds; the reference is ``reference_laguna``; the
step's aux carries per-expert assignment counts out, which a sixth check
holds to the reference's own top-k; and work is counted per layer kind
(``benchmark/lib/work_layers.py``, registered here).

From the program this file takes what ``train_dp`` takes, plus
``tfm.LayerSpec`` / ``tfm.RopeSpec``, the step's aux and
``hvd.metrics.record_moe_routing``.
"""

import glob
import math
import os
import re
import shutil
import statistics
import time

from benchmark.lib import layer_metrics, work_layers
from benchmark.modes.train_dp import (CompileWatch, Spans, _cut,
                                      counter_totals, replicas_identical,
                                      step_executable)

work_layers.register(layer_metrics)

#: the loss carries routing counters out of the step (``has_aux=True``)
LOSS_HAS_AUX = True


def base_optimizer(opt):
    """adamw at the cell's learning rate; where the cell states
    ``lr_warmup_steps``, reached by a linear warm-up over that many steps
    (step 0 takes ``learning_rate / lr_warmup_steps``)."""
    import optax
    if opt["name"] != "adamw":
        raise SystemExit(f"train_share: optimizer {opt['name']!r} is not "
                         "known here (adamw)")
    rate, ramp = opt["learning_rate"], opt.get("lr_warmup_steps")
    if ramp:
        rate = optax.linear_schedule(rate / ramp, rate, ramp)
    return optax.adamw(rate)


def _rope_spec(tfm, block, head_dim):
    yarn = block.get("rope_type") == "yarn"
    rotary = int(head_dim * block.get("partial_rotary_factor", 1))
    return tfm.RopeSpec(
        theta=float(block["rope_theta"]),
        rotary_dim=None if rotary == head_dim else rotary,
        yarn_factor=float(block["factor"]) if yarn else None,
        yarn_original_max_seq=block.get(
            "original_max_position_embeddings"),
        yarn_beta_fast=float(block.get("beta_fast", 32)),
        yarn_beta_slow=float(block.get("beta_slow", 1)),
        attention_factor=float(block["attention_factor"]) if yarn else 1.0)


def _window(conf, i):
    """Layer ``i``'s sliding window, ``None`` on a full-attention layer."""
    return (conf["sliding_window"]
            if conf["layer_types"][i] == "sliding_attention" else None)


def model_config(cell, interpret):
    """The ``TransformerConfig`` a cell runs, from the configuration
    file's published keys and the cell file's run-time choices. The counts
    in the file are what this chip holds; the router keeps its published
    width (``published.num_experts``). No size is set here."""
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm
    conf, run, traffic = cell["config"], cell["cell"], cell["traffic"]
    n, hd = conf["num_hidden_layers"], conf["head_dim"]
    ropes = {kind: _rope_spec(tfm, block, hd)
             for kind, block in conf["rope_parameters"].items()}
    layers = tuple(
        tfm.LayerSpec(
            n_heads=conf["num_attention_heads_per_layer"][i],
            window=_window(conf, i),
            rope=ropes[conf["layer_types"][i]],
            mlp=conf["mlp_layer_types"][i])
        for i in range(n))
    return tfm.TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"], head_size=hd, n_layers=n,
        d_ff=conf["intermediate_size"], max_seq=traffic["seq_len"],
        positional="rope", layers=layers,
        attn_gate=conf["gating"] == "per-head", mlp_gated=True,
        moe_num_experts=conf["published"]["num_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"],
        moe_shared_d_ff=conf["shared_expert_intermediate_size"],
        moe_routed_scale=float(conf["moe_routed_scaling_factor"]),
        moe_experts_held=(conf["deployment"]["first_expert_held"],
                          conf["num_experts"]),
        dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        attention_impl=run["attention_impl"], flash_interpret=interpret,
        loss_chunk=run["loss_chunk"], remat=run["remat"])


def reference_arch(cell):
    """What ``reference_laguna`` needs beside the parameters, from the
    configuration file alone."""
    conf = cell["config"]
    return {
        "layers": [
            {"window": _window(conf, i),
             "rope": conf["rope_parameters"][conf["layer_types"][i]]}
            for i in range(conf["num_hidden_layers"])],
        "moe": {"num_experts": conf["published"]["num_experts"],
                "top_k": conf["num_experts_per_tok"],
                "routed_scale": conf["moe_routed_scaling_factor"],
                "experts_held": [conf["deployment"]["first_expert_held"],
                                 conf["num_experts"]]}}


def work_shape(cell):
    """The sizes ``work_layers`` counts from, from the configuration
    file alone."""
    conf = cell["config"]
    n = conf["num_hidden_layers"]
    return {
        "d_model": conf["hidden_size"], "head_dim": conf["head_dim"],
        "n_kv_heads": conf["num_key_value_heads"],
        "vocab_size": conf["vocab_size"],
        "seq_len": cell["traffic"]["seq_len"],
        "d_ff": conf["intermediate_size"], "mlp_matrices": 3,
        "layers": [
            {"n_heads": conf["num_attention_heads_per_layer"][i],
             "window": _window(conf, i),
             "gate": conf["gating"] == "per-head",
             "mlp": conf["mlp_layer_types"][i]} for i in range(n)],
        "experts": {"router_width": conf["published"]["num_experts"],
                    "held": conf["num_experts"],
                    "width": conf["moe_intermediate_size"],
                    "shared_width": conf["shared_expert_intermediate_size"],
                    "matrices": 3}}


def apply_tiny(cell, tiny):
    """``--cpu-rehearsal <preset>``: the same cell at toy sizes, for
    walking the harness on the CPU. Never a measurement. The preset's
    ``config`` block replaces published keys of the same name, its
    ``cell`` block run-time choices (float32 activations, so that the
    reference's checks mean at toy widths what they mean at real ones)."""
    conf = dict(cell["config"], **tiny["config"])
    conf["published"] = dict(cell["config"]["published"],
                             **tiny.get("published", {}))
    traffic = dict(cell["traffic"], seq_len=tiny["traffic"]["seq_len"],
                   global_batch=tiny["traffic"]["batch_per_chip"]
                   * cell["chips"])
    run = dict(cell["cell"], **tiny.get("cell", {}),
               loss_chunk=tiny["traffic"]["loss_chunk"])
    return dict(cell, config=conf, traffic=traffic, cell=run)


def sampled_leaves(cfg):
    """Where the first update is held against the reference's gradient:
    the first layer's q projection (the far end of backprop), a held
    expert's first matrix and the router in a middle sparse layer, the
    shared expert in the last layer, the LM head. ``(path into the tree,
    columns compared)``."""
    sparse = [i for i, l in enumerate(cfg.layers) if l.mlp == "sparse"]
    mid, last = sparse[len(sparse) // 2 - 1], sparse[-1]
    return [(("layers", 0, "wq"), None),
            (("layers", mid, "moe", "w1"), None),
            (("layers", mid, "moe", "w_router"), None),
            (("layers", last, "moe", "shared", "w1"), None),
            (("lm_head",), 512)]


def reference_check(cfg, cell, seed, source, loss0, load0, p1, tol):
    """Checks (a), (b) and the sixth: step-0 loss against the plain
    reference on the first global batch, the sign of the first update on
    the sampled leaves against the reference's global-batch gradient, and
    the assignments each held expert took in each sparse layer against the
    reference's own top-k."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark.lib import reference_laguna as reference
    from horovod_tpu.models import transformer as tfm
    devices = jax.devices()
    mesh = Mesh(np.array(devices), ("ref",))
    rep, split = NamedSharding(mesh, P()), NamedSharding(mesh, P("ref"))
    p0 = jax.jit(lambda k: tfm.init_params(k, cfg), out_shardings=rep)(
        jax.random.PRNGKey(seed))
    arch = reference_arch(cell)
    leaves = sampled_leaves(cfg)
    paths = [p for p, _ in leaves]
    gb, seq = cell["traffic"]["global_batch"], cell["traffic"]["seq_len"]
    tokens, targets = source(range(gb))
    per_dev = max(1, min(gb // len(devices), 4096 // seq))
    micro = per_dev * len(devices)
    fn = jax.jit(lambda p, a, b: reference.loss_and_grads(
        p, a, b, arch, paths))
    loss, load, grads = 0.0, 0.0, None
    for i in range(0, gb, micro):
        a = jax.device_put(tokens[i:i + micro], split)
        b = jax.device_put(targets[i:i + micro], split)
        (l_mb, load_mb), g_mb = fn(p0, a, b)
        w = micro / gb
        loss += w * float(l_mb)
        load = load + np.asarray(load_mb, np.float64)
        g_mb = [w * _cut(g, c) for g, (_, c) in zip(g_mb, leaves)]
        grads = g_mb if grads is None else [x + y for x, y
                                            in zip(grads, g_mb)]
    moved = float(np.abs(np.asarray(load0, np.float64) - load).sum() / 2)
    out = {"loss0": loss0, "reference_loss0": loss,
           "loss0_abs_err": abs(loss0 - loss), "sign_agreement": {},
           "expert_load": np.asarray(load0).tolist(),
           "reference_expert_load": load.tolist(),
           "assignments_moved": moved,
           "assignments_moved_share": moved / max(load.sum(), 1.0)}
    ok = (out["loss0_abs_err"] <= tol["loss0_abs"]
          and out["assignments_moved_share"]
          <= tol["assignments_moved_share_max"])
    for (path, cols), g, after in zip(leaves, grads, p1):
        g = np.asarray(g, np.float32)
        before = np.asarray(_cut(reference.get_leaf(p0, path), cols))
        delta = after - before
        big = np.abs(g) > np.median(np.abs(g))
        agree = float(np.mean(np.sign(delta[big]) == -np.sign(g[big])))
        name = "/".join(map(str, path))
        out["sign_agreement"][name] = agree
        ok = ok and agree >= tol.get("sign_agreement_min_by_leaf", {}).get(
            name, tol["sign_agreement_min"])
    return ok, out


def run(cell, args, t_start):
    """One run of one cell. Returns the result dict ``run.py`` prints."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.lib import (cells, data, flops, reference_laguna,
                               trace_reduce)
    from horovod_tpu.models import transformer as tfm

    if not hasattr(tfm, "LayerSpec"):
        raise SystemExit(
            f"benchmark: cell {cell['name']} needs a TransformerConfig "
            "that describes its layers one by one (tfm.LayerSpec); this "
            "program has none")
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
    losses, loads = [], []

    def read_back(loss, aux):
        """The loss and the routing counters of a finished step, as a
        logging trainer reads them; the counters also go to the
        program's own ``hvd_moe_*`` families."""
        losses.append(float(loss))
        aux = jax.device_get(aux)
        hvd.metrics.record_moe_routing(aux)
        # the step means its aux over the chips; loads are per chip
        loads.append(np.asarray(aux["expert_load"], np.float64))

    with spans.span("first_batch"):
        batch = next(batches)
    first_ok = bool(np.array_equal(np.asarray(batch[0]),
                                   source(range(gb))[0]))
    with spans.span("first_step"):
        params, opt_state, loss, aux = step(params, opt_state, *batch)
        read_back(loss, aux)
    with spans.span("snapshot_leaves"):
        p1 = [np.asarray(_cut(reference_laguna.get_leaf(params, path),
                              cols)) for path, cols in leaves]
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
    del params, opt_state, batch, prev, loss, aux, step, tx
    jax.clear_caches()
    with spans.span("reference_check"):
        try:
            checks["reference"], ref = reference_check(
                cfg, cell, args.seed, source, losses[0], loads[0] * n, p1,
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
    per_token = (float(np.mean([l.sum(-1).mean() for l in window_loads]))
                 / tokens_per_chip)
    need_flops, per_layer = work_layers.required_flops_per_token(
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
