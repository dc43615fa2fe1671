"""Mode ``train_share_conv``: the compiled train step of ONE CHIP'S SHARE of
a model that mixes gated short-convolution layers with QK-normed
grouped-query attention layers and routes its sparse FFNs by a sigmoid
router with no shared expert (``lfm2-24b-a2b``), driven the way a user
drives it — the path of the four other modes:

``hvd.init()`` -> ``tfm.init_params`` from ``--seed`` ->
``hvd.broadcast_parameters`` -> ``hvd.DistributedOptimizer(optax.adamw)`` ->
``hvd.compiled_train_step(loss_fn, has_aux=True)`` with
``tfm.loss_and_stats`` -> batches from ``hvd.data.DistributedDataset`` -> a
loop that enqueues step *i* and then reads back the loss and the step's aux
of step *i-1*.

**The loop takes what differs between models as an argument.** ``run(cell,
args, t_start, model=LFM2)``: a :class:`Model` says how the configuration
file becomes a ``TransformerConfig``, which reference module and ``arch``
hold the timed path to account, which leaves of the step's aux are read
back and what the program's metric families are fed from them, which
leaves' first update is sampled, what is compared beyond the loss and the
signs, which work module counts the FLOPs and what the per-layer readers
are handed of the aux read back. :func:`run`, :func:`reference_reading` and
:func:`compare_with_reference` name no layer kind and no aux leaf: a dense
model's ``Model`` reads nothing back and hands the readers nothing. The
four older modes each wrote the loop out with their own names in
it (PERF.md section 7, ROADMAP C14); a later ``benchmark`` PR folds them
onto this one by writing a ``Model`` for each. This module edits none of
them and takes from them what can be taken as it is: ``Spans``,
``CompileWatch``, ``counter_totals``, ``replicas_identical``,
``step_executable`` (``train_dp``), ``base_optimizer`` and ``apply_tiny``
(``train_share``), ``_cut`` (``train_hybrid``).

Checks: (a)-(e) of PERF.md section 2, and for this model (f) the share of
the first batch's assignments to held experts that sit on another expert
than in the reference's own top-k (``train_share``'s). There is no
recurrent state to compare; the first update's sign is sampled on more
leaves at the far end of backprop instead (:func:`lfm2_sampled_leaves`).

Check (b) and the warm-up. Under the cell's 16,000-step warm-up the first
update is 1.9e-8, which float32 cannot hold on an element of 0.5 or more
(half an ulp there is 3e-8): a per-head norm weight of 1.0 and the larger
taps keep their bits with nothing wrong in the program. So the sign of the
first update is read from the parameters where float32 holds a step of the
first rate, and from the optimizer's first moment (the update before it is
scaled, added and rounded away) where it cannot; such an element must also
have kept its bits (:func:`compare_with_reference`). Which elements those
are is a rule on the element's magnitude and the rate, not on what the
program did.
"""

import dataclasses
import glob
import math
import os
import re
import shutil
import statistics
import time
from typing import Any, Callable

from benchmark.lib import layer_metrics, work_conv
from benchmark.modes.train_dp import (CompileWatch, Spans, counter_totals,
                                      replicas_identical, step_executable)
from benchmark.modes.train_hybrid import _cut
from benchmark.modes.train_share import apply_tiny, base_optimizer

work_conv.register(layer_metrics)

#: the loss carries the routing counters out of the step
LOSS_HAS_AUX = True


@dataclasses.dataclass(frozen=True)
class Model:
    """What :func:`run` needs to know of the model a cell trains."""
    name: str
    #: ``lacks(tfm) -> None`` or what the program under test is missing
    lacks: Callable
    #: ``model_config(cell, interpret) -> TransformerConfig``
    model_config: Callable
    #: module with ``loss_and_grads(p, tokens, targets, arch, paths)`` and
    #: ``get_leaf(tree, path)``, by name under ``benchmark.lib``
    reference: str
    #: ``reference_arch(cell) -> arch``
    reference_arch: Callable
    #: ``sampled_leaves(cfg) -> [(path, rows, cols)]``
    sampled_leaves: Callable
    #: leaves of the step's aux read back every step, and how the
    #: reference's aux of the sequences of a batch (one call a sequence)
    #: become one reading of the same leaves: ``{key: merge(list)}``
    aux: dict
    #: ``record(hvd, aux)``: feed the program's own metric families
    record: Callable
    #: ``extra_checks(want_aux, got_aux) -> (what was compared,
    #: [(limit's name in the cell's tolerances, value)])``
    extra_checks: Callable
    #: ``work_shape(cell)`` and the module whose
    #: ``required_flops_per_token(shape, assignments_per_token)`` counts it
    work_shape: Callable
    work: Any
    #: ``work_context(read, steps, tokens_per_chip) -> (assignments to held
    #: experts per token and sparse layer over the window's steps, what the
    #: per-layer readers get of the aux read back)``; a dense model gives
    #: ``(0.0, {})``
    work_context: Callable
    #: ``attention_layers(cfg)``: layers that must compile flash kernels
    attention_layers: Callable


# ------------------------------------------------------------------ LFM2

def _layer_kinds(conf):
    """``[(mixer, mlp)]`` of the layers held: ``layer_types`` as the file
    has it (cut to the layers held), the first ``num_dense_layers`` of
    them with a dense MLP."""
    names = {"conv": "sconv", "full_attention": "attention"}
    types = conf["layer_types"]
    if len(types) != conf["num_hidden_layers"] or set(types) - set(names):
        raise SystemExit(
            "train_share_conv: layer_types must name num_hidden_layers "
            f"layers, each one of {sorted(names)}")
    return [(names[t], "dense" if i < conf["num_dense_layers"] else "sparse")
            for i, t in enumerate(types)]


def lfm2_lacks(tfm):
    if not hasattr(tfm.TransformerConfig, "sconv_kernel") \
            or not hasattr(tfm.TransformerConfig, "qk_norm"):
        return ("a TransformerConfig with gated short-convolution layers "
                "and the per-head QK norm (LayerSpec.mixer 'sconv', "
                "sconv_kernel, qk_norm)")
    return None


def lfm2_model_config(cell, interpret):
    """The ``TransformerConfig`` a cell runs, from the configuration
    file's published keys and the cell file's run-time choices. The counts
    in the file are what this chip holds; the router keeps its published
    width (``published.num_experts``). No size is set here."""
    import jax.numpy as jnp
    from horovod_tpu.models import transformer as tfm
    conf, run, traffic = cell["config"], cell["cell"], cell["traffic"]
    rope = conf["rope_parameters"]
    if (conf["conv_bias"] or not conf["norm_topk_prob"]
            or not conf["use_expert_bias"]
            or rope["rope_type"] != "default"):
        raise SystemExit(
            "train_share_conv: a configuration with a convolution bias, "
            "unnormalised top-k weights, no expert bias or scaled rotary "
            "frequencies is not known here")
    h, d = conf["num_attention_heads"], conf["hidden_size"]
    spin = tfm.RopeSpec(theta=float(rope["rope_theta"]))
    layers = tuple(
        tfm.LayerSpec(n_heads=h, mixer=mixer, mlp=mlp,
                      rope=spin if mixer == "attention" else None)
        for mixer, mlp in _layer_kinds(conf))
    return tfm.TransformerConfig(
        vocab_size=conf["vocab_size"], d_model=d, n_heads=h,
        n_kv_heads=conf["num_key_value_heads"], head_size=d // h,
        n_layers=conf["num_hidden_layers"], d_ff=conf["intermediate_size"],
        max_seq=traffic["seq_len"], positional="rope", layers=layers,
        mlp_gated=True, norm_eps=conf["norm_eps"], qk_norm=True,
        tie_embeddings=True, sconv_kernel=conf["conv_L_cache"],
        moe_num_experts=conf["published"]["num_experts"],
        moe_top_k=conf["num_experts_per_tok"],
        moe_d_ff=conf["moe_intermediate_size"], moe_shared_d_ff=0,
        moe_routed_scale=float(conf["routed_scaling_factor"]),
        moe_experts_held=(conf["deployment"]["first_expert_held"],
                          conf["num_experts"]),
        moe_router="sigmoid", dtype=jnp.dtype(run["dtype"]),
        param_dtype=jnp.dtype(run["param_dtype"]),
        attention_impl=run["attention_impl"], flash_interpret=interpret,
        loss_chunk=run["loss_chunk"], remat=run["remat"])


def lfm2_reference_arch(cell):
    """What ``reference_lfm2`` needs beside the parameters, from the
    configuration file alone."""
    conf = cell["config"]
    return {"rms_norm_eps": conf["norm_eps"],
            "rope_theta": float(conf["rope_parameters"]["rope_theta"]),
            "moe": {"top_k": conf["num_experts_per_tok"],
                    "routed_scale": float(conf["routed_scaling_factor"]),
                    "experts_held": [
                        conf["deployment"]["first_expert_held"],
                        conf["num_experts"]]}}


def lfm2_work_shape(cell):
    """The sizes ``work_conv`` counts from, from the configuration file
    alone."""
    conf = cell["config"]
    h = conf["num_attention_heads"]
    return {
        "d_model": conf["hidden_size"], "vocab_size": conf["vocab_size"],
        "seq_len": cell["traffic"]["seq_len"],
        "d_ff": conf["intermediate_size"], "mlp_matrices": 3,
        "head_dim": conf["hidden_size"] // h, "n_heads": h,
        "n_kv_heads": conf["num_key_value_heads"],
        "conv_taps": conf["conv_L_cache"],
        "layers": [{"mixer": mixer, "mlp": mlp}
                   for mixer, mlp in _layer_kinds(conf)],
        "experts": {"router_width": conf["published"]["num_experts"],
                    "held": conf["num_experts"],
                    "width": conf["moe_intermediate_size"],
                    "shared_width": 0, "matrices": 3}}


def lfm2_sampled_leaves(cfg):
    """Where the first update is held against the reference's gradient,
    ``(path into the tree, rows compared, columns compared)``: the first
    layer's ``w_in`` and taps (the far end of backprop, through every
    convolution, norm, rotation and routing above), a later convolution
    layer's ``w_out``, the first attention layer's ``wq`` and the 64
    floats of its q norm, ALL the held experts' first matrices and the
    router of the first sparse layer (pooled over the experts: one starved
    expert's gradient has few rows and one flipped assignment turns a
    visible share of its signs, PERF.md section 6 PR 33), 512 rows of the
    tied embedding."""
    conv = [i for i, l in enumerate(cfg.layers) if l.mixer == "sconv"]
    attn = [i for i, l in enumerate(cfg.layers) if l.mixer == "attention"]
    sparse = [i for i, l in enumerate(cfg.layers) if l.mlp == "sparse"]
    return [(("layers", conv[0], "sconv", "w_in"), None, None),
            (("layers", conv[0], "sconv", "conv_w"), None, None),
            (("layers", conv[1], "sconv", "w_out"), None, None),
            (("layers", attn[0], "wq"), None, None),
            (("layers", attn[0], "q_norm"), None, None),
            (("layers", sparse[0], "moe", "w1"), None, None),
            (("layers", sparse[0], "moe", "w_router"), None, None),
            (("embed",), 512, None)]


def _record_routing(hvd, aux):
    hvd.metrics.record_moe_routing(aux)


def routing_checks(want, got):
    """Check (f): the assignments each held expert took on the first batch
    in each sparse layer against the reference's own top-k, as the share
    that sits on another expert."""
    import numpy as np
    load, load0 = want["expert_load"], np.asarray(got["expert_load"],
                                                  np.float64)
    moved = float(np.abs(load0 - load).sum() / 2)
    share = moved / max(load.sum(), 1.0)
    return ({"expert_load": load0.tolist(),
             "reference_expert_load": load.tolist(),
             "assignments_moved": moved, "assignments_moved_share": share},
            [("assignments_moved_share_max", share)])


def routing_context(read, steps, tokens_per_chip):
    """The window's routing: what ``work_conv`` counts the sparse FFNs'
    FLOPs from, and the loads the ``routing`` reader takes."""
    import numpy as np
    loads = read["expert_load"][-steps:]
    per_token = float(np.mean([l.sum(-1).mean() for l in loads])
                      ) / tokens_per_chip
    return per_token, {"routing": [l.tolist() for l in loads]}


def _sum(readings):
    import numpy as np
    return sum(np.asarray(r, np.float64) for r in readings)


LFM2 = Model(
    name="LFM2 (gated short convolution + QK-normed GQA + sigmoid router)",
    lacks=lfm2_lacks, model_config=lfm2_model_config,
    reference="reference_lfm2", reference_arch=lfm2_reference_arch,
    sampled_leaves=lfm2_sampled_leaves,
    # the reference calls the counters "load"
    aux={"expert_load": lambda readings: _sum(r["load"] for r in readings)},
    record=_record_routing, extra_checks=routing_checks,
    work_shape=lfm2_work_shape, work=work_conv,
    work_context=routing_context,
    attention_layers=lambda cfg: sum(
        1 for l in cfg.layers if l.mixer == "attention"))


def model_config(cell, interpret):
    """``fit_mode.py`` and the precision control take the mode's
    configuration by this name."""
    return LFM2.model_config(cell, interpret)


# ------------------------------------------------- the reference's reading

def _reference(model):
    import importlib
    return importlib.import_module(f"benchmark.lib.{model.reference}")


def _cut_leaf(leaf, rows, cols):
    """``train_hybrid._cut``; a leaf compared whole (a norm's 64 floats
    have no second dimension to cut) is left as it is."""
    return leaf if rows is None and cols is None else _cut(leaf, rows, cols)


def reference_reading(model, cfg, cell, seed, source, loss_and_grads=None):
    """What the plain reference reads on the first global batch of
    ``seed``: ``{"loss", "aux" (the leaves ``model.aux`` names), "grads"
    and "before" of the sampled leaves, cut as they are compared}``.
    ``loss_and_grads(p0, tokens, targets, arch, paths)`` takes the place
    of the reference module's where a reading in a lower precision is
    wanted (``benchmark/tools/precision_control_conv.py``)."""
    import jax
    import numpy as np

    from horovod_tpu.models import transformer as tfm
    reference = _reference(model)
    p0 = jax.jit(lambda k: tfm.init_params(k, cfg))(
        jax.random.PRNGKey(seed))
    arch = model.reference_arch(cell)
    leaves = model.sampled_leaves(cfg)
    paths = [p for p, _, _ in leaves]
    gb = cell["traffic"]["global_batch"]
    tokens, targets = source(range(gb))
    fn = jax.jit(lambda p, a, b: (loss_and_grads
                                  or reference.loss_and_grads)(
        p, a, b, arch, paths))
    loss, auxes, grads = 0.0, [], None
    for i in range(gb):  # one sequence at a time
        (l_mb, aux), g_mb = fn(p0, tokens[i:i + 1], targets[i:i + 1])
        loss += float(l_mb) / gb
        auxes.append(jax.device_get(aux))
        g_mb = [_cut_leaf(g, r, c) / gb for g, (_, r, c) in zip(g_mb, leaves)]
        grads = g_mb if grads is None else [x + y for x, y
                                            in zip(grads, g_mb)]
    return {"loss": loss,
            "aux": {key: merge(auxes) for key, merge in model.aux.items()},
            "grads": [np.asarray(g, np.float32) for g in grads],
            "before": [np.asarray(_cut_leaf(reference.get_leaf(p0, path), r, c))
                       for path, r, c in leaves]}


#: ``optax.adamw``'s first-moment decay, which ``base_optimizer`` leaves as
#: it is: after step 0 the first moment is ``(1 - ADAM_B1) * g``
ADAM_B1 = 0.9


def first_rate(optimizer):
    """The learning rate of step 0 as ``base_optimizer`` reads the cell."""
    return optimizer["learning_rate"] / (optimizer.get("lr_warmup_steps")
                                         or 1)


def first_moment(opt_state):
    """The tree of adam's first moments in an optimizer state: the update
    of the step just made before it is scaled by the rate and added."""
    import optax
    return optax.tree_utils.tree_get(opt_state, "mu")


def compare_with_reference(model, cfg, want, loss0, aux0, p1, m1, tol, lr0):
    """Checks (a) and (b) and the model's own of ``(step-0 loss, step-0
    aux, the sampled leaves ``p1`` and their first moments ``m1`` after the
    first update)`` against a :func:`reference_reading`: the loss; the sign
    of the first update on the sampled leaves against the reference's
    global-batch gradient, on the elements above the leaf's median |g|;
    ``model.extra_checks``.

    The update's sign is read from the parameters wherever float32 holds a
    step of ``lr0`` (the rate of step 0) on the element: ``before - lr0
    sign(g)`` differs from ``before``. Where it does not (under a long
    warm-up, elements of 0.5 and more), the sign is that of the first
    moment, and the element must have kept its bits.
    ``first_moment_share`` says how much of each leaf was read so, and
    ``grad_rel_err`` is ``|m1 / (1 - ADAM_B1) - g| / |g|`` over the whole
    sampled leaf: the program's first gradient against the reference's, a
    reading with no limit (PERF.md section 7).

    Returns ``(ok, what was compared)``, the limits missed among it
    (``limits_missed``)."""
    import numpy as np
    extra, limits = model.extra_checks(want["aux"], aux0)
    out = dict(extra, loss0=loss0, reference_loss0=want["loss"],
               loss0_abs_err=abs(loss0 - want["loss"]), sign_agreement={},
               first_moment_share={}, grad_rel_err={})
    missed = [name for name, value in
              [("loss0_abs", out["loss0_abs_err"])] + limits
              if not value <= tol[name]]
    for (path, _, _), g, before, after, moment in zip(
            model.sampled_leaves(cfg), want["grads"], want["before"], p1,
            m1):
        before = np.asarray(before, np.float32)
        down = np.sign(g).astype(np.float32)
        holds = before - np.float32(lr0) * down != before
        delta = after - before
        agree = np.where(holds, np.sign(delta) == -down,
                         (delta == 0) & (np.sign(moment) == down))
        big = np.abs(g) > np.median(np.abs(g))
        name = "/".join(map(str, path))
        out["sign_agreement"][name] = float(np.mean(agree[big]))
        out["first_moment_share"][name] = float(np.mean(~holds[big]))
        out["grad_rel_err"][name] = float(
            np.linalg.norm(np.asarray(moment, np.float64) / (1 - ADAM_B1)
                           - g) / np.linalg.norm(g))
        if not out["sign_agreement"][name] >= tol.get(
                "sign_agreement_min_by_leaf", {}).get(
                    name, tol["sign_agreement_min"]):
            missed.append(f"sign_agreement_min:{name}")
    return not missed, dict(out, limits_missed=missed)


# ------------------------------------------------------------------- run

def run(cell, args, t_start, model=LFM2):
    """One run of one cell. Returns the result dict ``run.py`` prints."""
    from horovod_tpu.models import transformer as tfm
    lacking = model.lacks(tfm)
    if lacking:
        raise SystemExit(f"benchmark: cell {cell['name']} needs {lacking}; "
                         "this program has none")
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from benchmark.lib import cells, data, flops, trace_reduce

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
    cfg = model.model_config(cell, interpret=rehearsal)
    run_cfg, traffic = cell["cell"], cell["traffic"]
    gb, seq = traffic["global_batch"], traffic["seq_len"]
    if gb % n:
        raise SystemExit(f"benchmark: global batch {gb} does not divide "
                         f"over {n} chips")
    source = data.make_source(traffic, args.seed, cfg.vocab_size)
    reference = _reference(model)

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
    leaves = model.sampled_leaves(cfg)
    losses, read = [], {key: [] for key in model.aux}

    def read_back(loss, aux):
        """The loss and the aux leaves of a finished step, as a logging
        trainer reads them; the aux also goes to the program's own metric
        families."""
        losses.append(float(loss))
        aux = jax.device_get(aux)
        model.record(hvd, aux)
        for key in read:  # the step means its aux over the chips
            read[key].append(np.asarray(aux[key], np.float64))

    with spans.span("first_batch"):
        batch = next(batches)
    first_ok = bool(np.array_equal(np.asarray(batch[0]),
                                   source(range(gb))[0]))
    with spans.span("first_step"):
        params, opt_state, loss, aux = step(params, opt_state, *batch)
        read_back(loss, aux)
    with spans.span("snapshot_leaves"):
        p1, m1 = ([np.asarray(_cut_leaf(reference.get_leaf(tree, path), rows,
                                        cols))
                   for path, rows, cols in leaves]
                  for tree in (params, first_moment(opt_state)))
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
    # the grouped matmuls are Mosaic calls too: the attention kernels are
    # told apart by the names the program gives them
    kernels = len(re.findall(
        r'custom_call_target="tpu_custom_call"[^\n]*hvd_flash_', hlo or ""))
    need = model.attention_layers(cfg) * run_cfg.get(
        "min_flash_calls_per_layer", 2)
    checks["flash_kernels_compiled"] = rehearsal or (
        kernels >= need and cfg.flash_interpret is False)
    checks["loss_finite"] = bool(np.all(np.isfinite(losses)) and all(
        np.all(np.isfinite(step_aux)) for series in read.values()
        for step_aux in series))
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
            # the step means its aux over the chips; counts are per chip
            aux0 = {key: series[0] * n for key, series in read.items()}
            checks["reference"], ref = compare_with_reference(
                model, cfg, reference_reading(model, cfg, cell, args.seed,
                                              source),
                losses[0], aux0, p1, m1, run_cfg["tolerances"],
                first_rate(run_cfg["optimizer"]))
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
    shape = model.work_shape(cell)
    tokens_per_chip = gb // n * seq
    per_token, aux_ctx = model.work_context(read, steps, tokens_per_chip)
    need_flops, per_layer = model.work.required_flops_per_token(
        shape, per_token)
    ctx = {"spans": spans.rows, "steps": steps, "trace": trace,
           "counters": {k: counters1[k] - counters0.get(k, 0.0)
                        for k in counters1},
           **aux_ctx,
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
    # the longest of each host span in the window: where a stalled run
    # lost its time (PERF.md section 7)
    longest = {}
    for phase, name, s, e in spans.rows:
        if phase == "window":
            longest[name] = max(longest.get(name, 0.0), e - s)
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
            "model": model.name, "counts": counts, "reference": ref,
            "n_params": n_params, "loss_band_mean": band_mean,
            "losses": losses, "flash_custom_calls": kernels,
            "trace_error": trace_err, "window_s": t_w1 - t_w0,
            "memory_stats": mem, "step_memory_analysis_bytes": program,
            "required_flops_per_token": need_flops,
            "forward_flops_per_token_by_layer": per_layer,
            "assignments_per_token_per_sparse_layer": per_token,
            "aux_by_step": {key: [a.tolist() for a in series]
                            for key, series in read.items()},
            "setup_spans_s": {name: e - s for phase, name, s, e
                              in spans.rows if phase == "setup"},
            "window_spans_max_s": longest,
            "post_spans_s": {name: e - s for phase, name, s, e
                             in spans.rows if phase == "post"},
            "total_s": time.perf_counter() - t_start},
    }
