#!/usr/bin/env python
"""Flagship transformer train-step benchmark: tokens/sec AND MFU.

The ResNet headline (bench.py) is a convolution workload; the transformer
is matmul-dominated, so this harness is where the chip's MXU utilization
would show: the TransformerLM (flash attention, bf16, RoPE, chunked cross
entropy) trained on synthetic data, reporting device-side tokens/sec and
MFU. Nothing here has been measured on the current code and chip — see
PERF.md.

Protocol mirrors bench.py (itself protocol-parity with the reference's
examples/tensorflow_synthetic_benchmark.py:88-107): untimed warmup of both
jit specializations, then ITERS iterations of STEPS_PER_ITER train steps
fused into one device program by lax.scan, mean +- 1.96 sigma, with the
measured per-dispatch host overhead reported and removed from the
device-side number.

MFU convention: analytic model FLOPs / device-side step time / peak bf16
FLOPs. FLOPs per token = 6 x (matmul params) + 6 x L x S x d_model — the
PaLM-style estimate with CAUSAL attention counted at half the full S^2
(flash computes only the lower triangle), fwd+bwd = 3x the forward matmuls.
Embedding gather, norms, and softmax are excluded (convention).

Prints ONE JSON line:
  {"metric": "transformer_tokens_per_sec_per_chip", "value": N,
   "unit": "tokens/sec", "mfu_pct": M, "batch_per_chip": B, "seq_len": S,
   ..., "platform": P, "device_kind": K, "device_count": C}
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

sys.path.insert(0, ".")

import horovod_tpu as hvd  # noqa: E402
from horovod_tpu.models import moe as moe_lib  # noqa: E402
from horovod_tpu.models import transformer as tfm  # noqa: E402

from horovod_tpu.hardware import device_info  # noqa: E402

from bench import _dispatch_profile, _peak_flops  # noqa: E402

ITERS = 10
STEPS_PER_ITER = 5


def build_cfg(args):
    return tfm.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_kv_heads=args.kv_heads or None,
        n_layers=args.layers, d_ff=4 * args.d_model, max_seq=args.seq_len,
        dtype=jnp.bfloat16, positional="rope",
        attention_impl="dense" if args.dense else "flash",
        flash_interpret=args.interpret,
        loss_chunk=args.loss_chunk, remat=args.remat)


def matmul_param_count(params):
    """Parameters that live on the MXU path: qkv/wo/mlp/lm_head. The
    embedding table (a gather) and norm scales are excluded by the MFU
    convention."""
    total = 0
    for layer in params["layers"]:
        for k, v in layer.items():
            if k.startswith(("wq", "wk", "wo", "w1", "w2", "moe")):
                total += sum(x.size for x in jax.tree.leaves(v))
    total += params["lm_head"].size
    return total


def flops_per_token(params, cfg):
    """Train-step (fwd + bwd = 3x fwd) matmul FLOPs per token."""
    p_mm = matmul_param_count(params)
    attn = cfg.n_layers * cfg.max_seq * cfg.d_model  # causal half of S^2
    return 6 * p_mm + 6 * attn


def build_step(cfg, tx, mesh):
    axes = tfm.ShardAxes(dp="hvd", sp=None, tp=None)

    def per_shard_iter(params, opt_state, tokens, targets):
        def one_step(carry, _):
            params, opt_state = carry
            loss, g = jax.value_and_grad(
                lambda p: tfm.loss_fn(p, tokens, targets, cfg, axes))(params)
            updates, opt_state = tx.update(g, opt_state, params)
            return (optax.apply_updates(params, updates), opt_state), loss

        (params, opt_state), losses = jax.lax.scan(
            one_step, (params, opt_state), None, length=STEPS_PER_ITER)
        return params, opt_state, losses[-1][None]

    return jax.jit(jax.shard_map(
        per_shard_iter, mesh=mesh,
        in_specs=(P(), P(), P("hvd"), P("hvd")),
        out_specs=(P(), P(), P("hvd")),
        check_vma=False), donate_argnums=(0, 1))


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    # Defaults: the flagship single-v5e config — d_model 2048 (~490M
    # params), GQA 16q/4kv, seq 4096, per-chip batch 4 (chip_smoke.py
    # trains exactly this). Its throughput and MFU are not measured on
    # current code; PERF.md has what the chip has shown so far.
    ap.add_argument("--d-model", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=-1,
                    help="grouped-query attention KV head count; 0 = MHA, "
                         "-1 (default) = heads/4 when divisible else MHA")
    ap.add_argument("--vocab", type=int, default=32768)
    ap.add_argument("--seq-len", type=int, default=4096)
    ap.add_argument("--batch-per-chip", type=int, default=4)
    ap.add_argument("--loss-chunk", type=int, default=512)
    ap.add_argument("--remat", action="store_true",
                    help="jax.checkpoint each layer: ~1/3 more FLOPs for "
                         "O(layers) less activation HBM (fits larger "
                         "batches)")
    ap.add_argument("--dense", action="store_true",
                    help="dense attention instead of the flash kernel")
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpreter; only with --cpu-devices "
                         "(the chip compiles the kernels)")
    ap.add_argument("--moe", action="store_true",
                    help="run the expert-parallel MoE scenario instead: "
                         "2-D (data, expert) mesh, chunked alltoall "
                         "dispatch/combine (docs/performance.md "
                         "\"Expert-parallel MoE\")")
    ap.add_argument("--expert-parallel", type=int, default=4,
                    help="expert-axis size of the 2-D mesh the MoE "
                         "scenario re-inits with when the runtime has "
                         "none (HOROVOD_EXPERT_PARALLEL)")
    ap.add_argument("--moe-chunks", type=int, default=8,
                    help="capacity slices the dispatch/combine alltoall "
                         "is pipelined into (HOROVOD_MOE_CHUNKS; 1 = "
                         "unchunked, bit-identical either way)")
    ap.add_argument("--moe-experts", type=int, default=8)
    ap.add_argument("--moe-capacity-factor", type=float, default=2.0)
    ap.add_argument("--moe-batch", type=int, default=32,
                    help="GLOBAL sequence count for the MoE scenario "
                         "(sharded over every mesh device)")
    ap.add_argument("--moe-seq", type=int, default=64)
    ap.add_argument("--moe-d-model", type=int, default=256)
    ap.add_argument("--moe-d-ff", type=int, default=1024)
    ap.add_argument("--mesh3d", action="store_true",
                    help="run the composable-parallelism scenario "
                         "instead: a TP dense trunk + expert-parallel "
                         "MoE FFN + ZeRO-2 striping compiled into one "
                         "donated step program on the 3-D (data, "
                         "expert, model) mesh (docs/performance.md "
                         "\"Composable parallelism\")")
    ap.add_argument("--mesh3d-ep", type=int, default=2,
                    help="expert-axis size of the 3-D mesh "
                         "(HOROVOD_EXPERT_PARALLEL)")
    ap.add_argument("--mesh3d-mp", type=int, default=2,
                    help="model-axis size of the 3-D mesh "
                         "(HOROVOD_MODEL_PARALLEL)")
    ap.add_argument("--mesh3d-batch", type=int, default=16,
                    help="GLOBAL sequence count (sharded over the data "
                         "and expert axes, replicated over model)")
    ap.add_argument("--mesh3d-seq", type=int, default=32)
    ap.add_argument("--mesh3d-d-model", type=int, default=64)
    ap.add_argument("--mesh3d-layers", type=int, default=2)
    ap.add_argument("--mesh3d-vocab", type=int, default=256)
    ap.add_argument("--serve", action="store_true",
                    help="run the continuous-batching serving scenario "
                         "instead: paged-KV decode engine on the mesh, "
                         "reporting TTFT and per-token latency "
                         "percentiles plus tokens/sec at N concurrent "
                         "streams (docs/serving.md)")
    ap.add_argument("--serve-streams", type=int, default=8,
                    help="concurrent generation streams")
    ap.add_argument("--serve-prompt-len", type=int, default=16)
    ap.add_argument("--serve-new-tokens", type=int, default=32)
    ap.add_argument("--serve-page-size", type=int, default=16,
                    help="KV pool page size in tokens "
                         "(HOROVOD_SERVE_PAGE_SIZE)")
    ap.add_argument("--serve-d-model", type=int, default=128)
    ap.add_argument("--serve-layers", type=int, default=2)
    ap.add_argument("--serve-heads", type=int, default=8)
    ap.add_argument("--serve-vocab", type=int, default=1024)
    ap.add_argument("--iters", type=int, default=ITERS)
    ap.add_argument("--cpu-devices", type=int, default=0,
                    help="force an N-device virtual CPU mesh (hermetic "
                         "smoke runs without a chip)")
    args = ap.parse_args(argv)
    if args.kv_heads == -1:
        # derive from --heads so overriding one flag never crashes the
        # config validation (heads 6 -> MHA, heads 16 -> GQA 16q/4kv)
        args.kv_heads = args.heads // 4 if args.heads % 4 == 0 else 0

    if args.interpret and not args.cpu_devices:
        ap.error("--interpret needs --cpu-devices: on a chip the flash "
                 "kernels are compiled, never interpreted")
    if args.cpu_devices:
        from horovod_tpu.utils.devices import force_host_device_count
        force_host_device_count(args.cpu_devices)
    return args


def run_benchmark(args):
    """The measurement, sans printing/shutdown — bench.py embeds this at
    reduced iters so the driver's BENCH json carries the flagship
    transformer row next to ResNet (round-3 verdict: the MFU number must
    be driver-captured, not docs-only). Returns the result dict."""
    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()
    overhead = _dispatch_profile()["full_ms"] / 1e3

    cfg = build_cfg(args)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tx = hvd.DistributedOptimizer(optax.adamw(3e-4), axis_name="hvd")
    opt_state = tx.init(params)
    step = build_step(cfg, tx, mesh)

    batch = args.batch_per_chip * n
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (batch, args.seq_len),
                           0, cfg.vocab_size),
        NamedSharding(mesh, P("hvd")))
    targets = jnp.roll(tokens, -1, axis=1)
    params = jax.device_put(params, NamedSharding(mesh, P()))
    opt_state = jax.device_put(opt_state, NamedSharding(mesh, P()))

    for _ in range(2):  # both jit specializations compile untimed
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        float(np.asarray(loss)[0])

    tok_per_iter = args.batch_per_chip * args.seq_len * STEPS_PER_ITER
    rates = []
    for _ in range(args.iters):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        float(np.asarray(loss)[0])
        rates.append(tok_per_iter / (time.perf_counter() - t0))
    mean = float(np.mean(rates))
    conf = float(1.96 * np.std(rates))
    # clamp: if the measured overhead swamps an (untypically short) wall
    # time, don't let the subtraction manufacture an absurd device rate
    dev_rates = [tok_per_iter / max(tok_per_iter / r - overhead,
                                    0.1 * tok_per_iter / r)
                 for r in rates]
    dev_mean = float(np.mean(dev_rates))

    ftok = flops_per_token(params, cfg)
    peak = _peak_flops()
    mfu = None if not peak else ftok * dev_mean / peak * 100.0

    print(f"# Tokens/sec per chip: {mean:,.0f} +-{conf:,.0f} (device-side "
          f"{dev_mean:,.0f}) at batch {args.batch_per_chip} x seq "
          f"{args.seq_len}, {ftok/1e6:.0f} MFLOPs/token, MFU "
          f"{mfu if mfu is None else round(mfu, 1)}%, dispatch overhead "
          f"{overhead*1e3:.1f} ms", file=sys.stderr)
    return {
        "metric": "transformer_tokens_per_sec_per_chip",
        "value": round(mean, 1),
        "unit": "tokens/sec",
        "tokens_per_sec_device_side": round(dev_mean, 1),
        "mfu_pct": None if mfu is None else round(mfu, 2),
        "flops_per_token": ftok,
        "batch_per_chip": args.batch_per_chip,
        "seq_len": args.seq_len,
        "d_model": args.d_model,
        "layers": args.layers,
        "attention": "dense" if args.dense else "flash",
        "dispatch_overhead_ms": round(overhead * 1e3, 2),
    }


def run_moe_benchmark(args):
    """Expert-parallel MoE scenario (docs/performance.md "Expert-parallel
    MoE"): the capacity-routed MoE layer trained through the single
    donated step program on the 2-D (data, expert) mesh, with the
    dispatch/combine alltoall chunked so expert FFN compute overlaps the
    wire inside one XLA schedule. Measures tokens/sec, then captures a
    phase-attributed device trace of the same program to report the
    alltoall ms/step and the overlap fraction ``alltoall_hidden_frac``
    (hvd_dispatch/hvd_combine device time covered by hvd_expert
    intervals), plus the routing drop fraction from a ``with_stats``
    evaluation. The acceptance numbers live in the returned dict's
    ``"moe"`` sub-dict — bench.py embeds it in the headline JSON and the
    CI ``moe-smoke`` step asserts ``alltoall_hidden_frac >= 0.3``,
    ``step_program_cache_hit_rate >= 0.9`` and zero fallback steps on
    the 8-device CPU mesh."""
    from horovod_tpu import metrics as hvd_metrics
    from horovod_tpu.exceptions import HorovodError

    hvd.init()
    try:
        mesh = hvd.expert_mesh()
    except HorovodError:
        # runtime is up on the flat 1-D mesh: re-init with the 2-D
        # (data, expert) factorization the MoE exchange maps over
        hvd.shutdown()
        os.environ["HOROVOD_EXPERT_PARALLEL"] = str(args.expert_parallel)
        hvd.init()
        mesh = hvd.expert_mesh()
    ep = hvd.expert_parallel_size()
    n = hvd.size()
    axes = tuple(mesh.axis_names)          # ("hvd", "ep")
    chunks = max(1, args.moe_chunks)

    cfg = moe_lib.MoEConfig(
        d_model=args.moe_d_model, d_ff=args.moe_d_ff,
        num_experts=args.moe_experts, top_k=2,
        capacity_factor=args.moe_capacity_factor, dtype=jnp.float32)
    e_loc = cfg.num_experts // ep
    full = moe_lib.init_moe_params(jax.random.PRNGKey(0), cfg)

    def shard_fn(p):
        i = lax.axis_index("ep") * e_loc
        return {"w_router": p["w_router"],
                "w1": lax.dynamic_slice_in_dim(p["w1"], i, e_loc, 0),
                "w2": lax.dynamic_slice_in_dim(p["w2"], i, e_loc, 0)}

    # fake-replicated expert shards: P() specs, per-device values differ
    # (the layout the moe step program consumes; check_vma=False idiom)
    params = jax.jit(jax.shard_map(shard_fn, mesh=mesh, in_specs=(P(),),
                                   out_specs=P(), check_vma=False))(full)

    def loss_fn(p, x, y):
        out, aux = moe_lib.moe_layer(p, x, cfg, ep_axis="ep",
                                     chunks=chunks)
        return jnp.mean((out - y) ** 2) + 0.01 * aux

    tx = hvd.DistributedOptimizer(optax.sgd(0.05),
                                  expert_keys=("w1", "w2"))
    step = hvd.compiled_train_step(loss_fn, tx, name="bench.moe")
    opt_state = step.init(params)

    batch, seq = args.moe_batch, args.moe_seq
    assert batch % n == 0, f"--moe-batch {batch} not divisible by {n}"
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    sharding = NamedSharding(mesh, P(axes))
    x = jax.device_put(
        jax.random.normal(kx, (batch, seq, cfg.d_model), jnp.float32),
        sharding)
    y = jax.device_put(
        jax.random.normal(ky, (batch, seq, cfg.d_model), jnp.float32),
        sharding)
    opt_state = jax.device_put(opt_state, NamedSharding(mesh, P()))

    for _ in range(2):  # untimed warmup: compile, then one steady step
        params, opt_state, loss = step(params, opt_state, x, y)
    jax.block_until_ready(loss)
    h0, m0 = step.cache_hits, step.cache_misses

    tok_per_chip = batch * seq // n
    iters = max(args.iters, 8)
    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, x, y)
        jax.block_until_ready(loss)
        rates.append(tok_per_chip / (time.perf_counter() - t0))
    mean = float(np.mean(rates))
    conf = float(1.96 * np.std(rates))
    hits = step.cache_hits - h0
    misses = step.cache_misses - m0
    hit_rate = hits / max(hits + misses, 1)

    # Phase-attributed device trace of the same program, AFTER the timed
    # loop (the _compiled_step_profile idiom) — the overlap number the
    # chunked pipeline exists for.
    import tempfile

    from horovod_tpu.config import Config
    trace_n = 4
    phase_ms = moe_trace = None
    a2a_ms = hidden_frac = None
    out_base = Config.from_env().diag_dir or tempfile.mkdtemp(
        prefix="bench-moe-trace-")
    tracer = hvd.trace_steps(trace_n, out_dir=out_base)
    for _ in range(trace_n + 2):
        params, opt_state, loss = step(params, opt_state, x, y)
        jax.block_until_ready(loss)
    if tracer.active or tracer.armed:
        tracer.stop()
    summary = tracer.last_summary
    trace_dir = tracer.last_dir
    if summary:
        per = 1e3 / trace_n / max(summary["lanes"], 1)
        phase_ms = {p: round(v * per, 3)
                    for p, v in summary["phases"].items()}
        moe_trace = summary.get("moe")
        if moe_trace:
            a2a_ms = round(moe_trace["alltoall_s"] * per, 3)
            hidden_frac = round(moe_trace["hidden_frac"], 4)

    # Routing accounting from one with_stats evaluation of the same
    # layer (psummed so every rank reports the same global numbers);
    # feeds the hvd_moe_* families (docs/observability.md).
    def stats_fn(p, xs):
        _, _, stats = moe_lib.moe_layer(p, xs, cfg, ep_axis="ep",
                                        chunks=chunks, with_stats=True)
        return {"routed": lax.psum(stats["routed_tokens"], axes),
                "dropped": lax.psum(stats["dropped_tokens"], axes),
                "lb": lax.pmean(stats["load_balance_loss"], axes),
                "chunks": jnp.int32(stats["chunks"])}

    stats = jax.jit(jax.shard_map(
        stats_fn, mesh=mesh, in_specs=(P(), P(axes)), out_specs=P(),
        check_vma=False))(params, x)
    routed = float(np.asarray(stats["routed"]))
    dropped = float(np.asarray(stats["dropped"]))
    lb = float(np.asarray(stats["lb"]))
    chunks_used = int(np.asarray(stats["chunks"]))
    drop_frac = dropped / max(routed + dropped, 1.0)
    hvd_metrics.record_moe_step(routed, dropped, lb, chunks_used)
    if hidden_frac is not None:
        hvd_metrics.MOE_ALLTOALL_HIDDEN_FRAC.set(hidden_frac)

    print(f"# MoE tokens/sec per chip: {mean:,.0f} +-{conf:,.0f} at "
          f"E={cfg.num_experts} ep={ep} chunks={chunks_used}, alltoall "
          f"{a2a_ms} ms/step hidden_frac {hidden_frac}, drop_frac "
          f"{drop_frac:.4f}, cache hit rate {hit_rate:.2f}, fallbacks "
          f"{step.fallback_steps}", file=sys.stderr)
    return {
        "metric": "moe_tokens_per_sec_per_chip",
        "value": round(mean, 1),
        "unit": "tokens/sec",
        "moe": {
            "tokens_per_sec_per_chip": round(mean, 1),
            "spread": round(conf, 1),
            # per-lane device ms of dispatch+combine alltoall per step,
            # and the fraction of it hidden behind expert FFN compute
            "alltoall_ms_per_step": a2a_ms,
            "alltoall_hidden_frac": hidden_frac,
            "drop_fraction": round(drop_frac, 4),
            "routed_tokens": routed,
            "dropped_tokens": dropped,
            "load_balance_loss": round(lb, 4),
            "num_experts": cfg.num_experts,
            "expert_parallel": ep,
            "moe_chunks": chunks_used,
            "capacity_factor": cfg.capacity_factor,
            "top_k": cfg.top_k,
            "batch_per_chip": batch // n,
            "seq_len": seq,
            "d_model": cfg.d_model,
            "d_ff": cfg.d_ff,
            "step_program_cache_hit_rate": round(hit_rate, 4),
            "step_program_cache_hits": hits,
            "step_program_cache_misses": misses,
            "fallback_steps": step.fallback_steps,
            "step_phase_breakdown": phase_ms,
            "xla_trace_dir": trace_dir,
            "steps": iters,
        },
    }


def run_mesh3d_benchmark(args):
    """Composable-parallelism scenario (docs/performance.md "Composable
    parallelism"): a small TransformerLM whose dense trunk is
    tensor-parallel over the ``model`` axis (head-sharded attention,
    column/row-split FFN, vocab-parallel embed/head and cross entropy),
    whose FFN at one layer is an expert-parallel MoE block routed over
    ``ep``, trained with ZeRO-2 gradient striping over the data axis —
    the formerly rejected moe x zero combination — all compiled into ONE
    donated step program on the 3-D (data, expert, model) mesh via the
    per-leaf sharding spec.

    Reports tokens/sec plus the numbers the CI ``mesh3d-smoke`` step
    asserts on the 2x2x2 CPU mesh: ``step_program_cache_hit_rate >=
    0.9``, zero fallback steps, and ``zero2_parity_max_delta`` — the
    same spec trained WITHOUT striping (zero_stage=0) from the same init
    must match the striped run within float noise over 5 steps (the
    moe+zero2 parity contract of tests/test_sharding_spec.py, run here
    on the real model). The acceptance numbers live in the returned
    dict's ``"mesh3d"`` sub-dict, which bench.py embeds in the headline
    JSON."""
    from jax.tree_util import tree_flatten_with_path
    from horovod_tpu.exceptions import HorovodError

    hvd.init()
    try:
        mesh = hvd.model_mesh()
    except HorovodError:
        # runtime is up without a model axis: re-init with the 3-D
        # (data, expert, model) factorization the spec compiles over
        hvd.shutdown()
        os.environ["HOROVOD_EXPERT_PARALLEL"] = str(args.mesh3d_ep)
        os.environ["HOROVOD_MODEL_PARALLEL"] = str(args.mesh3d_mp)
        hvd.init()
        mesh = hvd.model_mesh()
    n = hvd.size()
    ep = hvd.expert_parallel_size()
    mp = hvd.model_parallel_size()
    data_shards = n // (ep * mp) * ep  # batch shards: data x expert

    cfg = tfm.TransformerConfig(
        vocab_size=args.mesh3d_vocab, d_model=args.mesh3d_d_model,
        n_heads=4, n_kv_heads=None, n_layers=args.mesh3d_layers,
        d_ff=4 * args.mesh3d_d_model, max_seq=args.mesh3d_seq,
        dtype=jnp.float32, positional="rope", attention_impl="dense",
        moe_layers=(args.mesh3d_layers - 1,), moe_num_experts=2 * ep,
        moe_top_k=2)
    # dp/sp None: the compiled step owns the global batch mean (its
    # exchange reduces over the data and expert axes per leaf spec)
    axes = tfm.ShardAxes(dp=None, sp=None, tp="model", ep="ep")
    specs = tfm.param_specs(cfg, axes)
    model_keys = tfm.model_parallel_keys(cfg, axes)
    expert_keys = ("['moe']['w1']", "['moe']['w2']")
    full = tfm.init_params(jax.random.PRNGKey(0), cfg)

    def loss_fn(p, tokens, targets):
        return tfm.loss_fn(p, tokens, targets, cfg, axes)

    batch, seq = args.mesh3d_batch, args.mesh3d_seq
    assert batch % data_shards == 0, \
        f"--mesh3d-batch {batch} not divisible by {data_shards} " \
        f"(data x expert shards)"
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                           0, cfg.vocab_size),
        NamedSharding(mesh, P(tuple(a for a in mesh.axis_names
                                    if a != "model"))))
    targets = jnp.roll(tokens, -1, axis=1)

    def make_step(zero_stage):
        tx = hvd.DistributedOptimizer(
            optax.sgd(0.05), expert_keys=expert_keys,
            model_keys=model_keys, zero_stage=zero_stage)
        assert tx.update._hvd_exchange == "spec"
        return tx, hvd.compiled_train_step(
            loss_fn, tx, name=f"bench.mesh3d.z{zero_stage}")

    def train(step, steps):
        p = tfm.slice_param_shards(full, specs, mesh)
        s = step.init(p)
        for _ in range(steps):
            p, s, loss = step(p, s, tokens, targets)
        jax.block_until_ready(loss)
        return p, s, loss

    def max_delta(a, b):
        worst = 0.0
        for va, vb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            for sa, sb in zip(va.addressable_shards,
                              vb.addressable_shards):
                worst = max(worst, float(np.max(np.abs(
                    np.asarray(sa.data) - np.asarray(sb.data)))))
        return worst

    # Parity leg: the same spec without striping, 5 steps from the same
    # init (every train() call slices a fresh param copy, so the donated
    # programs never alias a buffer another leg still reads).
    combo_tx, step = make_step(zero_stage=2)
    _, step0 = make_step(zero_stage=0)
    p2, _, _ = train(step, 5)
    p0, _, _ = train(step0, 5)
    parity = max_delta(p2, p0)

    # Timed leg: the striped combo program, donated steady state.
    params, opt_state, loss = train(step, 2)  # untimed warmup
    h0, m0 = step.cache_hits, step.cache_misses
    tok_per_chip = batch * seq // n
    iters = max(args.iters, 8)
    rates = []
    for _ in range(iters):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, targets)
        jax.block_until_ready(loss)
        rates.append(tok_per_chip / (time.perf_counter() - t0))
    mean = float(np.mean(rates))
    conf = float(1.96 * np.std(rates))
    hits = step.cache_hits - h0
    misses = step.cache_misses - m0
    hit_rate = hits / max(hits + misses, 1)

    # What the spec decided, per exchange family (the hvd_spec_leaves
    # gauge families, recomputed here so the JSON is self-contained).
    spec = combo_tx.update._hvd_spec
    kinds = [spec._kind(path)
             for path, _ in tree_flatten_with_path(full)[0]]
    spec_leaves = {k: kinds.count(k) for k in ("dense", "expert", "model")}

    print(f"# 3-D mesh tokens/sec per chip: {mean:,.0f} +-{conf:,.0f} at "
          f"mesh {dict(mesh.shape)} (zero2 + moe + TP in one program), "
          f"parity vs unstriped {parity:.2e}, cache hit rate "
          f"{hit_rate:.2f}, fallbacks {step.fallback_steps}",
          file=sys.stderr)
    return {
        "metric": "mesh3d_tokens_per_sec_per_chip",
        "value": round(mean, 1),
        "unit": "tokens/sec",
        "mesh3d": {
            "tokens_per_sec_per_chip": round(mean, 1),
            "spread": round(conf, 1),
            "mesh_shape": {k: int(v) for k, v in mesh.shape.items()},
            "expert_parallel": ep,
            "model_parallel": mp,
            "zero_stage": 2,
            "spec_leaves": spec_leaves,
            "model_keys": len(model_keys),
            "zero2_parity_max_delta": parity,
            "parity_steps": 5,
            "global_batch": batch,
            "seq_len": seq,
            "d_model": cfg.d_model,
            "layers": cfg.n_layers,
            "moe_layers": list(cfg.moe_layers),
            "num_experts": cfg.moe_num_experts,
            "step_program_cache_hit_rate": round(hit_rate, 4),
            "step_program_cache_hits": hits,
            "step_program_cache_misses": misses,
            "fallback_steps": step.fallback_steps,
            "steps": iters,
        },
    }


def run_serve_benchmark(args):
    """Continuous-batching serving scenario (docs/serving.md): the
    paged-KV decode engine driven at ``--serve-streams`` concurrent
    generation streams on the runtime's mesh, tensor-parallel over the
    flat ``hvd`` axis. One untimed warmup round compiles the (single,
    bin-floor-pinned) prefill and decode programs; the measured round
    then reports TTFT p50/p99, per-token decode latency p50/p99, and
    generated tokens/sec across all streams. The acceptance numbers
    live in the returned dict's ``"serve"`` sub-dict — bench.py embeds
    it in the headline JSON and the CI ``serve-smoke`` step asserts
    ``decode_cache_hit_rate >= 0.9`` and zero fallback steps on the
    8-device CPU mesh."""
    from horovod_tpu import serve as hvd_serve

    hvd.init()
    n = hvd.size()
    mesh = hvd.mesh()

    streams = max(int(args.serve_streams), 1)
    prompt_len = max(int(args.serve_prompt_len), 1)
    new_tokens = max(int(args.serve_new_tokens), 2)
    page_size = max(int(args.serve_page_size), 1)
    pages_per_seq = -(-(prompt_len + new_tokens) // page_size)
    # headroom: two full generations' worth of pages + the null page
    num_pages = 1 + 2 * streams * pages_per_seq

    # Small MHA model (h_kv == heads must divide the tp axis so the KV
    # pool shards on the kv-head dim); dense attention — the prefill
    # trunk is the training forward, and the smoke mesh is CPU.
    cfg = tfm.TransformerConfig(
        vocab_size=args.serve_vocab, d_model=args.serve_d_model,
        n_heads=args.serve_heads, n_kv_heads=None,
        n_layers=args.serve_layers, d_ff=4 * args.serve_d_model,
        max_seq=prompt_len + new_tokens, dtype=jnp.float32,
        positional="rope", attention_impl="dense")
    assert cfg.n_heads % n == 0, \
        f"--serve-heads {cfg.n_heads} not divisible by world size {n}"
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    # Bin floors pinned to the stream count: exactly ONE prefill and
    # ONE decode signature for the whole run, so steady-state decode is
    # all cache hits (the >= 0.9 acceptance bound).
    eng = hvd_serve.Engine(
        cfg, params, mesh=mesh, tp_axis="hvd",
        num_pages=num_pages, page_size=page_size,
        max_batch=streams, queue_depth=max(2 * streams, 8),
        start=False, batch_bin_floor=streams,
        page_bin_floor=pages_per_seq, len_bin_floor=prompt_len)
    se = eng.engine

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size,
                            size=prompt_len).tolist()
               for _ in range(streams)]

    def run_round():
        handles = [eng.submit(p, max_new_tokens=new_tokens)
                   for p in prompts]
        t0 = time.perf_counter()
        eng.batcher.drain()
        wall = time.perf_counter() - t0
        toks = sum(len(h.request.generated) for h in handles)
        return handles, toks, wall

    run_round()  # untimed warmup: compiles both binned programs
    eng.batcher.recent_ttft.clear()
    eng.batcher.recent_token_latency.clear()
    dh0, dm0 = se.decode_hits, se.decode_misses

    handles, toks, wall = run_round()
    tps = toks / wall
    ttft = np.asarray([h.request.first_token_t - h.request.submitted_t
                       for h in handles])
    tok_lat = np.asarray(eng.batcher.recent_token_latency)
    dh, dm = se.decode_hits - dh0, se.decode_misses - dm0
    steady_hit_rate = dh / max(dh + dm, 1)
    sig = eng.write_slo_signal()  # the SLO-elasticity payload
    pool = se.update_pool_metrics()

    print(f"# Serve tokens/sec: {tps:,.0f} at {streams} streams x "
          f"{new_tokens} new tokens (prompt {prompt_len}), TTFT p99 "
          f"{np.percentile(ttft, 99)*1e3:.1f} ms, token latency p99 "
          f"{np.percentile(tok_lat, 99)*1e3:.1f} ms, decode hit rate "
          f"{se.decode_hit_rate():.2f} (steady {steady_hit_rate:.2f}), "
          f"fallbacks {se.fallback_steps}", file=sys.stderr)
    return {
        "metric": "serve_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "serve": {
            "tokens_per_sec": round(tps, 1),
            "streams": streams,
            "prompt_len": prompt_len,
            "new_tokens": new_tokens,
            "ttft_p50_ms": round(float(np.percentile(ttft, 50)) * 1e3, 3),
            "ttft_p99_ms": round(float(np.percentile(ttft, 99)) * 1e3, 3),
            "token_latency_p50_ms": round(
                float(np.percentile(tok_lat, 50)) * 1e3, 3),
            "token_latency_p99_ms": round(
                float(np.percentile(tok_lat, 99)) * 1e3, 3),
            "slo_p99_latency_s": round(float(sig["p99_latency"]), 6),
            "decode_cache_hit_rate": round(se.decode_hit_rate(), 4),
            "steady_state_decode_hit_rate": round(steady_hit_rate, 4),
            "prefill_cache_hits": se.prefill_hits,
            "prefill_cache_misses": se.prefill_misses,
            "decode_cache_hits": se.decode_hits,
            "decode_cache_misses": se.decode_misses,
            "fallback_steps": se.fallback_steps,
            "page_size": page_size,
            "num_pages": num_pages,
            "kv_page_utilization": round(pool["utilization"], 4),
            "scheduler_steps": eng.batcher.steps,
            "d_model": cfg.d_model,
            "layers": cfg.n_layers,
            "heads": cfg.n_heads,
            "vocab": cfg.vocab_size,
            "devices": n,
        },
    }


def main(argv=None):
    args = parse_args(argv)
    result = (run_serve_benchmark(args) if args.serve
              else run_mesh3d_benchmark(args) if args.mesh3d
              else run_moe_benchmark(args) if args.moe
              else run_benchmark(args))
    print(json.dumps({**result, **device_info()}))
    hvd.shutdown()


if __name__ == "__main__":
    main()
