"""Operations and bytes of a model whose layers differ, counted per layer
kind. ``flops.py`` counts from ONE homogeneous shape (one head count, one
window, two-matrix MLPs); this module counts the same way — every matrix
multiplication of the forward pass at 2 FLOPs per multiply-add, attention
with the keys each query really sees, times 3 for forward + backward, no
recompute — from a list of layer descriptions::

    {"d_model", "head_dim", "n_kv_heads", "vocab_size", "seq_len",
     "layers": [{"n_heads", "window", "gate", "mlp": "dense" | "sparse"}],
     "d_ff", "mlp_matrices",                      dense FFN
     "experts": {"router_width", "held", "width", "shared_width",
                 "matrices"}}

Nothing here is read from the program under test, with one exception that
``correct`` holds to the reference's own count: the routed experts' work
depends on how many assignments the router sent to the experts held, which
is data. ``assignments_per_token`` is that count per token and sparse
layer, a mean over the window's steps.

The mode that runs such a model registers the readers below with the
general reader (``layer_metrics.READERS`` / ``WORK``) when it is imported;
a cell of another mode never sees them. A later benchmark PR folds this
module and ``flops.py`` into one.
"""

from . import flops, kernel_work, trace_reduce


def layer_forward_flops_per_token(shape, layer, assignments_per_token):
    """Forward FLOPs one token needs in one layer, by part."""
    d, hd, hkv = shape["d_model"], shape["head_dim"], shape["n_kv_heads"]
    h = layer["n_heads"]
    out = {"projections": 2 * d * (2 * h * hd + 2 * hkv * hd)
           + (2 * d * h if layer["gate"] else 0),
           "attention": 4 * hd * h * flops.attended_keys(
               shape["seq_len"], layer["window"]) / shape["seq_len"]}
    if layer["mlp"] == "dense":
        out["mlp"] = 2 * d * shape["d_ff"] * shape["mlp_matrices"]
        return out
    ex = shape["experts"]
    per_row = 2 * d * ex["matrices"]
    out["router"] = 2 * d * ex["router_width"]
    out["routed"] = per_row * ex["width"] * assignments_per_token
    out["shared"] = per_row * ex["shared_width"]
    return out


def required_flops_per_token(shape, assignments_per_token):
    """Forward + backward FLOPs one trained token requires (no
    recompute), and the forward count of each layer."""
    per_layer = [layer_forward_flops_per_token(shape, layer,
                                               assignments_per_token)
                 for layer in shape["layers"]]
    head = 2 * shape["d_model"] * shape["vocab_size"]
    total = 3.0 * (sum(sum(parts.values()) for parts in per_layer) + head)
    return total, per_layer + [{"head": head}]


def expert_matmul_seconds(shape, assignments, peaks):
    """Least time of ONE grouped matmul over ``assignments`` rows
    (rows x d_model x expert width, either way round): FLOPs at the peak
    against the compulsory bytes (rows in and out in the activation type,
    the held experts' matrix read once)."""
    d, ex = shape["d_model"], shape["experts"]
    f = 2.0 * assignments * d * ex["width"]
    b = 2.0 * (assignments * (d + ex["width"]) + ex["held"] * d
               * ex["width"])
    return flops.roofline_seconds(f, b, peaks)


def moe_experts(ctx):
    """``WORK["moe_experts"]``: least seconds per step of the grouped
    matmuls the step makes: per sparse layer one per expert matrix
    forward (again under remat), two per matrix backward."""
    shape, work = ctx["work"]["shape"], ctx["work"]
    sparse = sum(1 for l in shape["layers"] if l["mlp"] == "sparse")
    calls = shape["experts"]["matrices"] * ((2 if work["remat"] else 1) + 2)
    t, bound = expert_matmul_seconds(
        shape, work["assignments_per_token"] * work["tokens_per_chip"],
        ctx["peaks"])
    return sparse * calls * t, bound


def attn_window(ctx):
    """``WORK["attn_window"]``: least seconds per step of the three flash
    kernels in the windowed layers, each at the calls the trace shows
    (``kernel_work``: remat's second forward is in both)."""
    shape = ctx["work"]["shape"]
    layer = next(l for l in shape["layers"] if l["window"])
    one = {"seq_len": shape["seq_len"], "head_dim": shape["head_dim"],
           "n_heads": layer["n_heads"], "n_kv_heads": shape["n_kv_heads"],
           "window": layer["window"]}
    work = flops.flash_kernel_work(one, ctx["work"]["seqs_per_chip"])
    total, bound_by = 0.0, set()
    for kernel in ("fwd", "dq", "dkv"):
        t, bound = flops.roofline_seconds(*work[kernel], ctx["peaks"])
        total += t * kernel_work.calls_per_step(
            ctx["trace"], f"hvd_attn_window.*hvd_flash_{kernel}")
        bound_by.add(bound)
    return total, "+".join(sorted(bound_by))


def read_step_work(reader, ctx):
    """``READERS["step_work"]``: numbers of the whole step that need the
    per-layer count. ``mfu``: required FLOPs per step on one chip over the
    median device span of a step times the peak."""
    trace, work = ctx.get("trace"), ctx.get("work")
    if not work or not trace or not trace["devices"]:
        return None
    if reader["reduce"] != "mfu":
        raise SystemExit(f"step_work: unknown reduce {reader['reduce']!r}")
    need = work["required_flops_per_token"] * work["tokens_per_chip"]
    return trace_reduce.mean_over_devices(
        trace, lambda dev, d: 100.0 * need
        / (d["step_span_ns"] * 1e-9 * ctx["peaks"]["bf16_flops_per_s"]))


def read_routing(reader, ctx):
    """``READERS["routing"]``: the step's own routing counters over the
    window (``ctx["routing"]``: per step the assignments each held expert
    took in each sparse layer). ``load_max_over_mean``: the largest
    expert's load over the mean load, per layer and step, averaged."""
    loads = ctx.get("routing")
    if not loads:
        return None
    if reader["reduce"] != "load_max_over_mean":
        raise SystemExit(f"routing: unknown reduce {reader['reduce']!r}")
    ratios = [max(layer) / (sum(layer) / len(layer))
              for step in loads for layer in step if sum(layer)]
    return sum(ratios) / len(ratios) if ratios else None


def register(layer_metrics):
    layer_metrics.WORK["moe_experts"] = moe_experts
    layer_metrics.WORK["attn_window"] = attn_window
    layer_metrics.READERS["step_work"] = read_step_work
    layer_metrics.READERS["routing"] = read_routing
