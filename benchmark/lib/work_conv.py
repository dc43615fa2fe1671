"""Operations and bytes of a model that mixes gated short-convolution
layers with grouped-query attention layers and sparse FFNs, counted per
layer kind from the configuration file alone — every matrix multiplication
of the forward pass at 2 FLOPs per multiply-add, attention with the keys
each query really sees, times 3 for forward + backward, no recompute —
from::

    {"d_model", "vocab_size", "seq_len", "d_ff", "mlp_matrices",
     "head_dim", "n_heads", "n_kv_heads",          the attention layers
     "conv_taps",
     "layers": [{"mixer": "sconv" | "attention",
                 "mlp": "dense" | "sparse"}, ...],
     "experts": {"router_width", "held", "width", "shared_width",
                 "matrices"}}

Nothing here is read from the program under test, with the exception
``work_layers`` has too: the routed experts' work follows the assignments
the router sent to the experts held (``assignments_per_token``), which is
data and which ``correct`` holds to the reference's own count.

**The gate chain's bound is one no implementation can beat.** Between its
two projections a short-convolution layer does ``C * conv(B * u)``: three
multiplies and ``conv_taps`` multiply-adds a channel and position, nothing
for the matmul unit, so its least time is its compulsory bytes: B, C, u
read and the result written once in the activation type for a forward (4
arrays of tokens x d_model), and for a backward B, C, u and the result's
cotangent read and three cotangents written (7 arrays). The taps' own
gradient (``conv_taps`` x d_model floats) is no traffic worth counting. A
kernel that does each pass in one sweep over HBM reads 100 %.

The attention kernels' work is ``work_hybrid.attn_full``'s (the flash
kernels at the configuration's heads over the whole causal triangle): the
per-head norm and the rotation sit outside the kernels.

The mode that runs such a model registers the readers below with the
general reader (``layer_metrics.READERS`` / ``WORK``) when it is imported.
"""

from . import flops, work_hybrid, work_layers


def layer_forward_flops_per_token(shape, layer, assignments_per_token):
    """Forward FLOPs one token needs in one layer, by part."""
    d = shape["d_model"]
    if layer["mixer"] == "sconv":
        out = {"projections": 2 * d * 3 * d + 2 * d * d}
    else:
        h, hkv, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
        out = {"projections": 2 * d * (2 * h * hd + 2 * hkv * hd),
               "attention": 4 * hd * h * flops.attended_keys(
                   shape["seq_len"]) / shape["seq_len"]}
    if layer["mlp"] == "dense":
        out["mlp"] = 2 * d * shape["d_ff"] * shape["mlp_matrices"]
        return out
    ex = shape["experts"]
    per_row = 2 * d * ex["matrices"]
    out["router"] = 2 * d * ex["router_width"]
    out["routed"] = per_row * ex["width"] * assignments_per_token
    if ex["shared_width"]:
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


#: arrays of (tokens, d_model) a pass over the gate chain must move
GATE_ARRAYS = {"forward": 4, "backward": 7}


def gate_seconds(shape, tokens, arrays, peaks, act_bytes=2):
    """Least time of one pass over one layer's gate chain that moves
    ``arrays`` arrays of ``tokens`` x d_model: its bytes at the memory's
    rate (its FLOPs, 3 + 2 taps a channel and position, are far under)."""
    moved = arrays * tokens * shape["d_model"]
    return flops.roofline_seconds(
        (3 + 2 * shape["conv_taps"]) * tokens * shape["d_model"],
        act_bytes * moved, peaks)


def sconv_gate(ctx):
    """``WORK["sconv_gate"]``: least seconds per step of the gate chains
    the step makes: per short-convolution layer one forward (again under
    remat) and one backward."""
    shape, work = ctx["work"]["shape"], ctx["work"]
    layers = sum(1 for l in shape["layers"] if l["mixer"] == "sconv")
    fwd, bound = gate_seconds(shape, work["tokens_per_chip"],
                              GATE_ARRAYS["forward"], ctx["peaks"])
    bwd, _ = gate_seconds(shape, work["tokens_per_chip"],
                          GATE_ARRAYS["backward"], ctx["peaks"])
    return layers * ((2 if work["remat"] else 1) * fwd + bwd), bound


def register(layer_metrics):
    layer_metrics.WORK["sconv_gate"] = sconv_gate
    layer_metrics.WORK["attn_qknorm"] = work_hybrid.attn_full
    layer_metrics.READERS["step_work"] = work_layers.read_step_work
    layer_metrics.READERS["routing"] = work_layers.read_routing
