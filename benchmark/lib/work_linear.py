"""Operations and bytes of a model that mixes KDA (gated delta rule) layers
with latent-attention layers and sparse FFNs, counted per layer kind from
the configuration file alone — every matrix multiplication of the forward
pass at 2 FLOPs per multiply-add, attention with the keys each query really
sees, the recurrence at what the recurrence itself needs, times 3 for
forward + backward, no recompute — from::

    {"d_model", "vocab_size", "seq_len", "d_ff", "mlp_matrices",
     "layers": [{"mixer": "kda" | "mla", "mlp": "dense" | "sparse"}, ...],
     "kda": {"n_heads", "head_dim"},
     "mla": {"n_heads", "qk_dim", "v_dim", "kv_rank", "qk_shared"},
     "experts": {"router_width", "held", "width", "shared_width",
                 "matrices"}}

Nothing here is read from the program under test, with the exception
``work_layers`` has too: the routed experts' work follows the assignments
the router sent to the experts held (``assignments_per_token``), which is
data and which ``correct`` holds to the reference's own count.

**The recurrence's bound is one no implementation can beat.** Per head
and position with ``D`` features: the decay ``Diag(exp(g)) S`` D^2, ``S'^T
k`` 2 D^2, the rank-1 update 2 D^2, ``S^T q`` 2 D^2 — 7 D^2 FLOPs; bytes:
q, k, v, g in and o out once in the activation type plus beta, and NO
state a chunk (a kernel that keeps the state on the chip must not read
over 100 %). The chunked form's extra products (the triangular system, the
decays inside a chunk) are the implementation's and are not counted.

**The latent-attention kernels** take q and k of ``qk_dim`` and v, o of
``v_dim``: per (query, key, head) pair the forward needs ``2 (qk_dim +
v_dim)`` FLOPs (``flops.flash_kernel_work`` counts ``4 head_dim`` for one
size), dQ ``2 (2 qk_dim + v_dim)``, dK/dV ``2 (2 qk_dim + 2 v_dim)``.

The mode that runs such a model registers the readers below with the
general reader (``layer_metrics.READERS`` / ``WORK``) when it is imported.
"""

from . import flops, kernel_work, work_layers


def recurrence_flops_per_token(kda):
    return 7 * kda["head_dim"] ** 2 * kda["n_heads"]


def recurrence_bytes_per_token(kda, act_bytes=2):
    return act_bytes * 5 * kda["n_heads"] * kda["head_dim"] \
        + 4 * kda["n_heads"]


def layer_forward_flops_per_token(shape, layer, assignments_per_token):
    """Forward FLOPs one token needs in one layer, by part."""
    d = shape["d_model"]
    if layer["mixer"] == "kda":
        k = shape["kda"]
        inner = k["n_heads"] * k["head_dim"]
        out = {"projections": 4 * 2 * d * inner           # q, k, v, o
               + 2 * (2 * d * k["head_dim"] + 2 * k["head_dim"] * inner)
               + 2 * d * k["n_heads"],                     # beta
               "recurrence": recurrence_flops_per_token(k)}
    else:
        m = shape["mla"]
        h = m["n_heads"]
        out = {"projections": 2 * d * h * m["qk_dim"]
               + 2 * d * (m["kv_rank"] + m["qk_shared"])
               + 2 * m["kv_rank"] * h * (m["qk_dim"] - m["qk_shared"]
                                         + m["v_dim"])
               + 2 * h * m["v_dim"] * d,
               "attention": 2 * (m["qk_dim"] + m["v_dim"]) * h
               * flops.attended_keys(shape["seq_len"]) / shape["seq_len"]}
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


def recurrence_seconds(shape, tokens, peaks):
    """Least time of ONE forward pass of one layer's recurrence over
    ``tokens`` positions: its FLOPs at the peak against its compulsory
    bytes."""
    return flops.roofline_seconds(
        tokens * recurrence_flops_per_token(shape["kda"]),
        tokens * recurrence_bytes_per_token(shape["kda"]), peaks)


def kda_scan(ctx):
    """``WORK["kda_scan"]``: least seconds per step of the recurrences
    the step makes: per KDA layer one forward (again under remat) and a
    backward at twice the forward."""
    shape, work = ctx["work"]["shape"], ctx["work"]
    layers = sum(1 for l in shape["layers"] if l["mixer"] == "kda")
    t, bound = recurrence_seconds(shape, work["tokens_per_chip"],
                                  ctx["peaks"])
    return layers * ((2 if work["remat"] else 1) + 2) * t, bound


def mla_kernel_work(shape, batch, act_bytes=2):
    """``{kernel: (flops, bytes)}`` for one call of each attention kernel
    on ``batch`` sequences of one latent-attention layer: every head with
    a key of its own (the shared part is copied to each), the whole causal
    triangle; bytes are every operand read once and every result written
    once (q, k, dQ, dK ``qk_dim`` wide, v, o, dO, dV ``v_dim`` wide, lse
    and delta float32 rows)."""
    m, s = shape["mla"], shape["seq_len"]
    pairs = flops.attended_keys(s) * m["n_heads"] * batch
    qk, v = m["qk_dim"], m["v_dim"]
    row = batch * m["n_heads"] * s
    wide, narrow = row * qk * act_bytes, row * v * act_bytes
    return {
        "fwd": (2 * (qk + v) * pairs, 2 * wide + 2 * narrow + 4 * row),
        "dq": (2 * (2 * qk + v) * pairs,
               2 * wide + 2 * narrow + 8 * row + wide),
        "dkv": (2 * (2 * qk + 2 * v) * pairs,
                2 * wide + 2 * narrow + 8 * row + wide + narrow)}


def attn_mla(ctx):
    """``WORK["attn_mla"]``: least seconds per step of the three flash
    kernels in the latent-attention layers, each at the calls the trace
    shows (``kernel_work``: remat's second forward is in both)."""
    work = mla_kernel_work(ctx["work"]["shape"],
                           ctx["work"]["seqs_per_chip"])
    total, bound_by = 0.0, set()
    for kernel in ("fwd", "dq", "dkv"):
        t, bound = flops.roofline_seconds(*work[kernel], ctx["peaks"])
        total += t * kernel_work.calls_per_step(
            ctx["trace"], f"hvd_attn_full.*hvd_flash_{kernel}")
        bound_by.add(bound)
    return total, "+".join(sorted(bound_by))


def register(layer_metrics):
    layer_metrics.WORK["kda_scan"] = kda_scan
    layer_metrics.WORK["attn_mla"] = attn_mla
    layer_metrics.READERS["step_work"] = work_layers.read_step_work
    layer_metrics.READERS["routing"] = work_layers.read_routing
