"""Operations and bytes of a model that mixes Mamba-2 layers with attention
layers, counted per layer kind from the configuration file alone — every
matrix multiplication of the forward pass at 2 FLOPs per multiply-add,
attention with the keys each query really sees, the state-space scan in
its chunked form at the published chunk size, times 3 for forward +
backward, no recompute — from::

    {"d_model", "vocab_size", "seq_len", "d_ff", "mlp_matrices",
     "head_dim", "n_heads", "n_kv_heads",          the attention layers
     "layers": ["mamba" | "attention", ...],
     "mamba": {"n_heads", "d_head", "d_state", "chunk"}}

Nothing here is read from the program under test, so the scan's work is
the same whatever implements it (XLA ops today, a kernel tomorrow).

The chunked scan's four products, per token, with H heads of P, state N,
chunk Q: ``C B^T`` 2 Q N, ``(C B^T * decay) (dt x)`` 2 Q H P, the chunk's
own end state 2 H P N, the state before the chunk times C 2 H P N. Its
compulsory bytes per sequence position: x in and y out (H P each) and B, C
(N each) in the activation type, dt (H) in float32, plus one float32 state
(H P N) written and read once per chunk.

The mode that runs such a model registers the readers below with the
general reader (``layer_metrics.READERS`` / ``WORK``) when it is imported.
"""

from . import flops, kernel_work, work_layers


def scan_flops_per_token(mamba):
    h, p, n, q = (mamba["n_heads"], mamba["d_head"], mamba["d_state"],
                  mamba["chunk"])
    return 2 * q * n + 2 * q * h * p + 2 * h * p * n + 2 * h * p * n


def scan_bytes_per_token(mamba, act_bytes=2):
    h, p, n, q = (mamba["n_heads"], mamba["d_head"], mamba["d_state"],
                  mamba["chunk"])
    return (act_bytes * (2 * h * p + 2 * n) + 4 * h
            + 2 * 4 * h * p * n / q)


def layer_forward_flops_per_token(shape, kind):
    """Forward FLOPs one token needs in one layer of ``kind``, by part."""
    d = shape["d_model"]
    out = {"mlp": 2 * d * shape["d_ff"] * shape["mlp_matrices"]}
    if kind == "mamba":
        m = shape["mamba"]
        inner = m["n_heads"] * m["d_head"]
        out["projections"] = 2 * d * (2 * inner + 2 * m["d_state"]
                                      + m["n_heads"]) + 2 * inner * d
        out["scan"] = scan_flops_per_token(m)
        return out
    h, hkv, hd = shape["n_heads"], shape["n_kv_heads"], shape["head_dim"]
    out["projections"] = 2 * d * (2 * h * hd + 2 * hkv * hd)
    out["attention"] = 4 * hd * h * flops.attended_keys(
        shape["seq_len"]) / shape["seq_len"]
    return out


def required_flops_per_token(shape):
    """Forward + backward FLOPs one trained token requires (no
    recompute), and the forward count of each layer."""
    per_layer = [layer_forward_flops_per_token(shape, kind)
                 for kind in shape["layers"]]
    head = 2 * shape["d_model"] * shape["vocab_size"]
    total = 3.0 * (sum(sum(parts.values()) for parts in per_layer) + head)
    return total, per_layer + [{"head": head}]


def scan_seconds(shape, tokens, peaks):
    """Least time of ONE forward pass of one layer's scan over ``tokens``
    positions: its FLOPs at the peak against its compulsory bytes."""
    return flops.roofline_seconds(
        tokens * scan_flops_per_token(shape["mamba"]),
        tokens * scan_bytes_per_token(shape["mamba"]), peaks)


def ssm_scan(ctx):
    """``WORK["ssm_scan"]``: least seconds per step of the scans the step
    makes: per Mamba-2 layer one forward (again under remat) and a
    backward at twice the forward."""
    shape, work = ctx["work"]["shape"], ctx["work"]
    layers = sum(1 for kind in shape["layers"] if kind == "mamba")
    t, bound = scan_seconds(shape, work["tokens_per_chip"], ctx["peaks"])
    return layers * ((2 if work["remat"] else 1) + 2) * t, bound


def attn_full(ctx):
    """``WORK["attn_full"]``: least seconds per step of the three flash
    kernels in the attention layers (every query sees the whole causal
    triangle; ``flops.flash_kernel_work`` at the configuration's heads),
    each at the calls the trace shows (``kernel_work``: remat's second
    forward is in both)."""
    shape = ctx["work"]["shape"]
    one = {key: shape[key] for key in ("seq_len", "head_dim", "n_heads",
                                       "n_kv_heads")}
    work = flops.flash_kernel_work(one, ctx["work"]["seqs_per_chip"])
    total, bound_by = 0.0, set()
    for kernel in ("fwd", "dq", "dkv"):
        t, bound = flops.roofline_seconds(*work[kernel], ctx["peaks"])
        total += t * kernel_work.calls_per_step(
            ctx["trace"], f"hvd_attn_full.*hvd_flash_{kernel}")
        bound_by.add(bound)
    return total, "+".join(sorted(bound_by))


def register(layer_metrics):
    layer_metrics.WORK["ssm_scan"] = ssm_scan
    layer_metrics.WORK["attn_full"] = attn_full
    layer_metrics.READERS["step_work"] = work_layers.read_step_work
