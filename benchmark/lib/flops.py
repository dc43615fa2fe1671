"""Operations and bytes the algorithm needs, computed from shapes.

The yardstick for ``mfu_device`` and ``flash_roofline``: nothing here is
read from the program under test, so a PR that changes the program cannot
change what its work is counted as.

Model FLOPs (``required_flops_per_token``): every matrix multiplication of
the forward pass at 2 FLOPs per multiply-add, attention with the context
each query really sees under the causal mask and the sliding window
(``min(q + 1, window)`` keys for query ``q``), times 3 for forward +
backward. The embedding gather is not a matmul and is not counted; work
that remat or a kernel's backward recomputes is not counted either.

Kernel FLOPs and bytes (``flash_kernel_work``): what the three fused
attention kernels need for one call each. The backward kernels recompute
the scores by design (FlashAttention-2), so that recompute IS the kernels'
algorithm and is counted here (forward 2, dQ 3, dK/dV 4 tile matmuls), but
not in the model FLOPs above. Bytes are compulsory traffic: every operand
read once and every result written once.
"""

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks_for(device_kind):
    """Published peaks of one chip, by ``device_kind``. A chip the table
    does not list is an error, never a default."""
    with open(_PEAKS, encoding="utf-8") as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r} in "
            f"{_PEAKS}; add the chip with its source")
    return table[device_kind]


def matmul_params(shape):
    """Parameters that sit in a matmul of the forward pass: per layer the
    q, k, v and output projections and the two MLP matrices, plus the LM
    head. Not the embedding table (a gather), the position table (an add)
    or the norm scales."""
    d, hd = shape["d_model"], shape["head_dim"]
    h, hkv = shape["n_heads"], shape["n_kv_heads"]
    attn = d * h * hd + 2 * d * hkv * hd + h * hd * d
    mlp = 2 * d * shape["d_ff"]
    return shape["n_layers"] * (attn + mlp) + d * shape["vocab_size"]


def attended_keys(seq_len, window=None):
    """Sum over the queries of one sequence of the keys each attends:
    query q sees min(q + 1, window) keys (causal, self included)."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    w = int(window)
    return w * (w + 1) // 2 + (seq_len - w) * w


def attention_flops_fwd(shape):
    """Forward attention FLOPs of ONE sequence in ONE layer: Q K^T and
    P V, 2 * head_dim FLOPs per (query, key, head) pair each."""
    pairs = attended_keys(shape["seq_len"], shape.get("window"))
    return 2 * 2 * shape["head_dim"] * shape["n_heads"] * pairs


def _forward_flops_per_token(shape):
    """``(matmul, attention)`` forward FLOPs per token, all layers."""
    attn = shape["n_layers"] * attention_flops_fwd(shape) / shape["seq_len"]
    return 2.0 * matmul_params(shape), attn


def required_flops_per_token(shape):
    """Forward + backward FLOPs one trained token requires (no
    recompute): 3 x (2 x matmul parameters + attention forward)."""
    return 3.0 * sum(_forward_flops_per_token(shape))


def attention_share(shape):
    """Share of the required FLOPs that is attention (scores and values,
    forward and backward)."""
    matmul, attn = _forward_flops_per_token(shape)
    return attn / (matmul + attn)


#: tile matmuls per (query, key, head) pair in each kernel:
#: forward S=QK^T, PV; dQ S, dP=dO V^T, dQ=dS K; dK/dV S, dP, dV=P^T dO,
#: dK=dS^T Q
KERNEL_MATMULS = {"fwd": 2, "dq": 3, "dkv": 4}


def flash_kernel_work(shape, batch, act_bytes=2):
    """``{kernel: (flops, bytes)}`` for one call of each attention kernel
    on ``batch`` sequences of one layer.

    Bytes, compulsory: q, out, dO, dQ are (batch, seq, n_heads, head_dim)
    activations; k, v are (batch, seq, n_kv_heads, head_dim); lse and
    delta are fp32 rows per (batch, head, query). The dK/dV kernel writes
    one fp32 partial per QUERY head when heads are grouped (the sum over
    the group runs outside it), in the activation type otherwise."""
    s, hd = shape["seq_len"], shape["head_dim"]
    h, hkv = shape["n_heads"], shape["n_kv_heads"]
    pairs = attended_keys(s, shape.get("window")) * h * batch
    per_matmul = 2 * hd * pairs
    q_like = batch * s * h * hd * act_bytes
    kv_like = batch * s * hkv * hd * act_bytes
    row = batch * h * s * 4
    part_bytes = 4 if h != hkv else act_bytes
    dkv_out = 2 * batch * s * h * hd * part_bytes
    return {
        "fwd": (KERNEL_MATMULS["fwd"] * per_matmul,
                q_like + 2 * kv_like + q_like + row),
        "dq": (KERNEL_MATMULS["dq"] * per_matmul,
               2 * q_like + 2 * kv_like + 2 * row + q_like),
        "dkv": (KERNEL_MATMULS["dkv"] * per_matmul,
                2 * q_like + 2 * kv_like + 2 * row + dkv_out),
    }


def roofline_seconds(flops, nbytes, peaks):
    """Least time the chip could take, and which limit sets it."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes,
                                                            "memory")
