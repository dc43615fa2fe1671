"""The plain reference of the ``laguna-s-2.1`` configuration: one chip's
share of a model with two kinds of attention layer and sparse FFNs, written
out in straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")`` — no kernels, no bf16, no sort, no gather of rows, nothing
imported from ``horovod_tpu``. It consumes the program's parameter tree
(the names ``embed``, ``layers[i].{ln1, wq, wkv, wg, wo, ln2}``, then ``w1,
w3, w2`` for a dense layer or ``moe.{w_router, w1, w3, w2, shared.{w1, w3,
w2}}`` for a sparse one, ``ln_f``, ``lm_head`` are the interface) and an
``arch`` description of what the parameters do not say::

    {"layers": [{"window": None | int, "rope": {...}}, ...],
     "moe": {"num_experts", "top_k", "routed_scale", "experts_held"}}

A ``rope`` block is the published one: ``rope_theta``,
``partial_rotary_factor`` and, for ``rope_type: "yarn"``, ``factor``,
``original_max_position_embeddings``, ``beta_fast``, ``beta_slow``,
``attention_factor``.

With ``h = rmsnorm(x) * ln``, per layer of ``H`` query heads held and one
key/value head held (the counts are the parameters' shapes):

    q = h wq;  k, v = h wkv;  g = sigmoid(h wg^T)          one gate per head
    rope:   the first partial_rotary_factor * 128 features of q and k are
            rotated, pairs (i, i + half), at inv_freq_i = theta^(-2i/rot);
            yarn blends inv_freq_i with inv_freq_i / factor along a linear
            ramp between the correction dims of beta_fast and beta_slow at
            the original length, and scales cos and sin by attention_factor
    a_head = softmax(mask(q k^T / sqrt(128))) v       causal, keys within
                                                      `window` of the query
    x = x + concat_heads(g_head * a_head) wo          partial sum: held heads
    dense:  x = x + (silu(h w1) * (h w3)) w2
    sparse: p = softmax(h w_router) over all experts; top_k;
            w = routed_scale * p_top / sum(p_top)
            x = x + sum_{e in top_k and held} w_e FFN_e(h) + FFN_shared(h)
    nll = logsumexp(rmsnorm(x) * ln_f @ lm_head) - logit[target]

What the absent chips would add (other heads' ``a wo``, other experts'
outputs, other vocabulary rows) is left out here as in the program. Every
held expert is applied densely to all tokens and weighted by a mask of the
tokens whose top-k names it. Attention and the head are computed one block
of queries at a time (against every key, or the window's), each block
under ``jax.checkpoint``, so that an 8,192-token sequence fits in float32;
blocks and held experts are loops with one body (``lax.map`` / ``scan``),
which keeps the float32 program small enough to compile in a minute.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NORM_EPS = 1e-6
Q_BLOCK = 1024


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def inv_freq(rope, head_dim):
    """Rotation frequencies of a published rope block (numpy float64)."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    base = float(rope["rope_theta"])
    freqs = base ** (-np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") != "yarn":
        return freqs

    def correction_dim(rotations):
        return rot * math.log(rope["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), rot - 1)
    ramp = np.clip((np.arange(rot // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    return freqs / rope["factor"] * ramp + freqs * (1 - ramp)


def _rope(x, positions, rope):
    if rope.get("rope_type", "default") == "yarn" \
            or rope.get("partial_rotary_factor", 1) != 1:
        freqs = jnp.asarray(inv_freq(rope, x.shape[-1]), jnp.float32)
    else:  # as every rotary layer of the program computes them
        half = x.shape[-1] // 2
        freqs = float(rope["rope_theta"]) ** (
            -jnp.arange(half, dtype=jnp.float32) / half)
    half = freqs.shape[0]
    ang = positions[:, None].astype(jnp.float32) * freqs[None]
    scale = rope.get("attention_factor", 1.0) \
        if rope.get("rope_type") == "yarn" else 1.0
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], -1)


def _attend_block(q, k, v, q0, k0, window):
    """Queries at positions q0.. against keys at positions k0..: dense
    scores, causal + window mask, softmax, values. q: (B, Sq, H, D);
    k, v: (B, Sk, H, D); key positions below 0 are padding."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    qp = q0 + jnp.arange(q.shape[1])[:, None]
    kp = k0 + jnp.arange(k.shape[1])[None, :]
    keep = (kp <= qp) & (kp >= 0)
    if window is not None:
        keep &= (qp - kp) < window
    scores = jnp.where(keep[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(q, k, v, window):
    """One block of Q_BLOCK queries at a time (one loop body for all of
    them): against every key where the layer has no window, else against
    the ``window`` keys before the block and the block's own."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    block = min(Q_BLOCK, s)
    back = 0 if window is None else min(window, s)
    span = s if window is None else back + block
    pad = ((0, 0), (back, 0), (0, 0), (0, 0))
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    @jax.checkpoint
    def one(q0):
        k0 = 0 if window is None else q0  # index into the padded keys
        keys = jax.lax.dynamic_slice_in_dim(k, k0, span, 1)
        values = jax.lax.dynamic_slice_in_dim(v, k0, span, 1)
        return _attend_block(
            jax.lax.dynamic_slice_in_dim(q, q0, block, 1), keys, values,
            q0, k0 - back, window)

    out = jax.lax.map(one, jnp.arange(0, s, block))       # (n, B, Q, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def _ffn(h, w):
    return (jax.nn.silu(h @ w["w1"]) * (h @ w["w3"])) @ w["w2"]


def _sparse(p, h, moe):
    """``(the held experts' part + the shared expert, assignments each
    held expert takes)``; h: (B, S, d)."""
    probs = jax.nn.softmax(h @ p["w_router"], -1)
    top_p, top_i = jax.lax.top_k(probs, moe["top_k"])
    gates = moe["routed_scale"] * top_p / jnp.sum(top_p, -1, keepdims=True)
    first, count = moe["experts_held"]

    def one_expert(y, e_and_w):  # one body for all the experts held
        e, expert = e_and_w
        named = top_i == first + e                          # (B, S, k)
        weight = jnp.sum(jnp.where(named, gates, 0.0), -1)  # (B, S)
        y = y + weight[..., None] * jax.checkpoint(_ffn)(h, expert)
        return y, jnp.sum(named, dtype=jnp.float32)

    y, load = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(count), {n: p[n] for n in ("w1", "w3", "w2")}))
    if "shared" in p:
        y = y + _ffn(h, p["shared"])
    return y, load


def _layer(p, x, spec, moe):
    h = _rmsnorm(x, p["ln1"])
    q = jnp.einsum("bsd,dhx->bshx", h, p["wq"])
    kv = jnp.einsum("bsd,dchx->bschx", h, p["wkv"])
    k, v = kv[:, :, 0], kv[:, :, 1]
    positions = jnp.arange(x.shape[1])
    q, k = _rope(q, positions, spec["rope"]), _rope(k, positions,
                                                    spec["rope"])
    a = _attention(q, k, v, spec["window"])
    a = a * jax.nn.sigmoid(h @ p["wg"].T)[..., None]
    x = x + jnp.einsum("bshx,hxd->bsd", a, p["wo"])
    h = _rmsnorm(x, p["ln2"])
    if "moe" in p:
        y, load = _sparse(p["moe"], h, moe)
        return x + y, load
    return x + _ffn(h, p), None


def _nll_block(x, targets, ln_f, lm_head):
    logits = _rmsnorm(x, ln_f) @ lm_head
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def loss(params, tokens, targets, arch):
    """``(mean next-token cross entropy of tokens (B, S) int32, the
    assignments each held expert takes in each sparse layer (layers,
    experts held))``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["embed"][tokens]
        loads = []
        for p, spec in zip(params["layers"], arch["layers"]):
            x, load = jax.checkpoint(functools.partial(
                _layer, spec=spec, moe=arch["moe"]))(p, x)
            if load is not None:
                loads.append(load)
        block = min(Q_BLOCK, tokens.shape[1])

        def nll_of(s0):
            return jax.checkpoint(_nll_block)(
                jax.lax.dynamic_slice_in_dim(x, s0, block, 1),
                jax.lax.dynamic_slice_in_dim(targets, s0, block, 1),
                params["ln_f"], params["lm_head"])

        total = jnp.sum(jax.lax.map(
            nll_of, jnp.arange(0, tokens.shape[1], block)))
        return total / tokens.size, jnp.stack(loads)


def get_leaf(tree, path):
    """The leaf at ``path``, a key sequence into the parameter tree."""
    for key in path:
        tree = tree[key]
    return tree


def _put(tree, path, leaf):
    if not path:
        return leaf
    out = list(tree) if isinstance(tree, list) else dict(tree)
    out[path[0]] = _put(tree[path[0]], path[1:], leaf)
    return out


def loss_and_grads(params, tokens, targets, arch, leaf_paths):
    """``((loss, expert load), [d loss / d leaf for each path])`` on one
    batch; a path is a key sequence into the parameter tree, e.g.
    ``("layers", 2, "moe", "w_router")``. Only the chosen leaves'
    gradients are formed."""
    def f(leaves):
        p = params
        for path, leaf in zip(leaf_paths, leaves):
            p = _put(p, path, leaf)
        return loss(p, tokens, targets, arch)

    return jax.value_and_grad(f, has_aux=True)(
        [get_leaf(params, p) for p in leaf_paths])
