"""The plain reference of the ``lfm2-24b-a2b`` configuration: one chip's
share of a model that mixes doubly-gated short-convolution layers with
grouped-query attention layers whose q and k heads are RMS-normed before
the rotation, sparse FFNs routed by a sigmoid router with a bias in the
choice and NO shared expert, a tied head — written out in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``: no
kernels, no bf16, no sort, nothing imported from ``horovod_tpu``. It
consumes the program's parameter tree (``embed``, ``layers[i].{ln1, ln2}``
with ``sconv.{w_in, conv_w, w_out}`` or ``wq, wkv, q_norm, k_norm, wo``,
then ``w1, w3, w2`` or ``moe.{w_router, router_bias, w1, w3, w2}``,
``ln_f``; the names are the interface, and a layer's kind is read off
them) and an ``arch`` description of what the parameters do not say::

    {"rms_norm_eps", "rope_theta",
     "moe": {"top_k", "routed_scale", "experts_held"}}

With ``n(x) = rmsnorm(x, eps)`` and ``h = n(x) * ln``, per layer::

    conv (d channels, K taps):
        [B | C | u] = h w_in                       split in this order
        z_t = sum_k conv_w[k] * (B * u)_{t - (K - 1) + k}    zeros before 0
        x = x + (C * z) w_out
    attention (H query heads on G kv heads of D):
        q = rope(n_D(h wq) * q_norm)   k = rope(n_D(h wkv[0]) * k_norm)
        v = h wkv[1]       n_D over each head's D features, one weight of D
                           for all heads; rope on the whole head, pairs
                           (i, i + D / 2), theta rope_theta
        a = softmax(causal(q k^T / sqrt(D))) v;  x = x + a wo
    dense:  x = x + (silu(h w1) * (h w3)) w2
    sparse: s = sigmoid(h w_router);  chosen = top_k(s + router_bias)
            w = routed_scale * s_chosen / (sum(s_chosen) + 1e-6)
            x = x + sum_{e chosen and held} w_e FFN_e(h)
    nll = logsumexp(n(x) * ln_f @ embed^T) - logit[target]

The convolution is its taps' shifted products written out. Attention and
the head are computed one block of queries at a time, each layer under
``jax.checkpoint``. What the absent chips would add (other experts'
outputs, other vocabulary rows) is left out here as in the program; every
held expert is applied densely to all tokens and weighted by a mask of the
tokens whose choice names it.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 256


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _proj(h, w):
    """Every projection of the model (one place, so that a reading in a
    lower precision can be taken of all of them)."""
    return h @ w


def _gate_step(x):
    """What follows each of the mixer's three elementwise steps (``B * u``,
    the tap sum, ``C *``): nothing. A reading that rounds between them
    puts its rounding here."""
    return x


def _rope(x, theta):
    """Rotate the pairs (i, i + D / 2) of each head of x (B, S, H, D) by
    ``position * theta ** (-i / (D / 2))``."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v):
    """Causal softmax attention, one block of Q_BLOCK queries at a time
    against every key. q: (B, S, H, D); k, v: (B, S, G, D), query head i
    on kv head i // (H / G)."""
    b, s, h, d = q.shape
    group = h // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    block = min(Q_BLOCK, s)
    while s % block:
        block -= 1
    kp = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(
            jnp.asarray(d, q.dtype))
        keep = kp <= q0 + jnp.arange(block)[:, None]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, jnp.arange(0, s, block))       # (n, B, Q, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def conv_mixer(p, h):
    """``(C * conv(B * u)) w_out`` of h (B, L, d), the convolution as its
    taps' shifted products."""
    d = h.shape[-1]
    bcu = _proj(h, p["w_in"])
    b, c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    k, l = p["conv_w"].shape[0], h.shape[1]
    bu = jnp.pad(_gate_step(b * u), ((0, 0), (k - 1, 0), (0, 0)))
    z = _gate_step(sum(p["conv_w"][i] * bu[:, i:i + l] for i in range(k)))
    return _proj(_gate_step(c * z), p["w_out"])


def _attn_mixer(p, h, arch):
    d, eps = p["wq"].shape[0], arch["rms_norm_eps"]
    heads = p["wq"].shape[1:]
    q = _proj(h, p["wq"].reshape(d, -1)).reshape(h.shape[:2] + heads)
    kv = _proj(h, p["wkv"].reshape(d, -1)).reshape(
        h.shape[:2] + p["wkv"].shape[1:])
    q = _rope(_rmsnorm(q, p["q_norm"], eps), arch["rope_theta"])
    k = _rope(_rmsnorm(kv[:, :, 0], p["k_norm"], eps), arch["rope_theta"])
    a = _attention(q, k, kv[:, :, 1])
    return _proj(a.reshape(h.shape[:2] + (-1,)), p["wo"].reshape(-1, d))


def _ffn(h, w):
    return _proj(jax.nn.silu(_proj(h, w["w1"])) * _proj(h, w["w3"]),
                 w["w2"])


def route(p, h, moe):
    """``(the chosen experts (B, S, top_k), their weights)``: sigmoid
    scores, the bias in the choice only, the family's 1e-6 in the sum."""
    scores = jax.nn.sigmoid(h @ p["w_router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], moe["top_k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    return chosen, moe["routed_scale"] * picked / (
        jnp.sum(picked, -1, keepdims=True) + 1e-6)


def sparse(p, h, moe):
    """``(the held experts' part, assignments each held expert takes)``;
    h: (B, S, d)."""
    chosen, gates = route(p, h, moe)
    first, count = moe["experts_held"]

    def one_expert(y, e_and_w):  # one body for all the experts held
        e, expert = e_and_w
        named = chosen == first + e                         # (B, S, k)
        weight = jnp.sum(jnp.where(named, gates, 0), -1)    # (B, S)
        y = y + weight[..., None] * jax.checkpoint(_ffn)(h, expert)
        return y, jnp.sum(named, dtype=jnp.float32)

    return jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(count), {n: p[n] for n in ("w1", "w3", "w2")}))


def _layer(p, x, arch):
    """``(x after the layer, the held experts' loads or None)``."""
    eps = arch["rms_norm_eps"]
    h = _rmsnorm(x, p["ln1"], eps)
    x = x + (conv_mixer(p["sconv"], h) if "sconv" in p
             else _attn_mixer(p, h, arch))
    h = _rmsnorm(x, p["ln2"], eps)
    if "moe" in p:
        y, load = sparse(p["moe"], h, arch["moe"])
        return x + y, load
    return x + _ffn(h, p), None


def nll_block(x, targets, ln_f, embed, eps):
    logits = _proj(_rmsnorm(x, ln_f, eps), embed.T)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def trunk(params, tokens, arch):
    """``(activations before the final norm, [loads of each sparse
    layer])``; the parameters in the type the arithmetic is wanted in."""
    x = params["embed"][tokens]
    loads = []
    for p in params["layers"]:
        x, load = jax.checkpoint(lambda p, x: _layer(p, x, arch))(p, x)
        if load is not None:
            loads.append(load)
    return x, loads


def total_nll(params, x, targets, arch):
    """Sum of the next-token cross entropies of x (B, S, d) through the
    final norm and the tied head, one block of positions at a time."""
    block = min(Q_BLOCK, x.shape[1])
    while x.shape[1] % block:
        block -= 1

    def nll_of(s0):
        return jax.checkpoint(
            lambda xb, tb, ln, emb: nll_block(
                xb, tb, ln, emb, arch["rms_norm_eps"]))(
            jax.lax.dynamic_slice_in_dim(x, s0, block, 1),
            jax.lax.dynamic_slice_in_dim(targets, s0, block, 1),
            params["ln_f"], params["embed"])

    return jnp.sum(jax.lax.map(
        nll_of, jnp.arange(0, x.shape[1], block)).astype(jnp.float32))


def loss(params, tokens, targets, arch):
    """``(mean next-token cross entropy of tokens (B, S) int32, {"load":
    the assignments each held expert takes in each sparse layer (layers,
    experts held)})``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x, loads = trunk(params, tokens, arch)
        return total_nll(params, x, targets, arch) / tokens.size, {
            "load": jnp.stack(loads)}


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


def loss_and_grads(params, tokens, targets, arch, leaf_paths, loss_fn=None):
    """``((loss, {"load"}), [d loss / d leaf for each path])`` on one
    batch; a path is a key sequence into the parameter tree, e.g.
    ``("layers", 0, "sconv", "conv_w")``. Only the chosen leaves'
    gradients are formed. ``loss_fn`` takes the place of :func:`loss`
    where a reading in another precision is wanted."""
    def f(leaves):
        p = params
        for path, leaf in zip(leaf_paths, leaves):
            p = _put(p, path, leaf)
        return (loss_fn or loss)(p, tokens, targets, arch)

    return jax.value_and_grad(f, has_aux=True)(
        [get_leaf(params, p) for p in leaf_paths])
