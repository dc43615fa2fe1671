"""The plain reference of the ``granite-4.0-h-micro`` configuration: Mamba-2
layers and NoPE grouped-query attention layers in one model, tied and
scaled embedding, written out in straightforward ``jax.numpy``, float32,
``default_matmul_precision("highest")`` — no kernels, no bf16, no chunked
scan, nothing imported from ``horovod_tpu``. It consumes the program's
parameter tree (``embed``, ``layers[i].{ln1, ln2, w1, w3, w2}`` with either
``wq, wkv, wo`` or ``ssm.{in_proj, conv_w, conv_b, dt_bias, A_log, D,
norm, out_proj}``, ``ln_f``; the names are the interface, and the layer's
kind is read off them) and an ``arch`` description of what the parameters
do not say::

    {"attention_multiplier", "embedding_multiplier", "residual_multiplier",
     "logits_scaling", "rms_norm_eps",
     "mamba": {"n_heads", "d_head", "d_state"}}

With ``n(x) = rmsnorm(x, eps)``, per layer::

    x = x + residual_multiplier * mixer(n(x) * ln1)
    x = x + residual_multiplier * (silu(h w1) * (h w3)) w2,  h = n(x) * ln2

    attention (q heads x 64, fewer k / v heads, no positions):
        a = softmax(causal(attention_multiplier * q k^T)) v;  mixer = a wo
    mamba-2 (H heads of P, state N, one group):
        [z | xBC | dt] = h in_proj                 widths H P, H P + 2 N, H
        xBC = silu(conv_b + sum_k conv_w[k] xBC[t - 3 + k])   zeros before 0
        x, B, C = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T           per head, P x N
        y_t = S_t C_t + D x_t
        mixer = (n(y * silu(z)) * norm) out_proj              n over all H P

    x0 = embedding_multiplier * embed[tokens]
    logits = (n(x) * ln_f) embed^T / logits_scaling           tied

The state-space layer is the SEQUENTIAL recurrence over positions
(``lax.scan`` over t), so it shares nothing with the chunked form under
test. Its backward is taken in blocks: an outer scan over blocks of
``SCAN_BLOCK`` positions whose body is under ``jax.checkpoint``, so that
one block's states are live at a time and not all 16,384 (2 MiB each).
Attention and the head are computed one block of queries at a time, each
layer under ``jax.checkpoint``.
"""

import jax
import jax.numpy as jnp

Q_BLOCK = 256
SCAN_BLOCK = 128
#: what the recurrence accumulates in (the state and its decay)
STATE_DTYPE = jnp.float32


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale


def _proj(h, w):
    """Every projection of the model (one place, so that a reading in a
    lower precision can be taken of all of them)."""
    return h @ w


def _attention(q, k, v, scale):
    """Causal softmax attention, one block of Q_BLOCK queries at a time
    against every key. q: (B, S, H, D); k, v: (B, S, Hkv, D)."""
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
        scores = scale * jnp.einsum("bqhd,bkhd->bhqk", qb, k)
        keep = kp <= q0 + jnp.arange(block)[:, None]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, jnp.arange(0, s, block))       # (n, B, Q, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, d)


def recurrence(x, dt, a, bm, cm):
    """``S_t = exp(dt_t a) S_{t-1} + dt_t x_t B_t^T``, ``y_t = S_t C_t``,
    one position at a time. x: (B, L, H, P), dt: (B, L, H), a: (H,),
    bm / cm: (B, L, N). Returns ``(y (B, L, H, P), S_L (B, H, P, N))``."""
    b, l, h, p = x.shape
    n = bm.shape[-1]
    block = min(SCAN_BLOCK, l)
    while l % block:
        block -= 1

    def step(s, t):
        xt, dtt, bt, ct = t
        decay = jnp.exp(dtt * a).astype(STATE_DTYPE)             # (B, H)
        s = decay[..., None, None] * s + (
            (dtt[..., None] * xt)[..., None]
            * bt[:, None, None, :]).astype(STATE_DTYPE)
        return s, jnp.einsum("bhpn,bn->bhp", s.astype(ct.dtype), ct)

    @jax.checkpoint
    def many(s, ts):
        return jax.lax.scan(step, s, ts)

    def by_time(t):  # (B, L, ...) -> (L / block, block, B, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((l // block, block) + t.shape[1:])

    s, y = jax.lax.scan(many, jnp.zeros((b, h, p, n), STATE_DTYPE),
                        tuple(map(by_time, (x, dt, bm, cm))))
    y = jnp.moveaxis(y.reshape((l,) + y.shape[2:]), 0, 1)
    return y, s.astype(jnp.float32)


def _mamba(p, h, arch):
    """``(mixer output, each head's root mean square of the final state
    (H,))``."""
    m = arch["mamba"]
    hn, hd, n = m["n_heads"], m["d_head"], m["d_state"]
    di = hn * hd
    b, l, _ = h.shape
    zxbcdt = _proj(h, p["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    k = p["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][i] * padded[:, i:i + l] for i in range(k)))
    x = xbc[..., :di].reshape(b, l, hn, hd)
    y, state = recurrence(
        x, jax.nn.softplus(dt + p["dt_bias"]), -jnp.exp(p["A_log"]),
        xbc[..., di:di + n], xbc[..., di + n:])
    y = (y + p["D"][:, None] * x).reshape(b, l, di)
    y = _rmsnorm(y * jax.nn.silu(z), p["norm"], arch["rms_norm_eps"])
    return _proj(y, p["out_proj"]), jnp.sqrt(
        jnp.mean(state * state, axis=(0, 2, 3)))


def _layer(p, x, arch):
    """``(x after the layer, the final state's rms or None)``."""
    eps, res = arch["rms_norm_eps"], arch["residual_multiplier"]
    h = _rmsnorm(x, p["ln1"], eps)
    if "ssm" in p:
        mixed, rms = _mamba(p["ssm"], h, arch)
    else:
        d = p["wq"].shape[0]
        q = _proj(h, p["wq"].reshape(d, -1)).reshape(
            h.shape[:2] + p["wq"].shape[1:])
        kv = _proj(h, p["wkv"].reshape(d, -1)).reshape(
            h.shape[:2] + p["wkv"].shape[1:])
        a = _attention(q, kv[:, :, 0], kv[:, :, 1],
                       arch["attention_multiplier"])
        mixed, rms = _proj(a.reshape(h.shape[:2] + (-1,)),
                           p["wo"].reshape(-1, d)), None
    x = x + res * mixed
    h = _rmsnorm(x, p["ln2"], eps)
    ffn = _proj(jax.nn.silu(_proj(h, p["w1"])) * _proj(h, p["w3"]),
                p["w2"])
    return x + res * ffn, rms


def _nll_block(x, targets, ln_f, embed, arch):
    logits = _proj(_rmsnorm(x, ln_f, arch["rms_norm_eps"]), embed.T) \
        / arch["logits_scaling"]
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def trunk(params, tokens, arch):
    """``(activations before the final norm, [final-state rms by head of
    each Mamba-2 layer])``; float32 parameters expected."""
    x = arch["embedding_multiplier"] * params["embed"][tokens]
    states = []
    for p in params["layers"]:
        x, rms = jax.checkpoint(lambda p, x: _layer(p, x, arch))(p, x)
        if rms is not None:
            states.append(rms)
    return x, states


def logits(params, tokens, arch):
    """Whole-sequence logits (B, S, V); for small sizes."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x, _ = trunk(params, tokens, arch)
        return _proj(_rmsnorm(x, params["ln_f"], arch["rms_norm_eps"]),
                     params["embed"].T) / arch["logits_scaling"]


def loss(params, tokens, targets, arch):
    """``(mean next-token cross entropy of tokens (B, S) int32, the final
    state's rms of each head of each Mamba-2 layer (layers, H))``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x, states = trunk(params, tokens, arch)
        block = min(Q_BLOCK, tokens.shape[1])
        while tokens.shape[1] % block:
            block -= 1

        def nll_of(s0):
            return jax.checkpoint(
                lambda xb, tb, ln, emb: _nll_block(xb, tb, ln, emb, arch))(
                jax.lax.dynamic_slice_in_dim(x, s0, block, 1),
                jax.lax.dynamic_slice_in_dim(targets, s0, block, 1),
                params["ln_f"], params["embed"])

        total = jnp.sum(jax.lax.map(
            nll_of, jnp.arange(0, tokens.shape[1], block)))
        return total / tokens.size, (jnp.stack(states) if states
                                     else jnp.zeros((0,), jnp.float32))


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
    """``((loss, state rms), [d loss / d leaf for each path])`` on one
    batch; a path is a key sequence into the parameter tree, e.g.
    ``("layers", 4, "ssm", "conv_w")``. Only the chosen leaves' gradients
    are formed."""
    def f(leaves):
        p = params
        for path, leaf in zip(leaf_paths, leaves):
            p = _put(p, path, leaf)
        return loss(p, tokens, targets, arch)

    return jax.value_and_grad(f, has_aux=True)(
        [get_leaf(params, p) for p in leaf_paths])
