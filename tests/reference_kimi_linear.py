"""The plain reference of the ``kimi-linear-48b-a3b`` configuration: one
chip's share of a model that mixes Kimi Delta Attention (KDA) layers with
latent-attention (MLA) layers without positions, sparse FFNs routed by a
sigmoid router with a balancing bias — written out in straightforward
``jax.numpy``, float32, ``default_matmul_precision("highest")``: no
kernels, no bf16, no chunked form, no sort, nothing imported from
``horovod_tpu``. It consumes the program's parameter tree (``embed``,
``layers[i].{ln1, ln2}`` with ``kda.{wq, wk, wv, conv_w, w_fa, w_fb,
dt_bias, A_log, w_ga, w_gb, w_b, norm, wo}`` or ``mla.{wq, w_kva, kv_norm,
w_kvb, wo}``, then ``w1, w3, w2`` or ``moe.{w_router, router_bias, w1, w3,
w2, shared.{w1, w3, w2}}``, ``ln_f``, ``lm_head``; the names are the
interface, and a layer's kind is read off them) and an ``arch`` description
of what the parameters do not say::

    {"rms_norm_eps", "kda": {"n_heads", "head_dim"},
     "mla": {"kv_rank", "qk_nope"},
     "moe": {"top_k", "routed_scale", "experts_held"}}

With ``n(x) = rmsnorm(x, eps)`` and ``h = n(x) * ln``, per layer::

    KDA (H heads of D, keys and values alike):
        q = l2norm(silu(conv(h wq)))  k = l2norm(silu(conv(h wk)))
        v = silu(conv(h wv))     conv: sum_i conv_w[i] x[t - 3 + i], zeros
                                 before position 0, no bias; l2norm over D
        g = -exp(A_log) * softplus((h w_fa) w_fb + dt_bias)         (H, D)
        beta = sigmoid(h w_b)                                       (H,)
        S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t / sqrt(D)
        x = x + (n_D(o_t) * norm * sigmoid((h w_ga) w_gb)) wo
    MLA (H heads, no positions):
        q = h wq  (H, nope + shared);  [c | k_s] = h w_kva;  c = n(c) * kv_norm
        [k_n | v] = c w_kvb  (H, nope + v);  k = [k_n | k_s]   k_s for every head
        a = softmax(causal(q k^T / sqrt(nope + shared))) v;  x = x + a wo
    dense:  x = x + (silu(h w1) * (h w3)) w2
    sparse: s = sigmoid(h w_router);  chosen = top_k(s + router_bias)
            w = routed_scale * s_chosen / sum(s_chosen)
            x = x + sum_{e chosen and held} w_e FFN_e(h) + FFN_shared(h)
    nll = logsumexp(n(x) * ln_f @ lm_head) - logit[target]

The KDA layer is the SEQUENTIAL recurrence over positions (``lax.scan``
over t), so it shares nothing with the chunked form under test. Its
backward is taken in blocks: an outer scan over blocks of ``SCAN_BLOCK``
positions whose body is under ``jax.checkpoint``, so that one block's
states are live at a time and not all 16,384 (2 MiB each). Attention and
the head are computed one block of queries at a time, each layer under
``jax.checkpoint``. What the absent chips would add (other experts'
outputs, other vocabulary rows) is left out here as in the program; every
held expert is applied densely to all tokens and weighted by a mask of
the tokens whose choice names it.
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


def _attention(q, k, v):
    """Causal softmax attention, one block of Q_BLOCK queries at a time
    against every key. q, k: (B, S, H, Dk); v: (B, S, H, Dv)."""
    b, s, h, d = q.shape
    block = min(Q_BLOCK, s)
    while s % block:
        block -= 1
    kp = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, block, 1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / jnp.sqrt(
            jnp.float32(d))
        keep = kp <= q0 + jnp.arange(block)[:, None]
        scores = jnp.where(keep[None, None], scores, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)

    out = jax.lax.map(one, jnp.arange(0, s, block))       # (n, B, Q, H, Dv)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, v.shape[-1])


def recurrence(q, k, v, g, beta):
    """The gated delta rule, one position at a time. q, k, v, g: (B, L, H,
    D), beta: (B, L, H). Returns ``(o (B, L, H, D), S_L (B, H, D, D))``
    with S indexed (key feature, value feature)."""
    b, l, h, d = q.shape
    block = min(SCAN_BLOCK, l)
    while l % block:
        block -= 1

    def step(s, t):
        qt, kt, vt, gt, bt = t
        s = jnp.exp(gt).astype(STATE_DTYPE)[..., None] * s
        seen = jnp.einsum("bhkv,bhk->bhv", s.astype(kt.dtype), kt)
        s = s + (bt[..., None, None] * kt[..., None]
                 * (vt - seen)[..., None, :]).astype(STATE_DTYPE)
        return s, jnp.einsum("bhkv,bhk->bhv", s.astype(qt.dtype), qt)

    @jax.checkpoint
    def many(s, ts):
        return jax.lax.scan(step, s, ts)

    def by_time(t):  # (B, L, ...) -> (L / block, block, B, ...)
        t = jnp.moveaxis(t, 1, 0)
        return t.reshape((l // block, block) + t.shape[1:])

    s, o = jax.lax.scan(many, jnp.zeros((b, h, d, d), STATE_DTYPE),
                        tuple(map(by_time, (q, k, v, g, beta))))
    o = jnp.moveaxis(o.reshape((l,) + o.shape[2:]), 0, 1)
    return o / jnp.sqrt(jnp.float32(d)), s.astype(jnp.float32)


def _conv(x, w):
    k, l = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(w[i] * padded[:, i:i + l] for i in range(k))


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda(p, h, arch):
    """``(mixer output, each head's root mean square of the final state
    (H,))``."""
    hn, hd = arch["kda"]["n_heads"], arch["kda"]["head_dim"]
    b, l, _ = h.shape

    def heads(x):
        return x.reshape(b, l, hn, hd)

    q, k, v = (heads(jax.nn.silu(_conv(_proj(h, p[name]), p["conv_w"][i])))
               for i, name in enumerate(("wq", "wk", "wv")))
    g = -jnp.exp(p["A_log"])[:, None] * heads(jax.nn.softplus(
        _proj(_proj(h, p["w_fa"]), p["w_fb"]) + p["dt_bias"]))
    o, state = recurrence(_l2norm(q), _l2norm(k), v, g,
                          jax.nn.sigmoid(_proj(h, p["w_b"])))
    o = _rmsnorm(o, p["norm"], arch["rms_norm_eps"]).reshape(b, l, -1)
    gate = jax.nn.sigmoid(_proj(_proj(h, p["w_ga"]), p["w_gb"]))
    return _proj(o * gate, p["wo"]), jnp.sqrt(
        jnp.mean(state * state, axis=(0, 2, 3)))


def _mla(p, h, arch):
    rank, nope = arch["mla"]["kv_rank"], arch["mla"]["qk_nope"]
    d = p["wq"].shape[0]
    q = _proj(h, p["wq"].reshape(d, -1)).reshape(
        h.shape[:2] + p["wq"].shape[1:])
    kva = _proj(h, p["w_kva"])
    latent = _rmsnorm(kva[..., :rank], p["kv_norm"], arch["rms_norm_eps"])
    kvb = _proj(latent, p["w_kvb"].reshape(rank, -1)).reshape(
        h.shape[:2] + p["w_kvb"].shape[1:])
    shared = jnp.broadcast_to(kva[:, :, None, rank:],
                              kvb.shape[:3] + (kva.shape[-1] - rank,))
    a = _attention(q, jnp.concatenate([kvb[..., :nope], shared], -1),
                   kvb[..., nope:])
    return _proj(a.reshape(h.shape[:2] + (-1,)), p["wo"].reshape(-1, d))


def _ffn(h, w):
    return _proj(jax.nn.silu(_proj(h, w["w1"])) * _proj(h, w["w3"]),
                 w["w2"])


def route(p, h, moe):
    """``(the chosen experts (B, S, top_k), their weights)``: sigmoid
    scores, the balancing bias in the choice only."""
    scores = jax.nn.sigmoid(h @ p["w_router"])
    _, chosen = jax.lax.top_k(scores + p["router_bias"], moe["top_k"])
    picked = jnp.take_along_axis(scores, chosen, -1)
    return chosen, moe["routed_scale"] * picked / jnp.sum(
        picked, -1, keepdims=True)


def _sparse(p, h, moe):
    """``(the held experts' part + the shared expert, assignments each
    held expert takes)``; h: (B, S, d)."""
    chosen, gates = route(p, h, moe)
    first, count = moe["experts_held"]

    def one_expert(y, e_and_w):  # one body for all the experts held
        e, expert = e_and_w
        named = chosen == first + e                         # (B, S, k)
        weight = jnp.sum(jnp.where(named, gates, 0.0), -1)  # (B, S)
        y = y + weight[..., None] * jax.checkpoint(_ffn)(h, expert)
        return y, jnp.sum(named, dtype=jnp.float32)

    y, load = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(count), {n: p[n] for n in ("w1", "w3", "w2")}))
    return y + _ffn(h, p["shared"]), load


def _layer(p, x, arch):
    """``(x after the layer, the KDA state's rms by head or None, the held
    experts' loads or None)``."""
    eps = arch["rms_norm_eps"]
    h = _rmsnorm(x, p["ln1"], eps)
    rms = None
    if "kda" in p:
        mixed, rms = _kda(p["kda"], h, arch)
    else:
        mixed = _mla(p["mla"], h, arch)
    x = x + mixed
    h = _rmsnorm(x, p["ln2"], eps)
    if "moe" in p:
        y, load = _sparse(p["moe"], h, arch["moe"])
        return x + y, rms, load
    return x + _ffn(h, p), rms, None


def _nll_block(x, targets, ln_f, lm_head, eps):
    logits = _proj(_rmsnorm(x, ln_f, eps), lm_head)
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def trunk(params, tokens, arch):
    """``(activations before the final norm, [state rms by head of each
    KDA layer], [loads of each sparse layer])``; float32 parameters
    expected."""
    x = params["embed"][tokens]
    states, loads = [], []
    for p in params["layers"]:
        x, rms, load = jax.checkpoint(lambda p, x: _layer(p, x, arch))(p, x)
        if rms is not None:
            states.append(rms)
        if load is not None:
            loads.append(load)
    return x, states, loads


def loss(params, tokens, targets, arch):
    """``(mean next-token cross entropy of tokens (B, S) int32, {"rms":
    the final state's rms of each head of each KDA layer (layers, H),
    "load": the assignments each held expert takes in each sparse layer
    (layers, experts held)})``."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x, states, loads = trunk(params, tokens, arch)
        block = min(Q_BLOCK, tokens.shape[1])
        while tokens.shape[1] % block:
            block -= 1

        def nll_of(s0):
            return jax.checkpoint(
                lambda xb, tb, ln, head: _nll_block(
                    xb, tb, ln, head, arch["rms_norm_eps"]))(
                jax.lax.dynamic_slice_in_dim(x, s0, block, 1),
                jax.lax.dynamic_slice_in_dim(targets, s0, block, 1),
                params["ln_f"], params["lm_head"])

        total = jnp.sum(jax.lax.map(
            nll_of, jnp.arange(0, tokens.shape[1], block)))
        return total / tokens.size, {"rms": jnp.stack(states),
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


def loss_and_grads(params, tokens, targets, arch, leaf_paths):
    """``((loss, {"rms", "load"}), [d loss / d leaf for each path])`` on
    one batch; a path is a key sequence into the parameter tree, e.g.
    ``("layers", 2, "kda", "conv_w")``. Only the chosen leaves' gradients
    are formed."""
    def f(leaves):
        p = params
        for path, leaf in zip(leaf_paths, leaves):
            p = _put(p, path, leaf)
        return loss(p, tokens, targets, arch)

    return jax.value_and_grad(f, has_aux=True)(
        [get_leaf(params, p) for p in leaf_paths])
