"""The plain reference: the architecture the cells train, written out in
straightforward ``jax.numpy``, float32, ``default_matmul_precision
("highest")`` — no kernels, no bf16, no fused exchange, nothing imported
from ``horovod_tpu``. It consumes the program's parameter tree (the names
``embed``, ``pos``, ``layers[i].{ln1, wq, wkv | wqkv, wo, ln2, w1, w2}``,
``ln_f``, ``lm_head`` are the interface) and returns the mean causal-LM
cross entropy of a batch and its gradient for chosen leaves.

The block, as ``TransformerLM`` runs it (each departure from the published
models is listed in the configuration files):

    x   = embed[tokens] (+ pos[:S] when positions are learned)
    h   = rmsnorm(x) * ln1;  q, k, v = h @ wq, h @ wkv  (or h @ wqkv)
    q,k = rope(q), rope(k)                     (rotary configurations)
    a   = softmax(mask(q k^T / sqrt(hd))) v    causal, keys within
                                               `window` of the query
    x   = x + a @ wo
    x   = x + gelu_tanh(rmsnorm(x) * ln2 @ w1) @ w2
    nll = logsumexp(rmsnorm(x) * ln_f @ lm_head) - logit[target]

Attention and the head are computed one block of queries at a time against
exactly the keys the mask admits, each block under ``jax.checkpoint``, so
that a 16,384-token sequence fits in float32: the same dense masked
arithmetic, never the whole S x S score matrix at once.
"""

import functools

import jax
import jax.numpy as jnp

ROPE_THETA = 10000.0
NORM_EPS = 1e-6
Q_BLOCK = 1024


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                             + NORM_EPS) * scale


def _rope(x, positions):
    half = x.shape[-1] // 2
    freqs = ROPE_THETA ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[:, None].astype(jnp.float32) * freqs[None]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend_block(q, k, v, q0, k0, window):
    """Queries at positions q0.. against keys at positions k0..: dense
    scores, causal + window mask, softmax, values. q: (B, Sq, H, D);
    k, v: (B, Sk, H, D)."""
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
        jnp.float32(q.shape[-1]))
    qp = q0 + jnp.arange(q.shape[1])[:, None]
    kp = k0 + jnp.arange(k.shape[1])[None, :]
    keep = kp <= qp
    if window is not None:
        keep &= (qp - kp) < window
    scores = jnp.where(keep[None, None], scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)


def _attention(q, k, v, window):
    s, group = q.shape[1], q.shape[2] // k.shape[2]
    if group > 1:
        k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    out = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(q0 + Q_BLOCK, s)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        block = jax.checkpoint(functools.partial(
            _attend_block, q0=q0, k0=k0, window=window))
        out.append(block(q[:, q0:q1], k[:, k0:q1], v[:, k0:q1]))
    return jnp.concatenate(out, axis=1)


def _layer(p, x, arch):
    h = _rmsnorm(x, p["ln1"])
    if "wq" in p:
        q = jnp.einsum("bsd,dhx->bshx", h, p["wq"])
        kv = jnp.einsum("bsd,dchx->bschx", h, p["wkv"])
        k, v = kv[:, :, 0], kv[:, :, 1]
    else:
        qkv = jnp.einsum("bsd,dchx->bschx", h, p["wqkv"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    if arch["positional"] == "rope":
        positions = jnp.arange(x.shape[1])
        q, k = _rope(q, positions), _rope(k, positions)
    a = _attention(q, k, v, arch.get("attention_window"))
    x = x + jnp.einsum("bshx,hxd->bsd", a, p["wo"])
    u = jax.nn.gelu(_rmsnorm(x, p["ln2"]) @ p["w1"], approximate=True)
    return x + u @ p["w2"]


def _nll_block(x, targets, ln_f, lm_head):
    logits = _rmsnorm(x, ln_f) @ lm_head
    picked = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.sum(jax.nn.logsumexp(logits, -1) - picked)


def loss(params, tokens, targets, arch):
    """Mean next-token cross entropy of ``tokens`` (B, S) int32."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["embed"][tokens]
        if arch["positional"] == "learned":
            x = x + params["pos"][None, :tokens.shape[1]]
        for p in params["layers"]:
            x = jax.checkpoint(functools.partial(_layer, arch=arch))(p, x)
        total = jnp.float32(0)
        for s0 in range(0, tokens.shape[1], Q_BLOCK):
            total += jax.checkpoint(_nll_block)(
                x[:, s0:s0 + Q_BLOCK], targets[:, s0:s0 + Q_BLOCK],
                params["ln_f"], params["lm_head"])
        return total / tokens.size


def get_leaf(tree, path):
    """The leaf at ``path``, a key sequence into the parameter tree."""
    for key in path:
        tree = tree[key]
    return tree


def _put(tree, path, leaf):
    if not path:
        return leaf
    if isinstance(tree, list):
        out = list(tree)
    else:
        out = dict(tree)
    out[path[0]] = _put(tree[path[0]], path[1:], leaf)
    return out


def loss_and_grads(params, tokens, targets, arch, leaf_paths):
    """``(loss, [d loss / d leaf for each path])`` on one batch; a path is
    a key sequence into the parameter tree, e.g. ``("layers", 0, "wq")``.
    Only the chosen leaves' gradients are formed."""
    def f(leaves):
        p = params
        for path, leaf in zip(leaf_paths, leaves):
            p = _put(p, path, leaf)
        return loss(p, tokens, targets, arch)

    return jax.value_and_grad(f)([get_leaf(params, p) for p in leaf_paths])
