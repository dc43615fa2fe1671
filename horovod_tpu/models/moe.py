"""Mixture-of-Experts FFN with expert parallelism over the ``ep`` axis.

No reference analog — the reference has no alltoall at all (message.h:
47-49; upstream Horovod only gained one in 0.20) and no model layers.
This is the layer the framework's :func:`horovod_tpu.ops.collectives.
alltoall` primitive exists for: tokens are routed to experts that live on
other chips, travel there in one fused all_to_all over ICI, are
transformed by the local expert slice, and return through the reverse
all_to_all (whose VJP is again an all_to_all — the whole layer is
differentiable end-to-end).

Routing is the Mesh-TensorFlow / Switch capacity-based scheme, chosen for
XLA: every shape is static. Each token picks its top-k experts; a
position-in-expert cumsum assigns capacity slots; tokens beyond an
expert's capacity are dropped (their residual path carries them). The
dispatch/combine tensors turn scatter/gather into einsums, which is what
the MXU wants.

Layout: ``num_experts`` is sharded over ``ep`` — each shard holds
``E_loc = E/|ep|`` expert FFNs and every shard routes its own tokens over
ALL experts:

    (t, d) --dispatch--> (E, C, d) --alltoall--> (E_loc, |ep|*C, d)
           --expert FFN--> (E_loc, |ep|*C, d) --alltoall--> (E, C, d)
           --combine--> (t, d)

Aux output is the Switch load-balancing loss (mean fraction-routed x
mean router-prob, scaled by E); add it to the task loss with a small
coefficient to keep routing uniform.

Two routing implementations live here, and the configuration picks:

- :func:`moe_layer` — the capacity layer above, for callers with an ``ep``
  axis (its all-to-all needs static rows per expert, so it drops).
- :func:`moe_dropless` — taken when ``MoEConfig.experts_held`` states
  which experts this chip holds: sort / gather routing with no capacity
  and no dropped token, over ALL ``num_experts`` router outputs, computing
  only the part of the result that the experts held give, as grouped
  matmuls (ops/grouped_matmul.py). On one chip the layer runs without
  its exchange; what the absent chips' experts would add is left out.

One routing implementation is the aim (ROADMAP C): it needs a ragged
all-to-all under :func:`moe_dropless`.
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.collectives import alltoall, alltoall_chunked
from ..ops.grouped_matmul import grouped_matmul


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int = 512
    d_ff: int = 2048
    num_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # --- moe_dropless only ---
    # (first index, count) of the routed experts this chip holds; the
    # router still scores all num_experts. Set = the dropless layer.
    experts_held: Optional[tuple] = None
    # expert FFNs are gated (act(x w1) * (x w3)) w2 with SiLU; else
    # gelu(x w1) w2
    gated: bool = False
    # the renormalised top-k probabilities are scaled by this
    routed_scale: float = 1.0
    # what scores the experts: "softmax" over all of them, or "sigmoid" of
    # each logit with the balancing bias ``router_bias`` added for the
    # CHOICE of the top k only (arXiv:2408.15664), never for the weights
    router: str = "softmax"
    # width of the shared expert every token passes through (0 = none)
    shared_d_ff: int = 0
    # run the grouped matmul kernels in the interpreter (CPU tests)
    interpret: bool = False


def init_moe_params(key, cfg):
    """Router over all ``num_experts`` plus the stacked expert FFNs: all
    of them, or, with ``experts_held``, the ``count`` held here (and the
    ``w3`` gate matrices / the ``shared`` expert when configured)."""
    k1, k2, k3 = jax.random.split(key, 3)
    pd = cfg.param_dtype
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    held = e if cfg.experts_held is None else cfg.experts_held[1]

    def ffn(ks, lead, width):
        out = {"w1": jax.random.normal(ks[0], lead + (d, width), pd)
               / math.sqrt(d),
               "w2": jax.random.normal(ks[1], lead + (width, d), pd)
               / math.sqrt(width)}
        if cfg.gated:
            out["w3"] = jax.random.normal(
                jax.random.fold_in(ks[0], 1), lead + (d, width), pd
            ) / math.sqrt(d)
        return out

    out = {"w_router": jax.random.normal(k1, (d, e), pd) / math.sqrt(d),
           **ffn((k2, k3), (held,), ff)}
    if cfg.router == "sigmoid":
        # starts at zero and gets no gradient: whoever balances the
        # experts' loads moves it between steps
        out["router_bias"] = jnp.zeros((e,), pd)
    if cfg.shared_d_ff:
        out["shared"] = ffn(jax.random.split(jax.random.fold_in(key, 1)),
                            (), cfg.shared_d_ff)
    return out


def moe_specs(ep_axis: Optional[str] = "ep"):
    """PartitionSpecs: expert dim sharded over ``ep_axis``; the router is
    tiny and replicated."""
    from jax.sharding import PartitionSpec as P
    return {
        "w_router": P(),
        "w1": P(ep_axis, None, None),
        "w2": P(ep_axis, None, None),
    }


def dropless_specs(cfg):
    """PartitionSpecs of a :func:`moe_dropless` layer's leaves: what a
    chip holds is stated by ``experts_held``, so nothing is sharded over
    a mesh axis (the tree of :func:`init_moe_params`)."""
    from jax.sharding import PartitionSpec as P
    ffn = {"w1": P(), "w2": P(), **({"w3": P()} if cfg.gated else {})}
    out = {"w_router": P(), **ffn}
    if cfg.router == "sigmoid":
        out["router_bias"] = P()
    if cfg.shared_d_ff:
        out["shared"] = dict(ffn)
    return out


def _top_k_dispatch(probs, top_k, capacity):
    """Build dispatch/combine tensors.

    probs: (t, E) router probabilities. Returns
      dispatch: (t, E, C) 0/1 — token t occupies expert e's slot c,
      combine:  (t, E, C) f32  — dispatch weighted by the (renormalized)
        gate probability.
    """
    t, e = probs.shape
    gates, idx = lax.top_k(probs, top_k)              # (t, k)
    gates = gates / jnp.maximum(
        jnp.sum(gates, axis=-1, keepdims=True), 1e-9)

    base_count = jnp.zeros((e,), jnp.int32)
    dispatch = jnp.zeros((t, e, capacity), jnp.bool_)
    combine = jnp.zeros((t, e, capacity), jnp.float32)
    for slot in range(top_k):                          # static, small
        onehot = jax.nn.one_hot(idx[:, slot], e, dtype=jnp.int32)  # (t, E)
        # position of each token within its chosen expert's queue,
        # continuing after the tokens already placed by earlier slots
        pos = jnp.cumsum(onehot, axis=0) - 1 + base_count[None, :]
        base_count = base_count + jnp.sum(onehot, axis=0)
        pos_tok = jnp.sum(pos * onehot, axis=1)        # (t,)
        keep = (pos_tok < capacity) & (onehot.sum(axis=1) > 0)
        slot_hot = (jax.nn.one_hot(pos_tok, capacity, dtype=jnp.float32)
                    * keep[:, None])                   # (t, C)
        d_slot = onehot[..., None] * slot_hot[:, None, :]  # (t, E, C)
        dispatch = dispatch | (d_slot > 0)
        combine = combine + d_slot * gates[:, slot, None, None]
    return dispatch.astype(jnp.float32), combine


def moe_layer(params, x, cfg, ep_axis: Optional[str] = None, chunks: int = 1,
              with_stats: bool = False, full_capacity: bool = False):
    """Apply the MoE FFN. x: (B, S, d) -> (y, aux_loss).

    ``ep_axis=None`` runs all experts locally (single-device / no expert
    parallelism); with an axis name, params["w1"]/["w2"] must hold this
    shard's expert slice (leading dim E_loc).

    ``chunks > 1`` pipelines the expert exchange (Tutel-style): the
    (E, C, d) dispatch tensor is cut into ``chunks`` capacity slices and
    each slice runs dispatch-alltoall -> expert FFN -> combine-alltoall
    independently, so inside one XLA program chunk *k*'s FFN overlaps
    chunk *k+1*'s alltoall. The result is bit-identical to ``chunks=1``
    (the FFN is independent per capacity slot and each chunk round-trips
    in place); a value that does not divide the capacity falls back to
    the largest divisor below it. The alltoall and FFN ops carry
    ``hvd_dispatch`` / ``hvd_expert`` / ``hvd_combine`` named_scope
    labels so the XLA phase tracer (docs/diagnostics.md) can attribute
    device time per MoE phase and measure the overlap.

    ``full_capacity=True`` is the inference/serving mode (serve/
    engine.py): capacity is set to ``t * top_k`` so every (token,
    expert) assignment gets a slot and nothing drops. Besides removing
    quality loss at decode batch sizes (where ``t`` is tiny and the
    capacity rounding is coarse), it makes each token's output
    independent of batch composition — a token's expert rows are its
    own regardless of which capacity slot the batch-order cumsum hands
    it, and with no drops the slot assignment can never push a
    neighbor's token out. Continuous batching (docs/serving.md) needs
    exactly this: a sequence's stream must not change when other
    sequences join or leave the batch mid-flight.

    ``with_stats=True`` returns ``(y, aux, stats)`` where ``stats`` has
    ``routed_tokens`` / ``dropped_tokens`` (token-slot assignments kept /
    lost to capacity, this shard), ``load_balance_loss`` and the static
    ``chunks`` actually used — the sources of the ``hvd_moe_*`` metric
    families (docs/observability.md)."""
    b, s, d = x.shape
    x_flat = x.reshape(b * s, d)
    t = b * s
    e = cfg.num_experts
    ep = lax.psum(1, ep_axis) if ep_axis else 1
    e_loc = params["w1"].shape[0]
    assert e_loc * ep == e, (
        f"expert shards ({e_loc} x {ep}) != num_experts ({e})")

    logits = jnp.einsum("td,de->te", x_flat.astype(jnp.float32),
                        params["w_router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    if full_capacity:
        capacity = max(1, t * cfg.top_k)
    else:
        capacity = max(1, int(math.ceil(
            t * cfg.top_k * cfg.capacity_factor / e)))
    dispatch, combine = _top_k_dispatch(probs, cfg.top_k, capacity)

    # Switch load-balancing aux loss: E * mean_e(frac_routed * mean_prob)
    frac = jnp.mean(dispatch.sum(axis=-1), axis=0)     # (E,)
    aux = e * jnp.sum(frac * jnp.mean(probs, axis=0))

    expert_in = jnp.einsum("tec,td->ecd", dispatch,
                           x_flat.astype(jnp.float32)).astype(cfg.dtype)

    def _ffn(z):
        with jax.named_scope("hvd_expert"):
            h = jnp.einsum("ecd,edf->ecf", z,
                           params["w1"].astype(cfg.dtype),
                           preferred_element_type=jnp.float32)
            h = jax.nn.gelu(h).astype(cfg.dtype)
            return jnp.einsum("ecf,efd->ecd", h,
                              params["w2"].astype(cfg.dtype),
                              preferred_element_type=jnp.float32
                              ).astype(cfg.dtype)

    if ep_axis:
        # (E, C, d) -> (E_loc, ep*C, d): rows for my experts, from all
        # shards — chunked along capacity so each slice's FFN overlaps
        # the next slice's dispatch inside the XLA schedule.
        with jax.named_scope("hvd_dispatch"):
            in_chunks = alltoall_chunked(expert_in, chunks,
                                         axis_name=ep_axis, split_axis=0,
                                         concat_axis=1, chunk_axis=1)
        out_chunks = []
        for piece in in_chunks:
            piece = _ffn(piece)
            with jax.named_scope("hvd_combine"):
                # (E_loc, ep*c, d) -> (E, c, d): every shard gets its
                # slice of tokens back
                piece = alltoall(piece, axis_name=ep_axis, split_axis=1,
                                 concat_axis=0)
            out_chunks.append(piece)
        n_chunks = len(out_chunks)
        expert_out = (out_chunks[0] if n_chunks == 1
                      else jnp.concatenate(out_chunks, axis=1))
    else:
        n_chunks = 1
        expert_out = _ffn(expert_in)

    y = jnp.einsum("tec,ecd->td", combine,
                   expert_out.astype(jnp.float32))
    y = y.reshape(b, s, d).astype(x.dtype)
    if not with_stats:
        return y, aux
    routed = jnp.sum(dispatch)                      # kept (token, slot)s
    attempted = jnp.float32(t * cfg.top_k)          # this shard's tokens
    stats = {
        "routed_tokens": routed,
        "dropped_tokens": attempted - routed,
        "load_balance_loss": aux,
        "chunks": n_chunks,
    }
    return y, aux, stats


# ------------------------------------------------------- dropless routing

def _expert_ffn(x, w, dtype, matmul):
    """One expert FFN: ``(silu(x w1) * (x w3)) w2`` when the parameters
    carry the gate matrix ``w3``, else ``gelu(x w1) w2``; float32 out, the
    activation in float32 whatever type ``matmul`` returns."""
    h = matmul(x, w["w1"].astype(dtype)).astype(jnp.float32)
    h = (jax.nn.silu(h) * matmul(x, w["w3"].astype(dtype)).astype(
        jnp.float32) if "w3" in w else jax.nn.gelu(h))
    return matmul(h.astype(dtype), w["w2"].astype(dtype)).astype(
        jnp.float32)


def _dense_matmul(a, m):
    return jnp.dot(a, m, preferred_element_type=jnp.float32)


def _chunk_rows(c, rows, x, gates, w, order, ends, top_k, dtype,
                interpret):
    """Rows ``[c * rows, (c + 1) * rows)`` of the assignments sorted by
    held expert: ``(each row's weighted expert output, its token)``, zeros
    in the rows past the last live assignment."""
    lo = c * rows
    idx = lax.dynamic_slice(order, (lo,), (rows,))
    tok = idx // top_k
    live = (lo + jnp.arange(rows)) < ends[-1]
    bounds = jnp.clip(jnp.concatenate([jnp.zeros((1,), ends.dtype), ends]),
                      lo, lo + rows)
    # Every row of the chunk goes through the kernels, the dead ones (zero
    # rows in, masked out again) as the tail of the last group: a step's
    # time then depends on how many chunks the routing fills and on
    # nothing else, and no row is left unwritten.
    sizes = (bounds[1:] - bounds[:-1]).at[-1].add(lo + rows - bounds[-1])
    with jax.named_scope("hvd_moe_dispatch"):
        xs = jnp.where(live[:, None], x[tok], 0)
    with jax.named_scope("hvd_moe_experts"):
        out = _expert_ffn(xs, w, dtype, lambda a, m: grouped_matmul(
            a, m, sizes, dtype, interpret))
    with jax.named_scope("hvd_moe_combine"):
        g = jnp.where(live, gates[idx], 0.0)
        return jnp.where(live[:, None], out, 0.0) * g[:, None], tok


def _live_chunks(rows, ends, body, init):
    """``body(c, carry)`` for every chunk of ``rows`` sorted assignments
    that holds a live one."""
    return lax.while_loop(lambda cc: cc[0] * rows < ends[-1],
                          lambda cc: (cc[0] + 1, body(*cc)),
                          (jnp.int32(0), init))[1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _routed(rows, top_k, dtype, interpret, x, gates, w, order, ends):
    """sum over the live assignments of gate * FFN_expert(x[token]), (t,
    d) float32. ``order``: assignment indices (token * top_k + slot)
    sorted by held expert, those no held expert takes last; ``ends``:
    running total of the assignments per held expert. The sorted rows are
    worked through ``rows`` at a time, for as many chunks as hold a live
    assignment: the buffers are sized for a multiple of the usual load,
    and the worst load (every token on the experts held) still drops
    nothing. The trip count is data, so forward and backward are written
    out as loops."""
    return _routed_fwd(rows, top_k, dtype, interpret, x, gates, w, order,
                       ends)[0]


def _routed_fwd(rows, top_k, dtype, interpret, x, gates, w, order, ends):
    def body(c, y):
        out, tok = _chunk_rows(c, rows, x, gates, w, order, ends, top_k,
                               dtype, interpret)
        with jax.named_scope("hvd_moe_combine"):
            return y.at[tok].add(out)

    with jax.named_scope("hvd_moe_combine"):
        y = jnp.zeros(x.shape, jnp.float32)
    return _live_chunks(rows, ends, body, y), (x, gates, w, order, ends)


def _routed_bwd(rows, top_k, dtype, interpret, res, dy):
    x, gates, w, order, ends = res

    def body(c, acc):
        _, vjp, tok = jax.vjp(
            lambda x, gates, w: _chunk_rows(c, rows, x, gates, w, order,
                                            ends, top_k, dtype, interpret),
            x, gates, w, has_aux=True)
        with jax.named_scope("hvd_moe_combine"):
            d_out = dy[tok]
        cts = vjp(d_out)
        with jax.named_scope("hvd_moe_combine"):
            return jax.tree.map(jnp.add, acc, cts)

    with jax.named_scope("hvd_moe_combine"):
        zeros = jax.tree.map(jnp.zeros_like, (x, gates, w))
    return _live_chunks(rows, ends, body, zeros) + (None, None)


_routed.defvjp(_routed_fwd, _routed_bwd)


#: rows of sorted assignments worked through at a time, as a multiple of
#: what uniform routing sends to the experts held
CHUNK_LOAD_FACTOR = 2.0


def chunk_rows(t, cfg):
    """Rows of sorted assignments :func:`moe_dropless` works through at a
    time: ``CHUNK_LOAD_FACTOR`` times what ``t`` tokens send to the experts
    held under uniform routing and at most the worst case, in whole
    512-row tiles."""
    count = cfg.experts_held[1]
    worst = t * min(cfg.top_k, count)
    usual = t * cfg.top_k * count / cfg.num_experts
    return 512 * math.ceil(
        min(worst, max(512, CHUNK_LOAD_FACTOR * usual)) / 512)


def moe_dropless(params, x, cfg):
    """Sparse FFN of a chip that holds ``cfg.experts_held`` of the
    experts. x: (B, S, d) -> ``(y, stats)``.

    ``p = softmax_fp32(x w_router)`` over all ``num_experts`` (``cfg.router
    == "sigmoid"``: ``p = sigmoid_fp32(x w_router)``, the top k chosen on
    ``p + router_bias``); each token takes its ``top_k``, weighted
    ``routed_scale * p / sum(p over the top_k)``; ``y = sum over (top_k and held) of weight * FFN_e(x)`` plus,
    when the parameters carry one, the ``shared`` expert's FFN of every
    token. No capacity, no dropped token: the assignments are sorted by
    expert (``lax.sort``), their tokens' rows gathered, run through
    grouped matmuls (ops/grouped_matmul.py) and added back (``_routed``).
    What experts on other chips would add is left out.

    ``stats`` (float32, for the step's aux): ``expert_load`` (count,)
    assignments each held expert took, ``unrouted_tokens`` tokens none of
    whose ``top_k`` is held here. Device scopes: ``hvd_moe`` around
    ``hvd_moe_route`` / ``_dispatch`` / ``_experts`` / ``_combine`` /
    ``_shared``.
    """
    b, s, d = x.shape
    t, k = b * s, cfg.top_k
    first, count = cfg.experts_held
    with jax.named_scope("hvd_moe"):
        with jax.named_scope("hvd_moe_route"):
            x_flat = x.reshape(t, d).astype(cfg.dtype)
            logits = jnp.dot(x_flat.astype(jnp.float32),
                             params["w_router"].astype(jnp.float32),
                             precision=lax.Precision.HIGHEST)
            if cfg.router == "sigmoid":
                scores = jax.nn.sigmoid(logits)
                _, top_i = lax.top_k(scores + lax.stop_gradient(
                    params["router_bias"].astype(jnp.float32)), k)
                top_p = jnp.take_along_axis(scores, top_i, axis=-1)
            else:
                top_p, top_i = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
            gates = cfg.routed_scale * top_p / jnp.sum(
                top_p, axis=-1, keepdims=True)
            local = top_i - first
            held = (local >= 0) & (local < count)
            key = jnp.where(held, local, count).astype(jnp.int32)
            _, order = lax.sort(
                (key.reshape(-1), jnp.arange(t * k, dtype=jnp.int32)),
                num_keys=1, is_stable=True)
            load = jnp.sum(key.reshape(-1, 1) == jnp.arange(count),
                           axis=0, dtype=jnp.int32)
            rows = chunk_rows(t, cfg)
            # the last chunk may reach past the t * k assignments
            order = jnp.pad(order, (0, rows))
            stats = {"expert_load": load.astype(jnp.float32),
                     "unrouted_tokens": jnp.sum(~jnp.any(held, axis=-1),
                                                dtype=jnp.float32)}
        y = _routed(rows, k, cfg.dtype, cfg.interpret, x_flat,
                    gates.reshape(-1),
                    {n: params[n] for n in ("w1", "w2", "w3")
                     if n in params}, order, jnp.cumsum(load))
        if "shared" in params:
            with jax.named_scope("hvd_moe_shared"):
                y = y + _expert_ffn(x_flat, params["shared"], cfg.dtype,
                                    _dense_matmul)
        with jax.named_scope("hvd_moe_combine"):
            return y.reshape(b, s, d).astype(x.dtype), stats
