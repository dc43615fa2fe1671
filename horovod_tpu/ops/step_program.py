"""Compiled hot loop: one jitted, buffer-donated XLA step program.

An eager training step pays per-step Python orchestration and a blocking
device->host readback that a fully in-graph step does not — the gap is
orchestration, not the wire. This module closes it by
compiling the *whole* training step — forward, backward, in-graph gradient
exchange, optimizer apply, and (opt-in) the guard health matrix — into
ONE jitted program with donated parameter/optimizer-state buffers, so a
steady-state step costs one Python dispatch and zero host readbacks
(docs/performance.md "Compiled hot loop").

Reference framing: the reference's per-step machinery (background thread,
rank-0 negotiation per tensor, fusion-buffer staging —
horovod/common/operations.cc:577-1100) exists to overlap exchange with
backward compute. Inside one XLA program the compiler does all of that
scheduling itself; what the eager engine still buys is dynamic-shape
negotiation and membership arbitration, so it stays untouched as the
negotiation-parity/legacy path and the compiled path falls back to it
cleanly (HOROVOD_DEVICE_RESIDENT=0, HOROVOD_STEP_PROGRAM=0, or shape
churn past HOROVOD_STEP_PROGRAM_CHURN_LIMIT).

Cache discipline (the PR 5 ``WireProgramCache`` made shared): every
program is keyed by a signature — exchange mode, averaging, compression,
optimizer digest, loss digest, param/opt-state/batch avals — plus the
engine's participants digest, through ``EagerEngine.step_program``. An
elastic re-init over survivors yields a different digest, so a program
compiled for a dead membership can never run again; the builder lru tier
below registers with ``engine.register_wire_program_builder`` so elastic
aborts clear its Mesh-keyed executables too.

Composable parallelism: ONE spec-driven body (``_spec_shard``) covers
the flat psum, the expert-parallel MoE layout, the ZeRO stripe ladder,
the staged DCN hop and tensor parallelism. What a leaf reduces over,
what it divides by, whether state is striped and whether a DCN link
carries a residual is said by an ``optimizers._ShardingSpec`` and by
nothing else, so every combination (moe x zero, moe x dcn,
model-parallel x any) compiles into the same single donated program
(docs/performance.md "Composable parallelism").

Guard integration (PR 8): with ``HOROVOD_GUARD=1`` the program gains a
distinct cache signature whose extra output is the per-segment
``[finite, l2]`` health matrix, and an IN-GRAPH gate that holds
params/opt state when any segment goes non-finite — the skip rung of the
ladder happens on device with no readback. The host-side fold
(accounting, LR backoff, rollback) is deferred by one step
(``GuardMonitor.consume_deferred``) so fetching the tiny health array
never serializes the hot loop. Without a monitor the compiled program is
byte-for-byte the no-guard build, exactly like ``_jit_psum_unfuse`` vs
``_jit_psum_unfuse_health``.
"""

import contextlib
import functools
import hashlib
import itertools
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import guard, metrics, runtime
from ..diag import xla_trace
from ..diag.recorder import span
from ..runtime import AXIS
from ..stats import record_jit_traced
from .collectives import _nbytes, exchange_bucket_plan, tree_health
from .compression import Compression
from .engine import register_wire_program_builder

__all__ = ["CompiledTrainStep", "compiled_train_step"]


# ------------------------------------------------------------- signatures
#
# The step-program cache key must be (a) stable across steps of one loop
# (steady state = one entry, hit rate -> 1), (b) distinct for genuinely
# different programs, and (c) collision-proof within a process even when
# two callables digest identically (a retrained lambda with equal
# bytecode). (b) comes from content digests over code objects; (c) from a
# per-object token handed out once per live callable.

_token_registry = weakref.WeakKeyDictionary()
_token_counter = itertools.count()


def _obj_token(obj):
    """Process-unique token for a live callable: same object => same
    token, different live objects => different tokens. Weak so dropping
    the last reference to a loss_fn/optimizer also drops the token."""
    try:
        tok = _token_registry.get(obj)
        if tok is None:
            tok = next(_token_counter)
            _token_registry[obj] = tok
        return tok
    except TypeError:  # unweakrefable (builtins, some partials)
        return id(obj)


def _callable_digest(fn):
    """Content digest of a callable: code bytes of the function, nested
    code constants, and closure cells holding callables or simple
    scalars. Two structurally identical loss functions digest equal (so
    a re-created loop re-hits the cache); a changed hyperparameter in a
    closure changes the digest."""
    h = hashlib.sha1()
    seen = set()

    def feed(obj):
        code = getattr(obj, "__code__", None)
        if code is None or id(code) in seen:
            h.update(type(obj).__name__.encode())
            return
        seen.add(id(code))
        h.update(code.co_name.encode())
        h.update(code.co_code)
        for const in code.co_consts:
            if hasattr(const, "co_code"):
                h.update(const.co_name.encode())
                h.update(const.co_code)
        for cell in getattr(obj, "__closure__", None) or ():
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if callable(v):
                feed(v)
            elif isinstance(v, (bool, int, float, str, bytes, type(None))):
                h.update(repr(v).encode())
    feed(fn)
    return h.hexdigest()[:12]


def _leaf_sd(leaf):
    """(shape, dtype-str) of a pytree leaf, scalars included."""
    if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
        return (tuple(leaf.shape), np.dtype(leaf.dtype).str)
    a = np.asarray(leaf)
    return (tuple(a.shape), a.dtype.str)


def _tree_avals_digest(tree):
    """Digest of a pytree's structure + per-leaf (shape, dtype): the
    signature component that makes a changed model/optimizer layout a
    different program without keying on values."""
    leaves, treedef = jax.tree.flatten(tree)
    h = hashlib.sha1(repr(treedef).encode())
    for leaf in leaves:
        h.update(repr(_leaf_sd(leaf)).encode())
    return h.hexdigest()[:12]


def _needs_x64(*trees):
    """64-bit dtypes anywhere in params/state/batch need JAX's x64 mode
    around the program call or XLA silently downcasts them — same
    contract as EagerEngine._x64_scope."""
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if np.dtype(_leaf_sd(leaf)[1]).itemsize == 8:
                return True
    return False


def _contains_inline_exchange(fn, depth=0):
    """True when ``fn``'s closure (recursively, shallow-bounded) holds a
    transform tagged as exchanging gradients inside its own update — a
    hand-rolled optax.chain around DistributedGradientTransform. The
    compiled step must not stack its own psum on top of that."""
    if depth > 4:
        return False
    if getattr(fn, "_hvd_exchange", None) is not None:
        return True
    for cell in getattr(fn, "__closure__", None) or ():
        try:
            v = cell.cell_contents
        except ValueError:
            continue
        for item in (v if isinstance(v, (tuple, list)) else (v,)):
            update = getattr(item, "update", item)
            if callable(update) and _contains_inline_exchange(
                    update, depth + 1):
                return True
    return False


# -------------------------------------------------------- in-graph exchange

def _psum_exchange(grads, axis, average, comp, with_health,
                   denom=None, buckets=1):
    """In-graph gradient exchange: ONE ``lax.psum`` over the tuple of
    gradient leaves, each at its wire dtype (compression is the dtype
    round-trip, ops/compression.py), then the per-element finish the
    device-resident eager wire program gives a segment
    (``collectives.unfuse_segments``): cast back from the wire dtype,
    float-divide / integer-floor-divide by the world size when
    ``average``, cast back — so the two paths agree within dtype
    tolerance. No flat wire row is built: the leaves go to the psum in
    their own shapes and XLA's all-reduce combiner decides what travels
    together; on axes of size 1 the psum is nothing and the gradients
    flow from the backward straight into the optimizer. Returns
    ``(exchanged_tree, health)`` where ``health`` (guard builds only) is
    one ``[finite, l2]`` float32 row per gradient leaf in ORIGINAL leaf
    order, computed on the reduced pre-average leaves via
    ``tree_health`` — bit-identical across ranks by construction.

    ``buckets > 1`` splits the exchange into that many layer-ordered
    buckets (``collectives.exchange_bucket_plan``): one psum call per
    bucket over that bucket's leaves, each traced under
    ``hvd_exchange_bucket{k}``, the last-produced leaves of backprop
    first. Per-element reduction math is untouched by bucket boundaries,
    so results are bit-identical at every setting. Health rows are
    reassembled into ORIGINAL leaf order either way, so the in-graph
    skip gate's verdict never depends on the bucket count.

    ``axis`` may be an axis-name tuple (one psum over the product of
    axes — the 2-D MoE mesh's dense-leaf exchange). ``denom`` overrides
    the averaging divisor: the MoE expert leaves psum over the data
    axes only but still divide by the FULL world size (their gradients
    already carry the expert-axis contributions via the backward
    alltoall — see optimizers._LeafSpec)."""
    leaves, treedef = jax.tree.flatten(grads)
    if not leaves:
        health = jnp.zeros((0, 2), jnp.float32) if with_health else None
        return grads, health
    axes = (axis,) if isinstance(axis, str) else tuple(axis)
    n = 1
    for a in axes:
        n *= int(lax.axis_size(a))
    if denom is not None:
        n = int(denom)
    summed = [None] * len(leaves)
    plan = exchange_bucket_plan(leaves, buckets)
    for b, idxs in enumerate(plan):
        scope = (jax.named_scope(f"hvd_exchange_bucket{b}")
                 if len(plan) > 1 else contextlib.nullcontext())
        with scope:
            wire = tuple(leaves[i] if comp is None
                         else comp.compress(leaves[i])[0] for i in idxs)
            record_jit_traced("allreduce_jit",
                              sum(_nbytes(w) for w in wire), axes)
            for i, s in zip(idxs, lax.psum(wire, axes)):
                summed[i] = s
    out = []
    for g, s in zip(leaves, summed):
        res = s.astype(g.dtype)
        if average:
            # unfuse_segments' branch, on the STATIC dtype
            if jnp.issubdtype(g.dtype, jnp.floating):
                res = res / n
            else:
                res = res // n
            res = res.astype(g.dtype)
        out.append(res)
    health = tree_health(summed) if with_health else None
    return jax.tree.unflatten(treedef, out), health


# ------------------------------------------------------------ the builder

@functools.lru_cache(maxsize=64)
def _build_step_program(mesh, loss_fn, tx, nbatch, exchange, average,
                        comp, with_health, donate, has_aux, zmeta=None,
                        buckets=1, spec=None):
    """Build ONE jitted step program: per-shard forward + backward, the
    in-graph gradient exchange (a psum over the gradient leaves — no
    flat wire row, ``_psum_exchange``), optimizer apply, and (guard
    builds) the health matrix plus the in-graph skip gate. Every
    argument is static and hashable — the lru tier dedupes construction
    per process the way engine._jit_psum_unfuse does, and the engine's
    step-program cache fronts it with membership-scoped keys.

    Program contract: ``prog(params, opt_state, *batch)`` with params
    and opt_state replicated (``P()``) and every batch leaf sharded on
    its leading axis across the batch axes (every mesh axis except the
    spec's model axis); returns ``(new_params, new_state,
    loss[, aux][, health])`` replicated. ``loss`` (and ``aux``) are
    ``lax.pmean``'d across shards — equal to the full-batch value for a
    mean-reduced loss over equal shards. Donation aliases params and
    opt_state with their updated outputs so the step runs in place
    (caller rebinds the returns; the stale inputs are dead buffers).
    jit is lazy: compilation happens at first execution, not here.

    ``exchange`` is ``"psum"`` (the program exchanges as ``spec`` — an
    :class:`optimizers._ShardingSpec` — says; ``spec=None`` means the
    keyless stage-0 spec over the mesh's axes) or ``"none"`` (``tx``
    exchanges inside its own update and the program adds nothing).

    ONE body serves every exchange layout (docs/performance.md
    "Composable parallelism") in three trace-time modes, each a
    property of the spec:

    - **decomposed** (``"psum"`` with ``zero_stage == 0`` and no DCN
      link): gradients group by their per-leaf ``(reduce, denom)``
      recipe — fully-reduced groups take the bucketed psum over their
      leaves, sharded groups (expert/model leaves) sum over their
      reduce axes and divide by their denominator, with health stats
      reduced over the missing axes so every rank gates identically.
      ``tx`` is the base optimizer. The values are those of the eager
      engine's flat wire row, bit for bit
      (tests/test_exchange_leaves.py).
    - **whole** (stage 1/2, a stage-0 DCN link, or ``"none"``):
      ``tx.update`` owns the exchange; health comes from the
      post-exchange updates, reduced over any non-data spec axes.
    - **resident** (stage 3; ``zmeta`` set): the first argument is this
      rank's flat parameter STRIPE (``CompiledTrainStep.shard_params``),
      not the full tree. ``zmeta = (treedef, shapes, dtype-strs,
      acc-dtype-str)`` carries the static full-tree layout; per step
      the program allgathers the stripe into full params just-in-time
      (full precision — forward numerics never ride the lossy hop),
      takes grads, pre-reduces each leaf over its non-stripe axes per
      the spec, reduce-scatters down to the stripe (optionally
      DCN-compressed with the error-feedback residual from opt_state),
      applies the base optimizer to the stripe, and returns the NEW
      STRIPE — full parameters and gradients are XLA temporaries that
      never persist between steps.

    ``buckets`` (HOROVOD_EXCHANGE_BUCKETS) splits the psum exchange
    into layer-ordered buckets, one psum call per bucket
    (``_psum_exchange``), and runs the parameter apply bucket-at-a-time
    (``optimizers.bucketed_apply_updates``). 1 (the default) is one psum
    call over all leaves; every count gives the same values. It is part
    of the lru key and the engine cache signature, so bucketed and
    unbucketed programs never collide.
    zero2/zero3 builds take their bucketing from the optimizer's
    ``_ZeroCore.chunk_layout`` instead (same knob, chunk-major stripe)."""
    from ..optimizers import (_ShardingSpec, _axes_size_prod,
                              _spec_pre_reduce)
    mesh_axes = tuple(mesh.axis_names)
    if spec is None:
        spec = _ShardingSpec(data_axes=mesh_axes, average=average)
    if exchange not in ("psum", "none"):
        raise ValueError(
            f"unknown exchange {exchange!r}: the step program takes "
            "'psum' (exchange as the sharding spec says) or 'none' (tx "
            "exchanges inside its own update)")
    batch_axes = tuple(a for a in mesh_axes if a != spec.model_axis)
    resident = zmeta is not None
    decomposed = (exchange == "psum" and spec.zero_stage == 0
                  and not spec.dcn_link)
    if not decomposed and not resident:
        # Whole-transform modes (striped stage 1/2, stage-0 DCN chain,
        # an inline transform) reduce inside tx.update over
        # spec.known_axes only — a mesh axis of size > 1 the spec
        # doesn't know about would be silently under-reduced, so reject
        # it at build time.
        for name, size in mesh.shape.items():
            if size > 1 and name not in spec.known_axes:
                raise ValueError(
                    f"mesh axis {name!r} (size {size}) is not named by "
                    f"the sharding spec axes {spec.known_axes} — the "
                    "striped/DCN transform cannot reduce over it. Pass "
                    "the matching expert_keys/model_keys, or give the "
                    "optimizer a tuple data axis (e.g. "
                    "axis_name=(\"hvd\", \"ep\"))")

    def _spec_shard(params, opt_state, *batch):
        # Resident mode: `params` is this rank's flat stripe; allgather
        # it into the full tree just-in-time (full precision — forward
        # numerics never ride the lossy DCN hop).
        if resident:
            core = tx.update._hvd_zero_core
            base = tx.update._hvd_base
            ztreedef, shapes, dtypes, acc_str = zmeta
            n = core.axis_size()
            total = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
            padded = core.padded_len(total, n)
            stripe = params
            with jax.named_scope("hvd_exchange"):
                flat = core.gather(stripe, padded, n, lossless=True)
            leaves, pos = [], 0
            for shp, dt in zip(shapes, dtypes):
                sz = int(np.prod(shp, dtype=np.int64))
                leaves.append(flat[pos:pos + sz].astype(dt).reshape(shp))
                pos += sz
            full = jax.tree.unflatten(ztreedef, leaves)
        else:
            full = params
        # vjp instead of value_and_grad (same primal/cotangent graph) so
        # forward and backward land in separate named scopes — the trace
        # parser's phase buckets (diag/xla_trace.py).
        fwd = lambda p: loss_fn(p, *batch)  # noqa: E731
        with jax.named_scope("hvd_forward"):
            if has_aux:
                loss, bwd, aux = jax.vjp(fwd, full, has_aux=True)
            else:
                loss, bwd = jax.vjp(fwd, full)
                aux = None
        with jax.named_scope("hvd_backward"):
            (grads,) = bwd(jnp.ones_like(loss))
        health = None
        groups = {}
        with jax.named_scope("hvd_exchange"):
            if has_aux:
                aux = jax.tree.map(lambda a: lax.pmean(a, batch_axes),
                                   aux)
            loss = lax.pmean(loss, batch_axes)
            if resident:
                # each leaf first reduces over its non-stripe axes and
                # pre-divides (nothing to do for a dense leaf on a 1-D
                # mesh), then rides the flat data-axis stripe
                g_leaves = [
                    _spec_pre_reduce(g.astype(acc_str), ls, core.axis,
                                     spec.average)
                    for g, ls in zip(jax.tree.leaves(grads),
                                     spec.leaf_specs(grads, mesh_axes))]
                flat_g, _ = core.flatten_pad(g_leaves, acc_str, n)
                g_stripe, new_res = core.scatter(flat_g,
                                                 opt_state.residual, n)
            elif decomposed:
                g_leaves, gdef = jax.tree.flatten(grads)
                for i, ls in enumerate(spec.leaf_specs(grads, mesh_axes)):
                    groups.setdefault(ls, []).append(i)
                out = [None] * len(g_leaves)
                hrows = [None] * len(g_leaves)
                for ls, idxs in groups.items():
                    sub = [g_leaves[i] for i in idxs]
                    missing = tuple(a for a in mesh_axes
                                    if a not in ls.reduce)
                    if not missing:
                        # fully-reduced leaves: the plain exchange,
                        # bucketed and health'd
                        res, hr = _psum_exchange(
                            sub, ls.reduce, average, comp, with_health,
                            buckets=buckets)
                        for k, i in enumerate(idxs):
                            out[i] = res[k]
                            if with_health:
                                hrows[i] = hr[k]
                    else:
                        # sharded leaves (expert/model): sum over the
                        # reduce axes, then the denominator finish —
                        # the health rows below want the pre-average
                        # sums.
                        summed, _ = _psum_exchange(
                            sub, ls.reduce, False, comp, False)
                        dn = _axes_size_prod(ls.denom)
                        res = ([(g / dn).astype(g.dtype)
                                for g in summed]
                               if average else summed)
                        for k, i in enumerate(idxs):
                            out[i] = res[k]
                        if with_health:
                            # Sharded rows differ across the missing
                            # axes, so their verdicts reduce over them
                            # (the zero3 stripe idiom):
                            # [all-shards-finite, global l2] —
                            # identical on every rank, so the in-graph
                            # gate never diverges the mesh.
                            fins = [jnp.isfinite(g) for g in summed]
                            bads = jnp.stack([
                                jnp.sum(~f).astype(jnp.float32)
                                for f in fins])
                            sqs = jnp.stack([
                                jnp.sum(jnp.square(jnp.where(
                                    f, g, 0).astype(jnp.float32)))
                                for g, f in zip(summed, fins)])
                            red = lax.psum(jnp.stack([bads, sqs]),
                                           missing)
                            hr = jnp.stack(
                                [(red[0] == 0).astype(jnp.float32),
                                 jnp.sqrt(red[1])], axis=1)
                            for k, i in enumerate(idxs):
                                hrows[i] = hr[k]
                if with_health:
                    health = (jnp.stack(hrows) if hrows
                              else jnp.zeros((0, 2), jnp.float32))
                grads = jax.tree.unflatten(gdef, out)
        with jax.named_scope("hvd_optimizer"):
            if resident:
                u_stripe, new_base = base.update(g_stripe,
                                                 opt_state.base, stripe)
                new_stripe = (stripe + u_stripe).astype(stripe.dtype)
                new_state = opt_state._replace(base=new_base,
                                               residual=new_res)
            else:
                updates, new_state = tx.update(grads, opt_state, full)
        if resident:
            if with_health:
                # Stripe values differ per rank, so the health row is
                # the psum-reduced global verdict — one [finite, l2]
                # row over the update stripes, identical on every rank.
                with jax.named_scope("hvd_guard"):
                    fin = jnp.isfinite(u_stripe)
                    bad = lax.psum(jnp.sum(~fin).astype(jnp.float32),
                                   mesh_axes)
                    sumsq = lax.psum(jnp.sum(jnp.square(
                        jnp.where(fin, u_stripe, 0)
                        .astype(jnp.float32))), mesh_axes)
                    health = jnp.stack([(bad == 0).astype(jnp.float32),
                                        jnp.sqrt(sumsq)]).reshape(1, 2)
                    ok = jnp.all((health[:, 0] >= 0.5)
                                 & jnp.isfinite(health[:, 1]))
                    new_stripe = jnp.where(ok, new_stripe, stripe)
                    new_state = jax.tree.map(
                        lambda new, old: jnp.where(ok, new, old),
                        new_state, opt_state)
            outs = (new_stripe, new_state, loss)
            if has_aux:
                outs += (aux,)
            if with_health:
                outs += (health,)
            return outs
        if with_health and health is None:
            # whole-transform modes reduce inside tx.update, so the
            # health rows come from the
            # post-exchange updates (allgathered, hence bit-identical
            # across ranks for a pure data-axis spec).
            with jax.named_scope("hvd_guard"):
                extra = tuple(a for a in mesh_axes
                              if a not in spec.data_axes)
                u_leaves = jax.tree.leaves(updates)
                if not extra:
                    health = tree_health(u_leaves)
                elif not u_leaves:
                    health = jnp.zeros((0, 2), jnp.float32)
                else:
                    # expert/model updates vary across the shard axes —
                    # reduce the per-leaf stats over them so every rank
                    # gates identically
                    fins = [jnp.isfinite(u) for u in u_leaves]
                    bads = jnp.stack([jnp.sum(~f).astype(jnp.float32)
                                      for f in fins])
                    sqs = jnp.stack([jnp.sum(jnp.square(jnp.where(
                        f, u, 0).astype(jnp.float32)))
                        for u, f in zip(u_leaves, fins)])
                    red = lax.psum(jnp.stack([bads, sqs]), extra)
                    health = jnp.stack(
                        [(red[0] == 0).astype(jnp.float32),
                         jnp.sqrt(red[1])], axis=1)
        with jax.named_scope("hvd_optimizer"):
            all_plain = all(
                all(a in ls.reduce for a in mesh_axes) for ls in groups)
            if decomposed and buckets > 1 and len(groups) == 1 \
                    and all_plain:
                # per-bucket apply: bucket k's p+u depends only on
                # bucket k's psum, so the tail bucket's apply overlaps
                # earlier buckets' wire (numerics identical — see the
                # helper).
                from ..optimizers import bucketed_apply_updates
                plan = exchange_bucket_plan(jax.tree.leaves(updates),
                                            buckets)
                new_params = bucketed_apply_updates(full, updates, plan)
            else:
                new_params = optax.apply_updates(full, updates)
        if with_health:
            # In-graph skip gate: any non-finite segment holds BOTH the
            # params and the optimizer state (momenta, step counts) — a
            # true skip, decided on device from rank-identical data so
            # every rank gates identically without coordination.
            with jax.named_scope("hvd_guard"):
                ok = jnp.all((health[:, 0] >= 0.5)
                             & jnp.isfinite(health[:, 1]))
                new_params = jax.tree.map(
                    lambda new, old: jnp.where(ok, new, old), new_params,
                    full)
                new_state = jax.tree.map(
                    lambda new, old: jnp.where(ok, new, old), new_state,
                    opt_state)
        outs = (new_params, new_state, loss)
        if has_aux:
            outs += (aux,)
        if with_health:
            outs += (health,)
        return outs

    # The batch shards over every non-model axis (model groups see the
    # same data); params stay P() — expert/model leaves ride the
    # fake-replicated per-shard idiom (check_vma=False).
    batch_spec = (P(batch_axes[0]) if len(batch_axes) == 1
                  else P(batch_axes))
    fn = jax.shard_map(_spec_shard, mesh=mesh,
                       in_specs=(P(), P()) + (batch_spec,) * nbatch,
                       out_specs=P(), check_vma=False)
    # compiler_options=None is the bare jit: a one-device or CPU step
    # compiles exactly as it did without them
    return jax.jit(fn, donate_argnums=(0, 1) if donate else (),
                   compiler_options=(
                       _exchange_compiler_options(mesh, exchange) or None))


register_wire_program_builder(_build_step_program)


# ------------------------------------------- what the step asks of XLA
#
# On a TPU an all-reduce runs beside compute only if BOTH of these are
# set (libtpu 0.0.34: either alone leaves every all-reduce synchronous),
# and then only as an `async_collective_fusion`: ONE all-reduce fused with
# one compute fusion, in the step a weight-gradient matmul. The compiler
# fuses single-operand all-reduces only, so whatever its combiner has
# packed into a variadic all-reduce stays synchronous and exposed.
_ASYNC_ALL_REDUCE = (
    ("xla_enable_async_all_reduce", True),
    ("xla_tpu_enable_async_collective_fusion_fuse_all_reduce", True),
)
# Hence the combiner's threshold: leaves above it travel alone and can
# fuse, the small ones still share one all-reduce (each all-reduce has a
# fixed cost). 32 MiB is the chip's answer (cgpt13b_dp4 on a v5e 2x2,
# PERF.md section 6 PR 30): 63 % of the gradient's bytes fused and the
# step 473.1 -> 453.5 ms; 64 MiB fuses 51 % (457.3 ms), 128 MiB and the
# compiler's default 19-22 % (461.7 / 463.2 ms); 48 MiB fuses no more
# than 32 and compiles to a higher peak, 24 and 16 MiB no longer fit the
# chip. The name is internal to the compiler, so a libtpu may refuse it;
# the step then compiles with the two public options alone
# (``_compiler_accepts``).
_COMBINER_THRESHOLD = ("xla_jf_crs_combiner_threshold_in_bytes", 32 << 20)


@functools.lru_cache(maxsize=None)
def _compiler_accepts(device, option):
    """Whether ``device``'s compiler knows ``option`` (a ``(name,
    value)`` pair): a one-scalar compile, once per process."""
    from ..utils.logging import get_logger
    x = jax.ShapeDtypeStruct((), jnp.float32,
                             sharding=jax.sharding.SingleDeviceSharding(
                                 device))
    try:
        jax.jit(lambda a: a,
                compiler_options=dict([option])).lower(x).compile()
    except Exception as e:  # noqa: BLE001 - any refusal means "no"
        if "No such compile option" not in str(e):
            raise
        get_logger().warning(
            "this libtpu has no compile option %r: the step's large "
            "gradient all-reduces stay combined, and fewer of them "
            "overlap the backward", option[0])
        return False
    return True


def _exchange_compiler_options(mesh, exchange):
    """The ``compiler_options`` of a step program's jit, derived from
    what the builder can see and from nothing else: ``{}`` (the bare
    jit) unless the program exchanges (``exchange="psum"``) over more
    than one TPU device — on one device there is no all-reduce, and the
    CPU backend knows none of these names. Dense leaves and the loss
    reduce over every mesh axis, so "some reduced axis is larger than
    one" is "the mesh has more than one device"."""
    devices = mesh.devices
    if (exchange != "psum" or devices.size < 2
            or devices.flat[0].platform != "tpu"):
        return {}
    options = dict(_ASYNC_ALL_REDUCE)
    if _compiler_accepts(devices.flat[0], _COMBINER_THRESHOLD):
        options.update([_COMBINER_THRESHOLD])
    return options


def engine_cached_program(signature, build):
    """Fetch a compiled program through the engine's membership-scoped
    step-program cache — the builder tier's public entry for consumers
    outside the train step (serve/engine.py routes its prefill/decode
    programs here, so inference programs share the same cache economics,
    hit/miss gauges, and elastic-abort invalidation as the train loop).
    ``build`` must be (or call into) a ``register_wire_program_builder``
    registered lru builder so aborts can clear it. Returns
    ``(program, was_hit)``."""
    from .. import runtime
    eng = runtime.state().engine
    prog, was_hit, _, _ = eng.step_program(signature, build)
    return prog, was_hit


def _zmeta_of(params):
    """Static full-tree layout carried by the stage-3 program signature:
    ``(treedef, shapes, dtype-strs, accumulation-dtype-str)`` — all
    hashable, so it rides the lru/cache keys directly."""
    leaves, treedef = jax.tree.flatten(params)
    if not leaves:
        raise ValueError("zero3 needs a non-empty parameter tree")
    shapes = tuple(tuple(leaf.shape) for leaf in leaves)
    dtypes = tuple(np.dtype(_leaf_sd(leaf)[1]).str for leaf in leaves)
    acc = np.dtype(jnp.result_type(*[np.dtype(d) for d in dtypes])).str
    return (treedef, shapes, dtypes, acc)


@register_wire_program_builder
@functools.lru_cache(maxsize=16)
def _build_shard_params(mesh, core, zmeta):
    """Jitted full-params -> stripe converter for the zero3 layout: the
    flatten/cast/pad + ``dcn_sigma``-owner slice, emitted fake-replicated
    (``P()`` under check_vma=False) so each device keeps exactly its
    stripe — per-device bytes = total/N, the zero1 stripe convention."""
    axis = mesh.axis_names[0]
    treedef, shapes, dtypes, acc = zmeta
    del treedef, shapes, dtypes

    def per_shard(params):
        n = core.axis_size()
        flat, _ = core.flatten_pad(jax.tree.leaves(params), acc, n)
        return core.param_stripe(flat, n)

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)


@register_wire_program_builder
@functools.lru_cache(maxsize=16)
def _build_unshard_params(mesh, core, zmeta):
    """Jitted stripe -> full-params converter (inverse of
    ``_build_shard_params``): full-precision staged allgather, then
    unflatten back to the original tree — for eval/checkpoint export."""
    axis = mesh.axis_names[0]
    del axis
    treedef, shapes, dtypes, acc = zmeta
    del acc

    def per_shard(stripe):
        n = core.axis_size()
        total = sum(int(np.prod(s, dtype=np.int64)) for s in shapes)
        padded = core.padded_len(total, n)
        flat = core.gather(stripe, padded, n, lossless=True)
        leaves, pos = [], 0
        for shp, dt in zip(shapes, dtypes):
            sz = int(np.prod(shp, dtype=np.int64))
            leaves.append(flat[pos:pos + sz].astype(dt).reshape(shp))
            pos += sz
        return jax.tree.unflatten(treedef, leaves)

    fn = jax.shard_map(per_shard, mesh=mesh, in_specs=(P(),),
                       out_specs=P(), check_vma=False)
    return jax.jit(fn)


def _chaos_perturb(tree):
    """Chaos 'corrupt' for the compiled path (guard/inject.py on_step):
    add a large FINITE value to the first element of the first float
    leaf of this rank's params/stripe — the in-graph health gate can't
    see it (everything stays finite), which is the point: only the
    cross-replica divergence probe catches it."""
    leaves, treedef = jax.tree.flatten(tree)
    for i, leaf in enumerate(leaves):
        if (hasattr(leaf, "dtype")
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and getattr(leaf, "size", 0)):
            flat = jnp.ravel(leaf).at[0].add(jnp.asarray(1e3, leaf.dtype))
            leaves[i] = flat.reshape(leaf.shape)
            break
    return jax.tree.unflatten(treedef, leaves)


# ----------------------------------------------------------- the entry point

class CompiledTrainStep:
    """The shared compiled-step entry point: ``DistributedOptimizer``
    in every configuration, plain optax optimizers, and the serving
    paths all route through this one builder + cache.

    ::

        step = hvd.compiled_train_step(loss_fn, optax.sgd(0.01))
        opt_state = step.init(params)
        for batch in data:
            params, opt_state, loss = step(params, opt_state, *batch)
        step.finish()   # flush the last deferred guard verdict

    ``loss_fn(params, *batch) -> loss`` (or ``(loss, aux)`` with
    ``has_aux=True``) must be mean-reduced over its batch shard; every
    batch array is sharded on its leading axis across the mesh, params
    and optimizer state are replicated. Steady state is zero per-step
    Python beyond one dispatch: params/state never leave the device, the
    loss return is an unfetched device scalar, and the donated inputs
    are consumed in place.

    ``exchange``: ``"auto"`` (default) reads the optimizer's sharding
    spec (``optimizers._ShardingSpec``, carried by every
    ``DistributedOptimizer`` product) and compiles the layout it says:
    stage 0 without a DCN link is **decomposed** — per-group psums over
    the gradient leaves replace the product's exchange link and only
    the base optimizer runs in the program, over the runtime's N-D mesh
    when the spec names expert/model leaves; stage 1/2 and a stage-0
    DCN link run the transform **whole** (the stripe / residual state
    IS the update transform); stage 3 is **resident** (parameters live
    as stripes, see :meth:`shard_params`). A plain optimizer gets the
    keyless stage-0 spec over ``axis_name`` — the psum in front. A
    transform that exchanges inside its own update (a bare
    ``DistributedGradientTransform``) runs as it is. ``"none"`` says
    the optimizer already exchanges and the program adds nothing: a
    hand-rolled ``optax.chain`` around ``DistributedGradientTransform``
    is detected and rejected under auto — pass ``exchange="none"``
    instead of silently exchanging twice.

    Fallback (``hvd_step_fallback_total`` by reason): the eager engine
    remains the negotiation-parity path — ``HOROVOD_DEVICE_RESIDENT=0``
    (``host_mode``), ``HOROVOD_STEP_PROGRAM=0`` (``disabled``), or more
    distinct shape signatures than HOROVOD_STEP_PROGRAM_CHURN_LIMIT
    (``shape_churn``) run a decomposed step on the flat mesh as host
    value_and_grad + ``exchange_gradients`` +
    ``guarded_apply_updates``. Layouts whose reduction lives inside the
    update transform or spans expert/model axes have no host
    decomposition; their fallback is the same per-shard program built
    undonated via the builder tier, bypassing the engine cache."""

    def __init__(self, loss_fn, optimizer, *, axis_name=AXIS,
                 exchange="auto", average=True,
                 compression=Compression.none, donate=None, has_aux=False,
                 name="hvd.step", exchange_buckets=None):
        if isinstance(optimizer, optax.MultiSteps):
            raise ValueError(
                "compiled_train_step cannot introspect optax.MultiSteps "
                "(DistributedOptimizer(backward_passes_per_step>1)); "
                "compile the inner step and accumulate outside, or wrap "
                "the compiled step's tx in MultiSteps yourself with "
                "exchange='none'")
        self._loss_fn = loss_fn
        self._axis = axis_name
        self._average = average
        self._compression = compression
        self._donate = donate
        self._has_aux = has_aux
        self._name = name
        # None defers to HOROVOD_EXCHANGE_BUCKETS at call time; the
        # explicit arg pins it per step object (bench's overlap A/B).
        self._buckets = exchange_buckets
        self._engine = None
        self._donate_eff = None
        self._signatures = set()
        self._guard_pending = None
        self._zmeta = None
        self._flops = {}   # signature -> whole-program FLOPs
        self._calls = 0    # the `step` span's ordinal
        self.flops_per_step = 0.0
        # xla_trace.exchange_async of the newest signature's executable
        self.exchange_async = None
        self.cache_hits = 0
        self.cache_misses = 0
        self.compiled_steps = 0
        self.fallback_steps = 0

        if exchange not in ("auto", "none"):
            raise ValueError(
                f"unknown exchange mode {exchange!r}: expected 'auto' "
                "(exchange as the optimizer's sharding spec says; a "
                "plain optimizer gets the psum in front) or 'none' (the "
                "optimizer exchanges inside its own update). ZeRO, "
                "expert and model layouts are options of "
                "hvd.DistributedOptimizer, not of the step")
        from ..optimizers import _ShardingSpec
        update = getattr(optimizer, "update", None)
        spec = (getattr(update, "_hvd_spec", None)
                if exchange == "auto" else None)
        # a transform that exchanges inside update() and carries no
        # spec (bare DistributedGradientTransform): the program adds
        # nothing
        self._inline = exchange == "none" or (
            spec is None
            and getattr(update, "_hvd_exchange", None) == "inline")
        if spec is None:
            if not self._inline and _contains_inline_exchange(update):
                raise ValueError(
                    "compiled_train_step(exchange='auto'): the "
                    "optimizer embeds a gradient-exchanging transform "
                    "(DistributedGradientTransform inside a chain) — "
                    "adding the step's psum would exchange twice. Pass "
                    "exchange='none', or use hvd.DistributedOptimizer "
                    "which auto-decomposes.")
            spec = _ShardingSpec(data_axes=axis_name, average=average)
        self._spec = spec
        self._tx = optimizer
        if self._decomposed and getattr(update, "_hvd_base",
                                        None) is not None:
            # a DistributedOptimizer stage-0 chain: the program's
            # per-group psums replace its exchange link
            self._average = update._hvd_average
            self._compression = update._hvd_compression
            self._tx = update._hvd_base
        self._comp = (None if self._compression is Compression.none
                      else self._compression)

    # ------------------------------------------------------------- plumbing

    @property
    def _decomposed(self):
        """True when the program exchanges the gradient leaves itself
        (per-group psums) and ``tx`` is the base optimizer."""
        return (not self._inline and self._spec.zero_stage == 0
                and not self._spec.dcn_link)

    @property
    def _resident(self):
        """True when the program runs the stripe-resident layout."""
        return not self._inline and self._spec.zero_stage == 3

    @property
    def _exchange(self):
        """The layout's short label (metrics, ``perf_signature``, the
        cache signature)."""
        return "none" if self._inline else self._spec.label

    def init(self, params):
        """Optimizer-state init for the transform the program runs (the
        base optimizer when decomposed, else the whole transform with
        its stripe / residual state). For the stripe-resident layout
        (stage 3), pass the FULL parameter tree here (it also fixes the
        static stripe layout); then convert with :meth:`shard_params`
        and feed the step stripes."""
        if self._resident:
            self._zmeta = _zmeta_of(params)
        return self._tx.init(params)

    # ---------------------------------------------------- zero3 conversion

    def _zero3_layout(self, params=None):
        if self._zmeta is None:
            if params is None:
                raise ValueError(
                    "stripe-resident layout not fixed yet — call "
                    "step.init(full_params) or step.shard_params("
                    "full_params) first")
            self._zmeta = _zmeta_of(params)
        return self._tx.update._hvd_zero_core, self._zmeta

    def shard_params(self, params):
        """Full replicated params -> this rank's flat stripe (the
        stripe-resident format; per-device bytes = total/N). The
        returned array is what the compiled step consumes and returns.
        Under an expert/model spec the stripe holds this shard column's
        values for the sharded leaves (the fake-replicated idiom)."""
        core, zmeta = self._zero3_layout(params)
        st = runtime.state()
        return _build_shard_params(self._step_mesh(st), core,
                                   zmeta)(params)

    def unshard_params(self, stripe):
        """Stripe -> full replicated parameter tree (full-precision
        staged allgather) — for eval, checkpointing, or handing back to
        non-sharded code."""
        core, zmeta = self._zero3_layout()
        st = runtime.state()
        return _build_unshard_params(self._step_mesh(st), core,
                                     zmeta)(stripe)

    @property
    def cache_hit_rate(self):
        """Lifetime step-program cache hit rate seen by THIS step object
        (the engine gauge aggregates across objects)."""
        total = self.cache_hits + self.cache_misses
        return self.cache_hits / total if total else 0.0

    @property
    def donates(self):
        """Whether the compiled program donates the params/opt-state
        buffers it is called with: the ``donate=`` pin, else the
        HOROVOD_FUSION_DONATE policy resolved against the mesh's platform
        on the first call (None until then)."""
        return self._donate_eff

    def _bind_engine(self, eng):
        """Elastic re-init / fresh session: signatures and deferred guard
        health belong to the dead engine; the new engine's participants
        digest cold-starts the cache (digest scoping)."""
        if eng is not self._engine:
            self._engine = eng
            self._donate_eff = None
            self._signatures = set()
            self._guard_pending = None
            self._flops = {}

    def _step_mesh(self, st):
        """The mesh the step program maps over: the flat data-parallel
        mesh unless the sharding spec names expert/model axes, in which
        case the smallest runtime mesh providing every spec axis wins —
        the 2-D (data, expert) mesh (HOROVOD_EXPERT_PARALLEL) or the
        3-D (data, expert, model) mesh (HOROVOD_MODEL_PARALLEL), both
        fixed at init time."""
        spec = self._spec
        if not spec.shard_axes:
            return st.mesh
        req = spec.required_axes()
        for mesh in (st.mesh, getattr(st, "expert_mesh", None),
                     getattr(st, "model_mesh", None)):
            if mesh is not None and req.issubset(mesh.axis_names):
                return mesh
        raise ValueError(
            f"no runtime mesh provides the sharding-spec axes "
            f"{tuple(sorted(req))}: set HOROVOD_EXPERT_PARALLEL and/or "
            "HOROVOD_MODEL_PARALLEL (Config.expert_parallel / "
            "Config.model_parallel) to degrees > 1 whose product "
            "divides the world size before hvd.init() so the matching "
            "expert/model mesh exists")

    def _resolve_donate(self, st):
        if self._donate_eff is None:
            if self._donate is not None:
                self._donate_eff = bool(self._donate)
            else:
                # Mirror the engine's fusion-donate auto policy: on CPU
                # jax may zero-copy-alias host arrays as device memory,
                # and donating an alias lets XLA scribble over a buffer
                # the caller still owns — so auto means accelerators only.
                flat0 = list(st.mesh.devices.flat)
                platform = flat0[0].platform if flat0 else "cpu"
                cfg = st.config
                self._donate_eff = (cfg.fusion_donate == 1 or
                                    (cfg.fusion_donate < 0
                                     and platform != "cpu"))
        return self._donate_eff

    def _resolve_buckets(self, cfg):
        """Effective exchange-bucket count for this call: the explicit
        constructor pin, else HOROVOD_EXCHANGE_BUCKETS. Only the
        decomposed layout traces the bucketed exchange; every other
        mode normalizes to 1 so the knob can't churn their cache
        signatures (zero2/zero3 bucketing rides the optimizer's
        _ZeroCore, which is already part of the signature via its
        object token)."""
        if not self._decomposed:
            return 1
        b = (self._buckets if self._buckets is not None
             else cfg.exchange_buckets)
        return max(int(b), 1)

    def _signature(self, params, opt_state, batch, with_health, donate,
                   buckets):
        comp_tag = ("" if self._comp is None
                    else type(self._comp).__name__)
        return (
            "step_program",
            "health" if with_health else "plain",
            self._exchange, bool(self._average), comp_tag, int(buckets),
            _callable_digest(self._tx.update), _obj_token(self._tx.update),
            _callable_digest(self._loss_fn), _obj_token(self._loss_fn),
            bool(donate), bool(self._has_aux), self._zmeta, self._spec,
            _tree_avals_digest(params), _tree_avals_digest(opt_state),
            # batch avals stay explicit (not digested) so shape churn is
            # visible in the key and debuggable from a cache dump
            tuple(_leaf_sd(leaf) for leaf in jax.tree.leaves(batch)),
        )

    @property
    def perf_signature(self):
        """Stable short workload id for the perf-sentry baseline (the
        model-digest component; the caller appends batch/world/zero)."""
        return f"{_callable_digest(self._loss_fn)[:12]}|{self._exchange}"

    def _analyze(self, prog, params, opt_state, batch):
        """One-time per-signature program introspection, before the first
        execution (donation leaves the example buffers dead afterwards):
        whole-program FLOPs from ``Lowered.cost_analysis`` for the MFU
        accounting, 0.0 when it cannot be had. This is where the
        program is traced and lowered, once — the first execution
        reuses both and adds only the backend compile. Span
        ``step.analyze``. The device-trace join needs no HLO from here:
        the tracer reads it from the executable that ran
        (diag/xla_trace.py ``live_hlo``)."""
        try:
            cost = prog.lower(params, opt_state, *batch).cost_analysis()
            cost = cost[0] if isinstance(cost, (list, tuple)) else cost
            return float((cost or {}).get("flops", 0.0))
        except Exception:  # noqa: BLE001 - introspection is best-effort
            return 0.0

    def _read_exchange_async(self, prog, client, before):
        """Once per signature, after its first execution: what the
        compiler made of the program's all-reduces
        (``xla_trace.exchange_async``), read from the optimized HLO of
        the executable that ran — the one ``before`` (the client's
        executables before that execution) lacks — and published on the
        step object and as the ``hvd_exchange_*`` gauges. No compile, no
        device work; span ``step.read_hlo``."""
        try:
            live = client.live_executables()
            new = [e for e in live if e not in before]
            texts = xla_trace.live_hlo({f"jit_{prog.__name__}"},
                                       new or live)
            stats = xla_trace.exchange_async("".join(texts.values()))
        except Exception:  # noqa: BLE001 - introspection is best-effort
            return
        self.exchange_async = stats
        metrics.EXCHANGE_ALL_REDUCES.set(stats["all_reduces"])
        metrics.EXCHANGE_ASYNC_ALL_REDUCES.set(stats["async_all_reduces"])
        metrics.EXCHANGE_ASYNC_BYTES_SHARE.set(stats["async_bytes_share"])

    def _flush_guard(self, monitor):
        """Fold the PREVIOUS compiled step's in-graph health matrix and
        run its policy ladder (deferred-by-one so the readback happens
        after the program has long completed — effectively free)."""
        pend, self._guard_pending = self._guard_pending, None
        if pend is None or monitor is None:
            return None
        return monitor.consume_deferred(*pend)

    def finish(self):
        """Flush the final step's deferred guard verdict; call once after
        the loop. Returns the verdict dict, or None with no guard/backlog."""
        return self._flush_guard(guard.get())

    # ------------------------------------------------------------- hot path

    def __call__(self, params, opt_state, *batch):
        # One `step` span per call with its parts as children (names are
        # the contract: docs/diagnostics.md "Host spans").
        self._calls += 1
        with span("step", step=self._calls) as sp:
            return self._step(sp, params, opt_state, *batch)

    def _step(self, sp, params, opt_state, *batch):
        st = runtime.state()
        self._bind_engine(st.engine)
        cfg = st.config
        inj = guard.inject.get()
        if inj is not None and inj.on_step(self._name):
            # chaos 'corrupt' on the compiled path: a finite SDC on this
            # rank's params/stripe — invisible to the in-graph health
            # gate, caught by the divergence probe (guard/inject.py).
            params = _chaos_perturb(params)
        enabled = cfg.step_program == 1 or (
            cfg.step_program != 0 and cfg.device_resident != 0)
        if not enabled:
            reason = "disabled" if cfg.step_program == 0 else "host_mode"
            return self._fallback(reason, params, opt_state, *batch)
        monitor = guard.get()
        with_health = monitor is not None
        self._flush_guard(monitor)
        donate = self._resolve_donate(st)
        buckets = self._resolve_buckets(cfg)
        with span("step.signature"):
            sig = self._signature(params, opt_state, batch, with_health,
                                  donate, buckets)
        if sig not in self._signatures:
            if len(self._signatures) >= cfg.step_program_churn_limit:
                return self._fallback("shape_churn", params, opt_state,
                                      *batch)
            self._signatures.add(sig)
        mesh, loss_fn, tx = self._step_mesh(st), self._loss_fn, self._tx
        exchange = "none" if self._inline else "psum"
        average, comp = self._average, self._comp
        nbatch, has_aux = len(batch), self._has_aux
        if self._resident:
            self._zero3_layout()  # raises before caching a bad signature
        zmeta = self._zmeta if self._resident else None
        spec = self._spec

        def build():
            return _build_step_program(mesh, loss_fn, tx, nbatch, exchange,
                                       average, comp, with_health, donate,
                                       has_aux, zmeta, buckets, spec)

        with span("step.lookup"):
            prog, was_hit, hits, misses = st.engine.step_program(sig, build)
        sp.set(hit=was_hit)
        if was_hit:
            self.cache_hits += 1
        else:
            self.cache_misses += 1
        metrics.STEP_PROGRAM_CACHE_HITS.set(hits)
        metrics.STEP_PROGRAM_CACHE_MISSES.set(misses)
        flops = self._flops.get(sig)
        tracer = xla_trace.get()
        scope = (jax.enable_x64() if _needs_x64(params, opt_state, batch)
                 else contextlib.nullcontext())
        first = flops is None
        with scope:
            if first:
                with span("step.analyze"):
                    flops = self._flops[sig] = self._analyze(
                        prog, params, opt_state, batch)
                    client = mesh.devices.flat[0].client
                    before = client.live_executables()
            if tracer is not None:
                # `params` is the previous step's output: waiting for it
                # drains the device (only at the two ends of a capture)
                tracer.tick(owner=self, drain=functools.partial(
                    jax.block_until_ready, params))
            with span("step.execute", step_trace=self._calls):
                outs = prog(params, opt_state, *batch)
            if first:
                with span("step.read_hlo"):
                    self._read_exchange_async(prog, client, before)
        metrics.STEP_COMPILED_TOTAL.inc()
        self.compiled_steps += 1
        if flops:
            self.flops_per_step = flops
            metrics.STEP_FLOPS_TOTAL.inc(flops)
        if with_health:
            health = outs[-1]
            outs = outs[:-1]
            names = tuple(f"{self._name}.seg.{i}"
                          for i in range(int(health.shape[0])))
            self._guard_pending = (names, health)
        return outs

    # ------------------------------------------------------------- fallback

    def _fallback(self, reason, params, opt_state, *batch):
        metrics.STEP_FALLBACK_TOTAL.labels(reason=reason).inc()
        self.fallback_steps += 1
        return self._eager_step(params, opt_state, *batch)

    def _eager_step(self, params, opt_state, *batch):
        """Negotiation-parity step. A decomposed layout on the flat mesh
        runs on the eager engine (host value_and_grad on the full local
        batch -> exchange_gradients -> guarded_apply_updates), matching
        the compiled program's numbers for a mean-reduced loss over
        equal shards. Every other layout reduces inside tx.update or
        over expert/model axes, which only has meaning in a mapped
        program — its fallback is the same per-shard program built
        undonated via the builder tier (no engine cache, no
        donation)."""
        monitor = guard.get()
        scope = (jax.enable_x64() if _needs_x64(params, opt_state, batch)
                 else contextlib.nullcontext())
        if self._decomposed and not self._spec.shard_axes:
            from ..optimizers import (exchange_gradients,
                                      guarded_apply_updates)
            if monitor is not None and self._guard_pending is not None:
                # previous compiled step's health folds into THIS step's
                # end_step (inside guarded_apply_updates) — never dropped
                monitor.note_device_health(*self._guard_pending)
                self._guard_pending = None
            with scope:
                grad_fn = jax.value_and_grad(self._loss_fn,
                                             has_aux=self._has_aux)
                if self._has_aux:
                    (loss, aux), grads = grad_fn(params, *batch)
                else:
                    loss, grads = grad_fn(params, *batch)
            grads = exchange_gradients(grads, average=self._average,
                                       compression=self._compression,
                                       name_prefix=f"{self._name}.grads")
            with scope:
                params, opt_state, _applied = guarded_apply_updates(
                    params, opt_state, grads, self._tx)
            if self._has_aux:
                return params, opt_state, loss, aux
            return params, opt_state, loss
        if monitor is not None and self._guard_pending is not None:
            monitor.consume_deferred(*self._guard_pending)
            self._guard_pending = None
        st = runtime.state()
        if self._resident:
            self._zero3_layout()
        prog = _build_step_program(self._step_mesh(st), self._loss_fn,
                                   self._tx, len(batch),
                                   "none" if self._inline else "psum",
                                   self._average, self._comp, False, False,
                                   self._has_aux,
                                   self._zmeta if self._resident else None,
                                   self._resolve_buckets(st.config),
                                   self._spec)
        with scope:
            return prog(params, opt_state, *batch)


def compiled_train_step(loss_fn, optimizer, *, axis_name=AXIS,
                        exchange="auto", average=True,
                        compression=Compression.none, donate=None,
                        has_aux=False, name="hvd.step",
                        exchange_buckets=None):
    """Build a :class:`CompiledTrainStep` — the compiled hot loop
    (docs/performance.md "Compiled hot loop"): forward, backward,
    in-graph gradient exchange, optimizer apply (and, under
    HOROVOD_GUARD=1, the health matrix + in-graph skip gate) as ONE
    jitted, buffer-donated XLA program, signature-cached through the
    engine's membership-scoped step-program cache.

    ``exchange_buckets`` (default: HOROVOD_EXCHANGE_BUCKETS, 1) splits
    the exchange into layer-ordered buckets, one psum call each —
    docs/performance.md "Bucketed backward/exchange overlap". Every
    count gives the same values."""
    return CompiledTrainStep(loss_fn, optimizer, axis_name=axis_name,
                             exchange=exchange, average=average,
                             compression=compression, donate=donate,
                             has_aux=has_aux, name=name,
                             exchange_buckets=exchange_buckets)
