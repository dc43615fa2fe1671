"""Distributed optimizer integration.

Reference equivalents:
- torch ``_DistributedOptimizer`` — allreduce-averages every gradient via
  per-parameter hooks with ``backward_passes_per_step`` accumulation and an
  explicit ``synchronize()`` for gradient clipping
  (reference: horovod/torch/__init__.py:44-208);
- TF ``DistributedOptimizer`` — wraps ``compute_gradients`` and allreduces the
  grads (reference: horovod/tensorflow/__init__.py:141-239).

TPU-native design: the primary integration is an **optax gradient
transformation**. Inside a jit/shard_map SPMD program the allreduce is
``lax.pmean`` — XLA fuses it with backward compute and schedules it on ICI,
which is exactly the overlap Horovod's background thread tries to approximate
with hooks. ``backward_passes_per_step`` maps to optax-style accumulation
handled by the caller (optax.MultiSteps composes cleanly around this
transform).
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .ops.compression import Compression
from .runtime import AXIS


def DistributedGradientTransform(axis_name=AXIS, average=True,
                                 compression=Compression.none,
                                 reduce_scatter=False, bucket_bytes=None):
    """An optax ``GradientTransformation`` that allreduces gradients across
    the mesh axis. Chain it before the base optimizer:

        tx = optax.chain(hvd.DistributedGradientTransform(), optax.sgd(lr))

    Must run inside a mapped program over ``axis_name`` (shard_map/pmap) —
    the idiomatic place for the per-step gradient exchange.

    ``reduce_scatter=True`` exchanges the gradients as bucketed
    reduce-scatter + allgather instead of one fused allreduce
    (ops/collectives.bucketed_reducescatter_allgather): numerically
    equivalent, but decomposed so each rank reduces only 1/N of every
    bucket and XLA can pipeline the bounded buckets (``bucket_bytes``,
    default HOROVOD_REDUCE_SCATTER_BUCKET or 32 MiB). To also shard the
    optimizer *state* ZeRO-1 style, use
    ``DistributedOptimizer(..., reduce_scatter=True)``.
    """

    def init_fn(params):
        del params
        return optax.EmptyState()

    def update_fn(updates, state_, params=None):
        del params
        comp = None if compression is Compression.none else compression
        if reduce_scatter:
            from .ops.collectives import bucketed_reducescatter_allgather
            if comp is None:
                return bucketed_reducescatter_allgather(
                    updates, axis_name, average,
                    bucket_bytes=bucket_bytes), state_
            # Compress every leaf FIRST, exchange the whole tree in one
            # bucketed call (dtype grouping fuses the compressed leaves),
            # then decompress — per-leaf exchanges would emit one padded
            # scatter+gather pair per gradient, the sliver traffic
            # bucketing exists to avoid.
            leaves, treedef = jax.tree.flatten(updates)
            comped = [comp.compress(g) for g in leaves]
            exchanged = bucketed_reducescatter_allgather(
                [g for g, _ in comped], axis_name, average,
                bucket_bytes=bucket_bytes)
            out = [comp.decompress(g, ctx)
                   for g, (_, ctx) in zip(exchanged, comped)]
            return jax.tree.unflatten(treedef, out), state_

        # Fork-profiler parity: count this gradient exchange (calls + wire
        # bytes) into the allreduce_jit slot at trace time
        # (reference hot-path counters: operations.cc:219-317).
        from .ops.collectives import _nbytes
        from .stats import record_jit_traced

        leaves = jax.tree.leaves(updates)
        if comp is None:
            wire_bytes = sum(_nbytes(g) for g in leaves)
        else:
            # one compression probe per distinct dtype, not per leaf
            wire_itemsize = {
                d: jnp.dtype(comp.compress(jnp.zeros((), d))[0].dtype).itemsize
                for d in {g.dtype for g in leaves}}
            wire_bytes = sum(
                (_nbytes(g) // jnp.dtype(g.dtype).itemsize)
                * wire_itemsize[g.dtype] for g in leaves)
        record_jit_traced("allreduce_jit", wire_bytes, axis_name)

        # VMA-aware gradient reduction: under check_vma=True shard_map,
        # grads of replicated params arrive pre-psummed and a plain pmean
        # would silently leave them size()x too large. Gradient-only
        # semantics — see ops/collectives._vma_grad_reduce for why the
        # public allreduce must NOT do this. The tree form batches all
        # varying leaves into one wire group (fusion).
        from .ops.collectives import _vma_grad_reduce_tree
        if comp is None:
            return _vma_grad_reduce_tree(updates, axis_name,
                                         average), state_

        def _reduce(g):
            g, ctx = comp.compress(g)
            g = _vma_grad_reduce_tree(g, axis_name, average)
            return comp.decompress(g, ctx)

        return jax.tree.map(_reduce, updates), state_

    # Tag for hvd.compiled_train_step (ops/step_program.py): this
    # transform exchanges gradients INSIDE update(), so a compiled step
    # wrapping it must not add its own psum on top.
    update_fn._hvd_exchange = "inline"
    return optax.GradientTransformation(init_fn, update_fn)


def exchange_gradients(grads, average=True, compression=Compression.none,
                       to_host=False, name_prefix="hvd.grads"):
    """Eager-engine gradient exchange for host-driven training loops —
    the device-resident hot-loop primitive (docs/performance.md).

    Submits every leaf of ``grads`` to the eager engine (one cycle fuses
    the whole pytree into a few wire buckets) and returns the exchanged
    pytree. With the default ``to_host=False`` the *results* are jax
    device arrays sliced out of the fused buffer inside the jitted wire
    program — the result readback that dominates the eager step cost
    never happens, and a jitted optimizer
    apply consumes them straight from HBM:

        grads = hvd.exchange_gradients(grads)           # stays on device
        params = jitted_apply(params, grads)            # consumes on device

    Input staging is unchanged: like every eager submission, the leaves
    are materialized host-side into the fusion buffer (``np.asarray``) —
    so device-array gradients still pay one host copy on the way IN.
    Gradients computed *inside* jit should use
    :func:`DistributedGradientTransform`, which never leaves the
    program; this helper serves loops that compute gradients outside
    jit (the torch/TF compatibility surfaces, line search / RL loops,
    debugging), where the inputs are host-side already and the result
    readback was the remaining serial cost. ``to_host=True`` (or
    ``HOROVOD_DEVICE_RESIDENT=0``) restores the legacy numpy-returning
    exchange."""
    import horovod_tpu as hvd
    leaves, treedef = jax.tree.flatten(grads)
    handles = [hvd.allreduce_async(np.asarray(leaf), average=average,
                                   name=f"{name_prefix}.{i}",
                                   compression=compression, to_host=to_host)
               for i, leaf in enumerate(leaves)]
    out = [hvd._first(hvd.synchronize(h)) for h in handles]
    return jax.tree.unflatten(treedef, out)


def guarded_apply_updates(params, opt_state, grads, tx):
    """Apply an optax update under the step-integrity guard
    (docs/robustness.md): the one-line way to honor the guard's
    skip-step verdict in a host-driven loop.

        grads = hvd.exchange_gradients(grads)
        params, opt_state, applied = hvd.guarded_apply_updates(
            params, opt_state, grads, tx)

    Calls ``GuardMonitor.end_step()`` — this must therefore be the
    step's single apply point — and on a bad verdict returns ``params``
    and ``opt_state`` UNCHANGED (a true skip: momenta and step counters
    don't advance on poisoned gradients; the verdict is computed from
    the bit-identical reduced buffers, so every rank skips the same
    steps and parameters stay in lockstep). With the guard disabled
    (default) this is exactly ``tx.update`` + ``optax.apply_updates``
    plus ``applied=True``."""
    from . import guard
    monitor = guard.get()
    if monitor is not None:
        verdict = monitor.end_step()
        if not verdict["ok"]:
            return params, opt_state, False
    updates, opt_state = tx.update(grads, opt_state, params)
    return optax.apply_updates(params, updates), opt_state, True


def bucketed_apply_updates(params, updates, plan):
    """``optax.apply_updates`` traced one exchange bucket at a time — the
    per-bucket apply half of the compiled step's backward/exchange
    overlap (ops/step_program.py; docs/performance.md "Bucketed
    backward/exchange overlap").

    ``plan`` is a :func:`~horovod_tpu.ops.collectives.exchange_bucket_plan`
    index partition over the flattened parameter leaves. Each bucket's
    ``p + u`` lands under its own ``hvd_apply_bucket{k}`` scope whose only
    data dependencies are that bucket's exchanged updates, so XLA applies
    the first-ready bucket while later buckets' psums are still on the
    wire. The arithmetic is exactly ``optax.apply_updates`` per leaf
    (``(p + u).astype(p.dtype)``) — numerics are identical at every
    bucket count; only the traced grouping changes.

    The whole-tree ``tx.update`` deliberately stays un-split: leafwise
    transforms (sgd/adam/...) already expose per-leaf dataflow XLA
    pipelines by itself, and transforms with cross-leaf joins
    (clip_by_global_norm) MUST see the full tree — splitting them would
    change the numbers. The zero2/zero3 analog is the chunk-major stripe
    update (``_ZeroCore.chunk_layout``), which is per-bucket by layout.
    """
    p_leaves, treedef = jax.tree.flatten(params)
    u_leaves = jax.tree.leaves(updates)
    out = [None] * len(p_leaves)
    for k, idxs in enumerate(plan):
        with jax.named_scope(f"hvd_apply_bucket{k}"):
            for i in idxs:
                p, u = p_leaves[i], u_leaves[i]
                out[i] = (p + u).astype(jnp.asarray(p).dtype)
    return jax.tree.unflatten(treedef, out)


def _stripe_axis_size(axis_name, spec=None):
    """Size of the stripe (data) axis for the sharded-state layout.

    Inside a mapped program this is the binding's extent (constant-folds
    at trace time). Host-side (a ``step.init`` call before the program
    is traced) it comes from the initialized runtime — and a multi-axis
    ``spec`` must NOT take the world size there: the compiled step maps
    over the smallest runtime mesh providing every spec axis
    (``CompiledTrainStep._step_mesh``), where the data axis spans
    world / (expert * model) devices — sizing the base optimizer's state
    or the DCN residual by the world instead would lay out 1/world
    stripes against the program's 1/axis_size scatter."""
    import jax.lax as lax
    try:
        return int(lax.axis_size(axis_name))
    except Exception:  # noqa: BLE001 — not inside a mapped program
        pass
    from . import runtime
    if not runtime.is_initialized():
        raise RuntimeError(
            "DistributedOptimizer(zero_stage>=1 / dcn_compression) needs "
            "the axis size to lay out the sharded state: call "
            "init()/update() inside the mapped program over "
            f"{axis_name!r}, or hvd.init() first.")
    if spec is not None and spec.shard_axes:
        st = runtime.state()
        req = spec.required_axes()
        for mesh in (st.mesh, getattr(st, "expert_mesh", None),
                     getattr(st, "model_mesh", None)):
            if (mesh is not None and req.issubset(mesh.axis_names)
                    and axis_name in mesh.axis_names):
                return int(dict(mesh.shape)[axis_name])
    return runtime.size()


class ZeroShardState(NamedTuple):
    """State of the generalized ZeRO-sharded wrapper (zero_stage=1|2|3
    with DCN staging): the base optimizer's state over this rank's flat
    1/N stripe, plus the persistent error-feedback residual of the lossy
    DCN hop (None when the hop is lossless or staging is off). The
    residual rides opt_state deliberately: elastic commits snapshot it,
    so a guard rollback also rewinds the compression-error carry."""
    base: Any
    residual: Any = None


class _ZeroCore:
    """Static layout + exchange engine shared by the zero-sharded optax
    transforms and the compiled zero3 step builder (ops/step_program.py).

    Owns everything both sides must agree on byte-for-byte: the flat
    concat-cast-pad layout, the bucket chunking (``bucket_bytes``, each
    chunk a multiple of the axis size so stripes stay uniform), the
    stripe-owner index (``collectives.dcn_sigma`` — staging permutes
    ownership), and the staged-vs-plain scatter/gather choice. Instances
    are cheap value objects hashable by identity, which is exactly the
    per-object keying the step-program lru builder wants.
    """

    def __init__(self, axis, average, compression, dcn_compression,
                 dcn_local_size, bucket_bytes, chunked,
                 exchange_buckets=None):
        from .ops.collectives import _axes_tuple
        axes = _axes_tuple(axis)
        if len(axes) != 1:
            raise ValueError("ZeRO sharding runs over exactly one mesh "
                             f"axis; got {axis!r}")
        self.axis = axes[0]
        self.average = bool(average)
        self.comp = (None if compression is Compression.none
                     else compression)
        self.dcn = dcn_compression or ""
        self.dcn_local = int(dcn_local_size or 0)
        self.bucket_bytes = bucket_bytes
        self.chunked = bool(chunked)
        # None defers to HOROVOD_EXCHANGE_BUCKETS at trace time (the
        # _rs_bucket_bytes idiom); >1 overrides the bytes-based chunk
        # count so the zero2/zero3 psum_scatter pipelines in exactly as
        # many pieces as the compiled step's bucketed psum exchange.
        self.exchange_buckets = exchange_buckets
        self._buckets_pin = None  # resolved once, first chunk_layout
        if self.dcn and self.comp is not None:
            raise ValueError(
                "dcn_compression composes the stage split itself — "
                "combine it with compression=Compression.none")

    # ------------------------------------------------------------ layout

    def axis_size(self):
        return _stripe_axis_size(self.axis)

    def local_for(self, n):
        from .ops.collectives import normalize_dcn_local_size
        return normalize_dcn_local_size(n, self.dcn_local)

    def staged(self, n):
        return self.local_for(n) < n

    def padded_len(self, total, n):
        return -(-total // n) * n

    def _resolved_buckets(self):
        # Pinned at first layout computation: scatter/gather/param_stripe
        # and the compiled zero3 programs must all agree on one chunking
        # for this core's lifetime — a mid-session env flip must not
        # desync a cached shard_params program from a new step trace.
        if self._buckets_pin is None:
            if self.exchange_buckets is not None:
                self._buckets_pin = max(int(self.exchange_buckets), 1)
            else:
                from .config import Config
                self._buckets_pin = Config.from_env().exchange_buckets
        return self._buckets_pin

    def chunk_layout(self, padded, itemsize, n):
        """Static ``(start, length)`` chunks, each a multiple of n.

        With an exchange-bucket count > 1 (constructor arg, default
        HOROVOD_EXCHANGE_BUCKETS) the chunk count is driven by the
        bucket count instead of ``bucket_bytes`` — the compiled step's
        backward/exchange overlap knob applied to the zero2/zero3
        scatter. Stripe layout is chunk-major, so every consumer
        (scatter/gather/param_stripe) shares this one layout; per-element
        reduction values are unaffected by chunk boundaries, only the
        stripe ORDER changes — full-row results are bit-identical at any
        setting (tests/test_exchange_overlap.py)."""
        if not self.chunked or padded == 0:
            return ((0, padded),)
        buckets = self._resolved_buckets()
        if buckets > 1:
            target = -(-padded // buckets)
            per = max(n, -(-target // n) * n)
        else:
            from .ops.collectives import _rs_bucket_bytes
            per = max(n, (_rs_bucket_bytes(self.bucket_bytes)
                          // int(itemsize)) // n * n)
        return tuple((s, min(per, padded - s))
                     for s in range(0, padded, per))

    def residual_len(self, total, n, itemsize):
        """Length of the persistent error-feedback carry: the DCN-stage
        input is the ICI chunk (1/local of each bucket), so the carry
        concatenated over buckets is padded/local. 0 when the DCN hop
        is lossless or absent."""
        local = self.local_for(n)
        if not self.dcn or local >= n:
            return 0
        return self.padded_len(total, n) // local

    # ---------------------------------------------------------- exchange

    def flatten_pad(self, leaves, acc_dt, n):
        total = sum(int(np.prod(l.shape, dtype=np.int64)) for l in leaves)
        flat = jnp.concatenate([l.reshape(-1).astype(acc_dt)
                                for l in leaves])
        padded = self.padded_len(total, n)
        if padded != total:
            flat = jnp.pad(flat, (0, padded - total))
        return flat, total

    def scatter(self, flat, residual, n):
        """Bucketed (reduce-)scatter of the padded flat row: returns
        ``(stripe, new_residual)`` with the stripe laid out chunk-major
        (each chunk contributes its 1/n segment at this rank's
        ``dcn_sigma`` position)."""
        import jax.lax as lax

        from .ops.collectives import (_nbytes, dcn_staged_psum_scatter)
        from .stats import record_jit_traced
        local = self.local_for(n)
        itemsize = jnp.dtype(flat.dtype).itemsize
        stripes, residuals = [], []
        rpos = 0
        for start, length in self.chunk_layout(int(flat.shape[0]),
                                               itemsize, n):
            chunk = flat[start:start + length]
            if local < n:
                res_c = None
                if residual is not None:
                    rlen = length // local
                    res_c = residual[rpos:rpos + rlen]
                    rpos += rlen
                stripe, new_res = dcn_staged_psum_scatter(
                    chunk, self.axis, local=local, dcn_compression=self.dcn,
                    residual=res_c)
                if new_res is not None:
                    residuals.append(new_res)
            else:
                ctx = None
                if self.comp is not None:
                    chunk, ctx = self.comp.compress(chunk)
                record_jit_traced("reducescatter_jit", _nbytes(chunk),
                                  self.axis)
                stripe = lax.psum_scatter(chunk, self.axis,
                                          scatter_dimension=0, tiled=True)
                if self.comp is not None:
                    stripe = self.comp.decompress(stripe, ctx)
            stripes.append(stripe)
        stripe = (stripes[0] if len(stripes) == 1
                  else jnp.concatenate(stripes))
        if self.average:
            stripe = (stripe / n).astype(stripe.dtype)
        new_residual = (jnp.concatenate(residuals) if len(residuals) > 1
                        else residuals[0]) if residuals else None
        return stripe, new_residual

    def gather(self, stripe, padded, n, lossless=False):
        """Reassemble the padded flat row from per-rank stripes (the
        inverse of :meth:`scatter`'s layout). ``lossless=True`` keeps the
        DCN hop at full width regardless of the compression setting —
        the zero3 parameter gather uses it so forward numerics never go
        through the transport cast."""
        import jax.lax as lax

        from .ops.collectives import _nbytes, dcn_staged_all_gather
        from .stats import record_jit_traced
        local = self.local_for(n)
        itemsize = jnp.dtype(stripe.dtype).itemsize
        outs, spos = [], 0
        dcn = "" if lossless else self.dcn
        for start, length in self.chunk_layout(padded, itemsize, n):
            seg = length // n
            part = stripe[spos:spos + seg]
            spos += seg
            if local < n:
                outs.append(dcn_staged_all_gather(
                    part, self.axis, local=local, dcn_compression=dcn))
            else:
                record_jit_traced("allgather_jit", _nbytes(part), self.axis)
                outs.append(lax.all_gather(part, self.axis, axis=0,
                                           tiled=True))
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs)

    def param_stripe(self, flat_p, n):
        """This rank's stripe of a padded flat row, chunk-major — pure
        slicing at the ``dcn_sigma`` owner position, no collectives."""
        import jax.lax as lax

        from .ops.collectives import dcn_sigma
        local = self.local_for(n)
        sig = dcn_sigma(self.axis, local)
        itemsize = jnp.dtype(flat_p.dtype).itemsize
        parts = []
        for start, length in self.chunk_layout(int(flat_p.shape[0]),
                                               itemsize, n):
            seg = length // n
            parts.append(lax.dynamic_slice_in_dim(
                flat_p, start + sig * seg, seg))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def _zero_sharded(base, spec, compression=Compression.none,
                  dcn_compression="", dcn_local_size=0, bucket_bytes=None,
                  exchange_buckets=None):
    """The ZeRO-sharded product of ``DistributedOptimizer(zero_stage=1|2|3)``
    for ``spec`` (a :class:`_ShardingSpec` with ``zero_stage >= 1``):
    reduce-scatter the gradients over the data axis, run ``base`` on this
    rank's flat 1/N stripe (1/N of the elements and of the state memory),
    allgather the resulting *updates*. Wire volume per step equals one
    allreduce, but the reduction and the optimizer math are each done
    once per element globally instead of N times.

    Striping is orthogonal to the reduce axes: every leaf is replicated
    across the data axis, so one flat stripe layout serves dense, expert
    and model leaves alike, and each leaf is pre-reduced over its
    remaining axes (and pre-divided by the rest of its averaging
    denominator) before the flatten (:func:`_spec_pre_reduce`; nothing
    to do for a spec without expert/model keys).

    Stage 1 scatters the whole row at once; stage 2 scatters per bucket
    (``bucket_bytes``) so gradients only ever exist stripe-at-a-time
    between scatter and apply; stage 3 used STANDALONE (a user's own
    shard_map) behaves exactly like stage 2 (full params in, full
    updates out) — the stripe-resident parameter storage needs
    program-level buffer control and lives in hvd.compiled_train_step,
    which reads ``spec.zero_stage == 3`` and compiles the
    gather-on-demand layout.

    Constraints (docs/performance.md): ``base`` must be elementwise over
    a flat parameter vector (sgd/momentum/adam family — anything whose
    init is shape-driven zeros/counters), and the gradients must
    genuinely vary over the data axis (a VMA-typed pre-summed cotangent
    is rejected at trace time).

    ``dcn_compression`` ("bf16"/"int8") turns on the two-stage exchange:
    ICI at full precision, only the cross-host DCN hop compressed, with
    the error-feedback residual carried in :class:`ZeroShardState`.
    """
    from .ops.collectives import _vma_checking
    zero_stage, average = spec.zero_stage, spec.average
    core = _ZeroCore(spec.data_axes, average, compression, dcn_compression,
                     dcn_local_size, bucket_bytes,
                     chunked=zero_stage >= 2,
                     exchange_buckets=exchange_buckets)
    axis = core.axis

    def _stripe_gauges(shard_len, itemsize, base_state, stage):
        from . import metrics
        try:
            opt_bytes = sum(
                int(np.prod(l.shape, dtype=np.int64))
                * np.dtype(_np_dtype(l)).itemsize
                for l in jax.tree.leaves(base_state)
                if hasattr(l, "shape"))
        except Exception:  # noqa: BLE001 — exotic state leaf; gauge only
            opt_bytes = 0
        metrics.ZERO_STRIPE_BYTES.labels(kind="grads").set(
            shard_len * itemsize)
        metrics.ZERO_STRIPE_BYTES.labels(kind="opt").set(opt_bytes)
        metrics.ZERO_STRIPE_BYTES.labels(kind="params").set(
            shard_len * itemsize if stage == 3 else 0)

    def _np_dtype(leaf):
        return np.dtype(getattr(leaf, "dtype", np.float32))

    def init_fn(params):
        leaves = jax.tree.leaves(params)
        if not leaves:
            return ZeroShardState(base=base.init(params), residual=None)
        total = sum(int(np.prod(l.shape, dtype=np.int64)) for l in leaves)
        n = _stripe_axis_size(axis, spec)
        acc_dt = jnp.result_type(*leaves)
        shard_len = core.padded_len(total, n) // n
        base_state = base.init(jnp.zeros((shard_len,), acc_dt))
        rlen = core.residual_len(total, n, jnp.dtype(acc_dt).itemsize)
        residual = jnp.zeros((rlen,), acc_dt) if rlen else None
        _stripe_gauges(shard_len, jnp.dtype(acc_dt).itemsize, base_state,
                       zero_stage)
        return ZeroShardState(base=base_state, residual=residual)

    def update_fn(updates, state, params=None):
        leaves, treedef = jax.tree.flatten(updates)
        if not leaves:
            upd, new_base = base.update(updates, state.base, params)
            return upd, ZeroShardState(base=new_base,
                                       residual=state.residual)
        if _vma_checking(axis) and any(
                axis not in jax.typeof(l).vma for l in leaves):
            raise ValueError(
                f"DistributedOptimizer(zero_stage={zero_stage}): some "
                "gradient leaves are unvarying over the reduce axis "
                "(pre-psummed cotangents of replicated params under "
                "check_vma=True). The stripe layout needs uniformly "
                "varying gradients; use DistributedGradientTransform("
                "reduce_scatter=True) + an unsharded optimizer instead.")
        n = core.axis_size()
        acc_dt = jnp.result_type(*leaves)
        lspecs = spec.leaf_specs(updates, spec.known_axes)
        pre = [_spec_pre_reduce(l.astype(acc_dt), ls, core.axis, average)
               for l, ls in zip(leaves, lspecs)]
        flat_g, total = core.flatten_pad(pre, acc_dt, n)
        g_stripe, new_residual = core.scatter(flat_g, state.residual, n)
        p_stripe = None
        if params is not None:
            flat_p, _ = core.flatten_pad(jax.tree.leaves(params), acc_dt, n)
            p_stripe = core.param_stripe(flat_p, n)
        u_stripe, new_base = base.update(g_stripe, state.base, p_stripe)
        flat_u = core.gather(u_stripe, int(flat_g.shape[0]), n)
        out, pos = [], 0
        for leaf in leaves:
            sz = int(np.prod(leaf.shape, dtype=np.int64))
            out.append(flat_u[pos:pos + sz].astype(leaf.dtype)
                       .reshape(leaf.shape))
            pos += sz
        return (jax.tree.unflatten(treedef, out),
                ZeroShardState(base=new_base, residual=new_residual))

    update_fn._hvd_exchange = "spec"
    update_fn._hvd_base = base
    update_fn._hvd_zero_core = core
    update_fn._hvd_spec = spec
    return optax.GradientTransformation(init_fn, update_fn)


class DcnExchangeState(NamedTuple):
    """State of the stage-0 DCN-compressed exchange transform: just the
    error-feedback residual (None when the DCN hop is lossless)."""
    residual: Any = None


class _LeafSpec(NamedTuple):
    """Per-leaf exchange recipe (hashable, groupable): ``reduce`` names
    the mesh axes this leaf's gradient is psummed over; ``denom`` names
    the axes whose size product divides it when averaging. The two
    differ exactly for expert-sharded leaves, whose backward alltoall
    already summed the expert-axis peers into the local gradient — they
    psum over the data axes only but still divide by the full world."""
    reduce: tuple
    denom: tuple


def _axes_size_prod(axes):
    """Trace-time product of mesh-axis sizes (constant-folds)."""
    import jax.lax as lax
    n = 1
    for a in axes:
        n *= int(lax.axis_size(a))
    return n


class _ShardingSpec:
    """Per-leaf sharding spec: the ONE description of how every
    parameter leaf exchanges its gradient on an N-D mesh — what it
    reduces over, what it divides by, whether optimizer state (and
    parameters) are striped, and whether a DCN link carries a residual.
    ``DistributedOptimizer`` builds one for every configuration and
    ``hvd.compiled_train_step`` compiles from it and from nothing else
    (ops/step_program.py; docs/performance.md "Composable parallelism").

    For each leaf, derived from the key patterns against the ACTUAL
    program mesh axes (:meth:`leaf_specs`):

    - expert leaves (``expert_keys``, tree-path substrings matched
      against ``jax.tree_util.keystr`` — explicit, not inferred, because
      dense towers reuse names like ``w1``/``w2``) hold
      per-``expert_axis``-column shards (the fake-replicated ``P()``
      idiom under check_vma=False); they reduce over every axis except
      ``expert_axis`` and average by the full world size (the backward
      alltoall pre-summed the expert peers);
    - model/tensor-parallel leaves (``model_keys``) reduce over every
      axis except ``model_axis`` and average by the product of the axes
      they reduce over (their shards are genuinely distinct parameters);
    - dense leaves reduce over ALL mesh axes and average by the world.

    ZeRO striping (``zero_stage`` 1-3) is orthogonal: every leaf —
    dense, expert, model — is replicated across the data axis
    (expert/model leaves vary over their own axis only), so one flat
    stripe over the data axis serves all of them; the stripe scatter
    divides by the data-axis size and each leaf is pre-reduced over its
    remaining axes and pre-divided by the rest of its denominator first
    (:func:`_spec_pre_reduce`). On a 1-D mesh both pre-steps vanish.

    Instances are value objects, fixed at construction (``label`` and
    the hash key are computed there): equal fields compare and hash
    equal, so two specs that say the same thing share one compiled
    program in the step-program builder's lru and the engine cache."""

    def __init__(self, data_axes=AXIS, expert_axis=None, expert_keys=(),
                 model_axis=None, model_keys=(), average=True,
                 zero_stage=0, dcn_link=False):
        from .ops.collectives import _axes_tuple
        self.data_axes = _axes_tuple(data_axes)
        self.expert_keys = tuple(str(k) for k in (expert_keys or ()))
        self.model_keys = tuple(str(k) for k in (model_keys or ()))
        self.expert_axis = str(expert_axis) if self.expert_keys else None
        self.model_axis = str(model_axis) if self.model_keys else None
        self.average = bool(average)
        self.zero_stage = int(zero_stage)
        # True when the stage-0 transform chain carries a DCN
        # error-feedback residual in its first link's state — the
        # compiled step then runs the chain whole instead of decomposing.
        self.dcn_link = bool(dcn_link)
        if self.expert_keys and expert_axis is None:
            raise ValueError("expert_keys need an expert_axis")
        if self.model_keys and model_axis is None:
            raise ValueError("model_keys need a model_axis")
        # The axes some leaves stay sharded over (empty: every leaf
        # reduces over every axis and the flat data mesh serves).
        self.shard_axes = tuple(a for a in (self.expert_axis,
                                            self.model_axis)
                                if a is not None)
        if len(set(self.shard_axes)) != len(self.shard_axes):
            raise ValueError(
                f"expert_axis and model_axis must differ, both are "
                f"{self.expert_axis!r}")
        for a in self.shard_axes:
            if a in self.data_axes:
                raise ValueError(
                    f"sharded axis {a!r} collides with the data axes "
                    f"{self.data_axes!r}")
        # The axes the spec was configured over — what the STANDALONE
        # transforms classify against (inside a user's own shard_map over
        # exactly these axes). The compiled step classifies against the
        # actual step-mesh axes instead, which may include extra size-1
        # axes.
        self.known_axes = self.data_axes + self.shard_axes
        # Short name of the layout, for metrics, ``perf_signature`` and
        # cache dumps (nothing branches on it): ``psum`` or ``zero<k>``,
        # then ``+dcn`` / ``+ep`` / ``+tp`` for a stage-0 DCN link,
        # expert leaves and model leaves.
        self.label = "+".join(
            ["psum" if self.zero_stage == 0 else f"zero{self.zero_stage}"]
            + [tag for tag, on in (("dcn", self.dcn_link),
                                   ("ep", self.expert_axis),
                                   ("tp", self.model_axis)) if on])
        self._key = (self.data_axes, self.expert_axis, self.expert_keys,
                     self.model_axis, self.model_keys, self.average,
                     self.zero_stage, self.dcn_link)

    def __eq__(self, other):
        return isinstance(other, _ShardingSpec) and self._key == other._key

    def __hash__(self):
        return hash(self._key)

    def required_axes(self):
        """Mesh axes a program running this spec must provide."""
        return set(self.known_axes)

    def _kind(self, path):
        s = jax.tree_util.keystr(path)
        e = any(k in s for k in self.expert_keys)
        m = any(k in s for k in self.model_keys)
        if e and m:
            raise ValueError(
                f"parameter leaf {s} matches both expert_keys and "
                "model_keys — a leaf shards over one axis; tighten the "
                "key patterns (model_parallel_keys gives exact paths)")
        return "expert" if e else ("model" if m else "dense")

    def leaf_specs(self, tree, mesh_axes):
        """Per-leaf :class:`_LeafSpec` in tree-flatten order, classified
        against the actual program mesh axes (axes the spec doesn't know
        about — e.g. a size-1 expert axis on the 3-D mesh under a
        TP-only spec — fold into the dense reduce set, which is always
        correct for batch-sharded gradients)."""
        axes = tuple(mesh_axes)
        out = []
        counts = {"dense": 0, "expert": 0, "model": 0}
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]:
            kind = self._kind(path)
            counts[kind] += 1
            if kind == "expert":
                red = tuple(a for a in axes if a != self.expert_axis)
                out.append(_LeafSpec(red, axes))
            elif kind == "model":
                red = tuple(a for a in axes if a != self.model_axis)
                out.append(_LeafSpec(red, red))
            else:
                out.append(_LeafSpec(axes, axes))
        # Host-side gauge, touched at trace/build time only (never per
        # step): what the spec decided, per exchange family.
        from . import metrics
        for kind, n in counts.items():
            metrics.SPEC_LEAVES.labels(kind=kind).set(n)
        return out


def _spec_pre_reduce(g, lf, stripe_axis, average):
    """Reduce one gradient leaf down to what the flat data-axis stripe
    exchange expects: psum over every reduce axis EXCEPT the stripe
    axis, and apply the part of the averaging divisor the stripe scatter
    won't (the scatter divides by the stripe-axis size uniformly, so the
    leaf arrives pre-divided by ``denom / |stripe_axis|``). On a 1-D
    mesh both steps are no-ops."""
    import jax.lax as lax
    extra = tuple(a for a in lf.reduce if a != stripe_axis)
    if extra:
        g = lax.psum(g, extra)
    if average:
        factor = _axes_size_prod(lf.denom) / _axes_size_prod((stripe_axis,))
        if factor != 1:
            g = (g / factor).astype(g.dtype)
    return g


def _spec_grad_exchange(spec, compression=Compression.none,
                        dcn_compression="", dcn_local_size=0,
                        bucket_bytes=None):
    """Stage-0 per-leaf spec exchange: psum each gradient leaf over its
    spec'd reduce axes and divide by its spec'd denominator — the link
    ``DistributedOptimizer`` chains before the base optimizer when the
    spec names expert/model leaves or a DCN link. Standalone it
    exchanges inside ``update()`` within a shard_map over
    ``spec.known_axes``; the compiled step decomposes it into
    per-group psums unless the DCN residual forces running whole
    (``spec.dcn_link``).

    With ``dcn_compression`` set, every leaf is pre-reduced over its
    non-data axes (:func:`_spec_pre_reduce`), then the whole tree rides
    the staged scatter + immediate gather over the data axis (an
    allreduce decomposition: full exchanged gradients come out, so any
    unsharded optimizer chains after it) with the error-feedback
    residual carried in :class:`DcnExchangeState`."""
    import jax.lax as lax
    comp = None if compression is Compression.none else compression
    core = None
    if dcn_compression:
        if comp is not None:
            raise ValueError(
                "dcn_compression composes the stage split itself — "
                "combine it with compression=Compression.none")
        core = _ZeroCore(spec.data_axes, spec.average, Compression.none,
                         dcn_compression, dcn_local_size, bucket_bytes,
                         chunked=True)

    def init_fn(params):
        leaves = jax.tree.leaves(params)
        if core is None or not leaves:
            return DcnExchangeState(residual=None)
        total = sum(int(np.prod(l.shape, dtype=np.int64)) for l in leaves)
        n = _stripe_axis_size(core.axis, spec)
        acc_dt = jnp.result_type(*leaves)
        rlen = core.residual_len(total, n, jnp.dtype(acc_dt).itemsize)
        return DcnExchangeState(
            residual=jnp.zeros((rlen,), acc_dt) if rlen else None)

    def update_fn(updates, state, params=None):
        del params
        leaves, treedef = jax.tree.flatten(updates)
        if not leaves:
            return updates, state
        lspecs = spec.leaf_specs(updates, spec.known_axes)
        if core is None:
            out = []
            for g, ls in zip(leaves, lspecs):
                ctx = None
                if comp is not None:
                    g, ctx = comp.compress(g)
                g = lax.psum(g, ls.reduce)
                if comp is not None:
                    g = comp.decompress(g, ctx)
                if spec.average:
                    g = (g / _axes_size_prod(ls.denom)).astype(g.dtype)
                out.append(g)
            return jax.tree.unflatten(treedef, out), state
        n = core.axis_size()
        acc_dt = jnp.result_type(*leaves)
        pre = [_spec_pre_reduce(l.astype(acc_dt), ls, core.axis,
                                spec.average)
               for l, ls in zip(leaves, lspecs)]
        flat_g, _ = core.flatten_pad(pre, acc_dt, n)
        stripe, new_residual = core.scatter(flat_g, state.residual, n)
        flat = core.gather(stripe, int(flat_g.shape[0]), n)
        out, pos = [], 0
        for leaf in leaves:
            sz = int(np.prod(leaf.shape, dtype=np.int64))
            out.append(flat[pos:pos + sz].astype(leaf.dtype)
                       .reshape(leaf.shape))
            pos += sz
        return (jax.tree.unflatten(treedef, out),
                DcnExchangeState(residual=new_residual))

    # inline: standalone, the exchange happens inside update(); the
    # chain DistributedOptimizer wraps around it carries the spec.
    update_fn._hvd_exchange = "inline"
    return optax.GradientTransformation(init_fn, update_fn)


def _normalize_dcn_compression(value):
    if value is None:
        return ""
    if isinstance(value, str):
        v = value.strip().lower()
        if v in ("", "none", "0", "off"):
            return ""
        if v in ("bf16", "bfloat16", "fp16", "16"):
            return "bf16"
        if v in ("int8", "8bit", "8"):
            return "int8"
        raise ValueError(f"unknown dcn_compression {value!r} "
                         "(expected '', 'bf16' or 'int8')")
    # compressor classes for API symmetry with compression=
    from .ops.compression import (BF16Compressor, Int8Compressor,
                                  NoneCompressor)
    if value is NoneCompressor or value is Compression.none:
        return ""
    if isinstance(value, type) and issubclass(value, Int8Compressor):
        return "int8"
    if isinstance(value, type) and issubclass(value, BF16Compressor):
        return "bf16"
    raise ValueError(f"unknown dcn_compression {value!r} "
                     "(expected '', 'bf16', 'int8' or a matching "
                     "Compression class)")


def DistributedOptimizer(optimizer, named_parameters=None, axis_name=AXIS,
                         average=True, compression=Compression.none,
                         backward_passes_per_step=1, reduce_scatter=False,
                         zero_stage=None, dcn_compression=None,
                         dcn_local_size=None, bucket_bytes=None,
                         expert_keys=None, expert_axis="ep",
                         exchange_buckets=None, model_keys=None,
                         model_axis="model"):
    """Wrap an optax optimizer so every update first allreduce-averages the
    gradients (reference: torch/__init__.py:161-208 DistributedOptimizer,
    tensorflow/__init__.py:141-239).

    Args mirror the reference where meaningful; ``named_parameters`` is
    accepted for signature parity and unused (JAX pytrees are already named by
    structure). ``backward_passes_per_step`` composes optax.MultiSteps around
    the wrapped optimizer, matching the reference's gradient accumulation
    (torch/__init__.py:78-92).

    ``zero_stage`` climbs the ZeRO ladder (default HOROVOD_ZERO_STAGE):

    - ``0`` — replicated everything; the classic allreduce chain.
    - ``1`` — optimizer-state sharding: gradients ride a reduce-scatter,
      the base optimizer updates this rank's flat 1/N stripe (momenta and
      second moments shard N-ways), an allgather of the updates replaces
      the allreduce's second half. ``reduce_scatter=True`` is the
      reference's spelling of this stage.
    - ``2`` — gradient sharding: same wire shape, but the scatter runs
      per bucket (``bucket_bytes``, default HOROVOD_REDUCE_SCATTER_BUCKET)
      so the full-gradient row never persists — inside the compiled step
      XLA frees each bucket after its stripe lands.
    - ``3`` — parameter sharding: params live as stripes and are
      allgathered on demand. The transform used standalone behaves like
      zero2 (see :func:`_zero_sharded`); ``hvd.compiled_train_step``
      reads the stage and compiles the true stripe-resident layout with
      donated stripe buffers (its ``shard_params``/``unshard_params``
      convert between full and striped storage).

    ``dcn_compression`` ("bf16" or "int8"; default HOROVOD_DCN_COMPRESSION)
    independently turns on the two-stage hierarchical exchange: intra-host
    (ICI, ``dcn_local_size`` ranks per group, default
    HOROVOD_DCN_LOCAL_SIZE or the launcher's local size) reduces at full
    precision and only the cross-host DCN hop is compressed, with
    persistent error-feedback residuals carried in the optimizer state so
    the compression error is corrected next step. Works at any
    ``zero_stage`` (stage 0 chains a staged exchange transform before the
    optimizer). The PR 8 divergence probe (HOROVOD_GUARD_DIVERGENCE) is
    the recommended safety net under a lossy wire.

    ``expert_keys`` (a tuple of tree-path substrings, e.g. ``("moe",)``)
    turns on the expert-parallel MoE exchange over the 2-D
    ``(axis_name, expert_axis)`` mesh: the named expert leaves stay
    sharded over ``expert_axis`` and their gradients psum over the data
    axis only, everything else psums over both axes (see
    :class:`_ShardingSpec`; docs/performance.md "Expert-parallel MoE").
    Requires ``HOROVOD_EXPERT_PARALLEL > 1`` at ``hvd.init()`` so the
    expert mesh exists.

    ``model_keys`` (tree-path substrings; ``models.transformer.
    model_parallel_keys`` computes exact paths) marks tensor-parallel
    leaves of a Megatron-style dense trunk — head-sharded attention,
    column/row-split FFN — sharded over ``model_axis`` on the 3-D
    ``(axis_name, expert_axis, model_axis)`` mesh
    (``HOROVOD_MODEL_PARALLEL``). Their gradients psum over every axis
    except ``model_axis`` and average by the axes they reduce over.

    Expert keys, model keys, the ZeRO ladder and ``dcn_compression``
    COMPOSE: every configuration — none of them given included — builds
    one per-leaf :class:`_ShardingSpec` (carried as ``_hvd_spec`` on the
    returned transform's ``update``) that ``hvd.compiled_train_step``
    compiles into a single donated program (docs/performance.md
    "Composable parallelism"). Stage 0 returns ``optax.chain(<exchange
    link>, optimizer)``, the link being
    :func:`DistributedGradientTransform` unless the spec names
    expert/model leaves or a DCN link; stages 1-3 return
    :func:`_zero_sharded`. Striping runs over the data axis for every leaf —
    expert/model leaves are replicated across it — so e.g.
    ``expert_keys + zero_stage=2 + dcn_compression`` trains
    expert-parallel FFNs with ZeRO-striped state and a compressed DCN
    hop in one program.
    """
    del named_parameters
    from . import metrics
    cfg = None
    if zero_stage is None or dcn_compression is None \
            or dcn_local_size is None:
        from .config import Config
        cfg = Config.from_env()
    if zero_stage is None:
        zero_stage = 1 if reduce_scatter else cfg.zero_stage
    zero_stage = int(zero_stage)
    if reduce_scatter and zero_stage == 0:
        zero_stage = 1
    if zero_stage not in (0, 1, 2, 3):
        raise ValueError(f"zero_stage must be 0..3, got {zero_stage}")
    if dcn_compression is None:
        dcn_compression = cfg.dcn_compression
    dcn_compression = _normalize_dcn_compression(dcn_compression)
    if dcn_local_size is None:
        dcn_local_size = cfg.dcn_local_size
    if dcn_compression and compression is not Compression.none:
        raise ValueError(
            "dcn_compression already defines the wire precision of the "
            "compressed hop — combine it with compression=Compression.none")
    spec = _ShardingSpec(
        data_axes=axis_name, expert_axis=expert_axis,
        expert_keys=expert_keys, model_axis=model_axis,
        model_keys=model_keys, average=average, zero_stage=zero_stage,
        dcn_link=bool(dcn_compression) and zero_stage == 0)
    metrics.ZERO_STAGE.set(zero_stage)
    if zero_stage == 0:
        if spec.shard_axes or spec.dcn_link:
            link = _spec_grad_exchange(spec, compression=compression,
                                       dcn_compression=dcn_compression,
                                       dcn_local_size=dcn_local_size,
                                       bucket_bytes=bucket_bytes)
        else:
            # the reference's public transform: VMA-aware, which the
            # spec link's plain psum is not (tests/test_vma_semantics.py)
            link = DistributedGradientTransform(axis_name=axis_name,
                                                average=average,
                                                compression=compression)
        tx = optax.chain(link, optimizer)
        # Tags for hvd.compiled_train_step (ops/step_program.py): it
        # reads the spec, replaces the link with per-group psums and
        # runs only the base optimizer's math in the program (a DCN
        # link's residual lives in the chain's state, so that chain
        # runs whole).
        tx.update._hvd_exchange = "spec"
        tx.update._hvd_base = optimizer
        tx.update._hvd_average = average
        tx.update._hvd_compression = compression
        tx.update._hvd_spec = spec
    else:
        tx = _zero_sharded(optimizer, spec, compression=compression,
                           dcn_compression=dcn_compression,
                           dcn_local_size=dcn_local_size,
                           bucket_bytes=bucket_bytes,
                           exchange_buckets=exchange_buckets)
    if backward_passes_per_step > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
    return tx


def resize_lr_factor(old_size, new_size, mode="linear"):
    """Learning-rate multiplier for an elastic world resize from
    ``old_size`` to ``new_size`` workers.

    With per-worker batch held fixed, global batch scales with world
    size; ``"linear"`` keeps the per-sample step size (Goyal et al.
    2017 — lr proportional to batch), ``"sqrt"`` keeps the gradient-noise
    scale (Krizhevsky 2014 — lr proportional to the square root of
    batch), the conservative choice for large swings.
    :class:`~horovod_tpu.callbacks.LearningRateRescaleCallback` applies
    this on every elastic resize, optionally ramped over a few batches.
    """
    old_size, new_size = int(old_size), int(new_size)
    if old_size <= 0 or new_size <= 0:
        raise ValueError(
            f"resize_lr_factor needs positive world sizes, got "
            f"{old_size} -> {new_size}")
    if mode == "linear":
        return new_size / old_size
    if mode == "sqrt":
        return (new_size / old_size) ** 0.5
    raise ValueError(f"unknown LR rescale mode {mode!r} "
                     f"(expected 'linear' or 'sqrt')")
