"""Functional collectives for use inside jit/shard_map programs.

Reference equivalent: the collective op implementations under
horovod/common/ops/ (MPIAllreduce mpi_operations.cc:45-128, MPIAllgather
:157-235, MPIBroadcast :396-449, NCCL variants nccl_operations.cc:79-485).

TPU-native design: these are *pure functions* meant to be traced inside a
``jax.jit`` / ``jax.shard_map`` program over a device mesh. XLA lowers them to
ICI collectives and handles everything the reference needed a runtime for —
fusion of adjacent collectives (≈ the fusion buffer), stream scheduling
(≈ NCCL streams + finalizer thread), and deterministic cross-replica program
order (≈ rank-0 negotiation). Each function takes the mesh axis name (default
``"hvd"``, the runtime's global data-parallel axis) instead of a communicator.

Gradient support comes for free: every op here is differentiable by JAX
(allreduce's backward is allreduce; allgather's backward is a
reduce-scatter-style narrow — the reference hand-writes these rules in
horovod/torch/mpi_ops.py:110-340 and tensorflow/mpi_ops.py:92-135).

Average semantics parity: the reference averages by default and implements it
as sum-then-divide-by-size (tensorflow/__init__.py:76-81, torch
mpi_ops_v2.cc:65 output.div_(size)); ``allreduce(average=True)`` lowers to
``lax.pmean`` which XLA computes the same way.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..runtime import AXIS
from ..stats import record_jit_traced


def _nbytes(x):
    """Wire bytes of a (possibly traced) array."""
    return int(np.prod(x.shape, dtype=np.int64)) * np.dtype(x.dtype).itemsize


def _axes_tuple(axis_name):
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


def _vma_checking(axis):
    """True when the surrounding shard_map traces with check_vma=True
    (JAX's default): a trivially varying probe value is typed as varying.
    Under check_vma=False every value reports an empty vma set, so the
    probe distinguishes the two typing modes."""
    try:
        return axis in jax.typeof(lax.axis_index(axis)).vma
    except Exception:
        return False


def _vma_grad_reduce(x, axis_name, average):
    """Average/sum a GRADIENT across ``axis_name`` with correct semantics
    under both shard_map typing modes. For gradients only — public
    allreduce keeps raw lax semantics (see below).

    Under ``check_vma=True``, differentiating a sharded-data loss w.r.t. a
    replicated (``P()``) param auto-psums the cotangent: the gradient
    reaching this reduce is already the cross-shard SUM, typed *unvarying*
    over the axis. On such a value ``lax.pmean`` is an identity (the
    result stays a sum — silently size()x the intended average) and
    ``lax.psum`` multiplies by axis size (overcounts). So: reduce only
    over the axes the value actually varies on, and finish an average by
    dividing by the sizes of the axes AD already summed. Under
    ``check_vma=False`` (or outside a VMA-checking trace) this degrades to
    the plain pmean/psum.

    Why gradients only: "unvarying == already-summed" is a statement about
    cotangents of replicated params under sharded data. A genuinely
    replicated non-gradient value (a scalar metric, jnp.ones) is also
    typed unvarying, and there raw lax already does the classically right
    thing (pmean = identity on identical contributions, psum = xsize) —
    applying the cotangent correction to it would silently divide by the
    axis size. The one ambiguous corner — a FULLY replicated training step
    (params AND data unsharded, so no auto-psum ever fires) — is a
    no-parallelism configuration this transform mis-averages by 1/size;
    shard the batch (the point of data parallelism) and the typing is
    unambiguous."""
    axes = _axes_tuple(axis_name)
    if _vma_checking(axes[0]):
        vma = jax.typeof(x).vma
        varying = tuple(a for a in axes if a in vma)
        summed = tuple(a for a in axes if a not in vma)
    else:
        varying, summed = axes, ()
    if varying:
        x = lax.pmean(x, varying) if average else lax.psum(x, varying)
    if summed and average:
        denom = 1
        for a in summed:
            denom *= lax.axis_size(a)
        x = (x / denom).astype(x.dtype)
    return x


_warned_all_unvarying = False


def _vma_grad_reduce_tree(tensors, axis_name, average):
    """Tree version of ``_vma_grad_reduce`` that keeps the fusion
    property: all fully-varying leaves go to XLA in ONE pmean/psum call
    (one wire group, the jit analog of the fusion buffer); already-summed
    leaves only need the arithmetic finish."""
    leaves, treedef = jax.tree.flatten(tensors)
    axes = _axes_tuple(axis_name)
    if not (leaves and _vma_checking(axes[0])):
        red = lax.pmean(leaves, axes) if average else lax.psum(leaves, axes)
        return jax.tree.unflatten(treedef, red)
    out = list(leaves)
    batch_idx = [i for i, l in enumerate(leaves)
                 if all(a in jax.typeof(l).vma for a in axes)]
    if average and not any(a in jax.typeof(l).vma
                           for l in leaves for a in axes):
        # The documented ambiguous corner (see _vma_grad_reduce): params
        # AND data unsharded means no cotangent was ever auto-psummed, and
        # the summed-axis division below mis-averages by 1/axis_size. Say
        # so once at trace time instead of silently.
        global _warned_all_unvarying
        if not _warned_all_unvarying:
            _warned_all_unvarying = True
            import warnings
            warnings.warn(
                "DistributedGradientTransform: every gradient leaf is "
                "unvarying over every reduce axis — the training step "
                "appears fully replicated (params and data unsharded). "
                "The already-summed correction divides by the axis size "
                "here, which mis-averages in this no-parallelism "
                "configuration; shard the batch over the reduce axis to "
                "make the typing unambiguous.")
    if batch_idx:
        batch = [leaves[i] for i in batch_idx]
        red = lax.pmean(batch, axes) if average else lax.psum(batch, axes)
        for i, r in zip(batch_idx, red):
            out[i] = r
    for i, l in enumerate(leaves):
        if i not in batch_idx:
            out[i] = _vma_grad_reduce(l, axis_name, average)
    return jax.tree.unflatten(treedef, out)


DEFAULT_RS_BUCKET_BYTES = 32 * 1024 * 1024


def _rs_bucket_bytes(bucket_bytes):
    if bucket_bytes is not None:
        return max(int(bucket_bytes), 1)
    from ..config import Config
    return Config.from_env().reduce_scatter_bucket


def _leaf_buckets(leaves, idxs, bucket_bytes):
    """Group leaf indices by dtype, then split each dtype run into buckets
    of at most ``bucket_bytes`` — the jit-path analog of the engine's
    fusion-threshold bucketing: several bounded collectives XLA can
    pipeline instead of one monolith (or thousands of slivers)."""
    by_dtype = {}
    for i in idxs:
        by_dtype.setdefault(jnp.dtype(leaves[i].dtype), []).append(i)
    buckets = []
    for group in by_dtype.values():
        cur, cur_bytes = [], 0
        for i in group:
            nb = _nbytes(leaves[i])
            if cur and cur_bytes + nb > bucket_bytes:
                buckets.append(cur)
                cur, cur_bytes = [], 0
            cur.append(i)
            cur_bytes += nb
        if cur:
            buckets.append(cur)
    return buckets


def bucketed_reducescatter_allgather(tensors, axis_name=AXIS, average=True,
                                     bucket_bytes=None):
    """Allreduce-equivalent gradient exchange as bucketed
    reduce-scatter + allgather.

    Reference equivalent: none in 0.16 — this is the ZeRO/ring
    decomposition of the fused allreduce. Each bucket's flat payload is
    ``psum_scatter``'d so every rank reduces only 1/N of the bytes (the
    bandwidth-optimal half of an allreduce on ICI), then allgathered
    back. Numerically equivalent to ``grouped_allreduce`` up to float
    reduction order; byte-identical wire volume on a ring, but the
    scatter half is what :func:`horovod_tpu.DistributedOptimizer`'s
    ZeRO-1 mode keeps (the allgather there moves optimizer *updates*,
    computed on 1/N of the elements).

    VMA-aware like ``_vma_grad_reduce_tree``: leaves whose cotangent was
    already auto-psummed (unvarying over the axis) only get the
    arithmetic finish; buckets carry the genuinely varying leaves.
    Multi-axis ``axis_name`` falls back to the allreduce tree form (the
    scatter staging is defined over one axis).
    """
    leaves, treedef = jax.tree.flatten(tensors)
    if not leaves:
        return tensors
    axes = _axes_tuple(axis_name)
    if len(axes) != 1:
        return _vma_grad_reduce_tree(tensors, axis_name, average)
    axis = axes[0]
    out = list(leaves)
    if _vma_checking(axis):
        varying = [i for i, l in enumerate(leaves)
                   if axis in jax.typeof(l).vma]
        varying_set = set(varying)
        summed = [i for i in range(len(leaves)) if i not in varying_set]
    else:
        varying, summed = list(range(len(leaves))), []
    n = lax.axis_size(axis)
    for i in summed:
        # pre-psummed cotangent of a replicated param: cross-rank sum
        # already happened, only the average's division remains
        if average:
            out[i] = (out[i] / n).astype(out[i].dtype)
    for idxs in _leaf_buckets(leaves, varying,
                              _rs_bucket_bytes(bucket_bytes)):
        flats = [leaves[i].reshape(-1) for i in idxs]
        flat = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        size = flat.shape[0]
        pad = -size % n
        if pad:
            flat = jnp.pad(flat, (0, pad))
        record_jit_traced("reducescatter_jit", _nbytes(flat), axis_name)
        shard = lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True)
        if average:
            shard = (shard / n).astype(shard.dtype)
        record_jit_traced("allgather_jit", _nbytes(shard), axis_name)
        full = lax.all_gather(shard, axis, axis=0, tiled=True)
        pos = 0
        for i in idxs:
            sz = int(np.prod(leaves[i].shape, dtype=np.int64))
            out[i] = full[pos:pos + sz].reshape(leaves[i].shape)
            pos += sz
    return jax.tree.unflatten(treedef, out)


# ------------------------------------------------------------- DCN staging
#
# A single-axis analog of hierarchical_allreduce: the one mesh axis
# ("hvd") is viewed as H hosts x L local chips (rank r = h*L + l) and the
# exchange runs in two tiers via axis_index_groups — the intra-host (ICI)
# tier at full precision, the cross-host (DCN) tier optionally compressed
# (bf16, or int8 on a group-shared per-bucket scale) with error-feedback
# residuals carried by the caller. This is the wire layout under
# DistributedOptimizer(dcn_compression=...): the paper's per-stage
# profiling showed DCN is the slowest hop, so only its bytes go lossy.

def dcn_index_groups(n, local):
    """(ici_groups, dcn_groups) for ``n`` ranks laid out as
    ``n // local`` hosts of ``local`` chips. ICI group h =
    [h*local, (h+1)*local); DCN group l = [l, local+l, 2*local+l, ...]
    (one member per host, ordered by host)."""
    hosts = n // local
    ici = [list(range(h * local, (h + 1) * local)) for h in range(hosts)]
    dcn = [list(range(l, n, local)) for l in range(local)]
    return ici, dcn


def normalize_dcn_local_size(n, local=0):
    """Effective ICI-group size for DCN staging over ``n`` ranks.

    0/None asks the config (HOROVOD_DCN_LOCAL_SIZE), then the runtime's
    launcher-provided local size — on a real multislice job that is the
    chips-per-host count, so "cross-group" genuinely means DCN. Values
    that cannot tile the axis (non-dividing, out of range) normalize to
    ``n``: a single full-precision ICI stage, i.e. staging disabled.
    """
    if not local:
        from ..config import Config
        local = Config.from_env().dcn_local_size
    if not local:
        from .. import runtime
        local = runtime.local_size() if runtime.is_initialized() else n
    local = int(local)
    if local <= 0 or local > n or n % local:
        return n
    return local


def dcn_sigma(axis_name, local):
    """This rank's stripe-owner index after a staged reduce-scatter.

    Staging permutes ownership: rank r = (h, l) ends up holding flat
    segment (l*H + h) — NOT segment r. Identity when staging is off
    (local == n) and, by the same formula, when every rank is its own
    host (local == 1). Param-stripe slicing and shard/unshard programs
    must use this index so they agree with the scatter layout."""
    axes = _axes_tuple(axis_name)
    axis = axes[0]
    n = lax.axis_size(axis)
    r = lax.axis_index(axis)
    if local >= n or n % local:
        return r
    hosts = n // local
    return (r % local) * hosts + r // local


def _record_stage(stage, wire_bytes, raw_bytes):
    """Trace-time per-stage wire accounting (hvd_wire_stage_bytes_total /
    _raw_): increments once per traced program, so actual/raw ratios are
    exact per-step compression factors."""
    from .. import metrics
    metrics.WIRE_STAGE_BYTES.labels(stage=stage).inc(int(wire_bytes))
    metrics.WIRE_STAGE_RAW_BYTES.labels(stage=stage).inc(int(raw_bytes))


def dcn_staged_psum_scatter(flat, axis_name=AXIS, local=None,
                            dcn_compression="", residual=None):
    """Reduce-scatter ``flat`` (length divisible by the axis size) in two
    tiers: full-precision psum_scatter within each ICI group, then a
    psum_scatter across hosts (the DCN hop) optionally compressed.

    Returns ``(stripe, new_residual)`` where ``stripe`` is this rank's
    1/N segment of the global sum — the segment at offset
    ``dcn_sigma(...) * (len(flat) // n)`` — and ``new_residual`` is the
    error-feedback carry for the lossy DCN hop (None when the hop is
    lossless or absent). Error feedback (Karimireddy et al.): each rank
    adds last step's residual to its DCN-stage input, sends the
    compressed value, and keeps the quantization error locally, so the
    compression bias is corrected on the next step instead of
    accumulating. ``residual``/``new_residual`` have the ICI-chunk shape
    (``len(flat) // local``,) and belong in persistent optimizer state.

    int8 mode quantizes on a group-shared scale (``lax.pmax`` of the
    max-abs over the DCN group, /127) so every rank's codes live on one
    grid and the summed codes dequantize exactly; the accumulation rides
    an int32 carrier (sums of H values in [-127, 127] cannot overflow),
    while the wire accounting records the 8-bit code width.
    """
    axes = _axes_tuple(axis_name)
    if len(axes) != 1:
        raise ValueError("dcn_staged_psum_scatter runs over exactly one "
                         f"mesh axis; got {axis_name!r}")
    axis = axes[0]
    n = int(lax.axis_size(axis))
    if local is None:
        local = n
    if flat.shape[0] % n:
        raise ValueError(
            f"dcn_staged_psum_scatter needs len(flat) % n == 0; got "
            f"{flat.shape[0]} over {n} ranks — pad before calling")
    comp = dcn_compression or "none"
    if local >= n or n % local:
        # single full-precision stage: the whole exchange is ICI
        _record_stage("ici", _nbytes(flat), _nbytes(flat))
        record_jit_traced("reducescatter_jit", _nbytes(flat), axis_name)
        with jax.named_scope("hvd_ici"):
            stripe = lax.psum_scatter(flat, axis, scatter_dimension=0,
                                      tiled=True)
        return stripe, None
    ici_groups, dcn_groups = dcn_index_groups(n, local)
    if local > 1:
        _record_stage("ici", _nbytes(flat), _nbytes(flat))
        record_jit_traced("reducescatter_jit", _nbytes(flat), axis_name)
        with jax.named_scope("hvd_ici"):
            chunk = lax.psum_scatter(flat, axis, scatter_dimension=0,
                                     tiled=True,
                                     axis_index_groups=ici_groups)
    else:
        chunk = flat
    raw = _nbytes(chunk)
    elems = int(chunk.shape[0])
    if comp == "none":
        _record_stage("dcn", raw, raw)
        record_jit_traced("reducescatter_jit", raw, axis_name)
        with jax.named_scope("hvd_dcn"):
            stripe = lax.psum_scatter(chunk, axis, scatter_dimension=0,
                                      tiled=True,
                                      axis_index_groups=dcn_groups)
        return stripe, None
    if residual is not None:
        e = chunk + residual.astype(chunk.dtype)
    else:
        e = chunk
    if comp == "bf16":
        wire = e.astype(jnp.bfloat16)
        new_residual = e - wire.astype(e.dtype)
        _record_stage("dcn", elems * 2, raw)
        record_jit_traced("reducescatter_jit", elems * 2, axis_name)
        with jax.named_scope("hvd_dcn"):
            stripe = lax.psum_scatter(wire, axis, scatter_dimension=0,
                                      tiled=True,
                                      axis_index_groups=dcn_groups)
        return stripe.astype(e.dtype), new_residual
    if comp == "int8":
        from .compression import Int8Compressor
        with jax.named_scope("hvd_dcn"):
            amax = lax.pmax(jnp.max(jnp.abs(e)), axis,
                            axis_index_groups=dcn_groups)
        scale = Int8Compressor.scale_for(amax)
        codes = Int8Compressor.quantize(e, scale)
        new_residual = e - (codes * scale).astype(e.dtype)
        _record_stage("dcn", elems, raw)
        record_jit_traced("reducescatter_jit", elems, axis_name)
        with jax.named_scope("hvd_dcn"):
            summed = lax.psum_scatter(codes.astype(jnp.int32), axis,
                                      scatter_dimension=0, tiled=True,
                                      axis_index_groups=dcn_groups)
        return (summed * scale).astype(e.dtype), new_residual
    raise ValueError(
        f"unknown DCN compression {dcn_compression!r} (expected '', "
        "'none', 'bf16' or 'int8')")


def dcn_staged_all_gather(stripe, axis_name=AXIS, local=None,
                          dcn_compression=""):
    """Reassemble the flat vector from per-rank stripes laid out by
    :func:`dcn_staged_psum_scatter`: gather across hosts first (the DCN
    hop — cast to bf16 on the wire when compression is on; every rank
    receives the same rounded values, so this is transport rounding, not
    a divergence source), then within each ICI group at full width. With
    staging off this is one plain tiled all_gather."""
    axes = _axes_tuple(axis_name)
    if len(axes) != 1:
        raise ValueError("dcn_staged_all_gather runs over exactly one "
                         f"mesh axis; got {axis_name!r}")
    axis = axes[0]
    n = int(lax.axis_size(axis))
    if local is None:
        local = n
    if local >= n or n % local:
        _record_stage("ici", _nbytes(stripe), _nbytes(stripe))
        record_jit_traced("allgather_jit", _nbytes(stripe), axis_name)
        with jax.named_scope("hvd_ici"):
            return lax.all_gather(stripe, axis, axis=0, tiled=True)
    ici_groups, dcn_groups = dcn_index_groups(n, local)
    comp = dcn_compression or "none"
    raw = _nbytes(stripe)
    if comp == "none":
        wire = stripe
        _record_stage("dcn", raw, raw)
        record_jit_traced("allgather_jit", raw, axis_name)
    else:
        wire = stripe.astype(jnp.bfloat16)
        _record_stage("dcn", int(stripe.shape[0]) * 2, raw)
        record_jit_traced("allgather_jit", int(stripe.shape[0]) * 2,
                          axis_name)
    with jax.named_scope("hvd_dcn"):
        chunk = lax.all_gather(
            wire, axis, axis=0, tiled=True,
            axis_index_groups=dcn_groups).astype(stripe.dtype)
    if local > 1:
        _record_stage("ici", _nbytes(chunk), _nbytes(chunk))
        record_jit_traced("allgather_jit", _nbytes(chunk), axis_name)
        with jax.named_scope("hvd_ici"):
            chunk = lax.all_gather(chunk, axis, axis=0, tiled=True,
                                   axis_index_groups=ici_groups)
    return chunk


def unfuse_segments(row, segs, world_size):
    """Slice per-tensor results out of a fused flat wire row *inside* the
    jitted wire program — the device-resident analog of the engine's
    host-side ``MemcpyOutFusionBuffer`` (engine._scatter_fused_results),
    with the same arithmetic in the same order so the two paths agree
    within dtype tolerance.

    ``segs`` is a static tuple of ``(offset, count, shape, dtype,
    average, postscale)`` records; ``world_size`` the collective's rank
    count. The cast from the wire dtype back to each tensor's dtype is
    the in-graph decompress (compression is a dtype round-trip here,
    ops/compression.py), averaging mirrors the host path's
    float-divide / integer-floor-divide split, and everything stays on
    device — no host readback anywhere downstream of the psum.
    """
    outs = []
    for off, cnt, shape, dtype, average, postscale in segs:
        out = row[off:off + cnt].astype(dtype)
        if average:
            # Same branch the host unfuse takes, on the STATIC dtype —
            # the decision constant-folds at trace time. jnp's
            # issubdtype: numpy's does not count bfloat16 as floating.
            if jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
                out = out / world_size
            else:
                out = out // world_size
            out = out.astype(dtype)
        if postscale is not None:
            out = (out * postscale).astype(dtype)
        outs.append(out.reshape(shape))
    return tuple(outs)


def segment_health(row, segs):
    """In-graph gradient-health digest for a fused wire row: one
    ``[finite, l2]`` float32 pair per segment of the REDUCED row, fused
    into the same wire program as the psum+unfuse so the guard layer
    (horovod_tpu.guard) costs one extra reduction per bucket instead of
    a host readback + scan.

    ``finite`` is 1.0 iff every element of the segment is finite; ``l2``
    is the L2 norm computed over the finite elements only (so the norm
    stays informative even on a poisoned bucket). Computed on the
    reduced row, which is bit-identical on every rank — so is the
    verdict, and no cross-rank coordination is needed to agree on it.
    """
    rows = []
    for off, cnt, _shape, _dtype, _average, _postscale in segs:
        seg = row[off:off + cnt].astype(jnp.float32)
        finite = jnp.isfinite(seg)
        all_finite = jnp.all(finite).astype(jnp.float32)
        l2 = jnp.sqrt(jnp.sum(jnp.where(finite, seg * seg, 0.0)))
        rows.append(jnp.stack([all_finite, l2]))
    return jnp.stack(rows)


def tree_health(leaves):
    """Per-leaf ``[finite, l2]`` float32 health rows for a list of
    already-exchanged tensors — the :func:`segment_health` analog for
    exchange modes whose reduction happens inside the optimizer
    transform (ZeRO-1 / inline-chained transforms), and for the compiled
    step program's psum over gradient leaves (ops/step_program.py),
    where no fused wire row exists to digest. Same row layout and fold
    semantics; computed on values that are bit-identical across ranks
    (reduced leaves, post-allgather updates), so every rank's guard
    verdict agrees without coordination."""
    rows = []
    for leaf in leaves:
        x = leaf.reshape(-1).astype(jnp.float32)
        finite = jnp.isfinite(x)
        all_finite = jnp.all(finite).astype(jnp.float32)
        l2 = jnp.sqrt(jnp.sum(jnp.where(finite, x * x, 0.0)))
        rows.append(jnp.stack([all_finite, l2]))
    if not rows:
        return jnp.zeros((0, 2), jnp.float32)
    return jnp.stack(rows)


def rank_index(axis_name=AXIS):
    """This shard's rank along the collective axis (usable only inside a
    mapped program). Reference: horovod_rank, per-replica."""
    return lax.axis_index(axis_name)


def allreduce(tensor, average=True, axis_name=AXIS, compression=None,
              prescale_factor=None, postscale_factor=None):
    """Sum or average ``tensor`` across the mesh axis.

    Reference semantics: hvd.allreduce (torch/mpi_ops.py:122-154,
    tensorflow/__init__.py:36-82): average by default, optional fp16
    compression applied before the wire (``compression``), executed as one
    fused XLA all-reduce over ICI.

    VMA note (``check_vma=True`` shard_map, JAX's default): this op keeps
    raw ``lax.pmean``/``psum`` semantics, which are classically correct
    for real inputs — varying values reduce across shards, replicated
    values average to themselves / sum to size x value. The ONE hazard is
    a gradient of a replicated param: AD auto-psums that cotangent before
    it reaches you, so reducing it here double-counts. For gradients use
    :func:`~horovod_tpu.DistributedGradientTransform` /
    ``DistributedOptimizer``, which detect and correct that case.
    """
    if prescale_factor is not None:
        tensor = tensor * prescale_factor
    if compression is not None:
        tensor, ctx = compression.compress(tensor)
    record_jit_traced("allreduce_jit", _nbytes(tensor), axis_name)
    reduced = (lax.pmean(tensor, axis_name) if average
               else lax.psum(tensor, axis_name))
    if compression is not None:
        reduced = compression.decompress(reduced, ctx)
    if postscale_factor is not None:
        reduced = reduced * postscale_factor
    return reduced


def grouped_allreduce(tensors, average=True, axis_name=AXIS, compression=None):
    """Allreduce a pytree of tensors as one logical group.

    Reference equivalent: tensor fusion — many small gradients batched into a
    single wire collective (horovod/common/fusion_buffer_manager.{h,cc} +
    FuseResponses operations.cc:577-700). Under jit, passing the whole pytree
    to one ``lax.pmean`` call gives XLA the same latitude: it emits one
    all-reduce group and tiles it over ICI, no staging buffer required.
    """
    if compression is not None:
        compressed = []
        ctxs = []
        for t in jax.tree.leaves(tensors):
            c, ctx = compression.compress(t)
            compressed.append(c)
            ctxs.append(ctx)
        treedef = jax.tree.structure(tensors)
        record_jit_traced("allreduce_jit",
                          sum(_nbytes(t) for t in compressed), axis_name)
        reduced = (lax.pmean(compressed, axis_name) if average
                   else lax.psum(compressed, axis_name))
        out = [compression.decompress(r, ctx)
               for r, ctx in zip(reduced, ctxs)]
        return jax.tree.unflatten(treedef, out)
    record_jit_traced("allreduce_jit",
                      sum(_nbytes(t) for t in jax.tree.leaves(tensors)),
                      axis_name)
    # raw lax semantics, like allreduce (see its VMA note); gradient trees
    # belong in DistributedGradientTransform, which VMA-corrects
    return (lax.pmean(tensors, axis_name) if average
            else lax.psum(tensors, axis_name))


def allgather(tensor, axis_name=AXIS):
    """Concatenate each rank's tensor along dim 0.

    Reference semantics: hvd.allgather — ranks may contribute different dim-0
    sizes, other dims must match (AllgatherOp, collective_operations.cc:68-135
    via MPI_Allgatherv). Under SPMD all shards have equal (static) shapes, so
    this is the equal-size case and lowers to one XLA all-gather; the
    varying-dim-0 case needs padding and lives in the eager engine
    (ops/engine.py) where per-rank shapes are visible.
    """
    record_jit_traced("allgather_jit", _nbytes(tensor), axis_name)
    return lax.all_gather(tensor, axis_name, axis=0, tiled=True)


def broadcast(tensor, root_rank, axis_name=AXIS):
    """Every rank receives ``root_rank``'s value.

    Reference semantics: hvd.broadcast (MPIBroadcast mpi_operations.cc:396-449).
    TPU-native lowering: mask all non-root contributions to zero and psum —
    one ICI all-reduce, which XLA lowers to an optimal broadcast-like
    collective; this avoids host round-trips and works for every numeric dtype
    (bool/int via a cast round-trip).
    """
    record_jit_traced("broadcast_jit", _nbytes(tensor), axis_name)
    idx = lax.axis_index(axis_name)
    orig_dtype = tensor.dtype
    work = tensor
    cast = jnp.issubdtype(orig_dtype, jnp.bool_)
    if cast:
        work = work.astype(jnp.int32)
    masked = jnp.where(idx == root_rank, work, jnp.zeros_like(work))
    out = lax.psum(masked, axis_name)
    if cast:
        out = out.astype(orig_dtype)
    return out


def hierarchical_allreduce(tensor, ici_axis, dcn_axis, average=True):
    """Two-level allreduce: reduce-scatter over the ICI tier, allreduce over
    the DCN tier, allgather back over ICI.

    Reference equivalent: ``NCCLHierarchicalAllreduce``
    (nccl_operations.cc:258-485) — intra-node ``ncclReduceScatter``, cross-node
    ``MPI_Allreduce`` of the host-staged shard, intra-node ``ncclAllGather``.
    On a TPU multislice mesh the same staging keeps the bandwidth-heavy
    reduce-scatter/allgather phases on ICI and moves only 1/ici_size of the
    bytes over DCN per device.

    The simple alternative — ``lax.psum(x, (dcn_axis, ici_axis))`` — lets XLA
    pick the decomposition itself and is usually what jit code should write;
    this explicit form exists for when the staging must be pinned (and so the
    HOROVOD_HIERARCHICAL_ALLREDUCE contract has a real jit-path analog).

    Sizes indivisible by the ICI axis are zero-padded before the
    reduce-scatter and sliced back after the allgather (the eager engine
    pads its fusion buffer the same way, engine._fused_nelem; the reference
    rounds the fusion threshold, operations.cc:552-574) — no caller-visible
    shape constraint.
    """
    record_jit_traced("allreduce_jit", _nbytes(tensor), ici_axis)
    flat = tensor.reshape(-1)
    size = flat.shape[0]
    ici = lax.axis_size(ici_axis)
    padded = -(-size // ici) * ici
    if padded != size:
        flat = jnp.pad(flat, (0, padded - size))
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    shard = lax.psum(shard, dcn_axis)
    if average:
        shard = shard / (lax.psum(1, ici_axis) * lax.psum(1, dcn_axis))
    out = lax.all_gather(shard, ici_axis, axis=0, tiled=True)
    return out[:size].reshape(tensor.shape)


def alltoall(tensor, axis_name=AXIS, split_axis=0, concat_axis=0):
    """Scatter dim-``split_axis`` slices to each rank and gather received
    slices along ``concat_axis``.

    The reference op set stops at allreduce/allgather/broadcast
    (message.h:47-49; upstream added alltoall only in 0.20+), but alltoall is
    the primitive expert-parallel and Ulysses-style sequence-parallel layers
    need, so the TPU framework ships it natively via lax.all_to_all.
    """
    nb = _nbytes(tensor)
    record_jit_traced("alltoall_jit", nb, axis_name)
    # alltoall is an ICI permutation: same bytes on the wire as in the
    # tensor, uncompressed — feed the per-stage wire accounting so MoE
    # dispatch/combine traffic shows up next to the gradient exchange
    # (hvd_wire_stage_bytes_total{stage="ici"}).
    _record_stage("ici", nb, nb)
    return lax.all_to_all(tensor, axis_name, split_axis=split_axis,
                          concat_axis=concat_axis, tiled=True)


def _largest_divisor_leq(n, k):
    """Largest divisor of ``n`` that is <= ``k`` (static ints)."""
    k = min(max(int(k), 1), int(n))
    while n % k:
        k -= 1
    return k


def alltoall_chunked(tensor, chunks, axis_name=AXIS, split_axis=0,
                     concat_axis=0, chunk_axis=1):
    """:func:`alltoall` split into ``chunks`` independent slices along
    ``chunk_axis``; returns the tuple of per-chunk results.

    This is the MoE dispatch pipelining primitive (Tutel, Hwang et al.
    2022; docs/performance.md "Expert-parallel MoE"): the caller
    interleaves per-chunk compute between the per-chunk collectives so
    that, inside one XLA program, chunk *k*'s expert FFN has no data
    dependence on chunk *k+1*'s alltoall — the scheduler overlaps them
    and the dispatch/combine latency hides behind compute. Each chunk
    round-trips independently, so re-concatenating the per-chunk results
    along ``chunk_axis`` reproduces the unchunked alltoall bit for bit.

    ``chunks`` that does not divide ``tensor.shape[chunk_axis]`` falls
    back to the largest divisor below it (chunk shapes must be equal and
    static for XLA); ``chunks=1`` degenerates to one alltoall.
    """
    k = _largest_divisor_leq(tensor.shape[chunk_axis], chunks)
    nb = _nbytes(tensor)
    record_jit_traced("alltoall_jit", nb, axis_name)
    _record_stage("ici", nb, nb)
    return tuple(
        lax.all_to_all(piece, axis_name, split_axis=split_axis,
                       concat_axis=concat_axis, tiled=True)
        for piece in jnp.split(tensor, k, axis=chunk_axis))


def exchange_bucket_plan(leaves, buckets):
    """Partition gradient-leaf indices into at most ``buckets`` contiguous
    groups in reverse leaf order, balanced by payload bytes. Returns a
    tuple of index tuples; every index appears exactly once.

    This is the bucket scheduler for the compiled step's pipelined
    gradient exchange (ops/step_program.py): the reference hides
    allreduce behind backprop by launching fusion buffers as gradients
    become ready (its background loop cycles while backward still runs);
    the XLA-native analog is one psum per bucket inside the same program,
    ordered so the *last* leaves of the tree — produced first by
    backprop — form the first bucket. XLA schedules each bucket's
    collective as soon as its leaves' data dependencies resolve, so the
    traced order is a hint, not a barrier.

    ``buckets=1`` returns the identity plan — all indices, ascending:
    one psum call over the whole tree, which XLA's all-reduce combiner
    splits and schedules as it sees fit. Byte balancing
    is greedy over cumulative equal-bytes boundaries; a cut is forced
    when the leaves remaining would otherwise leave a bucket empty.
    """
    n = len(leaves)
    buckets = max(int(buckets), 1)
    if n == 0:
        return ()
    if buckets == 1 or n == 1:
        return (tuple(range(n)),)
    buckets = min(buckets, n)
    order = list(range(n - 1, -1, -1))  # backprop completion order
    sizes = [_nbytes(leaves[i]) for i in order]
    total = sum(sizes) or 1
    boundary = total / buckets
    plan, cur, acc = [], [], 0
    for pos, (i, nb) in enumerate(zip(order, sizes)):
        cur.append(i)
        acc += nb
        remaining_leaves = n - pos - 1
        remaining_buckets = buckets - len(plan) - 1
        if (len(plan) < buckets - 1
                and (acc >= boundary * (len(plan) + 1)
                     or remaining_leaves <= remaining_buckets)):
            plan.append(tuple(cur))
            cur = []
    if cur:
        plan.append(tuple(cur))
    return tuple(plan)


def reducescatter(tensor, average=False, axis_name=AXIS):
    """Reduce across ranks, leaving each rank with its dim-0 stripe.

    No reference equivalent as a public op (the reference uses
    ncclReduceScatter only internally inside hierarchical allreduce,
    nccl_operations.cc:258-485); exposed here because psum_scatter is the
    bandwidth-optimal half of an allreduce on ICI and ZeRO-style sharded
    optimizers want it directly.
    """
    record_jit_traced("reducescatter_jit", _nbytes(tensor), axis_name)
    out = lax.psum_scatter(tensor, axis_name, scatter_dimension=0, tiled=True)
    if average:
        out = out / lax.psum(1, axis_name)
    return out
