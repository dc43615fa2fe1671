"""Eager op-at-a-time collective engine: handles, negotiation, fusion.

Reference equivalent: the core runtime's background-thread pipeline —
``EnqueueTensorAllreduce/Allgather/Broadcast`` (operations.cc:2013-2135),
the per-cycle coordinator loop ``RunLoopOnce`` (operations.cc:1434-1843),
rank-0 negotiation + ``ConstructResponse`` consistency checks
(operations.cc:191-527), tensor fusion ``FuseResponses``
(operations.cc:577-700) with the ``FusionBufferManager``, the ``ResponseCache``
steady-state bypass (response_cache.{h,cc}), and stall detection
``CheckForStalledTensors`` (operations.cc:815-896).

TPU-native redesign. There is no background thread, no MPI control plane and
no rank-0 master: JAX is single-controller per process, so every "rank"
(device) the process owns submits through the same in-process queue and the
negotiation below is ordinary synchronous Python executed when a handle is
synchronized (or the pending bytes exceed the fusion threshold). What survives
from the reference is its *observable contract*, which user code and tests
depend on:

- handle-based async API (``allreduce_async``/``poll``/``synchronize``, the
  torch binding surface torch/mpi_ops.py:54-438);
- name-keyed readiness: an op starts only when every rank submitted the name;
- duplicate-name rejection per rank (operations.cc:142-145, :2042);
- cross-rank dtype/op/shape/root mismatch errors with the reference's exact
  message wording (ConstructResponse, operations.cc:325-527);
- tensor fusion of small ops into one wire collective under
  ``HOROVOD_FUSION_THRESHOLD`` with dtype-grouped look-ahead
  (operations.cc:577-700), aligned to ``FUSION_BUFFER_ATOMIC_UNIT``;
- response cache keyed by tensor metadata so steady-state loops skip
  re-validation (response_cache.h:44);
- stall warnings/shutdown with the reference's message format
  (operations.cc:815-896);
- the fork's padding experiment (``PADDING_ALGO=1`` rounds wire element counts
  up to the next power of two, ops/mpi_operations.cc:24-63).

The data plane is a jitted ``shard_map`` program over the runtime's global
mesh: each rank's flattened contribution lives on its own device (a sharded
(nranks, L) buffer — the fusion buffer, but device-resident and built by XLA),
and one ``lax.psum``/``all_gather`` rides ICI. Results land back on every
device, and handles hand out per-rank views.
"""

import contextlib
import functools
import threading
import time
import warnings
from collections import OrderedDict, deque

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import diag, guard, metrics
from .. import timeline as tl
from ..config import FUSION_BUFFER_ATOMIC_UNIT, next_power_of_two
from ..exceptions import (DuplicateNameError, HorovodError,
                          HostsUpdatedError, MismatchError, ShutDownError,
                          StalledTensorError, TransientCollectiveError,
                          WorkerLostError)
from ..utils.logging import get_logger

_logger = get_logger()

ALLREDUCE = "ALLREDUCE"
ALLGATHER = "ALLGATHER"
BROADCAST = "BROADCAST"
ALLTOALL = "ALLTOALL"

_OP_NAMES = {ALLREDUCE: "allreduce", ALLGATHER: "allgather",
             BROADCAST: "broadcast", ALLTOALL: "alltoall"}

_donation_silenced = False


def _silence_donation_advisory():
    """Ignore jax's "Some donated buffers were not usable" advisory — the
    fused wire programs donate opportunistically on every dispatch, so
    the fallback is expected, not actionable. Installed ONCE at the
    module level: a per-dispatch warnings.catch_warnings() scope would
    mutate the process-global filter list from multiple threads
    (documented as thread-unsafe), and re-registering per engine would
    grow the filter list every elastic-recovery rebuild. Cost: an
    identical advisory from user-code donation is suppressed too while a
    donating engine has ever existed in the process."""
    global _donation_silenced
    if not _donation_silenced:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        _donation_silenced = True


class _InFlight:
    """A dispatched-but-unread fused wire bucket (the overlap pipeline's
    unit of work): the device op has been enqueued and its host copy
    started, but nobody has blocked on the result yet. Completion —
    blocking readback + unfuse + handle resolution — happens on the
    completion thread, in ``synchronize()``, or at drain.

    ``batch`` is the slim post-dispatch view (name, dtype, per-request
    metadata) — NOT the entry/request objects, whose submitted tensors
    would otherwise stay pinned for up to pipeline_depth fusion buckets
    past their useful life."""

    __slots__ = ("batch", "offsets", "counts", "out", "wire_dtype", "rows",
                 "op_stat", "nbytes", "t_dispatch")

    def __init__(self, batch, offsets, counts, out, wire_dtype, rows,
                 op_stat, nbytes):
        self.batch = batch
        self.offsets = offsets
        self.counts = counts
        self.out = out            # the un-materialized device result
        self.wire_dtype = wire_dtype
        self.rows = rows          # pooled host fusion buffer (returned on
        self.op_stat = op_stat    # completion; see pool notes)
        self.nbytes = nbytes      # profiler slot + payload for stats.record
        self.t_dispatch = time.perf_counter()


class _Request:
    """One rank's submission for one named tensor (reference: Request,
    message.h:45-98)."""

    __slots__ = ("op", "rank", "name", "tensor", "average", "root_rank",
                 "compression", "handle", "prescale", "postscale", "seq",
                 "to_host", "_meta")

    def __init__(self, op, rank, name, tensor, handle, average=True,
                 root_rank=0, compression=None, prescale=None, postscale=None,
                 seq=0, to_host=True):
        self.op = op
        self.rank = rank
        self.name = name
        self.tensor = tensor
        self.handle = handle
        self.average = average
        self.root_rank = root_rank
        self.compression = compression
        self.prescale = prescale
        self.postscale = postscale
        self.seq = seq
        self.to_host = to_host
        self._meta = None

    def meta(self):
        # Cached: publish cycles re-read every pending request's metadata
        # (a request is immutable after enqueue).
        if self._meta is None:
            from ..negotiation import RequestMeta
            self._meta = RequestMeta(rank=self.rank, op=self.op,
                                     dtype=str(np.dtype(self.tensor.dtype)),
                                     shape=tuple(self.tensor.shape),
                                     root_rank=self.root_rank,
                                     average=bool(self.average))
        return self._meta


class _Entry:
    """A fully-negotiated named tensor ready for execution (reference:
    TensorTableEntry, common.h:177-195)."""

    __slots__ = ("name", "op", "requests", "dtype", "nbytes", "sizes")

    def __init__(self, name, op, requests):
        self.name = name
        self.op = op
        self.requests = requests  # rank -> _Request (locally-owned ranks)
        t0 = requests[min(requests)].tensor
        self.dtype = t0.dtype
        self.nbytes = max(int(r.tensor.nbytes) for r in requests.values())
        self.sizes = None  # allgather per-rank dim-0 sizes (negotiated)


class ResponseCache:
    """LRU cache of negotiated responses keyed by tensor metadata.

    Reference: ResponseCache (response_cache.h:44) — steady-state training
    loops submit identical metadata every step, so negotiation (and here,
    cross-rank validation) can be skipped entirely. Capacity default 1024
    (global_state.h:169). Single-host, the reference's bit-vector MPI sync
    (response_cache.cc:304-390) needs no analog: all ranks share this
    process's cache, so a hit is globally consistent by construction. The
    multi-host analog is the coordinator's epoch-token bypass + memoized
    decisions (coordinator.py module docstring).
    """

    def __init__(self, capacity):
        self.capacity = capacity
        self._cache = OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(req):
        return (req.op, req.name, str(req.tensor.dtype),
                tuple(req.tensor.shape), req.root_rank, bool(req.average))

    def lookup(self, req):
        if self.capacity <= 0:
            return False
        k = self.key(req)
        if k in self._cache:
            self._cache.move_to_end(k)
            self.hits += 1
            return True
        self.misses += 1
        return False

    def put(self, req):
        if self.capacity <= 0:
            return
        self._cache[self.key(req)] = True
        self._cache.move_to_end(self.key(req))
        while len(self._cache) > self.capacity:
            self._cache.popitem(last=False)

    def invalidate_name(self, name):
        """Drop every entry for a name the stall detector flagged — a later
        resolution with different metadata must re-validate (reference:
        InvalidateStalledCachedTensors, operations.cc:899-913)."""
        for k in [k for k in self._cache if k[1] == name]:
            del self._cache[k]

    def clear(self):
        """Drop every cached response (elastic membership change: a
        response validated against the dead membership must never bypass
        re-validation in the rebuilt session)."""
        self._cache.clear()


class NativeResponseCache:
    """ctypes facade over csrc/response_cache.cc with the same contract as
    ResponseCache (the reference's LRU semantics live in C++)."""

    key = staticmethod(ResponseCache.key)

    def __init__(self, lib, capacity):
        self._lib = lib
        self.capacity = capacity
        self._h = lib.hvd_cache_new(int(capacity))
        # Shadow index for name-keyed invalidation, kept in LRU lockstep
        # with the native cache: recency bumps on BOTH put and lookup hit
        # (the native Lookup splices to the front, response_cache.cc), so
        # eviction order matches and a steady-state-hot key can't fall out
        # of the shadow while still live natively — which would let a
        # stalled tensor's stale response survive invalidate_name.
        # Removing a key the native side already evicted stays a no-op.
        self._key_names = OrderedDict()  # key repr -> name

    def lookup(self, req):
        k = repr(self.key(req))
        hit = bool(self._lib.hvd_cache_lookup(self._h, k.encode()))
        if hit and k in self._key_names:
            self._key_names.move_to_end(k)
        return hit

    def put(self, req):
        if self.capacity <= 0:
            return
        k = repr(self.key(req))
        self._key_names[k] = req.name
        self._key_names.move_to_end(k)
        while len(self._key_names) > self.capacity:
            self._key_names.popitem(last=False)
        self._lib.hvd_cache_put(self._h, k.encode())

    def invalidate_name(self, name):
        for k in [k for k, n in self._key_names.items() if n == name]:
            del self._key_names[k]
            self._lib.hvd_cache_remove(self._h, k.encode())

    def clear(self):
        for k in list(self._key_names):
            self._lib.hvd_cache_remove(self._h, k.encode())
        self._key_names.clear()

    @property
    def hits(self):
        return int(self._lib.hvd_cache_hits(self._h))

    @property
    def misses(self):
        return int(self._lib.hvd_cache_misses(self._h))


def _participants_digest(mesh):
    """Short stable digest of the participant set (process, device) pairs
    the mesh spans. Part of every wire-program cache key: a compiled
    collective is only ever valid for the exact membership it was
    compiled against, so a program cached before an elastic membership
    change can never be served to the rebuilt session even if its shape
    signature matches."""
    import hashlib
    ids = sorted((int(d.process_index), int(d.id))
                 for d in mesh.devices.flat)
    return hashlib.sha1(repr(ids).encode()).hexdigest()[:12]


class WireProgramCache:
    """Signature-keyed cache of compiled wire programs (the tentpole's
    second half): one executable per ``(op, wire_dtype, padded_rows,
    extras..., participants_digest)`` signature, LRU-bounded, with
    hit/miss accounting surfaced as ``hvd_engine_wire_cache_*``.

    The fork's power-of-two padding experiment (PADDING_ALGO,
    ops/mpi_operations.cc:24-63) is load-bearing here: the engine bins
    fused element counts so steady-state training maps every bucket onto
    ONE cached executable per shape class and recompiles drop to ~zero.
    Compare with the module-level ``functools.lru_cache`` on the jit
    builders below: that tier dedupes program *construction* per process;
    this tier is per-engine, observable, membership-scoped, and
    explicitly invalidated on elastic aborts/shutdown.
    """

    def __init__(self, participants_digest, capacity=256):
        self.participants_digest = participants_digest
        self.capacity = capacity
        self._programs = OrderedDict()
        self.hits = 0
        self.misses = 0

    def get(self, signature, build):
        key = (self.participants_digest,) + tuple(signature)
        prog = self._programs.get(key)
        if prog is not None:
            self._programs.move_to_end(key)
            self.hits += 1
            return prog
        self.misses += 1
        prog = self._programs[key] = build()
        while len(self._programs) > self.capacity:
            self._programs.popitem(last=False)
        return prog

    def __len__(self):
        return len(self._programs)

    @property
    def hit_rate(self):
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def invalidate(self):
        """Drop every compiled program reference in THIS tier (elastic
        membership change / shutdown). The digest already guarantees a
        stale program cannot serve a NEW membership. Note the builder
        ``lru_cache`` tier below holds its own references keyed by the
        Mesh — deliberately kept across ordinary shutdown/re-init so an
        identical topology doesn't recompile, but cleared on elastic
        aborts (``_clear_wire_program_builders``) where the dead mesh's
        programs would otherwise accumulate for process lifetime."""
        self._programs.clear()


class EagerEngine:
    """In-process coordinator + XLA data plane for eager collectives."""

    # Shared-state discipline, enforced by hvdlint HVD002: these fields
    # are touched by the app threads, the completion thread, the ticker
    # and the hang watchdog, and every access must hold the engine lock
    # (the Condition _cv shares it). Methods named *_locked are
    # caller-holds-the-lock by convention.
    _GUARDED_BY = {
        "_inflight": "_lock",
        "_buffer_pool": "_lock",
        "_dev_pending": "_lock",
        "_table": "_lock",
        "_first_seen": "_lock",
        "_stall_warned": "_lock",
        "_handles": "_lock",
        "_next_handle": "_lock",
        "_pending_bytes": "_lock",
        "_next_seq": "_lock",
    }
    _LOCK_ALIASES = {"_cv": "_lock"}

    def __init__(self, mesh, num_ranks, config, stats, timeline):
        self.mesh = mesh
        self.num_ranks = num_ranks
        self.config = config
        self.stats = stats
        self.timeline = timeline
        self.autotuner = None
        self._lock = threading.RLock()
        # Completion signaling shares the engine lock: waiters park here
        # and every handle resolution (cycle or completion thread) notifies.
        self._cv = threading.Condition(self._lock)
        self._shutdown = False
        # Overlap pipeline state (docs/performance.md): dispatched fused
        # buckets awaiting readback, the host fusion-buffer pool they
        # borrow from, and the completion thread that drains them.
        self._inflight = deque()
        self._buffer_pool = OrderedDict()  # (nrows, total, dtype) -> [rows]
        self._completion_thread = None
        self._completion_stop = threading.Event()
        flat0 = list(mesh.devices.flat)
        platform = flat0[0].platform if flat0 else "cpu"
        # Donation auto-policy: on CPU jax may zero-copy-alias the host
        # fusion buffer as device memory, and donating an alias would let
        # XLA scribble over a pooled buffer we reuse — so auto means
        # accelerators only.
        self._donate = (config.fusion_donate == 1
                        or (config.fusion_donate < 0 and platform != "cpu"))
        if self._donate:
            _silence_donation_advisory()
        # Recent genuinely-measured wire-op span (dispatch -> result
        # host-available), for estimating spans of buckets that finished
        # before their completer arrived. See _complete_inflight.
        self._wire_span_ema = None
        # Signature-keyed compiled-program cache, membership-scoped (see
        # WireProgramCache). Invalidated on elastic abort and shutdown.
        self._wire_cache = WireProgramCache(_participants_digest(mesh))
        # Compiled train-step programs (ops/step_program.py): same
        # membership-scoped signature discipline, kept a separate tier so
        # step-program hit rates are observable on their own
        # (hvd_step_program_cache_*) and wire-bucket churn can never
        # evict a steady-state step program. All access goes through the
        # step_program() gateway under the engine lock.
        self._step_cache = WireProgramCache(_participants_digest(mesh))
        # Device-resident buckets whose fusion buffers are still possibly
        # aliased by an in-flight program (CPU zero-copy): (out, rows)
        # pairs reaped back into the pool once the program completed.
        self._dev_pending = deque()
        # name -> {rank: _Request}; insertion order is submission order
        # (reference: message_table, global_state.h:36).
        self._table = OrderedDict()
        self._first_seen = {}    # name -> perf_counter of first submission
        self._stall_warned = set()
        self._handles = {}       # handle -> ("pending" | result | exception)
        self._next_handle = 0
        self._pending_bytes = 0
        from .. import native
        self._native_lib = native.get_lib()
        if self._native_lib is not None:
            self._response_cache = NativeResponseCache(self._native_lib,
                                                       config.cache_capacity)
        else:
            self._response_cache = ResponseCache(config.cache_capacity)
        self._axis = mesh.axis_names[0]
        self._row_sharding = NamedSharding(mesh, P(self._axis))
        self._replicated = NamedSharding(mesh, P())

        # Hierarchical (two-level ICI+DCN) topology, honored when the
        # HOROVOD_HIERARCHICAL_* flags are set and the device pool actually
        # has two tiers (reference: NCCLHierarchicalAllreduce,
        # nccl_operations.cc:258-485; MPIHierarchicalAllgather,
        # mpi_operations.cc:241-391). The local tier defaults to this job's
        # per-process device grouping (the ICI-connected slice);
        # HOROVOD_TPU_LOCAL_SIZE overrides it (and is how tests model a 2x4
        # two-node topology on a virtual 8-device pool).
        self._hier_mesh = None
        self._hier_axes = None
        if config.hierarchical_allreduce or config.hierarchical_allgather:
            self._init_hierarchical()

        # Multi-host: each process owns the ranks of its local devices; a
        # KV-store coordinator (coordinator.py) arbitrates global readiness
        # (the reference's rank-0 negotiation, operations.cc:1576-1843).
        flat = list(mesh.devices.flat)
        self._local_ranks = [r for r, d in enumerate(flat)
                             if d.process_index == jax.process_index()]
        self._multihost = jax.process_count() > 1
        self._coord = None
        self._next_seq = 0
        # Elastic abort: set when the coordinator declares a peer lost (or
        # a cooperative membership change) — sticky until the runtime is
        # rebuilt over the surviving processes (elastic/runner.py).
        self._elastic_abort = None
        # Ordered record of synced autotune applications (multi-host); the
        # SyncParams test asserts this sequence is identical across
        # processes, which is the whole point of routing through the log.
        self.applied_autotune = []
        self._ticker = None
        self._ticker_stop = threading.Event()
        self._last_cycle = 0.0  # app-thread cycle clock (ticker suppression)
        if self._multihost:
            from ..coordinator import MultiHostCoordinator
            # Session membership: the processes owning mesh devices. After
            # an elastic recovery this is the survivor set, so the new
            # session's coordinator neither polls the dead process's keys
            # nor re-declares it lost.
            participants = sorted({d.process_index for d in flat})
            self._coord = MultiHostCoordinator(config, self.num_ranks,
                                               stats=stats,
                                               participants=participants)
            if not config.ticker_disable:
                self._ticker = threading.Thread(
                    target=self._ticker_loop, name="hvd-tpu-ticker",
                    daemon=True)
                self._ticker.start()
        # Flight recorder (diag/): installed by runtime.init before the
        # engine exists (None when disabled or constructed standalone);
        # cached so hot paths pay one attribute load and no import.
        self._flight = diag.get()
        # Step-integrity guard (guard/): monitor + chaos injector, also
        # installed by runtime.init before the engine. Both None by
        # default, in which case every hook below is a single attribute
        # load and a skipped branch — the inert-by-default contract.
        self._guard = guard.get()
        self._inject = guard.inject.get()
        if self._guard is not None and self._coord is not None:
            # Multi-host: route non-apply step verdicts through the
            # coordinator's decision log (append_guard no-ops off pid 0)
            # so the log can prove no rank disagreed on a step's fate.
            self._guard.decision_sink = self.publish_guard
        # Point-in-time engine health for hvd.metrics_snapshot() and the
        # exporters; replaced on re-init, removed at shutdown.
        metrics.registry().set_collect_hook("engine", self._collect_metrics)

    def _collect_metrics(self):
        # Exporter-thread gauge snapshot: len()/attribute reads are
        # GIL-atomic and a stale value is fine; taking the engine lock
        # here could park the exporter behind a whole locked data-plane
        # step.
        metrics.ENGINE_QUEUE_DEPTH.set(len(self._table))  # hvdlint: disable=HVD002 -- relaxed gauge read, GIL-atomic len()
        metrics.ENGINE_PENDING_BYTES.set(self._pending_bytes)  # hvdlint: disable=HVD002 -- relaxed gauge read
        metrics.ENGINE_CACHE_HITS.set(self._response_cache.hits)
        metrics.ENGINE_CACHE_MISSES.set(self._response_cache.misses)
        metrics.ENGINE_INFLIGHT_DEPTH.set(len(self._inflight))  # hvdlint: disable=HVD002 -- relaxed gauge read, GIL-atomic len()
        metrics.ENGINE_WIRE_CACHE_HITS.set(self._wire_cache.hits)
        metrics.ENGINE_WIRE_CACHE_MISSES.set(self._wire_cache.misses)
        metrics.STEP_PROGRAM_CACHE_HITS.set(self._step_cache.hits)
        metrics.STEP_PROGRAM_CACHE_MISSES.set(self._step_cache.misses)

    def step_program(self, signature, build):
        """Signature-keyed compiled train-step programs (the compiled
        hot loop's cache tier; ops/step_program.py is the only caller).
        Same contract as the wire-program tier: keys are scoped by the
        participants digest, so a step program compiled for a dead
        elastic membership can never serve the rebuilt session, and
        both tiers are invalidated together on abort and shutdown.
        ``build`` constructs a lazily-compiling jit (compilation happens
        at first execution), so running it under the engine lock is
        cheap. Returns ``(program, was_hit, hits, misses)`` — the
        totals feed the hvd_step_program_cache_* gauges."""
        with self._lock:
            before = self._step_cache.hits
            prog = self._step_cache.get(signature, build)
            return (prog, self._step_cache.hits > before,
                    self._step_cache.hits, self._step_cache.misses)

    def _init_hierarchical(self):
        """Build the 2-D (cross, local) mesh hierarchical collectives run
        over, or warn loudly when the topology can't support two tiers
        (a reference user setting HOROVOD_HIERARCHICAL_ALLREDUCE=1 must
        never get silent flat behavior)."""
        from ..parallel.mesh import hierarchical_axes, hierarchical_mesh
        flat = list(self.mesh.devices.flat)
        local = int(getattr(self.config, "tpu_local_size", 0))
        if local <= 0:
            # Per-process grouping: contiguous rank runs owned by one process
            # (== one host's ICI-connected chips).
            by_proc = {}
            for d in flat:
                by_proc.setdefault(d.process_index, 0)
                by_proc[d.process_index] += 1
            sizes = set(by_proc.values())
            local = sizes.pop() if len(sizes) == 1 else 0
        if (local <= 1 or local >= self.num_ranks
                or self.num_ranks % local != 0):
            _logger.warning(
                "HOROVOD_HIERARCHICAL_ALLREDUCE/ALLGATHER requested but the "
                "topology has no two-level structure (local_size=%d of %d "
                "ranks); falling back to flat collectives. Set "
                "HOROVOD_TPU_LOCAL_SIZE to define the local (ICI) tier.",
                local, self.num_ranks)
            return
        self._hier_mesh = hierarchical_mesh(flat, local)
        self._hier_axes = hierarchical_axes(self._hier_mesh)
        _logger.info("hierarchical collectives over a %dx%d (cross, local) "
                     "mesh", self.num_ranks // local, local)

    @property
    def hier_local_size(self):
        return (self._hier_mesh.shape["local"]
                if self._hier_mesh is not None else 0)

    # ------------------------------------------------------------------ API

    def enqueue(self, op, tensor, name, rank=None, average=True, root_rank=0,
                compression=None, prescale=None, postscale=None,
                to_host=True):
        """Submit one rank's tensor; returns an async handle.

        Reference: EnqueueTensorAllreduce/Allgather/Broadcast
        (operations.cc:2013-2135) including the duplicate-name check at :2042.
        ``rank=None`` submits on behalf of *all* ranks this process owns with
        the same data (the common single-host replicated case); tests pass an
        explicit rank to model divergent per-rank tensors.

        ``to_host=False`` (allreduce only) opts into the device-resident
        fast path: the result resolves to a jax device array sliced out
        of the fused wire buffer inside the jitted program, and no
        device->host readback ever happens — synchronize() waits on
        dispatch only. Ignored (exact legacy numpy behavior) when
        HOROVOD_DEVICE_RESIDENT=0.
        """
        with self._lock:
            if self._elastic_abort is not None:
                # Sticky until elastic recovery rebuilds the runtime: a
                # post-abort submission must fail fast with the elastic
                # error, not negotiate against a dead membership.
                raise self._elastic_abort
            if self._shutdown:
                raise ShutDownError()
            if rank is None:
                ranks = list(self._local_ranks)
            else:
                if not 0 <= rank < self.num_ranks:
                    raise ValueError(f"rank {rank} out of range "
                                     f"[0, {self.num_ranks})")
                if self._multihost and rank not in self._local_ranks:
                    raise ValueError(
                        f"rank {rank} is not owned by this process "
                        f"(local ranks: {self._local_ranks})")
                ranks = [rank]
            tensor = np.asarray(tensor)
            if self._inject is not None:
                # Chaos 'nan' injection point: all local ranks enqueued by
                # this call share the (possibly poisoned) tensor, so the
                # fault enters this process's whole wire contribution.
                tensor = np.asarray(self._inject.on_enqueue(name, tensor))
            handle = self._next_handle
            self._next_handle += 1
            self._handles[handle] = "pending"
            pending = self._table.get(name)
            created = False
            if pending is None:
                pending = self._table[name] = {}
                created = True
                self._first_seen[name] = time.perf_counter()
                self.timeline.negotiate_start(name, op)
            added = []
            for r in ranks:
                if r in pending:
                    # Roll back everything this call added before raising
                    # (duplicate-name check parity: operations.cc:2042).
                    for a in added:
                        del pending[a]
                    if created and not pending:
                        del self._table[name]
                        self._first_seen.pop(name, None)
                    self._handles.pop(handle)
                    raise DuplicateNameError()
                self._next_seq += 1
                pending[r] = _Request(op, r, name, tensor, handle,
                                      average=average, root_rank=root_rank,
                                      compression=compression,
                                      prescale=prescale, postscale=postscale,
                                      seq=self._next_seq, to_host=to_host)
                added.append(r)
            self._pending_bytes += tensor.nbytes * len(added)
            fr = self._flight
            if fr is not None:
                fr.record("enqueue", name, op, tensor.nbytes,
                          str(tensor.dtype))
            # Mirror the reference's cycle trigger: once enough bytes are
            # pending to fill a fusion buffer, run a cycle eagerly rather
            # than waiting for synchronize() (≈ the 5 ms cycle waking up).
            if self._pending_bytes >= self.config.fusion_threshold:
                self._run_cycle()
            return handle

    def poll(self, handle):
        """True once the op completed (reference: horovod_torch_poll,
        torch/mpi_ops_v2.cc:223-226). A dispatched-but-unread pipeline
        bucket is NOT complete — its readback can still block or fail —
        so True must mean the result (or error) actually landed. An
        in-flight handle's bucket is completed inline here: no more
        blocking than the pre-pipeline poll, whose cycle did the readback
        inline. False with an empty deque means the completion thread
        owns the bucket and resolution is imminent."""
        with self._lock:
            result = self._handles.get(handle, "pending")
            if result == "pending":
                self._run_cycle()
                result = self._handles.get(handle, "pending")
            if result == "inflight":
                # Complete our bucket inline only while it is still
                # queued; a completion-thread-owned bucket resolves on
                # its own, and draining newer buckets here would
                # serialize their readbacks for a False anyway.
                while self._owns_inflight_locked(handle) and \
                        isinstance(self._handles.get(handle), str):
                    self._complete_inflight(self._inflight.popleft())
                result = self._handles.get(handle, "pending")
            return result != "pending" and not isinstance(result, str)

    def synchronize(self, handle):
        """Block until completion; return the result or raise the op's error
        (reference: horovod_torch_wait_and_clear polling loop,
        torch/mpi_ops_v2.cc:228-234)."""
        deadline_kill = self.config.stall_shutdown_time_seconds
        t0 = time.perf_counter()
        while True:
            with self._cv:
                # Resolved-handle fast path BEFORE running a cycle: in
                # multi-host mode a cycle blocks up to the decision-fetch
                # timeout, and a batch of N fused tensors resolves N
                # handles at once — synchronizing the other N-1 must not
                # pay a blocking KV wait each (measured 50 ms x N/step).
                result = self._handles.get(handle)
                if result is None:
                    raise HorovodError(f"unknown handle {handle}")
                if result == "inflight":
                    # Dispatched but unread: while ours is still queued,
                    # drain from the oldest bucket here instead of paying
                    # a cv.wait tick per bucket for the completion thread
                    # (FIFO — buckets ahead of ours resolve first, ours
                    # lands last). If the completion thread owns our
                    # bucket, resolution is imminent — draining newer
                    # buckets would only serialize their readbacks under
                    # the lock; just park on the condition below.
                    while self._owns_inflight_locked(handle) and isinstance(
                            self._handles.get(handle), str):
                        self._complete_inflight(self._inflight.popleft())
                elif isinstance(result, str):
                    self._run_cycle()
                result = self._handles.get(handle)
                if result is not None and not isinstance(result, str):
                    del self._handles[handle]
                    if isinstance(result, Exception):
                        raise result
                    return result
                if not self.config.stall_check_disable:
                    self._check_stalls_locked()
                waited = time.perf_counter() - t0
                if deadline_kill > 0 and waited > deadline_kill:
                    # The background-thread reference shuts the whole job
                    # down (operations.cc:1458-1461); in-process we surface
                    # it as an exception on the waiting handle.
                    raise StalledTensorError(
                        "One or more rank is stalled for longer than "
                        f"{int(deadline_kill)} seconds. Will shutdown.")
                # Parked on the shared condition: a completion-thread or
                # peer-thread resolution wakes us immediately instead of
                # costing a full cycle-time sleep.
                self._cv.wait(max(self.config.cycle_time_ms, 1.0) / 1000.0)

    def _ticker_loop(self):
        """Continuous coordination cadence: the reference's background
        thread runs its coordinator loop every ~cycle_time regardless of
        what the application thread does (operations.cc:985,1434-1449).
        Here the analog is control-plane ONLY — publish the locked pending
        snapshot and (on process 0) run ``coordinate()``; decisions are
        still applied by application threads in ``_run_cycle``, so no
        device work ever launches from this thread (the multi-controller
        XLA program-order rule). Restores the overlap property: a process
        that async-submits and then computes no longer stalls its peers
        until its next synchronize."""
        def _interval():
            # Floor at 1 ms: HOROVOD_CYCLE_TIME=0 means "cycle eagerly"
            # on the app threads, not a busy-looping ticker.
            return max(self.config.cycle_time_ms, 1.0) / 1000.0

        # Idle back-off (round-4 verdict #1): with nothing pending
        # anywhere, a ~5 ms always-on ticker on 256 hosts is tens of
        # thousands of KV RPCs per second for nothing. Any sign of work
        # (local pending set, or coordinate() observing submissions)
        # snaps the cadence back to cycle_time; otherwise it doubles up
        # to ~1 s. The resumption cost is bounded at one back-off period
        # once per idle gap.
        backoff = 1.0
        interval = _interval()
        while not self._ticker_stop.wait(min(interval * backoff, 1.0)
                                         if interval < 1.0
                                         else interval):
            interval = _interval()
            # Elastic liveness beat BEFORE the suppression checks: the
            # detector must keep hearing from this process whether the
            # app threads are cycling, computing, or blocked (throttled
            # internally; no-op unless HOROVOD_ELASTIC).
            try:
                self._coord.publish_liveness()
            except Exception:  # noqa: BLE001 — best-effort beacon
                pass
            # Suppress when application threads are already cycling at
            # the coordination cadence (a synchronize-heavy loop): the
            # ticker exists to cover COMPUTE gaps, and duplicating a busy
            # loop's publishes only adds lock/KV contention.
            if time.perf_counter() - self._last_cycle < interval:
                backoff = 1.0
                continue
            # Snapshot under the engine lock, but run the KV round
            # WITHOUT it — on a real DCN a publish + coordinate is many
            # RPC round-trips, and enqueue/synchronize must never wait on
            # control-plane I/O (coordinator state is guarded by its own
            # internal lock; lock order engine -> coordinator only).
            # Try-acquire: an application thread holding the lock IS a
            # cycle in progress — skip instead of racing it.
            if not self._lock.acquire(blocking=False):
                backoff = 1.0
                continue
            try:
                if self._shutdown:
                    return
                if time.perf_counter() - self._last_cycle < interval:
                    backoff = 1.0
                    continue
                pending_meta = [(req.seq, name, req.meta())
                                for name, pend in self._table.items()  # hvdlint: disable=HVD002 -- lock IS held: try-acquire above succeeded (trylock is outside the With-pattern the rule models)
                                for req in pend.values()]
            finally:
                self._lock.release()
            busy = bool(pending_meta)
            try:
                # Quiet during fast-lane steady state: the application
                # will execute this exact set locally, so publishing it
                # would only create orphan decisions nobody fetches
                # promptly. coordinate() still runs (process 0 must keep
                # serving peers that DID publish).
                if not self._coord.fast_lane_would_hit(pending_meta):
                    self._coord.publish(pending_meta)
                # Tree fan-in sweep (no-op off group heads / in star
                # mode): batch this group's blobs so the root's next
                # round reads one aggregate instead of the group.
                if self._coord.aggregate_round():
                    busy = True
                if self._coord.coordinate():
                    busy = True
            except Exception:  # app threads surface transport errors
                _logger.debug("ticker cycle failed", exc_info=True)
            backoff = 1.0 if busy else min(backoff * 2.0, 1024.0)

    def shutdown(self):
        """Shut down this process's engine; in multi-host jobs, announce the
        exit so peers fail fast with ShutDownError instead of stalling
        (reference: shutdown piggybacked on the RequestList and echoed by the
        coordinator, operations.cc:135-140,1664-1667,1882-1886).

        In-flight (dispatched-but-unread) buckets are drained so
        deferred-readback handles resolve to real results instead of
        hanging or leaking at exit; queued never-dispatched handles then
        fail fast with ShutDownError as before. The shutdown flag flips
        BEFORE the drain — otherwise a bucket dispatched concurrently
        (submission raced past the flag check) lands after the drain and
        its successfully-exchanged handles would be overwritten with
        ShutDownError while peers saw real results."""
        with self._lock:
            if self._shutdown:
                return
            self._shutdown = True
        self._drain_inflight()
        self._ticker_stop.set()
        metrics.registry().remove_collect_hook("engine")
        with self._lock:
            # A cycle that was already past the submission gate can have
            # dispatched between the drain and this lock; finish it here
            # so its handles resolve to the exchanged results.
            while self._inflight:
                self._complete_inflight(self._inflight.popleft())
            for h, v in list(self._handles.items()):
                if isinstance(v, str):
                    self._handles[h] = ShutDownError()
            self._wire_cache.invalidate()
            self._step_cache.invalidate()
            self._dev_pending.clear()
            if self._coord is not None:
                try:
                    self._coord.publish_shutdown()
                    # Process 0 is the decision maker: emit the echo now so
                    # it lands even when rank 0 is the one exiting.
                    self._coord.coordinate()
                except Exception:  # KV service may already be gone
                    _logger.debug("shutdown announce failed", exc_info=True)
                finally:
                    self._coord.close()
            self._cv.notify_all()

    # ------------------------------------------------------ overlap pipeline

    def _pipeline_depth(self):
        """Live-read so autotune's depth decisions apply next dispatch."""
        return max(int(self.config.pipeline_depth), 0)

    def _acquire_rows_locked(self, nrows, total, dtype):
        """Host fusion buffer from the reuse pool (reference: the
        persistent FusionBufferManager buffer — allocated once, reused
        every cycle — instead of a fresh allocation per batch). Pooled
        per shape: steady-state training hits the same fused shape every
        step. The caller owns zeroing the pad tail."""
        key = (nrows, int(total), np.dtype(dtype).str)
        pool = self._buffer_pool.get(key)
        if pool:
            self._buffer_pool.move_to_end(key)
            return pool.pop()
        return np.empty((nrows, int(total)), dtype=dtype)

    def _release_rows_locked(self, rows):
        """Return a fusion buffer to the pool — only ever AFTER its wire
        program's result was read back (or discarded): on CPU jax may
        zero-copy-alias the host buffer as device memory, so reusing it
        while the program is pending would corrupt the wire payload."""
        key = (rows.shape[0], int(rows.shape[1]), rows.dtype.str)
        pool = self._buffer_pool.setdefault(key, [])
        self._buffer_pool.move_to_end(key)
        # double-buffering + one per extra in-flight slot is all steady
        # state can use; beyond that (and beyond a few live shapes) free
        # the memory instead of hoarding it
        if len(pool) <= self._pipeline_depth() + 1:
            pool.append(rows)
        while len(self._buffer_pool) > 8:
            self._buffer_pool.popitem(last=False)

    def _ensure_completion_thread(self):
        t = self._completion_thread
        if (t is None or not t.is_alive()) \
                and not self._completion_stop.is_set():
            self._completion_thread = threading.Thread(
                target=self._completion_loop, name="hvd-tpu-completer",
                daemon=True)
            self._completion_thread.start()

    def _completion_loop(self):
        """Drain in-flight buckets so handles resolve even when the
        application never synchronizes promptly — the async half of the
        reference's background thread. Readback runs WITHOUT the engine
        lock; only handle resolution takes it."""
        while True:
            rec = None
            with self._cv:
                if self._inflight:
                    rec = self._inflight.popleft()
                elif self._completion_stop.is_set():
                    return
                else:
                    self._cv.wait(0.2)
                    continue
            try:
                self._complete_inflight(rec)
            except Exception:  # noqa: BLE001 — the loop must survive
                _logger.exception("completion thread failed on a bucket")

    def _complete_inflight(self, rec):
        """Blocking readback + unfuse + handle resolution for one
        dispatched bucket. Thread-safe: the readback runs outside any
        lock it can avoid (callers already holding the engine lock simply
        block here, like the pre-pipeline inline readback did)."""
        if self._elastic_abort is not None:
            # Aborted membership: every handle already carries the elastic
            # error and the wire op may never complete — never risk a
            # blocked fetch on a dead collective.
            with self._cv:
                self._discard_inflight_locked(rec)
            return
        err = None
        summed = None
        t_block = time.perf_counter()
        try:
            summed = np.asarray(rec.out)
        except Exception as e:  # noqa: BLE001 — XLA/runtime error surfaces
            err = e             # on the batch's handles below
        t_ready = time.perf_counter()
        wait = t_ready - t_block
        total = t_ready - rec.t_dispatch
        # Wire-op span (dispatch -> result host-available), for the
        # profiler's allreduce slot (pre-pipeline meaning: the full op
        # cost, not just the enqueue) and the overlap telemetry. When the
        # fetch genuinely blocked, the op was still running until now and
        # dispatch->now IS the span — any completer queue wait overlapped
        # real execution. When the fetch returned instantly, the op
        # finished at some unknown earlier point; crediting the whole
        # dispatch->now window would count queue wait behind other
        # buckets' readbacks as wire/hidden time (and bias depth tuning
        # toward deeper-for-nothing pipelines), so estimate with the
        # recent genuinely-measured span instead.
        if wait > 1e-4:
            span = total
            self._wire_span_ema = (span if self._wire_span_ema is None
                                   else 0.8 * self._wire_span_ema
                                   + 0.2 * span)
        elif self._wire_span_ema is not None:
            span = min(total, self._wire_span_ema)
        else:
            span = total
        hidden = max(span - wait, 0.0)
        self.stats.record(rec.op_stat, rec.nbytes, span)
        metrics.ENGINE_READBACK_WAIT_SECONDS.observe(wait)
        if span > 0:
            metrics.ENGINE_COMM_HIDDEN_RATIO.observe(min(hidden / span, 1.0))
        fr = self._flight
        if fr is not None:
            fr.record("wire_end", rec.batch[0][0] if rec.batch else "",
                      "allreduce", rec.nbytes,
                      extra={"span": span, "wait": wait, "hidden": hidden,
                             "n": len(rec.batch),
                             "err": repr(err) if err is not None else None})
        with self._cv:
            try:
                if wait > 1e-4:
                    # Feed the wire profiler (and the autotune
                    # largest-message guard) MEASURED spans only: the
                    # estimated branch above reuses a size-agnostic EMA,
                    # and attributing an EMA dominated by small buckets
                    # to a large bucket's size bin would fabricate
                    # per-bin goodput — inflating an incumbent's number
                    # can wedge the guard against every honest
                    # candidate.
                    self._observe_wire("allreduce", rec.nbytes, span)
                if self.autotuner is not None:
                    self.autotuner.record_overlap(hidden, wait)
                if err is None:
                    self._scatter_fused_results(rec.batch, rec.offsets,
                                                summed, rec.wire_dtype,
                                                rec.counts)
                else:
                    self._fail_inflight_locked(rec, err)
            except Exception as e:  # noqa: BLE001 — unfuse must never
                self._fail_inflight_locked(rec, e)  # strand a handle
            finally:
                self._release_rows_locked(rec.rows)
                metrics.ENGINE_INFLIGHT_DEPTH.set(len(self._inflight))
                self._cv.notify_all()

    def _owns_inflight_locked(self, handle):
        """Whether ``handle``'s dispatched bucket is still in the deque —
        i.e. a waiter can complete it inline. False once the completion
        thread popped it (resolution imminent). Caller holds the lock."""
        return any(handle == h for rec in self._inflight
                   for _, _, reqs in rec.batch for _, h, _, _, _ in reqs)

    def _fail_inflight_locked(self, rec, err):
        """Resolve a bucket's handles to ``err`` and close its timeline
        spans. Partial per-rank results from a scatter that raised midway
        are replaced — the fused op failed as a unit, and pre-pipeline the
        caller saw the exception, never the fragment. Handles already
        carrying an exception (an elastic abort that landed first) keep
        it: that error names the cause. Caller holds the lock."""
        for name, _, reqs in rec.batch:
            for _, handle, _, _, _ in reqs:
                v = self._handles.get(handle)
                if v is not None and not isinstance(v, Exception):
                    self._handles[handle] = err
            self.timeline.activity_end(name)
            self.timeline.end(name)

    def _discard_inflight_locked(self, rec):
        """Drop a bucket without readback (elastic abort: handles already
        failed). Caller holds the lock."""
        for name, _, _ in rec.batch:
            self.timeline.activity_end(name)
            self.timeline.end(name)
        self._release_rows_locked(rec.rows)
        self._cv.notify_all()

    def _drain_inflight(self):
        """Flush every dispatched-but-unread bucket (shutdown path): stop
        the completion thread, let it finish what it owns, then complete
        the rest inline. After an elastic abort the readbacks are skipped
        — those wire ops belong to a dead membership."""
        self._completion_stop.set()
        with self._cv:
            self._cv.notify_all()
        t = self._completion_thread
        if t is not None and t.is_alive():
            # A post-abort wire op can hang in gloo until the transport
            # notices the dead peer; don't stall exit on it.
            t.join(timeout=1.0 if self._elastic_abort is not None else 10.0)
            if t.is_alive():
                _logger.warning(
                    "completion thread still blocked on an in-flight wire "
                    "op at shutdown; abandoning it (daemon)")
        while True:
            with self._cv:
                if not self._inflight:
                    break
                rec = self._inflight.popleft()
            self._complete_inflight(rec)

    # ---------------------------------------------------------- negotiation

    def _run_cycle(self):
        """One coordinator cycle: collect ready names, validate, fuse,
        execute (reference: RunLoopOnce, operations.cc:1434-1843)."""
        metrics.ENGINE_CYCLES.inc()
        # Re-entrant for the API paths that already hold the lock; direct
        # callers (tests, external drivers) get the locking they need.
        with self._lock, metrics.ENGINE_CYCLE_SECONDS.time():
            return self._run_cycle_body_locked()

    def _run_cycle_body_locked(self):
        self.timeline.mark_cycle_start()
        if self._multihost:
            return self._run_cycle_multihost()
        ready = [name for name, pend in self._table.items()
                 if len(pend) == self.num_ranks]
        if not ready:
            return
        cache = self._cache()
        entries = []
        for name in ready:
            pending = self._table.pop(name)
            self._first_seen.pop(name, None)
            self._stall_warned.discard(name)
            self.timeline.negotiate_end(name)
            reqs = [pending[r] for r in sorted(pending)]
            self._pending_bytes -= sum(r.tensor.nbytes for r in reqs)
            # A cache hit is only valid when every rank submitted the *same*
            # metadata — the reference's bit-vector sync guarantees this
            # cross-rank agreement (response_cache.cc:304-390); here we check
            # key equality directly before skipping validation.
            keys = {ResponseCache.key(r) for r in reqs}
            if len(keys) == 1 and cache.lookup(reqs[0]):
                entries.append((_Entry(name, reqs[0].op, pending), True))
                continue
            err = self._construct_response(name, reqs)
            if err is not None:
                exc = MismatchError(err)
                for r in reqs:
                    self._handles[r.handle] = exc
                continue
            for r in reqs:
                cache.put(r)
            entries.append((_Entry(name, reqs[0].op, pending), False))
        if entries:
            self._execute(entries)

    def _cache(self):
        return self._response_cache

    # ---------------------------------------------------------- multi-host

    def _run_cycle_multihost(self):
        """Publish pending set → (process 0) decide → apply decisions in
        order. Transport and protocol: coordinator.py; the data-plane
        programs below launch in decision order on every process, keeping
        multi-controller XLA program order consistent."""
        # Stamp at entry AND exit (finally): the data-plane execution
        # below runs inside the engine lock, so a ticker blocked on that
        # lock would otherwise see a stale stamp the moment the lock
        # frees and add a redundant coordination round after every step.
        self._last_cycle = time.perf_counter()
        try:
            self._run_cycle_multihost_locked()
        finally:
            self._last_cycle = time.perf_counter()

    def _run_cycle_multihost_locked(self):
        self._coord.publish_liveness()
        pending_meta = [(req.seq, name, req.meta())
                        for name, pend in self._table.items()
                        for req in pend.values()]
        # Local-replay fast lane (RunBypass analog): validated steady
        # state executes straight from the decision registry — no KV
        # round trips at all (coordinator.fast_replay_entries).
        if not self._shutdown:
            replay = self._coord.fast_replay_entries(pending_meta)
            if replay is not None:
                entries = self._entries_from_decision_locked(replay)
                if entries:
                    self._execute(entries)
                return
        # Keep the shutdown bit sticky: once announced, later publishes from
        # this process must not clear it before the coordinator reads it.
        self._coord.publish(pending_meta, shutdown=self._shutdown)
        # Group heads fold their group's fresh blobs into one aggregate
        # before the root sweeps (no-op in star mode / off heads).
        self._coord.aggregate_round()
        fr = self._flight
        if fr is not None:
            fr.record("negotiate_submit", extra={"n": len(pending_meta)})
            fr.last_cycle_wall = time.time()
        self._coord.coordinate()
        for decision in self._coord.fetch_decisions(
                timeout_ms=max(int(self.config.cycle_time_ms * 10), 50)):
            if decision.get("warning"):
                _logger.warning(decision["warning"])
            if decision.get("autotune"):
                # SyncParams apply point (parameter_manager.cc:223-262):
                # every process — including the tuning process 0 — mutates
                # its knobs HERE, at the same decision index, so every
                # subsequent decision's fusion plan (and wire program
                # shape) is identical across processes.
                at = decision["autotune"]
                self.config.fusion_threshold = int(at["fusion"])
                self.config.cycle_time_ms = float(at["cycle"])
                self.config.padding_algo = int(at["padding"])
                if at.get("depth") is not None:
                    # In-flight depth is host-local (readback cadence, not
                    # wire program shape) but synced anyway so every
                    # process runs the tuned pipeline.
                    self.config.pipeline_depth = int(at["depth"])
                self.applied_autotune.append(
                    (int(at["fusion"]), float(at["cycle"]),
                     int(at["padding"]),
                     None if at.get("depth") is None else int(at["depth"])))
            if decision.get("guard"):
                # Audit lane: every process observes the same guard
                # verdict at the same decision index; the monitor screams
                # if its local ladder ever disagreed (guard/).
                if self._guard is not None:
                    self._guard.apply_decision(decision["guard"])
            if decision.get("abort"):
                # Elastic membership abort (a lost worker, or a
                # cooperative hosts-updated interrupt): fail in-flight
                # handles cleanly and stop applying this session's log —
                # recovery rebuilds the session (elastic/runner.py).
                self._apply_abort_locked(decision["abort"])
                return
            if decision.get("shutdown"):
                # A peer exited cooperatively: its own shutdown() drained
                # its in-flight buckets first, so dispatched wire ops have
                # every participant and will complete — finish ours before
                # the sweep, or handles whose exchange succeeded would be
                # overwritten with ShutDownError while peers saw real
                # results. Then fail every still-pending handle fast
                # (SHUT_DOWN_ERROR on all ranks, operations.cc:1882-1886).
                self._shutdown = True
                while self._inflight:
                    self._complete_inflight(self._inflight.popleft())
                for h, v in list(self._handles.items()):
                    if isinstance(v, str):
                        self._handles[h] = ShutDownError()
                return
            entries = self._entries_from_decision_locked(decision["tensors"])
            if entries:
                self._execute(entries)

    def _apply_abort_locked(self, info):
        """Elastic abort: turn worker failure from a silent negotiation
        stall (the 0.16 reference hangs inside the blocking MPI
        collective, operations.cc:815-896 can only report it) into an
        immediate, catchable failure of every in-flight handle. The
        pending table is dropped whole — those submissions belong to the
        dead membership and re-submit after recovery."""
        if info.get("kind") == "hosts_updated":
            exc = HostsUpdatedError(epoch=info.get("epoch", 0))
        elif info.get("kind") == "planned_departure":
            # Cooperative: a preempted peer said goodbye inside its grace
            # window. Carries the departing pids (recovery excludes them
            # from the rendezvous) but nothing FAILED — workers_lost
            # stays untouched so the metric keeps meaning real failures.
            exc = HostsUpdatedError(epoch=info.get("epoch", 0),
                                    lost_pids=info.get("lost_pids", ()))
        else:
            lost = list(info.get("lost_pids", ()))
            exc = WorkerLostError(lost_pids=lost,
                                  epoch=info.get("epoch", 0))
            metrics.ELASTIC_WORKERS_LOST.inc(max(len(lost), 1))
        self._elastic_abort = exc
        # Membership-scoped caches die with the membership: a response
        # validated against the dead participant set must re-validate in
        # the rebuilt session, and a compiled wire program for the old
        # participants must never run again (its digest already excludes
        # it from the new engine's keys). The builder lru tier is
        # cleared too — it holds the executables keyed by the now-dead
        # Mesh, and without this each recovery would leak a meshful of
        # compiled programs for process lifetime.
        self._response_cache.clear()
        self._wire_cache.invalidate()
        self._step_cache.invalidate()
        _clear_wire_program_builders()
        self._dev_pending.clear()
        for h, v in list(self._handles.items()):
            if isinstance(v, str):
                self._handles[h] = exc
        for name in self._table:
            self.timeline.negotiate_end(name)
        self._table.clear()
        self._first_seen.clear()
        self._stall_warned.clear()
        self._pending_bytes = 0
        fr = self._flight
        if fr is not None:
            fr.record("abort", type(exc).__name__,
                      extra={"kind": info.get("kind", "worker_lost"),
                             "epoch": info.get("epoch", 0),
                             "lost_pids": list(info.get("lost_pids", ()))})
        # Every worker loss leaves a durable post-mortem (gated on
        # diagnostics being configured — see diag.dump_post_mortem).
        diag.dump_post_mortem("abort", extra={
            "abort_kind": info.get("kind", "worker_lost"),
            "abort_epoch": info.get("epoch", 0),
            "lost_pids": list(info.get("lost_pids", ()))})
        _logger.error("elastic abort (epoch %s): %s",
                      info.get("epoch", 0), exc)

    def _entries_from_decision_locked(self, tensors):
        """Turn decided per-name records into executable entries (shared
        by the fetched-decision path and the local-replay fast lane)."""
        entries = []
        for t in tensors:
            name = t["name"]
            pend = self._table.get(name)
            if pend is None:
                # decided before we ever submitted — cannot happen for
                # ready tensors (readiness requires all ranks), but be
                # defensive against replays
                continue
            # Error decisions deliver unconditionally: the coordinator
            # fails a name globally (reference: an error Response reaches
            # every rank, operations.cc:325-527), and a mismatch means
            # per-rank metadata NEVER agrees with the echoed first-rank
            # metadata — running the staleness guard on them would strand
            # the mismatching side's handles until the stall deadline.
            if t["error"]:
                self._table.pop(name)
                self._first_seen.pop(name, None)
                reqs = [pend[r] for r in sorted(pend)]
                self._pending_bytes -= sum(r.tensor.nbytes for r in reqs)
                self.timeline.negotiate_end(name)
                exc = MismatchError(t["error"])
                for r in reqs:
                    self._handles[r.handle] = exc
                continue
            # Staleness guard: a backlogged decision (made from an older
            # publish while this process fast-laned) must not execute a
            # later submission that happens to reuse the name with
            # different metadata — mismatched op, dtype, or shape
            # (advisor r4: op alone let a same-op reshape execute against
            # the wrong-generation tensor), or allgather sizes that
            # contradict the local tensors, mark the decision stale for
            # this name; the fresh decision follows in the log.
            reqs_probe = list(pend.values())
            if reqs_probe:
                meta0 = reqs_probe[0].meta()
                if meta0.op != t["op"]:
                    continue
                if (t.get("dtype") is not None
                        and meta0.dtype != t["dtype"]):
                    continue
                tshape = t.get("shape")
                if tshape is not None:
                    if t["op"] == ALLGATHER:
                        # ranks legitimately differ in dim 0
                        if list(meta0.shape[1:]) != list(tshape[1:]):
                            continue
                    elif list(meta0.shape) != list(tshape):
                        continue
            if t.get("sizes") is not None and any(
                    int(r.tensor.shape[0]) != t["sizes"][r.rank]
                    for r in reqs_probe):
                continue
            self._table.pop(name)
            self._first_seen.pop(name, None)
            reqs = [pend[r] for r in sorted(pend)]
            self._pending_bytes -= sum(r.tensor.nbytes for r in reqs)
            self.timeline.negotiate_end(name)
            entry = _Entry(name, t["op"], pend)
            entry.sizes = t.get("sizes")
            entries.append((entry, False))
        return entries

    def publish_autotune(self, fusion, cycle, padding, depth=None):
        """Multi-host ParameterManager hook: route tuned parameters through
        the decision log instead of mutating config locally (reference:
        SyncParams, parameter_manager.cc:223-262)."""
        self._coord.append_autotune(fusion, cycle, padding, depth)

    def publish_guard(self, verdict):
        """Guard decision-log hook (multi-host): record a non-apply step
        verdict in the coordinator's log. Advisory — ranks act on their
        locally-computed (bit-identical) verdicts; the log entry is the
        auditable proof they agreed (guard.GuardMonitor.apply_decision)."""
        self._coord.append_guard(verdict)

    def _construct_response(self, name, reqs):
        """Cross-rank consistency validation; returns an error string or None.

        Message wording parity: ConstructResponse
        (reference: operations.cc:325-527). "MPI operations" stays in the
        dtype-op mismatch text because reference tests assert on it.
        """
        first = reqs[0]
        for r in reqs[1:]:
            if r.tensor.dtype != first.tensor.dtype:
                return (f"Mismatched data types: One rank had type "
                        f"{_dtype_name(first.tensor.dtype)}, but another rank "
                        f"had type {_dtype_name(r.tensor.dtype)}.")
        for r in reqs[1:]:
            if r.op != first.op:
                return (f"Mismatched MPI operations: One rank did an "
                        f"{first.op.lower()}, but another rank did an "
                        f"{r.op.lower()}.")
        if first.op in (ALLREDUCE, BROADCAST):
            for r in reqs[1:]:
                if r.tensor.shape != first.tensor.shape:
                    return (f"Mismatched {first.op.lower()} tensor shapes: "
                            f"One rank sent a tensor of shape "
                            f"{_shape_str(first.tensor.shape)}, but another "
                            f"rank sent a tensor of shape "
                            f"{_shape_str(r.tensor.shape)}.")
        if first.op == ALLGATHER:
            if first.tensor.ndim == 0:
                return (f"Rank zero tried to {first.op.lower()} a rank-zero "
                        f"tensor.")
            for r in reqs[1:]:
                if r.tensor.ndim != first.tensor.ndim:
                    return (f"Mismatched {first.op.lower()} tensor shapes: "
                            f"One rank sent a tensor of rank "
                            f"{first.tensor.ndim}, but another rank sent a "
                            f"tensor of rank {r.tensor.ndim}.")
                for dim in range(1, first.tensor.ndim):
                    if r.tensor.shape[dim] != first.tensor.shape[dim]:
                        return (f"Mismatched {first.op.lower()} tensor "
                                f"shapes: One rank sent a tensor with "
                                f"dimension {dim} equal to "
                                f"{first.tensor.shape[dim]}, but another rank "
                                f"sent a tensor with dimension {dim} equal "
                                f"to {r.tensor.shape[dim]}.")
        if first.op == BROADCAST:
            for r in reqs[1:]:
                if r.root_rank != first.root_rank:
                    return (f"Mismatched {first.op.lower()} root ranks: One "
                            f"rank specified root rank {first.root_rank}, "
                            f"but another rank specified root rank "
                            f"{r.root_rank}.")
        if first.op == ALLTOALL:
            # No reference analog (op added post-0.16); same shape-agreement
            # contract as allreduce plus the dim-0 divisibility alltoall needs.
            for r in reqs[1:]:
                if r.tensor.shape != first.tensor.shape:
                    return (f"Mismatched {first.op.lower()} tensor shapes: "
                            f"One rank sent a tensor of shape "
                            f"{_shape_str(first.tensor.shape)}, but another "
                            f"rank sent a tensor of shape "
                            f"{_shape_str(r.tensor.shape)}.")
            if first.tensor.ndim == 0 or (
                    first.tensor.shape[0] % self.num_ranks != 0):
                return (f"alltoall tensor dimension 0 "
                        f"({first.tensor.shape[0] if first.tensor.ndim else 0}) "
                        f"must be divisible by the number of ranks "
                        f"({self.num_ranks}).")
        return None

    def _check_stalls_locked(self):
        """Warn about names stuck waiting for a subset of ranks (reference:
        CheckForStalledTensors, operations.cc:815-896)."""
        now = time.perf_counter()
        warn_after = self.config.stall_check_time_seconds
        missing_by_rank = {}
        for name, pend in self._table.items():
            if name in self._stall_warned:
                continue
            if now - self._first_seen.get(name, now) <= warn_after:
                continue
            self._stall_warned.add(name)
            # A stalled name's cached response may no longer match what the
            # missing ranks eventually submit (reference:
            # InvalidateStalledCachedTensors, operations.cc:899-913).
            self._response_cache.invalidate_name(name)
            for r in range(self.num_ranks):
                if r not in pend:
                    missing_by_rank.setdefault(r, []).append(name)
        if missing_by_rank:
            metrics.ENGINE_STALL_WARNINGS.inc()
            fr = self._flight
            if fr is not None:
                fr.record("stall_warn",
                          extra={"missing_by_rank":
                                 {str(r): n[:8] for r, n
                                  in missing_by_rank.items()}})
            msg = ["One or more tensors were submitted to be reduced, "
                   "gathered or broadcasted by subset of ranks and are "
                   f"waiting for remainder of ranks for more than "
                   f"{int(warn_after)} seconds. This may indicate that "
                   "different ranks are trying to submit different tensors or "
                   "that only subset of ranks is submitting tensors, which "
                   "will cause deadlock. \nStalled ranks:"]
            for r in sorted(missing_by_rank):
                names = missing_by_rank[r]
                shown = ", ".join(names[:6])
                if len(names) > 6:
                    shown += " ..."
                msg.append(f"\n{r}: [{shown}]")
            _logger.warning("".join(msg))

    # ------------------------------------------------------------ execution

    def _execute(self, entries):
        """Fuse + run ready entries on the mesh (reference: FuseResponses
        operations.cc:577-700 + PerformOperation operations.cc:722-812)."""
        # Single-rank worlds: every collective is mathematically the
        # identity (MPI with one rank is a no-op too) — complete on the
        # host without any device round-trip. Compression still does its
        # lossy wire-dtype round-trip, and stats/timeline record the op,
        # so observable behavior matches the multi-rank path.
        if self.num_ranks == 1:
            for entry, cached in entries:
                self._execute_single_rank(entry, cached)
            return
        # Group: allreduces fuse by wire dtype under the fusion threshold with
        # look-ahead past oversized/mismatched entries (the reference's
        # skipped-entries loop); allgather/broadcast/alltoall run per entry.
        # Device-resident entries (to_host=False) fuse separately — their
        # wire program carries the in-graph unfuse, so they cannot share a
        # bucket with host-readback entries.
        allreduces = []
        dev_allreduces = []
        singles = []
        for entry, cached in entries:
            if entry.op == ALLREDUCE:
                if self._entry_device_resident(entry):
                    dev_allreduces.append((entry, cached,
                                           self._wire_dtype(entry)))
                else:
                    allreduces.append((entry, cached,
                                       self._wire_dtype(entry)))
            else:
                singles.append((entry, cached))
        for batch, wire in self._plan_fusion(allreduces):
            self._execute_allreduce_fused_locked(batch, wire)
        for batch, wire in self._plan_fusion(dev_allreduces):
            self._execute_allreduce_fused_device_locked(batch, wire)
        for entry, cached in singles:
            if entry.op == ALLGATHER:
                self._execute_allgather(entry, cached)
            elif entry.op == BROADCAST:
                self._execute_broadcast(entry, cached)
            elif entry.op == ALLTOALL:
                self._execute_alltoall(entry, cached)

    def _execute_single_rank(self, entry, cached):
        """Identity completion for a 1-rank world (no device round-trip)."""
        name = entry.name
        self.timeline.start(name, entry.op)
        (rank, req), = entry.requests.items()
        out = req.tensor
        stat = entry.op.lower()
        if entry.op == ALLREDUCE:
            stat = "allreduce_cached" if cached else "allreduce"
            wire = self._wire_dtype(entry)
            if req.prescale is not None:
                out = out * req.prescale
            if np.dtype(wire) != out.dtype:
                # the lossy compression round-trip still applies on 1 rank
                out = out.astype(wire)
            out = out.astype(entry.dtype, copy=True)
            if req.postscale is not None:
                out = (out * req.postscale).astype(entry.dtype, copy=False)
            if self.autotuner is not None:
                self.autotuner.record_bytes(
                    out.size * np.dtype(wire).itemsize)
        else:
            out = np.array(out, dtype=entry.dtype, copy=True)
        if (entry.op == ALLREDUCE and not req.to_host
                and self._device_resident_enabled()):
            # Device-resident contract holds at world size 1 too: the
            # caller gets a device array it can feed a jitted apply.
            # Routed through the wire-program cache (a trivial jitted
            # identity) so single-device jobs exercise — and report —
            # the same signature-cache machinery as real meshes.
            with self._x64_scope(entry.dtype):
                sig = ("identity", str(np.dtype(entry.dtype)),
                       tuple(int(s) for s in np.shape(out)))
                prog = self._wire_cache.get(
                    sig, lambda: jax.jit(lambda x: x))
                out = prog(np.ascontiguousarray(out))
        with self.stats.timer(stat, req.tensor.nbytes):
            pass
        self._complete_locked(req.handle, rank, out)
        self.timeline.end(name)

    def _plan_fusion(self, allreduces):
        """Partition ready allreduces into fused batches under the fusion
        threshold (reference: FuseResponses, operations.cc:577-700).

        With the native library, the C++ planner (csrc/fusion.cc) assigns
        groups with the reference's same-dtype look-ahead; the fallback is a
        simple per-dtype sequential split.
        """
        if not allreduces:
            return []
        if self._native_lib is not None and len(allreduces) > 1:
            import ctypes
            n = len(allreduces)
            dtype_ids = {}
            nb = (ctypes.c_int64 * n)(*[e.nbytes for e, _, _ in allreduces])
            dt = (ctypes.c_int32 * n)(
                *[dtype_ids.setdefault(str(w), len(dtype_ids))
                  for _, _, w in allreduces])
            groups = (ctypes.c_int32 * n)()
            ngroups = self._native_lib.hvd_fusion_plan(
                nb, dt, n, int(self.config.fusion_threshold), groups)
            batches = [[] for _ in range(ngroups)]
            wires = [None] * ngroups
            for i, (entry, cached, wire) in enumerate(allreduces):
                batches[groups[i]].append((entry, cached))
                wires[groups[i]] = wire
            return list(zip(batches, wires))
        out = []
        by_wire = {}
        for entry, cached, wire in allreduces:
            by_wire.setdefault(wire, []).append((entry, cached))
        unit = FUSION_BUFFER_ATOMIC_UNIT
        for wire, group in by_wire.items():
            batch, batch_bytes = [], 0
            for item in group:
                # each entry charges its atomic-unit-aligned footprint
                # against the threshold, like the native planner
                # (csrc/fusion.cc::AlignUp; reference operations.h:30)
                nbytes = -(-item[0].nbytes // unit) * unit
                if batch and (batch_bytes + nbytes
                              > self.config.fusion_threshold):
                    out.append((batch, wire))
                    batch, batch_bytes = [], 0
                batch.append(item)
                batch_bytes += nbytes
            if batch:
                out.append((batch, wire))
        return out

    def _wire_dtype(self, entry):
        req = entry.requests[min(entry.requests)]
        if req.compression is not None:
            wd = getattr(req.compression, "wire_dtype", None)
            if wd is not None:
                return np.dtype(wd(entry.dtype))
            # custom compressor without the optional wire_dtype protocol
            # (ops/compression.py): probe by compressing a zero scalar
            probe, _ = req.compression.compress(jnp.zeros((), entry.dtype))
            return probe.dtype
        return entry.dtype

    def _device_resident_enabled(self):
        """HOROVOD_DEVICE_RESIDENT: -1 auto / 1 on (fast path serves
        opted-in callers), 0 = exact legacy behavior (to_host ignored)."""
        return self.config.device_resident != 0

    def _entry_device_resident(self, entry):
        """Whether this allreduce rides the device-resident wire program:
        every locally-owned request opted in (to_host=False) and shares
        the scalar knobs the in-graph unfuse bakes in statically. The
        hierarchical decomposition keeps the host path (its wire program
        predates the unfuse extension; flat meshes are where the
        readback cost lives)."""
        if not self._device_resident_enabled():
            return False
        if self.config.hierarchical_allreduce and self._hier_mesh is not None:
            return False
        reqs = list(entry.requests.values())
        first = reqs[0]
        return all(not r.to_host
                   and r.average == first.average
                   and r.postscale == first.postscale for r in reqs)

    def _fused_nelem(self, counts, binned=False):
        """Total fused element count, honoring alignment and the fork's
        power-of-two padding experiment (PADDING_ALGO=1,
        reference: ops/mpi_operations.cc:24-63). Under hierarchical
        allreduce the buffer is additionally rounded up to a multiple of the
        local tier size so the ICI reduce-scatter stripes evenly (the
        reference rounds its fusion threshold the same way,
        operations.cc:552-574).

        ``binned=True`` (the device-resident path) applies the
        power-of-two rounding unconditionally: the fork's padding
        experiment is load-bearing there as the wire-program cache's size
        binning — every steady-state bucket shape maps onto one cached
        executable per power-of-two class, so shape jitter cannot cause
        per-step recompiles. The autotuner's PADDING_ALGO decision keeps
        governing the host path."""
        total = sum(counts)
        if binned or self.config.padding_algo == 1:
            total = next_power_of_two(total)
        if self.config.hierarchical_allreduce and self._hier_mesh is not None:
            local = self.hier_local_size
            total = ((total + local - 1) // local) * local
        return total

    def _observe_wire(self, op, nbytes, seconds):
        """Paper-parity wire profiler feed (the fork's
        time_map_allreduce): one histogram observation per wire op,
        labeled by power-of-two message-size bin, plus the autotuner's
        largest-message guard telemetry."""
        size_bin = next_power_of_two(max(int(nbytes), 1))
        metrics.WIRE_SECONDS.labels(op=op, size_bin=str(size_bin)) \
            .observe(seconds)
        if self.autotuner is not None:
            self.autotuner.record_wire(nbytes, seconds)

    def _execute_allreduce_fused_locked(self, batch, wire_dtype):
        """Fill a pooled fusion buffer, dispatch the fused wire op, and —
        pipeline enabled — hand the un-read result to the completion
        stage instead of blocking: the next bucket fills while this one
        rides the wire (the overlap Horovod's background thread exists
        for). Depth 0 keeps the original dispatch+blocking-readback
        behavior inline."""
        for e, _ in batch:
            self.timeline.start(e.name, ALLREDUCE)
            self.timeline.activity_start(e.name, tl.MEMCPY_IN_FUSION_BUFFER)
        counts = [int(np.prod(e.requests[min(e.requests)].tensor.shape,
                              dtype=np.int64))
                  for e, _ in batch]
        offsets = np.cumsum([0] + counts)
        total = self._fused_nelem(counts)
        nbytes = total * np.dtype(wire_dtype).itemsize
        if self.config.fusion_threshold > 0:  # ratio is undefined when
            metrics.ENGINE_FUSION_FILL.observe(  # fusion is disabled
                nbytes / self.config.fusion_threshold)
        metrics.ENGINE_BUCKET_FLUSHES.inc()
        # Fill the (pooled, reused) fusion buffer: one row per locally-owned
        # rank, each row the rank's concatenated flattened tensors
        # (reference: MemcpyInFusionBuffer). Remote ranks' rows live on
        # their processes. Every payload element is written below, so only
        # the alignment/padding tail needs explicit zeroing on reuse.
        local_pos = {r: i for i, r in enumerate(self._local_ranks)}
        rows = self._acquire_rows_locked(len(self._local_ranks), total, wire_dtype)
        if total > offsets[-1]:
            rows[:, offsets[-1]:] = 0
        for i, (e, _) in enumerate(batch):
            for r, req in e.requests.items():
                flat = np.ravel(req.tensor)
                if req.prescale is not None:
                    flat = flat * req.prescale
                rows[local_pos[r],
                     offsets[i]:offsets[i + 1]] = flat.astype(wire_dtype)
        if self._inject is not None:
            # Chaos 'corrupt' injection point: SDC between fill and wire.
            rows = self._inject.on_rows(rows,
                                        tuple(e.name for e, _ in batch))
        for e, _ in batch:
            self.timeline.activity_end(e.name)
            self.timeline.activity_start(e.name, tl.XLA_ALLREDUCE)
        op_stat = ("allreduce_cached" if all(c for _, c in batch)
                   else "allreduce")
        # Post-dispatch view: everything unfuse/failure handling needs,
        # without keeping the submitted tensors alive while the bucket
        # rides the wire.
        slim = [(e.name, e.dtype,
                 tuple((r, req.handle, req.tensor.shape, req.average,
                        req.postscale) for r, req in e.requests.items()))
                for e, _ in batch]
        fr = self._flight
        if fr is not None:
            fr.record("dispatch", slim[0][0] if slim else "", "allreduce",
                      nbytes, str(wire_dtype),
                      extra={"n": len(slim),
                             "names": [n for n, _, _ in slim[:16]]})
        depth = self._pipeline_depth()
        if depth <= 0:
            # Synchronous fallback (HOROVOD_PIPELINE_DEPTH=0).
            t0 = time.perf_counter()
            with self.stats.timer(op_stat, nbytes):
                summed = np.asarray(self._guarded_wire(
                    lambda: self._dispatch_allreduce(rows), "allreduce"))
            span = time.perf_counter() - t0
            self._observe_wire("allreduce", nbytes, span)
            if fr is not None:
                fr.record("wire_end", slim[0][0] if slim else "",
                          "allreduce", nbytes,
                          extra={"span": span, "wait": span, "hidden": 0.0,
                                 "n": len(slim)})
            self._scatter_fused_results(slim, offsets, summed, wire_dtype,
                                        counts)
            self._release_rows_locked(rows)
            return
        # Profiler stats for the pipelined path record at COMPLETION
        # (dispatch->ready, the same wire-op span the pre-pipeline timer
        # measured) — timing just the non-blocking dispatch here would
        # collapse the allreduce slot to enqueue cost.
        out = self._guarded_wire(lambda: self._dispatch_allreduce(rows),
                                 "allreduce")
        try:
            # Start the device->host copy NOW: by the time a completer
            # blocks, the transfer has ridden behind compute (deferred
            # readback — the bench's 74 ms/step blocking-fetch killer).
            out.copy_to_host_async()
        except Exception:  # noqa: BLE001 — optional backend fast path
            pass
        rec = _InFlight(slim, offsets, counts, out, wire_dtype, rows,
                        op_stat, nbytes)
        for _, _, reqs in slim:
            for _, handle, _, _, _ in reqs:
                if self._handles.get(handle) == "pending":
                    self._handles[handle] = "inflight"
        self._inflight.append(rec)
        metrics.ENGINE_INFLIGHT_DEPTH.set(len(self._inflight))
        metrics.ENGINE_INFLIGHT_DEPTH_HIST.observe(len(self._inflight))
        self._ensure_completion_thread()
        self._cv.notify_all()
        # Backpressure: never run more than `depth` buckets ahead — drain
        # the oldest inline (this is where a too-deep pipeline would
        # otherwise hoard host+device buffers without bound).
        while len(self._inflight) > depth:
            self._complete_inflight(self._inflight.popleft())

    def _execute_allreduce_fused_device_locked(self, batch, wire_dtype):
        """Device-resident fused allreduce (the ISSUE-5 tentpole): fill
        the pooled fusion buffer exactly like the host path, then run ONE
        jitted wire program that psums the fused rows AND slices/casts/
        averages every per-tensor result out of the summed row in-graph
        (ops/collectives.unfuse_segments). The outputs are replicated jax
        device arrays handed to the handles immediately — dispatch IS
        completion, there is no readback stage, no in-flight record, and
        ``synchronize()`` returns as soon as the dispatch lands. The
        optimizer apply (or any jitted consumer) reads them on device;
        the host round-trip the pipeline could only *hide* is gone
        entirely."""
        for e, _ in batch:
            self.timeline.start(e.name, ALLREDUCE)
            self.timeline.activity_start(e.name, tl.MEMCPY_IN_FUSION_BUFFER)
        counts = [int(np.prod(e.requests[min(e.requests)].tensor.shape,
                              dtype=np.int64))
                  for e, _ in batch]
        offsets = np.cumsum([0] + counts)
        # binned=True: power-of-two size binning is load-bearing for the
        # wire-program cache (one executable per bucket shape class).
        total = self._fused_nelem(counts, binned=True)
        nbytes = total * np.dtype(wire_dtype).itemsize
        if self.config.fusion_threshold > 0:
            metrics.ENGINE_FUSION_FILL.observe(
                nbytes / self.config.fusion_threshold)
        metrics.ENGINE_BUCKET_FLUSHES.inc()
        metrics.ENGINE_DEVICE_BUCKETS.inc()
        local_pos = {r: i for i, r in enumerate(self._local_ranks)}
        self._reap_device_rows_locked()
        rows = self._acquire_rows_locked(len(self._local_ranks), total, wire_dtype)
        if total > offsets[-1]:
            rows[:, offsets[-1]:] = 0
        segs = []
        for i, (e, _) in enumerate(batch):
            req0 = e.requests[min(e.requests)]
            for r, req in e.requests.items():
                flat = np.ravel(req.tensor)
                if req.prescale is not None:
                    flat = flat * req.prescale
                rows[local_pos[r],
                     offsets[i]:offsets[i + 1]] = flat.astype(wire_dtype)
            segs.append((int(offsets[i]), int(counts[i]),
                         tuple(int(s) for s in req0.tensor.shape),
                         np.dtype(e.dtype), bool(req0.average),
                         None if req0.postscale is None
                         else float(req0.postscale)))
        segs = tuple(segs)
        if self._inject is not None:
            # Chaos 'corrupt' injection point: SDC between fill and wire.
            rows = self._inject.on_rows(rows,
                                        tuple(e.name for e, _ in batch))
        for e, _ in batch:
            self.timeline.activity_end(e.name)
            self.timeline.activity_start(e.name, tl.XLA_ALLREDUCE)
        op_stat = ("allreduce_cached" if all(c for _, c in batch)
                   else "allreduce")
        g = self._guard
        t0 = time.perf_counter()
        # Profiler slot records the (non-blocking) dispatch span: the
        # zero-readback contract means nothing ever waits for the wire
        # here. HOROVOD_WIRE_PROFILE=1 additionally measures the true
        # wire span below by blocking once — profiling mode explicitly
        # trades the zero-sync property for the measurement.
        with self.stats.timer(op_stat, nbytes):
            outs = self._guarded_wire(
                lambda: self._dispatch_allreduce_device(
                    rows, segs, with_health=g is not None), "allreduce")
        if g is not None:
            # The extra output is the in-graph [finite, l2] health row
            # per segment (collectives.segment_health): hand it to the
            # monitor un-read — it stays a device array until end_step(),
            # preserving the zero-readback hot loop.
            outs, health = outs[:-1], outs[-1]
            g.note_device_health([e.name for e, _ in batch], health)
        # Flight recorder, zero-readback contract intact: one lock-free
        # tuple store recording the dispatch (which IS completion here).
        fr = self._flight
        if fr is not None:
            fr.record("device_dispatch", batch[0][0].name, "allreduce",
                      nbytes, str(wire_dtype),
                      extra={"n": len(batch),
                             "enqueue_s": time.perf_counter() - t0})
        for i, (e, _) in enumerate(batch):
            for r, req in e.requests.items():
                self._complete_locked(req.handle, r, outs[i])
            self.timeline.activity_end(e.name)
            self.timeline.end(e.name)
        if self.autotuner is not None:
            self.autotuner.record_bytes(sum(counts)
                                        * np.dtype(wire_dtype).itemsize)
        if self.config.wire_profile:
            jax.block_until_ready(outs)
            span = time.perf_counter() - t0
            self._observe_wire("allreduce", nbytes, span)
            if fr is not None:
                fr.record("wire_end", batch[0][0].name, "allreduce", nbytes,
                          extra={"span": span, "wait": 0.0, "hidden": span,
                                 "n": len(batch)})
            self._release_rows_locked(rows)
        else:
            # The fusion buffer may still be aliased by the in-flight
            # program (CPU zero-copy device_put); pool it back only once
            # the program's outputs are ready (_reap_device_rows_locked).
            self._dev_pending.append((outs[0] if outs else None, rows))

    def _reap_device_rows_locked(self):
        """Return device-bucket fusion buffers to the pool once their
        wire program completed — non-blocking (`jax.Array.is_ready`), so
        the zero-readback hot loop never waits here. Bounded: buffers
        stuck behind a slow program past a small window are dropped to
        the allocator instead of pooled (correct either way; pooling is
        an optimization)."""
        while self._dev_pending:
            out, rows = self._dev_pending[0]
            try:
                ready = out is None or out.is_ready()
            except Exception:  # noqa: BLE001 — backend without is_ready
                ready = True
            if ready:
                self._dev_pending.popleft()
                self._release_rows_locked(rows)
            elif len(self._dev_pending) > 8:
                self._dev_pending.popleft()  # drop, don't pool
            else:
                break

    def _dispatch_allreduce_device(self, rows, segs, with_health=False):
        """Launch the fused psum+unfuse wire program via the signature
        cache. The signature — (op, wire dtype, padded rows shape, the
        static per-tensor segment layout, donate) plus the cache's
        participants digest — is exactly what determines the compiled
        executable, so steady-state training hits one cached program per
        power-of-two bucket class. ``with_health=True`` (guard enabled)
        selects the variant that also emits the in-graph per-segment
        health digest as one extra output — a distinct signature, so
        toggling the guard never invalidates the plain program."""
        # The scope covers 8-byte OUTPUT dtypes too (the host path casts
        # in numpy and never needs this for outputs).
        with self._x64_scope(rows.dtype, *(s[3] for s in segs)):
            arr = self._put_rows(rows)
            if with_health:
                sig = ("psum_unfuse_health", str(arr.dtype),
                       tuple(arr.shape), segs, self._donate)
                prog = self._wire_cache.get(
                    sig, lambda: _jit_psum_unfuse_health(
                        self.mesh, str(arr.dtype), tuple(arr.shape), segs,
                        self.num_ranks, self._donate))
                return prog(arr)
            sig = ("psum_unfuse", str(arr.dtype), tuple(arr.shape), segs,
                   self._donate)
            prog = self._wire_cache.get(
                sig, lambda: _jit_psum_unfuse(self.mesh, str(arr.dtype),
                                              tuple(arr.shape), segs,
                                              self.num_ranks, self._donate))
            return prog(arr)

    def _scatter_fused_results(self, batch, offsets, summed, wire_dtype,
                               counts):
        """Unfuse a completed wire buffer back into per-handle results
        (reference: MemcpyOutFusionBuffer). ``batch`` is the slim
        post-dispatch view built at dispatch. Caller holds the engine
        lock — runs from the dispatching thread (sync mode), the
        completion thread, or a synchronize() drain."""
        for name, _, _ in batch:
            self.timeline.activity_end(name)
            self.timeline.activity_start(name, tl.MEMCPY_OUT_FUSION_BUFFER)
        g = self._guard
        for i, (name, dtype, reqs) in enumerate(batch):
            seg = summed[offsets[i]:offsets[i + 1]]
            if g is not None and np.issubdtype(seg.dtype, np.floating):
                # Host-path gradient health, computed on the REDUCED
                # buffer — bit-identical on every rank, so every rank's
                # verdict is too (no coordination needed, guard/).
                mask = np.isfinite(seg)
                finite = bool(mask.all())
                g.note_bucket(name, finite,
                              float(np.linalg.norm(seg if finite
                                                   else seg[mask])))
            for r, handle, shape, average, postscale in reqs:
                out = seg.astype(dtype, copy=True).reshape(shape)
                if average:
                    out = out / self.num_ranks if jnp.issubdtype(
                        dtype, jnp.floating) else out // self.num_ranks
                    out = out.astype(dtype, copy=False)
                if postscale is not None:
                    out = (out * postscale).astype(dtype, copy=False)
                self._complete_locked(handle, r, out)
            self.timeline.activity_end(name)
            self.timeline.end(name)
        if self.autotuner is not None:
            self.autotuner.record_bytes(sum(counts)
                                        * np.dtype(wire_dtype).itemsize)

    @staticmethod
    def _x64_scope(*dtypes):
        """64-bit dtypes (float64/int64/uint64) anywhere in the program —
        wire OR output (a bf16-wire bucket decompressing back to float64)
        — need JAX's x64 mode or the device program silently downcasts
        them; the reference carries every MPI dtype at full width
        (mpi_context.h:26-53). Scoped, not global: user jit code keeps
        the JAX default."""
        if any(np.dtype(d).itemsize == 8 for d in dtypes):
            return jax.enable_x64()
        return contextlib.nullcontext()

    def _guarded_wire(self, dispatch, op):
        """Run one wire dispatch under the guard layer's chaos-injection
        and bounded-retry policy (docs/robustness.md). With injection off
        and ``HOROVOD_GUARD_RETRY=0`` (the defaults) this is exactly
        ``dispatch()`` behind one None check and a try that never fires.

        Retryable: :class:`TransientCollectiveError` (injected chaos, or
        anything a wrapper classified as transient) and raw backend
        ``RuntimeError``/``OSError`` from the dispatch itself. Protocol
        errors (mismatch, shutdown, worker-lost — all other
        HorovodErrors) propagate immediately: retrying those can only
        desync. Exponential backoff from
        ``HOROVOD_GUARD_RETRY_BASE_SECONDS`` under the
        ``HOROVOD_GUARD_RETRY_DEADLINE_SECONDS`` deadline; exhaustion
        re-raises the last error into the normal abort path."""
        retries = int(getattr(self.config, "guard_retry", 0))
        deadline = time.monotonic() + float(
            getattr(self.config, "guard_retry_deadline_seconds", 30.0))
        base = float(getattr(self.config, "guard_retry_base_seconds", 0.05))
        attempt = 0
        while True:
            try:
                if self._inject is not None:
                    # 'fail'/'delay' chaos fires per attempt, so its
                    # occurrence counter advances across retries and a
                    # count=1 fault costs exactly one retry.
                    self._inject.on_dispatch(op)
                return dispatch()
            except HorovodError as err:
                if not isinstance(err, TransientCollectiveError):
                    raise
                last = err
            except (RuntimeError, OSError) as err:
                last = err
            attempt += 1
            now = time.monotonic()
            if retries <= 0 or attempt > retries or now >= deadline:
                raise last
            delay = min(base * (2 ** (attempt - 1)),
                        max(deadline - now, 0.0))
            metrics.GUARD_RETRIES.inc()
            fr = self._flight
            if fr is not None:
                fr.record("guard_retry", "", op,
                          extra={"attempt": attempt, "delay_s": delay,
                                 "error": str(last)[:200]})
            _logger.warning(
                "guard: transient %s dispatch failure (attempt %d/%d), "
                "retrying in %.3fs: %s", op, attempt, retries, delay, last)
            time.sleep(delay)

    def _put_rows(self, local_rows):
        """This process's rank rows -> the global (num_ranks, ...) array,
        one row per device (works identically single- and multi-process)."""
        sharding = self._row_sharding
        return jax.make_array_from_process_local_data(
            sharding, local_rows,
            (self.num_ranks,) + tuple(local_rows.shape[1:]))

    def _dispatch_allreduce(self, rows):
        """Enqueue one XLA all-reduce over the mesh WITHOUT blocking: row r
        lives on device r; psum rides ICI. Returns the un-materialized
        device result (readback is the completion stage's job). This is
        the wire op the reference delegates to MPI_Allreduce /
        ncclAllReduce (mpi_operations.cc:92-111, nccl_operations.cc:
        115-175). With HOROVOD_HIERARCHICAL_ALLREDUCE on a two-tier
        topology, the wire program is instead the reference's three-stage
        decomposition (nccl_operations.cc:258-485): reduce-scatter(local)
        -> allreduce(cross) -> allgather(local). The fusion buffer's
        device array is donated to the program where the backend supports
        aliasing, eliminating the separate output allocation."""
        with self._x64_scope(rows.dtype):
            if (self.config.hierarchical_allreduce
                    and self._hier_mesh is not None):
                arr = self._put_rows_hier(rows)
                prog = self._wire_cache.get(
                    ("psum_hier", str(arr.dtype), tuple(arr.shape),
                     self._donate),
                    lambda: _jit_psum_rows_hier(self._hier_mesh,
                                                self._hier_axes, arr.dtype,
                                                arr.shape, self._donate))
                return prog(arr)
            arr = self._put_rows(rows)
            prog = self._wire_cache.get(
                ("psum", str(arr.dtype), tuple(arr.shape), self._donate),
                lambda: _jit_psum_rows(self.mesh, arr.dtype, arr.shape,
                                       self._donate))
            return prog(arr)

    def _device_allreduce(self, rows):
        """Blocking wire op: dispatch + readback (kept for the synchronous
        callers/tests; the pipeline uses the split stages directly)."""
        return np.asarray(self._dispatch_allreduce(rows))

    def _put_rows_hier(self, local_rows):
        """Rank rows -> the (num_ranks, ...) global array over the 2-D
        (cross, local) mesh; rank r's row on device (r // local, r % local)."""
        cross_ax, local_ax = self._hier_mesh.axis_names
        sharding = NamedSharding(self._hier_mesh, P((cross_ax, local_ax)))
        return jax.make_array_from_process_local_data(
            sharding, local_rows,
            (self.num_ranks,) + tuple(local_rows.shape[1:]))

    def _execute_allgather(self, entry, cached):
        """Varying-dim-0 allgather: pad every rank's block to the max dim-0,
        run one XLA all-gather, slice the real rows back out (the reference
        sizes the output from negotiated per-rank dims and uses
        MPI_Allgatherv; collective_operations.cc:68-135)."""
        name = entry.name
        self.timeline.start(name, ALLGATHER)
        reqs = [entry.requests[r] for r in sorted(entry.requests)]
        # Per-rank dim-0 sizes: negotiated globally in multi-host mode
        # (decision carries them, like the reference's Response tensor_sizes);
        # derivable locally when every rank is in-process.
        dims0 = (entry.sizes if entry.sizes is not None
                 else [int(r.tensor.shape[0]) for r in reqs])
        maxd = max(dims0)
        rest = reqs[0].tensor.shape[1:]
        rows = np.zeros((len(self._local_ranks), maxd) + tuple(rest),
                        dtype=entry.dtype)
        local_pos = {r: i for i, r in enumerate(self._local_ranks)}
        for r_id, req in entry.requests.items():
            rows[local_pos[r_id], :req.tensor.shape[0]] = req.tensor
        self.timeline.activity_start(name, tl.XLA_ALLGATHER)
        t0 = time.perf_counter()
        with self.stats.timer("allgather", rows.nbytes), \
                self._x64_scope(rows.dtype):
            if (self.config.hierarchical_allgather
                    and self._hier_mesh is not None):
                arr = self._put_rows_hier(rows)
                prog = self._wire_cache.get(
                    ("allgather_hier", str(arr.dtype), tuple(arr.shape)),
                    lambda: _jit_allgather_rows_hier(
                        self._hier_mesh, self._hier_axes, arr.dtype,
                        arr.shape))
                gathered = np.asarray(prog(arr))
            else:
                arr = self._put_rows(rows)
                prog = self._wire_cache.get(
                    ("allgather", str(arr.dtype), tuple(arr.shape)),
                    lambda: _jit_allgather_rows(self.mesh, arr.dtype,
                                                arr.shape))
                gathered = np.asarray(prog(arr))
        span = time.perf_counter() - t0
        self._observe_wire("allgather", rows.nbytes, span)
        fr = self._flight
        if fr is not None:
            fr.record("wire_end", name, "allgather", rows.nbytes,
                      extra={"span": span, "wait": span, "hidden": 0.0})
        self.timeline.activity_end(name)
        pieces = [gathered[i, :dims0[i]] for i in range(self.num_ranks)]
        out = np.concatenate(pieces, axis=0)
        for r in sorted(entry.requests):
            self._complete_locked(entry.requests[r].handle, r, out.copy())
        self.timeline.end(name)

    def _execute_broadcast(self, entry, cached):
        """Root's tensor to every rank via a psum of pre-zeroed rows on the
        mesh (reference: MPIBroadcast, mpi_operations.cc:396-449).

        Non-root rows are zeros built host-side — only root's tensor is
        memcpy'd into the buffer, so broadcast_parameters of a large model
        pays one host copy, not one per local rank. The wire cost is one
        psum (reduce-scatter + all-gather ≈ 2x payload on ICI): XLA has no
        root-sourced broadcast primitive at shard_map level, and the
        dense-collective alternatives (all_gather-and-index, alltoall
        scatter + all_gather) move the same or more bytes — measured in
        bench_eager.py, documented in docs/benchmarks.md.
        """
        name = entry.name
        self.timeline.start(name, BROADCAST)
        reqs = [entry.requests[r] for r in sorted(entry.requests)]
        root = reqs[0].root_rank
        work_dtype = np.dtype(entry.dtype)
        cast = work_dtype == np.bool_
        if cast:
            work_dtype = np.dtype(np.int32)
        shape = reqs[0].tensor.shape
        rows = np.zeros((len(self._local_ranks),) + tuple(shape), work_dtype)
        local_pos = {r: i for i, r in enumerate(self._local_ranks)}
        if root in entry.requests:
            rows[local_pos[root]] = entry.requests[root].tensor.astype(
                work_dtype, copy=False)
        self.timeline.activity_start(name, tl.XLA_BCAST)
        t0 = time.perf_counter()
        with self.stats.timer("broadcast", reqs[0].tensor.nbytes), \
                self._x64_scope(rows.dtype):
            arr = self._put_rows(rows)
            prog = self._wire_cache.get(
                ("broadcast", str(arr.dtype), tuple(arr.shape)),
                lambda: _jit_broadcast_rows(self.mesh, arr.dtype, arr.shape))
            out = np.asarray(prog(arr))
        span = time.perf_counter() - t0
        self._observe_wire("broadcast", reqs[0].tensor.nbytes, span)
        fr = self._flight
        if fr is not None:
            fr.record("wire_end", name, "broadcast", reqs[0].tensor.nbytes,
                      extra={"span": span, "wait": span, "hidden": 0.0})
        self.timeline.activity_end(name)
        if cast:
            out = out.astype(np.bool_)
        for r in sorted(entry.requests):
            self._complete_locked(entry.requests[r].handle, r,
                           out.astype(entry.dtype, copy=True))
        self.timeline.end(name)

    def _execute_alltoall(self, entry, cached):
        """Each rank scatters dim-0 slices to peers (no reference equivalent
        pre-0.20; see ops/collectives.py:alltoall)."""
        name = entry.name
        self.timeline.start(name, ALLTOALL)
        reqs = [entry.requests[r] for r in sorted(entry.requests)]
        rows = np.stack([r.tensor for r in reqs])  # local ranks, sorted
        t0 = time.perf_counter()
        with self.stats.timer("alltoall", rows.nbytes), \
                self._x64_scope(rows.dtype):
            arr = self._put_rows(rows)
            prog = self._wire_cache.get(
                ("alltoall", str(arr.dtype), tuple(arr.shape)),
                lambda: _jit_alltoall_rows(self.mesh, arr.dtype, arr.shape))
            out = prog(arr)
            # Output is per-rank (sharded); read back locally-owned rows.
            for shard in out.addressable_shards:
                r = shard.index[0].start or 0
                if r in entry.requests:
                    self._complete_locked(entry.requests[r].handle, r,
                                   np.asarray(shard.data)[0].copy())
        span = time.perf_counter() - t0
        self._observe_wire("alltoall", rows.nbytes, span)
        fr = self._flight
        if fr is not None:
            fr.record("wire_end", name, "alltoall", rows.nbytes,
                      extra={"span": span, "wait": span, "hidden": 0.0})
        self.timeline.end(name)

    def _complete_locked(self, handle, rank, result):
        prev = self._handles.get(handle)
        if isinstance(prev, str):
            self._handles[handle] = {rank: result}
        elif isinstance(prev, dict):
            prev[rank] = result
        self._cv.notify_all()


# --------------------------------------------------------------------------
# Jitted wire programs, cached per (mesh, dtype, shape). Compiles once per
# fused-buffer shape — the same compile-count economics as the reference's
# persistent fusion buffer. The engine's WireProgramCache fronts these with
# membership-scoped keys and hit/miss accounting; this tier persists across
# ordinary re-inits (same Mesh hash => no recompile) and is cleared as a
# whole on elastic aborts, where its Mesh keys are dead.

_EXTRA_BUILDERS = []


def register_wire_program_builder(fn):
    """Register an out-of-module lru_cache'd jit builder whose compiled
    programs embed a Mesh in their cache key, so elastic aborts clear it
    along with the engine's own builders (ops/step_program.py registers
    its step builder plus the zero3 stripe shard/unshard converters here
    — keeps the clear list from hardcoding every consumer module; their
    signatures carry the ZeRO layout via the hashable ``zmeta`` tuple
    and the per-object ``_ZeroCore``, so a changed stage/topology is a
    different program, never a stale hit). Returns ``fn`` so it can be
    used as a decorator."""
    if fn not in _EXTRA_BUILDERS:
        _EXTRA_BUILDERS.append(fn)
    return fn


def _clear_wire_program_builders():
    """Drop every builder-tier compiled program (elastic abort path): the
    lru keys embed the dead membership's Mesh objects, so without this
    each recovery would pin up to 256 executables per builder forever."""
    for fn in (_jit_psum_rows, _jit_psum_unfuse, _jit_psum_unfuse_health,
               _jit_psum_rows_hier, _jit_allgather_rows_hier,
               _jit_allgather_rows, _jit_broadcast_rows, _jit_alltoall_rows,
               *_EXTRA_BUILDERS):
        fn.cache_clear()


@functools.lru_cache(maxsize=256)
def _jit_psum_rows(mesh, dtype, shape, donate=False):
    axis = mesh.axis_names[0]

    def per_shard(x):  # x: (1, L) on each device
        with jax.named_scope("hvd_exchange"):
            return lax.psum(x, axis)

    # Replicated output (every shard holds the sum row) so the result is
    # fully addressable on every process in multi-host runs. Donation lets
    # XLA alias the per-device (1, L) input shard with the (1, L) output —
    # the fused update runs in place instead of copying (falls back
    # harmlessly where the backend can't alias).
    f = jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                              out_specs=P(None), check_vma=False),
                donate_argnums=(0,) if donate else ())

    def run(arr):
        return f(arr)[0]

    return run


@functools.lru_cache(maxsize=256)
def _jit_psum_unfuse(mesh, dtype, shape, segs, num_ranks, donate=False):
    """Device-resident fused allreduce wire program (ISSUE-5 tentpole):
    psum the fused rows AND unfuse every per-tensor result — slice, cast
    back from the wire dtype (the in-graph decompress), average,
    postscale, reshape — inside the same jitted program, returning a
    tuple of replicated device arrays. Nothing downstream of the psum
    ever touches the host; the engine hands these arrays to the handles
    at dispatch time. ``segs`` is the static (offset, count, shape,
    dtype, average, postscale) layout; it is part of the compile key, so
    a steady-state training loop (same tensors every step) compiles this
    exactly once per power-of-two bucket class."""
    from .collectives import unfuse_segments
    axis = mesh.axis_names[0]

    def per_shard(x):  # x: (1, L) on each device
        with jax.named_scope("hvd_exchange"):
            row = lax.psum(x, axis)[0]
            return unfuse_segments(row, segs, num_ranks)

    return jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(None), check_vma=False),
                   donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=256)
def _jit_psum_unfuse_health(mesh, dtype, shape, segs, num_ranks,
                            donate=False):
    """Guard variant of :func:`_jit_psum_unfuse`: identical psum+unfuse,
    plus ONE extra replicated output — the per-segment ``[finite, l2]``
    health digest (ops/collectives.segment_health) computed on the
    reduced row *inside* the program. The digest is over the summed wire
    row (pre-average), which is what every rank holds bit-identically,
    so every rank's later verdict is identical by construction. Selected
    only when a GuardMonitor is installed; the plain builder above keeps
    its own cache entries, so the default path's executables are
    byte-for-byte the no-guard build."""
    from .collectives import segment_health, unfuse_segments
    axis = mesh.axis_names[0]

    def per_shard(x):  # x: (1, L) on each device
        with jax.named_scope("hvd_exchange"):
            row = lax.psum(x, axis)[0]
            outs = unfuse_segments(row, segs, num_ranks)
        with jax.named_scope("hvd_guard"):
            return outs + (segment_health(row, segs),)

    return jax.jit(jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                                 out_specs=P(None), check_vma=False),
                   donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=256)
def _jit_psum_rows_hier(mesh, hier_axes, dtype, shape, donate=False):
    """Three-stage hierarchical allreduce wire program (reference:
    NCCLHierarchicalAllreduce, nccl_operations.cc:258-485). The buffer length
    is pre-padded to a multiple of the local tier size (_fused_nelem)."""
    ici_axis, dcn_axis = hier_axes
    cross_ax, local_ax = mesh.axis_names

    def per_shard(x):  # x: (1, L) on each device, L % local_size == 0
        v = x[0]
        with jax.named_scope("hvd_exchange"):
            # intra-tier reduce-scatter: each local device owns a summed
            # stripe
            with jax.named_scope("hvd_ici"):
                stripe = lax.psum_scatter(v, ici_axis, scatter_dimension=0,
                                          tiled=True)
            # cross-tier allreduce of the stripe (1/local_size the bytes)
            with jax.named_scope("hvd_dcn"):
                stripe = lax.psum(stripe, dcn_axis)
            # intra-tier allgather reassembles the full row
            with jax.named_scope("hvd_ici"):
                return lax.all_gather(stripe, ici_axis, axis=0,
                                      tiled=True)[None]

    f = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                              in_specs=P((cross_ax, local_ax)),
                              out_specs=P(None), check_vma=False),
                donate_argnums=(0,) if donate else ())

    def run(arr):
        return f(arr)[0]

    return run


@functools.lru_cache(maxsize=256)
def _jit_allgather_rows_hier(mesh, hier_axes, dtype, shape):
    """Two-stage hierarchical allgather: gather the local tier first (ICI),
    then the cross tier (DCN) — rank order is row-major over (cross, local),
    matching the reference's local-stripe + cross-node MPI_Allgatherv
    (MPIHierarchicalAllgather, mpi_operations.cc:241-391)."""
    ici_axis, dcn_axis = hier_axes
    cross_ax, local_ax = mesh.axis_names

    def per_shard(x):  # x: (1, maxd, ...) -> (R, maxd, ...)
        with jax.named_scope("hvd_exchange"):
            with jax.named_scope("hvd_ici"):
                local_block = lax.all_gather(x[0], ici_axis, axis=0,
                                             tiled=False)
            with jax.named_scope("hvd_dcn"):
                both = lax.all_gather(local_block, dcn_axis, axis=0,
                                      tiled=False)
            return both.reshape((-1,) + both.shape[2:])

    f = jax.shard_map(per_shard, mesh=mesh,
                      in_specs=P((cross_ax, local_ax)),
                      out_specs=P(None), check_vma=False)
    return jax.jit(f)


@functools.lru_cache(maxsize=256)
def _jit_allgather_rows(mesh, dtype, shape):
    axis = mesh.axis_names[0]

    def per_shard(x):  # x: (1, maxd, ...) -> gathered (R, maxd, ...)
        with jax.named_scope("hvd_exchange"):
            return lax.all_gather(x[0], axis, axis=0, tiled=False)

    f = jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                      out_specs=P(None), check_vma=False)
    return jax.jit(f)


@functools.lru_cache(maxsize=256)
def _jit_broadcast_rows(mesh, dtype, shape):
    """Broadcast wire program: non-root rows arrive pre-zeroed from the
    host (engine._execute_broadcast), so one psum emits root's row — no
    in-program mask needed. Leading row axis is kept so rank-0 payloads
    (scalar tensors, e.g. BN num_batches_tracked in a broadcast
    state_dict) stay rank>=1."""
    axis = mesh.axis_names[0]

    def per_shard(x):  # x: (1, ...) per device; zeros except root's row
        with jax.named_scope("hvd_exchange"):
            return lax.psum(x, axis)

    f = jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                      out_specs=P(None), check_vma=False)
    g = jax.jit(f)

    def run(arr):
        return g(arr)[0]

    return run


@functools.lru_cache(maxsize=256)
def _jit_alltoall_rows(mesh, dtype, shape):
    axis = mesh.axis_names[0]

    def per_shard(x):  # x: (1, d0, ...) per device; d0 divisible by R
        with jax.named_scope("hvd_exchange"):
            out = lax.all_to_all(x[0], axis, split_axis=0, concat_axis=0,
                                 tiled=True)
            return out[None]

    f = jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                      out_specs=P(axis))
    return jax.jit(f)


def _dtype_name(dt):
    """Reference DataType_Name strings (message.cc DataType_Name)."""
    mapping = {
        "uint8": "uint8", "int8": "int8", "uint16": "uint16",
        "int16": "int16", "int32": "int32", "int64": "int64",
        "float16": "float16", "float32": "float32", "float64": "float64",
        "bool": "bool", "bfloat16": "bfloat16",
    }
    return mapping.get(np.dtype(dt).name, np.dtype(dt).name)


def _shape_str(shape):
    """Reference TensorShape::DebugString format '[d1, d2]'
    (common.cc TensorShape::DebugString)."""
    return "[" + ", ".join(str(d) for d in shape) + "]"
