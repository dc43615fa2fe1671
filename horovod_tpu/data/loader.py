"""DistributedDataset: sharded, prefetched, elastic-resumable input.

The training loop's side of the subsystem. One object owns the three
concerns every example used to hand-roll:

- **sharding** — a deterministic per-epoch global shuffle and this
  rank's equal-steps slice of it (sharding.py); every rank takes the
  same number of batches per epoch, so a collective-per-step loop can
  never be wedged by a peer that ran dry early;
- **staging** — batches are assembled (and optionally ``device_put``)
  on a background producer thread feeding a bounded queue, so host-side
  decode/transfer rides behind device compute instead of serializing
  with it (tf.data's prefetch; Murray et al., VLDB 2021). The queue
  depth is ``HOROVOD_DATA_PREFETCH`` (default 2 — double buffering);
  ``0`` is the exact synchronous fallback, mirroring the
  ``HOROVOD_PIPELINE_DEPTH=0`` contract of the overlap pipeline.
  With ``HOROVOD_AUTOTUNE=1`` the depth is tuned off the measured
  input-wait (autotune.py), applied at epoch boundaries;
- **resumable position** — ``state_dict()``/``load_state_dict()``
  round-trip the iterator position (epoch, seed, segment history —
  state.py); committed into an ``elastic.State``
  (:func:`~horovod_tpu.data.attach_to_state`), a SIGKILL recovery
  resumes mid-epoch without duplicating or dropping samples, and
  re-shards the unconsumed remainder across the survivors.

Telemetry rides the process-wide registry (``hvd_data_*`` families,
docs/observability.md): batches/samples/epochs counters, the input-wait
histogram (time the loop blocked on the next batch — the input analog
of ``hvd_engine_readback_wait_seconds``), prefetch-queue occupancy, and
the re-shard counter.

Usage::

    ds = hvd.data.DistributedDataset(
        (images, labels), batch_size=32,
        seed=1234, sharding=NamedSharding(mesh, P("hvd")))
    for epoch in range(epochs):
        for x, y in ds:                  # one epoch per for-loop
            params, opt_state = step(params, opt_state, x, y)
"""

import queue
import threading
import time

import numpy as np

from .. import diag, metrics
from ..utils.logging import get_logger
from . import sharding as sharding_mod
from .state import IteratorState, rebuild_plan

_logger = get_logger()

DEFAULT_PREFETCH = 2
# Producer-side put/get poll quantum: bounds how long a stale producer
# can outlive an invalidation, without busy-waiting.
_POLL_S = 0.05

_END = object()


def _env_prefetch():
    from ..config import Config
    return Config.from_env().data_prefetch


def process_topology():
    """``(rank, size)`` at PROCESS granularity for the current job: this
    process's position among the processes owning the job's devices, and
    their count. This is the input-loading topology — a process stages
    batches for ALL its local chips, so on a 2-host x 4-chip job the
    split is 2-way, not 8-way — and it follows elastic membership: after
    a recovery the survivors renumber densely, the corpse drops out.
    Returns ``(0, 1)`` outside an initialized multi-process job, and for
    a process owning none of the job's devices (an excluded rank must
    not submit collectives, so it has no shard to load either)."""
    try:
        import jax

        import horovod_tpu as hvd
        if hvd.is_initialized() and jax.process_count() > 1:
            procs = sorted({d.process_index for d in hvd.state().devices})
            me = jax.process_index()
            if me in procs and len(procs) > 1:
                return procs.index(me), len(procs)
    except Exception:  # noqa: BLE001 — standalone use stays (0, 1)
        pass
    return 0, 1


class DistributedDataset:
    """Deterministically sharded, background-prefetched batch iterator.

    Args:
      source: the samples — a pytree of equal-length arrays indexed on
        axis 0 (batches are pytrees of the same structure), or a
        callable ``fetch(indices) -> batch`` for out-of-core sources
        (``num_samples`` is then required).
      batch_size: samples per batch *staged by this process* (the
        global batch is ``batch_size * size``). On a multi-chip process
        that is the batch for ALL its local chips.
      num_samples: dataset length; inferred from array sources.
      seed: base seed of the per-epoch global shuffle (identical on
        every rank — the order is derived, never communicated).
      shuffle: reshuffle globally each epoch; ``False`` keeps natural
        order (sharding still applies).
      policy: ``"contiguous"`` or ``"strided"`` rank slicing
        (sharding.py).
      remainder: ``"pad"`` (wrap-around padding; equal steps, a few
        duplicated samples on uneven splits — the safe default for
        collective-per-step loops) or ``"drop"``.
      rank, size: sharding topology. Default: :func:`process_topology`
        — one shard per participating PROCESS (a process loads for all
        its local chips; survivors renumber densely after an elastic
        recovery); ``(0, 1)`` single-process — an SPMD driver feeds
        the whole global batch itself.
      prefetch: queue depth; ``0`` = synchronous. Default: the live
        ``HOROVOD_DATA_PREFETCH`` config (re-read each epoch, so the
        autotuner's choice applies at epoch boundaries).
      sharding: optional ``jax.sharding.Sharding``; batches are
        ``jax.device_put`` with it on the producer thread, so the
        host->device copy is dispatched before the loop asks for the
        batch (double-buffered staging).
      transform: optional ``fn(batch) -> batch`` applied on the
        producer thread (augmentation/collation off the step path).
    """

    def __init__(self, source, batch_size, num_samples=None, seed=0,
                 shuffle=True, policy="contiguous", remainder="pad",
                 rank=None, size=None, prefetch=None, sharding=None,
                 transform=None):
        if callable(source):
            if num_samples is None:
                raise ValueError(
                    "callable sources need num_samples= (an array source "
                    "infers it from the leaves)")
            self._fetch = source
            self._num_samples = int(num_samples)
        else:
            import jax
            leaves = jax.tree.flatten(source)[0]
            if not leaves:
                raise ValueError("source pytree has no array leaves")
            lens = {len(x) for x in leaves}
            if len(lens) != 1:
                raise ValueError(
                    f"source leaves disagree on length: {sorted(lens)}")
            n = lens.pop()
            if num_samples is not None and int(num_samples) != n:
                raise ValueError(
                    f"num_samples={num_samples} != source length {n}")
            self._source = source
            self._fetch = self._fetch_arrays
            self._num_samples = n
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive: {batch_size}")
        self.batch_size = int(batch_size)
        self.policy = policy
        self.remainder = remainder
        self._explicit_rank = rank
        self._explicit_size = size
        self._explicit_prefetch = prefetch
        self._sharding = sharding
        self._transform = transform

        self._state = IteratorState(epoch=0, seed=int(seed), shuffle=shuffle)
        self.rank, self.size = self._resolve_topology()
        self._state.begin_epoch(0, self.size)
        self._plan, self._step, _ = rebuild_plan(
            self._num_samples, self._state, self.rank, self.size,
            self.batch_size, policy, remainder)

        self._wait_accum = 0.0
        self._gen = 0
        self._producer = None     # (thread, queue, stop_event, gen)

    # ------------------------------------------------------------ sources

    def _fetch_arrays(self, indices):
        import jax
        return jax.tree.map(lambda a: np.take(np.asarray(a), indices,
                                              axis=0), self._source)

    # ----------------------------------------------------------- topology

    def _resolve_topology(self):
        if self._explicit_rank is not None or self._explicit_size is not None:
            if self._explicit_rank is None or self._explicit_size is None:
                raise ValueError("pass rank= and size= together")
            r, s = int(self._explicit_rank), int(self._explicit_size)
        else:
            r, s = process_topology()
        if not 0 <= r < s:
            raise ValueError(f"rank {r} out of range for size {s}")
        return r, s

    def _resolve_prefetch(self):
        if self._explicit_prefetch is not None:
            return max(int(self._explicit_prefetch), 0)
        try:
            import horovod_tpu as hvd
            if hvd.is_initialized():
                return max(int(hvd.state().config.data_prefetch), 0)
        except Exception:  # noqa: BLE001
            pass
        return _env_prefetch()

    def _autotuner(self):
        try:
            import horovod_tpu as hvd
            if hvd.is_initialized():
                return hvd.state().autotuner
        except Exception:  # noqa: BLE001
            pass
        return None

    # ----------------------------------------------------------- position

    @property
    def epoch(self):
        return self._state.epoch

    @property
    def num_samples(self):
        return self._num_samples

    @property
    def steps_per_epoch(self):
        """Steps in a FRESH epoch at the current topology (a job-wide
        constant — the equal-steps invariant)."""
        return sharding_mod.steps_for(self._num_samples, self.size,
                                      self.batch_size, self.remainder)

    @property
    def steps_remaining(self):
        """Batches left in the current (possibly re-sharded) epoch."""
        return len(self._plan) // self.batch_size - self._step

    def state_dict(self):
        """The committed-position codec: a dict of small ints (epoch,
        seed, segment history) — see data/state.py."""
        return self._state.to_dict()

    def load_state_dict(self, sd):
        """Rewind to a captured position. Reads the CURRENT topology, so
        a load after a membership change re-shards the epoch's
        unconsumed remainder across the survivors (counted by
        ``hvd_data_reshards_total``). Any prefetched batches from the
        abandoned position are discarded."""
        self._invalidate()
        self._state = IteratorState.from_dict(sd)
        self.rank, self.size = self._resolve_topology()
        self._plan, self._step, resharded = rebuild_plan(
            self._num_samples, self._state, self.rank, self.size,
            self.batch_size, self.policy, self.remainder)
        if resharded:
            metrics.DATA_RESHARDS.inc()
            _logger.warning(
                "data: re-sharded epoch %d remainder across %d rank(s) "
                "(%d step(s) left on this rank)", self._state.epoch,
                self.size, self.steps_remaining)

    # ---------------------------------------------------------- iteration

    def __iter__(self):
        """Yield the REMAINING batches of the current epoch, then advance
        to the next epoch (fresh permutation, full topology). One epoch
        per for-loop; a loop entered after ``load_state_dict`` continues
        mid-epoch."""
        return self._iterate_epoch()

    def __len__(self):
        return self.steps_remaining

    def _iterate_epoch(self):
        depth = self._resolve_prefetch()
        metrics.DATA_PREFETCH_DEPTH.set(depth)
        tuner = self._autotuner()
        if tuner is not None:
            try:
                # Tell the tuner which depth this epoch actually runs at:
                # it must not step again off measurements taken before
                # its last change landed (depth applies at epoch start).
                tuner.record_prefetch_depth(depth)
            except Exception:  # noqa: BLE001
                pass
        steps_left = self.steps_remaining
        if depth > 0 and steps_left > 0:
            self._start_producer(depth)
        for _ in range(steps_left):
            with diag.span("data.wait", batch=self._step) as sp:
                if depth > 0 and self._producer is not None:
                    sp.set(depth=self._producer[1].qsize())
                    batch = self._get_prefetched()
                else:
                    batch = self._produce(self._plan, self._step)
                wait = time.perf_counter() - sp.t0
            self._record_wait(wait, tuner)
            self._step += 1
            self._state.segments[-1][1] = self._step
            metrics.DATA_BATCHES.inc()
            metrics.DATA_SAMPLES.inc(self.batch_size)
            yield batch
            if self._step >= len(self._plan) // self.batch_size:
                break  # position moved under us (load_state_dict mid-loop)
        if self.steps_remaining <= 0:
            self._advance_epoch()

    def _advance_epoch(self):
        self._invalidate()
        self.rank, self.size = self._resolve_topology()
        self._state.begin_epoch(self._state.epoch + 1, self.size)
        self._plan, self._step, _ = rebuild_plan(
            self._num_samples, self._state, self.rank, self.size,
            self.batch_size, self.policy, self.remainder)
        metrics.DATA_EPOCHS.inc()

    def _produce(self, plan, step):
        idx = plan[step * self.batch_size:(step + 1) * self.batch_size]
        with diag.span("data.fetch", batch=step):
            batch = self._fetch(idx)
            if self._transform is not None:
                batch = self._transform(batch)
        if self._sharding is not None:
            import jax
            sh = self._sharding
            with diag.span("data.put", batch=step):
                if getattr(sh, "is_fully_addressable", True):
                    batch = jax.device_put(batch, sh)
                else:
                    # Multi-process sharding: each process holds only ITS
                    # shard of the global batch, so the global array is
                    # assembled from per-process local data (device_put
                    # would expect the full global value).
                    batch = jax.tree.map(
                        lambda a: jax.make_array_from_process_local_data(
                            sh, np.asarray(a)), batch)
        return batch

    def _record_wait(self, wait, tuner):
        metrics.DATA_WAIT_SECONDS.observe(wait)
        self._wait_accum += wait
        fr = diag.get()
        if fr is not None:
            fr.record("input_wait", extra={"wait": wait})
        if tuner is not None:
            try:
                tuner.record_input_wait(wait)
            except Exception:  # noqa: BLE001 — telemetry must not kill work
                pass

    def take_wait(self):
        """Input-wait seconds accumulated since the last call — how long
        the loop blocked on batches (TelemetryCallback turns this into
        ``hvd_data_stall_ratio``; bench.py into ``data_wait_ms``)."""
        w = self._wait_accum
        self._wait_accum = 0.0
        return w

    def prefetch_occupancy(self):
        """Current prefetch-queue fill fraction (0.0–1.0), or None when
        prefetch is off. The instantaneous read behind the autoscaler's
        compute-bound signal (elastic/policy.py): pinned near 1.0 the
        producer is comfortably ahead; near 0.0 the job is input-bound
        (the histogram form is ``hvd_data_prefetch_occupancy``)."""
        if self._producer is None:
            return None
        _t, q, _stop, _gen = self._producer
        depth = q.maxsize or 1
        return min(q.qsize() / depth, 1.0)

    # ----------------------------------------------------------- prefetch

    def _start_producer(self, depth):
        self._invalidate()
        q = queue.Queue(maxsize=depth)
        stop = threading.Event()
        gen = self._gen
        plan, start = self._plan, self._step
        steps = len(plan) // self.batch_size

        def produce():
            try:
                for step in range(start, steps):
                    if stop.is_set():
                        return
                    item = self._produce(plan, step)
                    while not stop.is_set():
                        try:
                            q.put(item, timeout=_POLL_S)
                            break
                        except queue.Full:
                            continue
                while not stop.is_set():
                    try:
                        q.put(_END, timeout=_POLL_S)
                        return
                    except queue.Full:
                        continue
            except BaseException as e:  # noqa: BLE001 — surface on consumer
                # Same stop-aware poll as the data path: a full queue
                # must delay the exception, never drop it (a dropped one
                # would leave the consumer blocked in q.get() forever).
                while not stop.is_set():
                    try:
                        q.put(e, timeout=_POLL_S)
                        return
                    except queue.Full:
                        continue

        t = threading.Thread(target=produce, daemon=True,
                             name=f"hvd-data-prefetch-{gen}")
        t.start()
        self._producer = (t, q, stop, gen)

    def _get_prefetched(self):
        t, q, stop, gen = self._producer
        metrics.DATA_PREFETCH_OCCUPANCY.observe(q.qsize())
        item = q.get()
        if item is _END:
            raise RuntimeError(
                "prefetch producer ended before the plan did (dataset "
                "mutated mid-epoch without load_state_dict?)")
        if isinstance(item, BaseException):
            raise item
        return item

    def _invalidate(self):
        """Retire the current producer (position change / epoch end).
        The thread observes its stop event within one poll quantum; its
        queue is dropped wholesale, so stale batches can't leak into the
        new position."""
        if self._producer is not None:
            t, q, stop, gen = self._producer
            stop.set()
            self._producer = None
            t.join(timeout=5.0)
        self._gen += 1

    def close(self):
        """Stop the background producer. Idempotent; iteration after
        close() restarts it."""
        self._invalidate()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
