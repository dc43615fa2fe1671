"""Per-collective profiling statistics (fork parity).

The reference fork instruments every collective with call counters and
per-message-size time histograms kept in the global state
(reference: horovod/common/global_state.h:113-141 — ``counter_allreduce``,
``map_allreduce``, ``time_map_allreduce``, bcast/gather/allgather variants) and
dumps them all to ``profiler.txt`` in a CSV-ish format at shutdown
(reference: horovod/common/operations.cc:219-317 ``write_to_file``,
:1934-1962 ``horovod_shutdown``).

Here the same registry is kept in Python (thread-safe; the eager engine and the
jit-path wrappers both record into it) and the dump format mirrors the fork's:
a ``Counter <op>,N`` line, a ``Time <op>,T,microseconds`` line, then a
``Message size,count,Time per call,Total time`` histogram table per collective.
"""

import os
import threading
import time
from collections import defaultdict


def record_jit(op, nbytes, elapsed_s=0.0):
    """Record a collective issued on the jit path into the live registry.

    Called by the jit-path wrappers (ops/collectives.py, optimizers.py) at
    TRACE time: under ``jax.jit`` the Python body runs once per compiled
    specialization, so the counter reflects dispatch/trace events, not
    per-step executions — XLA owns the executed hot loop and its device time
    belongs to jax.profiler. This is the TPU-native analog of the fork's
    always-on hot-path counters (reference: operations.cc:219-317,
    global_state.h:113-141): zero overhead at step time, and the shutdown
    dump (profiler.txt) shows every collective the program contains with its
    wire bytes; their DEVICE time is what a trace capture adds (the
    ``*_xla`` rows, ``hvd.trace_steps``, diag/xla_trace.py). Set
    ``HOROVOD_PROFILER_JIT_CALLBACKS=1`` to additionally
    count every *execution* via a host callback (precise, small per-step
    host-sync cost).

    A no-op before init()/after shutdown() — jit-path ops are usable without
    the runtime, matching their standalone contract.
    """
    from . import runtime
    if not runtime.is_initialized():
        return
    st = runtime._state.stats
    if st is not None:
        st.record(op, int(nbytes), elapsed_s)


def record_jit_traced(op, nbytes, axis_name=None):
    """Record a jit-path collective: per-execution when
    HOROVOD_PROFILER_JIT_CALLBACKS=1 (host callback baked into the program),
    else once per trace (free).

    ``axis_name`` is the mapped collective axis: inside shard_map/pmap the
    callback would otherwise fire once per device shard, inflating the
    per-execution count by the local shard count — so it is gated to the
    axis's rank-0 shard (one record per logical collective).

    Multi-process shard_map note: the axis's rank-0 shard lives on exactly
    ONE process, so with callbacks enabled only the process owning mesh
    position 0 accumulates per-execution counts — which is the process
    whose shutdown dump the launcher keeps (runtime.shutdown dumps on
    rank 0), mirroring the reference where rank 0's profiler file is the
    artifact. Other processes' registries keep trace-time counts only."""
    from .config import Config
    if Config.from_env().profiler_jit_callbacks:
        import jax
        from jax import lax

        def _cb():
            record_jit(op, nbytes)

        if axis_name is not None:
            first = (axis_name[0] if isinstance(axis_name, (tuple, list))
                     else axis_name)
            lax.cond(lax.axis_index(first) == 0,
                     lambda: jax.debug.callback(_cb), lambda: None)
        else:
            jax.debug.callback(_cb)
    else:
        record_jit(op, nbytes)


def register_metrics(stats):
    """Expose the live session's per-collective registry through the
    process-wide metrics snapshot (metrics.py): a collect hook mirrors each
    op's call counter and cumulative time into labeled gauges, so
    ``hvd.metrics_snapshot()``, the exporters, and the profiler.txt
    shutdown dump all read the same numbers. Gauges (not counters) because
    the values reset with each session's stats object."""
    from . import metrics

    def _collect():
        for op in CollectiveStats.OPS:
            try:
                calls = stats.counter(op)
                time_us = stats.total_time_us(op)
            except KeyError:
                continue
            metrics.COLLECTIVE_CALLS.labels(op=op).set(calls)
            metrics.COLLECTIVE_TIME_US.labels(op=op).set(time_us)

    metrics.registry().set_collect_hook("collective_stats", _collect)


class _OpStats:
    __slots__ = ("counter", "total_time_us", "size_count", "size_time_us")

    def __init__(self):
        self.counter = 0
        self.total_time_us = 0
        self.size_count = defaultdict(int)
        self.size_time_us = defaultdict(int)


def create_stats():
    """Native-backed registry when the control-plane library is available
    (csrc/stats.cc), else the pure-Python mirror below."""
    from . import native
    if native.available():
        return NativeCollectiveStats(native.get_lib())
    return CollectiveStats()


class _StatsTimer:
    def __init__(self, stats, op, nbytes):
        self._stats, self._op, self._nbytes = stats, op, nbytes

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._stats.record(self._op, self._nbytes,
                           time.perf_counter() - self._t0)
        return False


class NativeCollectiveStats:
    """ctypes facade over csrc/stats.cc (same dump format and API as
    CollectiveStats)."""

    def __init__(self, lib):
        self._lib = lib
        self._h = lib.hvd_stats_new()

    def record(self, op, nbytes, elapsed_s):
        self._lib.hvd_stats_record(self._h, op.encode(), int(nbytes),
                                   int(elapsed_s * 1e6))

    def timer(self, op, nbytes):
        return _StatsTimer(self, op, nbytes)

    def counter(self, op):
        return int(self._lib.hvd_stats_counter(self._h, op.encode()))

    def total_time_us(self, op):
        return int(self._lib.hvd_stats_total_time_us(self._h, op.encode()))

    def histogram(self, op):
        import ctypes
        cap = 256
        while True:
            sizes = (ctypes.c_int64 * cap)()
            counts = (ctypes.c_int64 * cap)()
            times = (ctypes.c_int64 * cap)()
            n = self._lib.hvd_stats_histogram(self._h, op.encode(), sizes,
                                              counts, times, cap)
            if n <= cap:
                return {int(sizes[i]): (int(counts[i]), int(times[i]))
                        for i in range(n)}
            cap = n

    def write_to_file(self, path):
        rc = self._lib.hvd_stats_write_file(self._h, str(path).encode())
        if rc != 0:
            raise OSError(f"native stats dump to {path} failed")


class CollectiveStats:
    """Registry of per-collective counters and message-size histograms."""

    # Collective classes tracked by the fork (global_state.h:113-141). The
    # reference's nccl/cache variants map here to the engine's execution tiers:
    # "allreduce" = negotiated eager ops, "allreduce_cached" = response-cache
    # hits (the fork's BcastState counters), "allreduce_jit" = collectives
    # issued inside user jit programs. "gather"/"gatherv" are the
    # control plane — the fork times its coordination MPI_Gather/Gatherv
    # (operations.cc:1593-1648); here "gather" records multi-host KV request
    # publishes and "gatherv" decision fetches (coordinator.py).
    OPS = ("allreduce", "allreduce_cached", "allreduce_jit",
           "allgather", "allgather_jit", "broadcast", "broadcast_jit",
           "alltoall", "alltoall_jit", "reducescatter", "reducescatter_jit",
           "gather", "gatherv")

    # What a device-trace capture found inside the compiled step
    # (diag/xla_trace.py: calls, message size and DEVICE time per XLA
    # collective). Labels of their own, dumped after the fork's list and
    # not mirrored into hvd_collective_calls: those count host-issued ops.
    XLA_OPS = ("allreduce_xla", "allgather_xla", "reducescatter_xla",
               "alltoall_xla", "collectivepermute_xla")

    def __init__(self):
        self._lock = threading.Lock()
        self._ops = {op: _OpStats() for op in self.OPS + self.XLA_OPS}

    def record(self, op, nbytes, elapsed_s):
        with self._lock:
            s = self._ops.setdefault(op, _OpStats())
            us = int(elapsed_s * 1e6)
            s.counter += 1
            s.total_time_us += us
            s.size_count[int(nbytes)] += 1
            s.size_time_us[int(nbytes)] += us

    class _Timer:
        def __init__(self, stats, op, nbytes):
            self._stats, self._op, self._nbytes = stats, op, nbytes

        def __enter__(self):
            self._t0 = time.perf_counter()
            return self

        def __exit__(self, *exc):
            self._stats.record(self._op, self._nbytes,
                               time.perf_counter() - self._t0)
            return False

    def timer(self, op, nbytes):
        """Context manager timing one collective call of ``nbytes`` bytes."""
        return self._Timer(self, op, nbytes)

    def counter(self, op):
        return self._ops[op].counter

    def total_time_us(self, op):
        return self._ops[op].total_time_us

    def histogram(self, op):
        s = self._ops[op]
        with self._lock:
            return {sz: (s.size_count[sz], s.size_time_us[sz])
                    for sz in sorted(s.size_count)}

    def write_to_file(self, path):
        """Dump in the fork's profiler.txt CSV-ish layout
        (reference: operations.cc:219-317)."""
        lines = []
        for op in self.OPS + self.XLA_OPS:
            s = self._ops[op]
            pretty = op.replace("_", " ")
            lines.append(f"Counter {pretty},{s.counter}")
            lines.append(f"Time {pretty},{s.total_time_us},microseconds")
            lines.append("Message size,count,Time per call,Total time")
            with self._lock:
                for sz in sorted(s.size_count):
                    cnt = s.size_count[sz]
                    tot = s.size_time_us[sz]
                    lines.append(f"{sz},{cnt},{tot // max(cnt, 1)},{tot}")
        parent = os.path.dirname(path)
        if parent:
            os.makedirs(parent, exist_ok=True)
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
