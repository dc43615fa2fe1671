"""Process-wide runtime state: init/shutdown and rank topology.

Reference equivalent: the C API + global state + background-thread bootstrap
(horovod/common/operations.cc:1891-2009 ``InitializeHorovodOnce`` /
``horovod_init`` / ``horovod_rank`` etc., horovod/common/global_state.h:46, and
the ctypes wrapper horovod/common/basics.py:22).

TPU-native design: there is no MPI and no background thread. ``init()``:

1. bootstraps multi-process JAX (``jax.distributed.initialize``) when launched
   by our ``horovodrun`` equivalent or any launcher that sets the standard
   coordinator env vars — this replaces ``MPI_Init`` + the rank-0 coordinator
   handshake (reference: operations.cc:1019-1133);
2. builds a 1-D ``jax.sharding.Mesh`` with axis ``"hvd"`` over every device in
   the job — the ICI/DCN mesh replaces the MPI global communicator, and XLA's
   in-program collective scheduling replaces the negotiation/fusion background
   loop;
3. reads the ``HOROVOD_*`` env config once (reference: operations.cc:1164-1265)
   and starts the aux subsystems (stats, timeline, stall watchdog, eager engine).

Rank model. The reference runs one process per GPU, so process rank == device
rank. On TPU a process owns all its local chips. We keep Horovod's *device
granularity*: ``size()`` is the total number of participating chips and every
chip is a rank. ``rank()`` returns the first rank owned by this process (equal
to the process rank when launched one-process-per-chip, which is what our
launcher does on CPU pools and what Horovod semantics assume). ``local_rank``/
``local_size``/``cross_rank``/``cross_size`` mirror the reference's node-local
and cross-node communicators (reference: operations.cc:1061,1133) and come from
launcher env vars when present.
"""

import atexit
import os
import threading
import time

import jax
import numpy as np

from . import config as config_mod
from .exceptions import NotInitializedError
from .utils.logging import get_logger

AXIS = "hvd"  # global mesh axis name for the data-parallel collective dimension


class _State:
    def __init__(self):
        self.initialized = False
        self.shutdown = False
        self.mesh = None
        self.expert_mesh = None
        self.model_mesh = None
        self.devices = None
        self.num_ranks = 0
        self.local_num_ranks = 0
        self.first_rank = 0
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self.config = None
        self.stats = None
        self.timeline = None
        self.engine = None
        self.autotuner = None
        self.metrics_exporters = None
        self.diag_watchdog = None
        self.lock = threading.RLock()


_state = _State()
_logger = get_logger()


def compile_cache_dir():
    """Where this process keeps XLA's persistent compile cache: the
    directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set, else
    ``<checkout>/.jax_cache``. The path is part of what a later process
    must find again, so it is fixed — never derived from a temp dir, a
    pid or the clock."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")


def _place_compile_cache():
    """Point jax at :func:`compile_cache_dir`. With the variable set jax
    reads it itself and nothing is set in code, so the cache can be
    placed from outside."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", compile_cache_dir())


def _maybe_init_distributed():
    """Join the multi-process job if launcher env vars are present.

    Replaces MPI_Init + rank discovery (reference: operations.cc:1019-1042).
    Our launcher (horovod_tpu/run) sets HOROVOD_TPU_COORDINATOR /
    HOROVOD_TPU_NUM_PROCESSES / HOROVOD_TPU_PROCESS_ID; on Cloud TPU pods the
    runtime autodetects everything and plain initialize() suffices.
    """
    coord = os.environ.get("HOROVOD_TPU_COORDINATOR")  # hvdlint: disable=HVD003 -- launcher-worker protocol var set by run/, not a knob
    if not coord:
        return
    # Re-init after shutdown(): the jax.distributed session outlives the
    # horovod session (like MPI, it initializes once per process).
    if jax.distributed.is_initialized():
        return
    # Must run before anything touches an XLA backend (jax.distributed's
    # contract); the env check above is therefore ordered first. CPU
    # multi-process jobs ride jax's default cross-process collectives
    # (gloo) unless the user picked another
    # (JAX_CPU_COLLECTIVES_IMPLEMENTATION=mpi).
    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["HOROVOD_TPU_NUM_PROCESSES"]),  # hvdlint: disable=HVD003 -- launcher-worker protocol var
        process_id=int(os.environ["HOROVOD_TPU_PROCESS_ID"]),  # hvdlint: disable=HVD003 -- launcher-worker protocol var
    )


#: jax.monitoring's duration events (jax 0.9.0: dispatch.py, compiler.py)
#: under the span names they are recorded as. ``backend_compile_duration``
#: wraps ``compile_or_get_cached``, so on a persistent-cache hit it
#: contains ``cache_retrieval_time_sec``, which fires first: the listener
#: takes that part out so that ``jax.compile`` + ``jax.cache_load`` is the
#: backend time jax reports, counted once.
_JAX_DURATION_SPANS = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "jax.cache_load",
}
_JAX_SPAN_MIN_S = 1e-3
_jax_watch = threading.local()
_jax_watch_registered = False


def _on_jax_duration(event, duration, **_):
    name = _JAX_DURATION_SPANS.get(event)
    if name is None:
        return
    from . import diag
    now = time.perf_counter()
    if name == "jax.cache_load":
        _jax_watch.loaded = getattr(_jax_watch, "loaded", 0.0) + duration
    elif name == "jax.compile":
        duration = max(duration - getattr(_jax_watch, "loaded", 0.0), 0.0)
        _jax_watch.loaded = 0.0
    # jax reports the trace of every jitted helper (jnp.where ...) inside
    # a trace as an event of its own: thousands per program, each inside
    # the outer one's interval. Under a millisecond is not worth a slot
    # of the ring.
    if duration >= _JAX_SPAN_MIN_S:
        diag.record_span(name, now - duration, now)


def _watch_jax_compiles():
    """One ``jax.monitoring`` listener per process: every trace, lowering,
    backend compile and persistent-cache load becomes a ``jax.*`` span,
    the child of whichever span is open on that thread (the first
    ``step.execute``, the jitted init). jax has no way to take a listener
    back, so a re-init registers nothing new."""
    global _jax_watch_registered
    if not _jax_watch_registered:
        jax.monitoring.register_event_duration_secs_listener(
            _on_jax_duration)
        _jax_watch_registered = True


def init(comm=None, num_ranks=None):
    """Initialize the runtime. Idempotent, like the reference's
    ``InitializeHorovodOnce`` (operations.cc:1891-1907).

    Args:
      comm: rank-subset job, API parity with ``hvd.init(comm=...)``
        (reference: common/basics.py:29-55, which accepts an MPI
        communicator OR a list of world ranks; operations.cc:1924 runs the
        job on the sub-communicator). There is no MPI here, so the list
        form is the supported one: a sequence of device positions (world
        ranks) to run on — the mesh spans exactly those chips and ranks
        renumber 0..len(comm)-1 within the job, like MPI sub-communicator
        ranks. An actual mpi4py communicator object is not meaningful
        without MPI and raises. In multi-process jobs a process owning
        none of the listed devices must not submit collectives (the same
        contract MPI sub-communicators impose on excluded ranks).
      num_ranks: restrict the mesh to the first ``num_ranks`` devices
        (shorthand for ``comm=range(num_ranks)``). Mutually exclusive
        with ``comm``.
    """
    from . import diag
    with _state.lock:
        if _state.initialized and not _state.shutdown:
            return
        with diag.span("init"):
            _init_locked(comm, num_ranks)


def _init_locked(comm, num_ranks):
    """``init`` proper, under the state lock and inside its span."""
    if comm is not None and num_ranks is not None:
        raise ValueError("pass either comm= or num_ranks=, not both")
    if comm is not None and not (
            isinstance(comm, (list, tuple, range))
            and all(isinstance(r, (int, np.integer)) for r in comm)):
        raise ValueError(
            "horovod_tpu has no MPI: init(comm=...) takes a list of "
            "device positions (world ranks), e.g. comm=[0, 2, 5] — "
            "not an MPI communicator object.")
    _place_compile_cache()
    _maybe_init_distributed()

    cfg = config_mod.Config.from_env()
    devices = list(jax.devices())
    if comm is not None:
        ranks = [int(r) for r in comm]
        if len(set(ranks)) != len(ranks):
            raise ValueError(f"comm has duplicate ranks: {ranks}")
        bad = [r for r in ranks if not 0 <= r < len(devices)]
        if bad:
            raise ValueError(
                f"comm ranks {bad} out of range [0, {len(devices)})")
        devices = [devices[r] for r in ranks]
    elif num_ranks is not None:
        if num_ranks > len(devices):
            raise ValueError(
                f"num_ranks={num_ranks} exceeds available devices "
                f"({len(devices)})")
        devices = devices[:num_ranks]
    # The topology layer owns mesh construction (parallel/mesh.py);
    # elastic recovery rebuilds the job through this same call with
    # the surviving device subset (init(comm=survivor_positions)).
    from .parallel.mesh import (data_parallel_mesh, expert_data_mesh,
                                model_expert_data_mesh)
    mesh = data_parallel_mesh(devices, axis_name=AXIS)
    # The 2-D (data, expert) mesh for expert-parallel MoE training
    # (docs/performance.md "Expert-parallel MoE"). Built from the
    # SAME device list as the 1-D mesh, so an elastic re-init over
    # survivors rebuilds it too — and validates the degree still
    # divides the shrunken world before any MoE program can run.
    exp_mesh = None
    if cfg.expert_parallel > 1:
        exp_mesh = expert_data_mesh(
            devices, expert_parallel=cfg.expert_parallel,
            data_axis=AXIS, expert_axis="ep")
    # The 3-D (data, expert, model) mesh for tensor-parallel dense
    # trunks (docs/performance.md "Composable parallelism"). The ep
    # axis is present even at size 1 so per-leaf sharding specs can
    # always name the full ("hvd", "ep", "model") axis set.
    mdl_mesh = None
    if cfg.model_parallel > 1:
        mdl_mesh = model_expert_data_mesh(
            devices, expert_parallel=cfg.expert_parallel,
            model_parallel=cfg.model_parallel,
            data_axis=AXIS, expert_axis="ep", model_axis="model")

    _state.config = cfg
    _state.devices = devices
    _state.mesh = mesh
    _state.expert_mesh = exp_mesh
    _state.model_mesh = mdl_mesh
    _state.num_ranks = len(devices)
    # Ranks are mesh positions, NOT device ids (device ids are not dense
    # across processes on every backend).
    local_positions = [i for i, d in enumerate(devices)
                       if d.process_index == jax.process_index()]
    _state.local_num_ranks = max(len(local_positions), 1)
    first_local = min(local_positions, default=0)
    _state.first_rank = first_local

    # Launcher-provided topology (one-process-per-chip deployments);
    # mirrors OMPI_COMM_WORLD_LOCAL_RANK-style discovery the reference
    # relies on (reference: test/common.py:26-59). Fallback: position of
    # this process's first device among the host's devices.
    _state.local_rank = int(os.environ.get("HOROVOD_TPU_LOCAL_RANK", 0))  # hvdlint: disable=HVD003 -- launcher-worker protocol var
    _state.local_size = int(os.environ.get("HOROVOD_TPU_LOCAL_SIZE",  # hvdlint: disable=HVD003 -- launcher-worker protocol var (the knob form is Config.tpu_local_size)
                                           _state.local_num_ranks))
    _state.cross_rank = int(os.environ.get("HOROVOD_TPU_CROSS_RANK",  # hvdlint: disable=HVD003 -- launcher-worker protocol var
                                           jax.process_index()))
    _state.cross_size = int(os.environ.get("HOROVOD_TPU_CROSS_SIZE",  # hvdlint: disable=HVD003 -- launcher-worker protocol var
                                           jax.process_count()))

    from .stats import create_stats
    from .timeline import create_timeline
    _state.stats = create_stats()
    # Multi-host: ONE global trace, written by process 0 (reference:
    # rank 0's writer consumes every rank's events, timeline.h:46-74).
    # Non-zero processes collect in memory and ship at shutdown.
    multihost = jax.process_count() > 1
    _state.timeline = create_timeline(
        cfg.timeline, enabled=bool(cfg.timeline),
        mark_cycles=cfg.timeline_mark_cycles,
        collect=multihost and jax.process_index() != 0,
        multihost=multihost)

    # Flight recorder BEFORE the engine: the engine caches diag.get()
    # at construction for its lock-free hot-path instrumentation
    # (docs/diagnostics.md). The membership digest ties dumps to the
    # participant set the events belong to.
    from . import diag
    from .diag import sentry as _sentry
    from .diag import xla_trace as _xla_trace
    from .ops.engine import _participants_digest
    diag.install(cfg, rank=first_local,
                 process_index=jax.process_index(),
                 digest=_participants_digest(mesh))
    _watch_jax_compiles()
    # XLA step tracer + perf sentry, both None unless their knobs
    # opt in (HOROVOD_XPROF_STEPS / HOROVOD_PERF_SENTRY): disabled
    # builds hold no tracer object and no profiler state.
    _xla_trace.install(cfg, rank=first_local)
    _sentry.install(cfg, rank=first_local)

    # Step-integrity guard + chaos injector, same BEFORE-the-engine
    # rule: the engine caches guard.get()/guard.inject.get() at
    # construction (docs/robustness.md). Both None unless
    # HOROVOD_GUARD / HOROVOD_GUARD_INJECT opt in.
    from . import guard
    guard.install(cfg, process_index=jax.process_index())

    from .ops.engine import EagerEngine
    _state.engine = EagerEngine(mesh=mesh, num_ranks=_state.num_ranks,
                                config=cfg, stats=_state.stats,
                                timeline=_state.timeline)
    # Hang watchdog (None unless HOROVOD_STALL_TIMEOUT_SECONDS > 0 —
    # the zero default is fully inert: no thread, no KV beacons).
    _state.diag_watchdog = diag.start_watchdog(_state.engine, cfg)
    if cfg.autotune:
        # Multi-host: only process 0 runs the tuning loop; its parameter
        # changes ride the coordinator's decision log so every process
        # applies them at the same decision index (reference SyncParams,
        # parameter_manager.cc:223-262). Non-zero processes apply
        # incoming autotune decisions in the engine and never tune.
        if jax.process_count() > 1 and jax.process_index() != 0:
            _logger.info("autotune: process %d defers to process 0's "
                         "synced parameters", jax.process_index())
        else:
            from .autotune import ParameterManager
            _state.autotuner = ParameterManager(cfg)
            if jax.process_count() > 1:
                _state.autotuner.sync_publish = \
                    _state.engine.publish_autotune
            _state.engine.autotuner = _state.autotuner

    # Runtime metrics: lifecycle counters, the stats/device-memory
    # collect hooks, and the export sinks (JSONL / Prometheus /
    # timeline counter splice) — see metrics.py and docs/observability.md.
    from . import metrics
    from .stats import register_metrics
    register_metrics(_state.stats)
    metrics.registry().set_collect_hook("device_memory",
                                        _collect_device_memory)
    _state.metrics_exporters = metrics.start_exporters(
        cfg, timeline=_state.timeline,
        process_index=jax.process_index())
    metrics.RUNTIME_INITS.inc()
    metrics.RUNTIME_UP.set(1)
    metrics.RUNTIME_RANKS.set(_state.num_ranks)
    metrics.MODEL_PARALLEL.set(cfg.model_parallel if mdl_mesh
                               is not None else 1)
    # The autoscaler's resize observable: worker PROCESSES in this
    # session (ranks count chips) — shrinks when an elastic recovery
    # re-inits over the survivors' devices (docs/elastic.md).
    metrics.ELASTIC_WORLD_SIZE.set(
        len({d.process_index for d in devices}))
    _record_elastic_restarts()
    _record_elastic_resize()

    _state.shutdown = False
    _state.initialized = True
    _logger.info("Started horovod_tpu with %d ranks over %d process(es); "
                 "eager dispatch %s",
                 _state.num_ranks, jax.process_count(),
                 f"overlapped (pipeline depth {cfg.pipeline_depth})"
                 if cfg.pipeline_depth > 0 else
                 "synchronous (HOROVOD_PIPELINE_DEPTH=0)")
    atexit.register(_shutdown_atexit)


_elastic_restarts_recorded = False


def _record_elastic_restarts():
    """Surface supervisor restarts in THIS worker's metrics registry
    (the launcher's own registry is never exported): the elastic
    supervisor stamps how many times it respawned this slot into the
    environment. Once per process — re-inits within one life (elastic
    recovery) are not restarts."""
    global _elastic_restarts_recorded
    if _elastic_restarts_recorded:
        return
    _elastic_restarts_recorded = True
    try:
        n = int(os.environ.get("HOROVOD_TPU_ELASTIC_RESTARTS", "0") or 0)  # hvdlint: disable=HVD003 -- supervisor-worker protocol var, stamped per restart
    except ValueError:
        n = 0
    if n > 0:
        from . import metrics
        metrics.ELASTIC_RESTARTS.inc(n)


_elastic_resize_recorded = False


def _record_elastic_resize():
    """Surface a gang resize in THIS worker's metrics registry: the
    autoscaling supervisor stamps the direction of the resize that
    relaunched this gang into the environment (run/run.py), because a
    grown world can only arrive by gang restart — the relaunched
    workers are the only processes left to count it. In-job shrinks are
    counted by the survivors in elastic/runner.py instead. Once per
    process, like _record_elastic_restarts."""
    global _elastic_resize_recorded
    if _elastic_resize_recorded:
        return
    _elastic_resize_recorded = True
    direction = os.environ.get("HOROVOD_TPU_ELASTIC_RESIZED", "")  # hvdlint: disable=HVD003 -- supervisor-worker protocol var, stamped per resize
    if direction in ("up", "down"):
        from . import metrics
        metrics.ELASTIC_RESIZES.labels(direction=direction).inc()


_mem_sampled_t = float("-inf")


def _collect_device_memory():
    """Low-rate device-memory gauges via ``jax.Device.memory_stats()``
    (backends without stats — CPU — simply publish nothing). Runs as a
    metrics collect hook, so the exporter thread's tick cadence is the
    sampling clock; throttled to the configured interval so an aggressive
    scraper cannot turn snapshotting into a per-device stats storm."""
    global _mem_sampled_t
    import time as _time

    from . import metrics
    cfg = _state.config
    interval = cfg.metrics_interval if cfg is not None else 10.0
    now = _time.perf_counter()
    if now - _mem_sampled_t < interval:
        return
    _mem_sampled_t = now
    for d in jax.local_devices():
        try:
            st = d.memory_stats()
        except Exception:  # noqa: BLE001 — backend may not implement it
            st = None
        if not st:
            continue
        label = str(d.id)
        if "bytes_in_use" in st:
            metrics.DEVICE_BYTES_IN_USE.labels(device=label).set(
                st["bytes_in_use"])
        if "peak_bytes_in_use" in st:
            metrics.DEVICE_PEAK_BYTES.labels(device=label).set(
                st["peak_bytes_in_use"])
        if "bytes_limit" in st:
            metrics.DEVICE_BYTES_LIMIT.labels(device=label).set(
                st["bytes_limit"])


def _shutdown_atexit():
    try:
        if _state.initialized and not _state.shutdown:
            shutdown()
    except Exception:  # pragma: no cover - atexit best effort
        pass


def shutdown():
    """Shut down and dump profiling stats.

    Parity with ``horovod_shutdown``: rank 0 writes the per-collective counter /
    time-histogram dump to ``profiler.txt`` on the way out (reference fork:
    operations.cc:1934-1962 + write_to_file at operations.cc:219-317).
    """
    with _state.lock:
        if not _state.initialized or _state.shutdown:
            return
        # Watchdog first: a beacon/stall scan must not race the engine
        # teardown it observes.
        if _state.diag_watchdog is not None:
            _state.diag_watchdog.stop()
            _state.diag_watchdog = None
        if _state.engine is not None:
            _state.engine.shutdown()
        # Lifecycle gauges flip BEFORE the exporters' final export, so the
        # persistent artifacts (.prom textfile, last JSONL line, timeline
        # splice) of a cleanly shut-down job report hvd_up 0 — an
        # up/down alert on the textfile must not ring forever after exit.
        from . import metrics
        metrics.RUNTIME_SHUTDOWNS.inc()
        metrics.RUNTIME_UP.set(0)
        # Exporters close BEFORE the timeline exchange/close: their final
        # tick splices the closing counter values into the trace while it
        # can still accept events (and, on collect-mode processes, before
        # the collected list ships to process 0) and flushes a last
        # JSONL/textfile snapshot.
        if _state.metrics_exporters is not None:
            _state.metrics_exporters.close()
            _state.metrics_exporters = None
        _exchange_timeline()
        if (_state.stats is not None and rank() == 0
                and not _state.config.profiler_disable):
            try:
                _state.stats.write_to_file(_state.config.profiler_path)
            except OSError as e:
                _logger.warning("could not write profiler dump: %s", e)
        # Paper-parity wire profiler (HOROVOD_WIRE_PROFILE=1): the
        # per-message-size wire latency table (hvd_wire_seconds by
        # power-of-two size bin — the fork's time_map_allreduce) lands
        # as profiler.csv next to the counter dump above.
        if _state.config.wire_profile and rank() == 0:
            try:
                metrics.dump_wire_profile(_state.config.wire_profile_path)
            except OSError as e:
                _logger.warning("could not write wire profile CSV: %s", e)
        if _state.timeline is not None:
            _state.timeline.close()
        metrics.registry().remove_collect_hook("collective_stats")
        metrics.registry().remove_collect_hook("device_memory")
        from . import diag, guard
        from .diag import sentry as _sentry
        from .diag import xla_trace as _xla_trace
        # Tracer first (stops any still-active device capture), then the
        # sentry (persists its EMA baselines) — both no-ops when their
        # knobs never armed anything.
        _xla_trace.uninstall()
        _sentry.uninstall()
        diag.uninstall()
        guard.uninstall()
        _state.shutdown = True
        _state.initialized = False


def _exchange_timeline():
    """Multi-host global timeline: at shutdown, non-zero processes publish
    their collected events over the coordination KV store; process 0
    splices them into its trace before closing (reference: rank 0 writes
    one file covering every rank's tensors, timeline.h:46-74)."""
    import json as _json
    tl = _state.timeline
    if tl is None or not getattr(tl, "enabled", False):
        return
    engine = _state.engine
    if engine is None or engine._coord is None:
        return
    coord = engine._coord
    ns = f"{coord._ns}/tl"
    try:
        if getattr(tl, "collected", None) is not None:
            tl.drain()
            blob = _json.dumps({"epoch": tl.epoch,
                                "events": tl.collected}).encode()
            coord._client.key_value_set_bytes(
                f"{ns}/{coord.pid}", blob, allow_overwrite=True)
        elif coord.pid == 0:
            for p in (q for q in coord._pid_list() if q != 0):
                try:
                    blob = coord._client.blocking_key_value_get_bytes(
                        f"{ns}/{p}", 5000)
                except Exception:  # noqa: BLE001 — peer may have died; its timeline is best-effort
                    _logger.warning(
                        "timeline merge: no events from process %d "
                        "(crashed or exited without shutdown)", p)
                    # Keep the dead process's pid space visible in the
                    # merged trace (merge_remote emits a placeholder row
                    # for an empty event list).
                    tl.merge_remote([], tl.epoch, label=f"p{p}")
                    continue
                payload = _json.loads(bytes(blob).decode())
                tl.merge_remote(payload["events"], payload["epoch"],
                                label=f"p{p}")
    except Exception:  # noqa: BLE001 — timeline exchange must never block shutdown
        _logger.warning("timeline exchange failed", exc_info=True)


def is_initialized():
    return _state.initialized and not _state.shutdown


def _check_init():
    if not is_initialized():
        raise NotInitializedError()


def state():
    """Internal: the live global state (engine, mesh, config...)."""
    _check_init()
    return _state


def mesh():
    """The global 1-D collective mesh (axis name ``hvd``)."""
    _check_init()
    return _state.mesh


def expert_mesh():
    """The 2-D (data, expert) mesh — axes ``("hvd", "ep")`` — built when
    ``HOROVOD_EXPERT_PARALLEL > 1`` (docs/performance.md "Expert-parallel
    MoE"). Raises when expert parallelism was not configured at init."""
    _check_init()
    if _state.expert_mesh is None:
        from .exceptions import HorovodError
        raise HorovodError(
            "no expert mesh: set HOROVOD_EXPERT_PARALLEL (or "
            "Config.expert_parallel) to a degree > 1 dividing the world "
            "size before hvd.init()")
    return _state.expert_mesh


def expert_parallel_size():
    """Configured expert-parallel degree (1 = no expert mesh)."""
    _check_init()
    return (_state.expert_mesh.shape["ep"]
            if _state.expert_mesh is not None else 1)


def model_mesh():
    """The 3-D (data, expert, model) mesh — axes
    ``("hvd", "ep", "model")`` — built when ``HOROVOD_MODEL_PARALLEL > 1``
    (docs/performance.md "Composable parallelism"). The expert axis is
    present even at degree 1 so sharding specs can always reference the
    full axis set. Raises when model parallelism was not configured at
    init."""
    _check_init()
    if _state.model_mesh is None:
        from .exceptions import HorovodError
        raise HorovodError(
            "no model mesh: set HOROVOD_MODEL_PARALLEL (or "
            "Config.model_parallel) to a degree > 1 such that "
            "expert_parallel * model_parallel divides the world size "
            "before hvd.init()")
    return _state.model_mesh


def model_parallel_size():
    """Configured model-parallel degree (1 = no model mesh)."""
    _check_init()
    return (_state.model_mesh.shape["model"]
            if _state.model_mesh is not None else 1)


def rank():
    """First rank owned by this process (== process rank when launched
    one-process-per-chip). Reference: horovod_rank (operations.cc:1968)."""
    _check_init()
    return _state.first_rank


def size():
    """Total number of ranks (chips). Reference: horovod_size
    (operations.cc:1976)."""
    _check_init()
    return _state.num_ranks


def local_rank():
    """Rank within the host. Reference: horovod_local_rank
    (operations.cc:1972)."""
    _check_init()
    return _state.local_rank


def local_size():
    """Ranks on this host. Reference: horovod_local_size
    (operations.cc:1980)."""
    _check_init()
    return _state.local_size


def cross_rank():
    """Host index (the reference's cross communicator rank,
    operations.cc:1133)."""
    _check_init()
    return _state.cross_rank


def cross_size():
    """Number of hosts."""
    _check_init()
    return _state.cross_size


def mpi_threads_supported():
    """API parity with hvd.mpi_threads_supported() (reference:
    common/basics.py:57-66, operations.cc:1996). There is no MPI; the eager
    engine is thread-safe, which is what callers actually probe for."""
    _check_init()
    return True
