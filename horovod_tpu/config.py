"""Environment-variable configuration, read once at init.

The reference configures everything through ``HOROVOD_*`` env vars parsed once
when the background thread starts (reference: horovod/common/operations.cc:1164-1265;
canonical name list horovod/common/operations.h:33-47). We keep the same names and
defaults so reference users' deployment scripts carry over unchanged, plus the
fork's ``PADDING_ALGO`` knob (reference: horovod/common/operations.h:47,
operations.cc:1189-1195).
"""

import dataclasses
import os

# Fusion-buffer alignment unit, bytes (reference: horovod/common/operations.h:30).
FUSION_BUFFER_ATOMIC_UNIT = 64


def _env_int(name, default):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        return default


def _env_float(name, default):
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        return default


def _env_flag(name):
    return os.environ.get(name, "") not in ("", "0", "false", "False")


@dataclasses.dataclass
class Config:
    # Tensor fusion threshold in bytes; default 64 MiB
    # (reference: operations.cc:1176-1186).
    fusion_threshold: int = 64 * 1024 * 1024
    # Coordination cycle time in ms; default 5 ms (reference: operations.cc:1196-1203).
    cycle_time_ms: float = 5.0
    # Response cache capacity; default 1024 (reference: global_state.h:169,
    # operations.cc:1205-1212).
    cache_capacity: int = 1024
    # Timeline output path ('' disables) (reference: operations.cc:1164-1171).
    timeline: str = ""
    timeline_mark_cycles: bool = False
    # Stall-check knobs (reference: global_state.h:70-78, operations.cc:1172-1174).
    stall_check_disable: bool = False
    stall_check_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0
    # Hierarchical collective toggles (reference: operations.cc:1215-1263).
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False
    # Autotune (reference: operations.cc:1228-1244).
    autotune: bool = False
    autotune_log: str = ""
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8
    # Disable the multi-host steady-state epoch-token bypass (full
    # RequestList published every cycle). Measurement/debug knob — the
    # reference's HOROVOD_CACHE_CAPACITY=0 disables its response cache
    # the same way (response_cache.h:44); kept separate here because the
    # in-process response cache and the coordinator bypass are distinct
    # tiers.
    coordinator_bypass_disable: bool = False
    # Disable the multi-host control-plane ticker thread (the reference's
    # ~5 ms background coordination cadence, operations.cc:985,1434-1449;
    # here a control-plane-ONLY daemon — publish + coordinate, decisions
    # still applied by application threads). Debug/measurement knob.
    ticker_disable: bool = False
    # Pod-scale control plane (docs/controlplane.md). Tree-aggregated
    # negotiation fan-in: participants are grouped into slices of
    # `fanout` (by pid order); the first pid of each group batches its
    # group's request blobs (plus liveness/goodbye beacons under
    # HOROVOD_ELASTIC) into ONE combined KV write, and rank 0 reads the
    # combined blobs — O(fanout + world/fanout) reads per round instead
    # of O(world). 0 (default) keeps the rank-0 star. Values < 2 are
    # treated as off; the tree only engages when world > fanout.
    coord_tree_fanout: int = 0
    # Static-schedule graduation (docs/controlplane.md): after this many
    # consecutive rounds answered by the SAME replayed decision, a
    # process's steady-state pending set graduates to a negotiation-free
    # fixed schedule — no publish, no fetch, entries executed straight
    # from the shared decision registry. Demoted instantly (at the same
    # decision index everywhere) on membership change, shape churn, or
    # any abort/stall/shutdown decision. 0 (default) disables.
    coord_graduate_after: int = 0
    # Upper bound on how stale a graduated process's view of the
    # decision log may get: while running the static schedule it
    # re-fetches the log at least this often (demotion latency bound).
    coord_graduate_refresh_seconds: float = 2.0
    # Overlap pipeline (docs/performance.md): how many fused wire buckets
    # may be dispatched-but-unread at once. The eager engine launches the
    # fused device op without blocking, defers the device->host readback
    # to a completion thread, and keeps filling the next fusion bucket
    # while the previous one is in flight — the reference's background
    # thread overlapping gradient exchange with backward compute. 0 =
    # synchronous fallback (dispatch + blocking readback inline, the
    # pre-pipeline behavior). Autotunable (HOROVOD_AUTOTUNE=1).
    pipeline_depth: int = 2
    # Input-data prefetch depth (data/loader.py): how many batches the
    # DistributedDataset's background producer may assemble (and
    # device_put) ahead of the training loop. 0 = synchronous fallback
    # (batch built inline when asked for — the pre-subsystem behavior),
    # mirroring HOROVOD_PIPELINE_DEPTH's contract. Autotuned off the
    # measured input-wait when HOROVOD_AUTOTUNE=1 (applied at epoch
    # boundaries; a user's explicit 0 is never overridden).
    data_prefetch: int = 2
    # Donate the fusion buffer's device array to the fused wire program so
    # XLA writes the reduction in place instead of allocating a second
    # buffer. -1 = auto (on for accelerator backends, off on CPU where
    # jax may zero-copy-alias the host fusion buffer); 0/1 force.
    fusion_donate: int = -1
    # Elastic fault tolerance (elastic/; no 0.16 reference analog — the
    # corresponding upstream feature is v0.20 "Elastic Horovod").
    # HOROVOD_ELASTIC=1 turns on liveness heartbeats + the coordinator's
    # lost-worker detector; a worker whose heartbeat stops for longer than
    # the timeout is declared lost and in-flight collectives abort with
    # WorkerLostError instead of hanging. The settle window is how long
    # the rendezvous leader waits for stragglers after quorum before
    # fixing the surviving membership.
    elastic: bool = False
    elastic_timeout_seconds: float = 10.0
    elastic_settle_seconds: float = 1.0
    # Preemption grace (docs/elastic.md "Autoscaling & preemption"): when
    # > 0, elastic.run installs a SIGTERM handler that finishes the
    # current step, commits, writes a grace snapshot (elastic_grace_dir),
    # announces a PLANNED departure through the coordinator (peers
    # re-shard immediately instead of waiting out the lost-worker
    # timeout), and exits EX_PREEMPTED — all within the grace window,
    # with a watchdog that force-saves the last commit at the deadline.
    # 0 (the default) leaves SIGTERM's default die-now semantics intact.
    elastic_grace_seconds: float = 0.0
    elastic_grace_dir: str = ""
    # SIGTERM -> SIGKILL escalation deadline used by the launcher/task
    # service teardown paths; also the supervisor's extra allowance past
    # the grace window before a drained worker is hard-killed.
    elastic_drain_seconds: float = 3.0
    # Fork profiling knob: pad message sizes to the next power of two
    # (reference fork: ops/mpi_operations.cc:24-63, PADDING_ALGO env).
    padding_algo: int = 0
    # Device-resident gradient exchange (docs/performance.md): opted-in
    # eager allreduces (hvd.allreduce(..., to_host=False) and the
    # exchange_gradients helper) keep the fused result on device — the
    # per-tensor outputs are sliced/cast out of the fused buffer inside
    # the same jitted wire program, so synchronize() waits only on
    # dispatch, never on a device->host readback. -1 = auto (the fast
    # path serves opted-in callers), 1 = same, explicit; 0 = exact
    # pre-device-resident behavior (to_host is ignored and every eager
    # result is host numpy).
    device_resident: int = -1
    # Compiled hot loop (ops/step_program.py; docs/performance.md
    # "Compiled hot loop"): hvd.compiled_train_step runs forward,
    # backward, fused gradient exchange and optimizer apply as ONE
    # jitted, buffer-donated XLA program. -1 = auto (enabled whenever
    # the device-resident path is, i.e. device_resident != 0); 0 =
    # always fall back to the eager/legacy step; 1 = force on even
    # under HOROVOD_DEVICE_RESIDENT=0.
    step_program: int = -1
    # How many distinct step-program signatures (batch shapes / dtypes /
    # optimizer layouts) one CompiledTrainStep may compile before each
    # further NEW signature falls back to the eager path instead of
    # recompiling (shape-churn protection; docs/troubleshooting.md "my
    # compiled step keeps recompiling"). Minimum 1.
    step_program_churn_limit: int = 8
    # Paper-parity wire profiler (the fork's time_map_allreduce): record
    # per-message-size wire latency histograms (hvd_wire_seconds, labeled
    # by power-of-two size bin) and dump them as profiler.csv at
    # shutdown. Device-resident buckets are only *measured* in this mode
    # (measuring a wire span requires blocking on the result once).
    wire_profile: bool = False
    wire_profile_path: str = "profiler.csv"
    # Per-collective stats dump path (fork parity: profiler.txt written on
    # shutdown by rank 0, reference: operations.cc:1934-1962).
    profiler_path: str = "profiler.txt"
    profiler_disable: bool = False
    # Runtime metrics exporters (metrics.py). metrics_dir enables the JSONL
    # + Prometheus-textfile sinks; metrics_port >= 0 enables the HTTP scrape
    # endpoint (0 binds an ephemeral port); metrics_interval is the export
    # cadence in seconds (also the device-memory sampling floor).
    metrics_dir: str = ""
    metrics_port: int = -1
    # Scrape-endpoint bind address. Loopback by default: /metrics is
    # unauthenticated, so reaching it from another host (a Prometheus
    # scraper) is an explicit opt-in (HOROVOD_METRICS_BIND=0.0.0.0).
    metrics_bind: str = "127.0.0.1"
    metrics_interval: float = 10.0
    # Collective flight recorder + hang diagnosis (diag/;
    # docs/diagnostics.md). flight_buffer is the per-rank ring capacity in
    # events (rounded up to a power of two; 0 disables recording).
    # stall_timeout_seconds > 0 starts the hang watchdog: any collective
    # in-flight past the timeout triggers a durable flight dump and (on
    # process 0) a desync report; 0 (default) is fully inert — no thread,
    # no KV beacons. diag_dir is where flight-rank<N>.json /
    # desync-report.json land ('' = CWD when a dump is triggered).
    flight_buffer: int = 4096
    stall_timeout_seconds: float = 0.0
    diag_dir: str = ""
    # On-demand XLA device tracing (diag/xla_trace.py;
    # docs/diagnostics.md "Seeing inside the compiled step").
    # xprof_steps > 0 arms a one-shot capture at init: the first N
    # compiled steps are recorded with jax.profiler into a
    # xla-trace-<seq> directory under diag_dir and parsed into per-phase
    # device-time totals (hvd.trace_steps(n) is the programmatic form).
    # 0 (default) is fully inert — no tracer object, no profiler state.
    xprof_steps: int = 0
    # Perf-regression sentry (diag/sentry.py): per-signature EMA
    # baseline of step time and MFU persisted under metrics_dir as
    # perf-baseline.json. A step slower (or an MFU lower) than the
    # baseline by more than perf_sentry_threshold increments
    # hvd_perf_regressions_total, records a flight-recorder event and
    # auto-arms one trace window. Off (default) = no state, no I/O.
    perf_sentry: bool = False
    perf_sentry_threshold: float = 0.25
    # Peak per-chip FLOPs override for MFU accounting (hvd_step_mfu,
    # bench.py mfu). 0 (default) = derive from the device kind
    # (hardware.py table); CPU and unknown accelerators report no MFU
    # unless this is set.
    peak_flops: float = 0.0
    # Step-integrity guard (guard/; docs/robustness.md). Everything
    # defaults OFF: with the defaults the engine and optimizer paths are
    # bit-identical to a build without the guard. HOROVOD_GUARD=1 turns
    # on in-graph gradient-health checks (per-bucket isfinite + norm on
    # the reduced wire buffer) with the policy ladder: every bad step is
    # skipped; after guard_lr_backoff_steps consecutive bad steps the
    # learning rate is multiplied by guard_lr_backoff_factor; after
    # guard_bad_step_limit consecutive bad steps training rolls back to
    # the last elastic.State commit.
    guard: bool = False
    guard_bad_step_limit: int = 3
    guard_lr_backoff_steps: int = 2
    guard_lr_backoff_factor: float = 0.5
    # Cross-replica divergence probe cadence in steps (0 = off): a cheap
    # parameter digest is allgathered and compared every N steps; on
    # mismatch the guard records the event, dumps a flight post-mortem
    # and repairs by broadcasting the majority replica's parameters.
    guard_divergence_interval: int = 0
    # Bounded collective retry (HOROVOD_GUARD_RETRY): how many times a
    # transient wire/dispatch failure is retried with exponential backoff
    # before escalating to the normal abort path. 0 (default) = exact
    # legacy behavior: the first failure propagates immediately.
    guard_retry: int = 0
    guard_retry_deadline_seconds: float = 30.0
    guard_retry_base_seconds: float = 0.05
    # Deterministic chaos injection (guard/inject.py): ';'-separated specs
    # like "nan,name=hvd.grads.0,step=2,rank=0" / "fail,op=allreduce,
    # count=1" / "corrupt,step=1" / "delay,seconds=0.2,count=1".
    # Empty (default) = no injection hooks installed.
    guard_inject: str = ""
    # Control-plane KV client retry (utils/kvstore.py): bounded retries
    # with jittered exponential backoff on transient CONNECTION errors
    # (refused/reset while establishing the per-request socket). Protocol
    # errors and DEADLINE_EXCEEDED timeouts are never retried.
    kv_retries: int = 2
    kv_retry_base_seconds: float = 0.05
    # Expert parallelism degree for the 2-D (data, expert) mesh
    # (parallel/mesh.py expert_data_mesh; docs/performance.md
    # "Expert-parallel MoE"). 1 (default) builds no expert mesh — the
    # runtime stays exactly the 1-D data-parallel topology. > 1 makes
    # init() lay the same devices out as (world/ep, ep) with axes
    # ("hvd", "ep"), expert axis innermost (contiguous devices, pure
    # ICI for the dispatch/combine alltoall). Must divide the world
    # size; validated at every init(), including elastic re-inits over
    # survivors.
    expert_parallel: int = 1
    # Tensor/model parallelism degree for the dense trunk on the 3-D
    # (data, expert, model) mesh (parallel/mesh.py model_expert_data_mesh;
    # docs/performance.md "Composable parallelism"). 1 (default) builds
    # no model mesh. > 1 makes init() lay the devices out as
    # (world/(ep*mp), ep, mp) with axes ("hvd", "ep", "model"), model
    # axis innermost (contiguous devices, pure ICI for the per-layer
    # activation all-reduce of head-sharded attention and column/row-
    # split FFN). expert_parallel * model_parallel must divide the world
    # size; validated at every init(), including elastic re-inits.
    model_parallel: int = 1
    # How many capacity slices the MoE dispatch/combine alltoall is
    # split into (ops/collectives.py alltoall_chunked): chunk k's
    # expert FFN overlaps chunk k+1's dispatch alltoall inside one XLA
    # program. 1 = unchunked (single alltoall round-trip); numerics are
    # bit-identical at every setting. Capacity must divide evenly —
    # non-dividing values fall back to the largest divisor below.
    moe_chunks: int = 1
    # How many layer-ordered buckets the compiled step's gradient
    # exchange is split into (ops/step_program.py): one psum call over
    # each bucket's leaves, so bucket L's psum can dispatch while bucket
    # L-1's backward still computes inside one donated XLA program. 1 =
    # one psum call over all leaves (the default), where XLA's
    # all-reduce combiner decides what travels together; every setting
    # gives the same values (per-element reductions are unaffected by
    # bucket boundaries). docs/performance.md "Bucketed
    # backward/exchange overlap".
    exchange_buckets: int = 1
    # Jit-path reduce-scatter/allgather bucket size in bytes
    # (ops/collectives.py bucketed_reducescatter_allgather): the fusion-
    # threshold analog for the sharded jit path — dtype runs are split
    # into buckets of at most this many bytes so XLA can pipeline them.
    reduce_scatter_bucket: int = 32 * 1024 * 1024
    # ZeRO sharding stage used by DistributedOptimizer when the call site
    # doesn't pass zero_stage= explicitly (optimizers.py): 0 = replicated
    # allreduce, 1 = optimizer-state sharding, 2 = gradient sharding,
    # 3 = parameter sharding (docs/performance.md "ZeRO stages & DCN
    # compression").
    zero_stage: int = 0
    # DCN-stage wire compression for the two-stage hierarchical gradient
    # exchange ('' = off, 'bf16', 'int8'): the intra-host ICI reduce runs
    # full precision and only the cross-host DCN hop is compressed, with
    # error-feedback residuals carried in the optimizer state.
    dcn_compression: str = ""
    # Ranks per ICI (intra-host) group for the DCN staging. 0 = auto:
    # the launcher-reported local size (runtime.local_size()). Must
    # divide the world size; out-of-range values disable staging.
    dcn_local_size: int = 0
    # Per-execution jit collective accounting (stats.py): when on, jitted
    # collectives record per-execution counts through a debug callback on
    # the axis's rank-0 shard instead of trace-time counts only. Costs a
    # host callback per collective execution — measurement knob.
    profiler_jit_callbacks: bool = False
    # Where TelemetryCallback drops its per-rank autoscale signal files
    # ('' disables; docs/elastic.md "Autoscaling & preemption").
    elastic_policy_dir: str = ""
    # Inference serving (serve/; docs/serving.md "Knobs"). Pool size of
    # the paged KV cache in pages (page 0 is the reserved null page) and
    # tokens per page — together they bound resident cache rows at
    # (serve_pages - 1) * serve_page_size across all live sequences.
    serve_pages: int = 512
    serve_page_size: int = 16
    # Continuous-batch width cap (sequences decoding per step) and the
    # bounded admission queue's depth (submissions past it push back —
    # docs/serving.md "Backpressure").
    serve_max_batch: int = 8
    serve_queue_depth: int = 64
    # Per-token p99 latency SLO the serve engine exports next to its
    # queue depth for the autoscale policy (elastic/policy.py
    # p99_high=; docs/serving.md "SLO-driven elasticity").
    serve_slo_p99_seconds: float = 0.5
    # Spark driver: seconds to wait for all executors to register before
    # failing the job (docs/spark.md).
    spark_start_timeout: int = 600
    # Hierarchical-collective local tier size override (ops/engine.py
    # _init_hierarchical). 0 = auto: group contiguous rank runs by owning
    # process. Set explicitly when the per-process grouping doesn't match
    # the physical ICI domain (e.g. multi-process-per-host tests).
    tpu_local_size: int = 0
    # Launcher (run/): seconds each worker gets to reach its first
    # rendezvous before the job is declared failed, and the opt-in that
    # forces the RPC driver/task-service launch path for local hosts.
    start_timeout: int = 30
    launch_rpc: bool = False
    # Logging (reference: common/logging.{h,cc}).
    log_level: str = "WARNING"
    log_hide_time: bool = False

    @classmethod
    def from_env(cls):
        c = cls()
        c.fusion_threshold = _env_int("HOROVOD_FUSION_THRESHOLD", c.fusion_threshold)
        # HOROVOD_CYCLE_TIME accepts fractional ms like the reference
        # (operations.cc:1196-1203 parses it as float).
        c.cycle_time_ms = _env_float("HOROVOD_CYCLE_TIME", c.cycle_time_ms)
        c.cache_capacity = _env_int("HOROVOD_CACHE_CAPACITY", c.cache_capacity)
        c.timeline = os.environ.get("HOROVOD_TIMELINE", "")
        c.timeline_mark_cycles = _env_flag("HOROVOD_TIMELINE_MARK_CYCLES")
        c.stall_check_disable = _env_flag("HOROVOD_STALL_CHECK_DISABLE")
        c.stall_check_time_seconds = _env_float(
            "HOROVOD_STALL_CHECK_TIME_SECONDS", c.stall_check_time_seconds)
        c.stall_shutdown_time_seconds = _env_float(
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS",
            c.stall_shutdown_time_seconds)
        c.hierarchical_allreduce = _env_flag("HOROVOD_HIERARCHICAL_ALLREDUCE")
        c.hierarchical_allgather = _env_flag("HOROVOD_HIERARCHICAL_ALLGATHER")
        c.coordinator_bypass_disable = _env_flag(
            "HOROVOD_COORDINATOR_BYPASS_DISABLE")
        c.ticker_disable = _env_flag("HOROVOD_TPU_TICKER_DISABLE")
        c.coord_tree_fanout = max(_env_int("HOROVOD_COORD_TREE_FANOUT",
                                           c.coord_tree_fanout), 0)
        c.coord_graduate_after = max(_env_int("HOROVOD_COORD_GRADUATE_AFTER",
                                              c.coord_graduate_after), 0)
        c.coord_graduate_refresh_seconds = max(_env_float(
            "HOROVOD_COORD_GRADUATE_REFRESH_SECONDS",
            c.coord_graduate_refresh_seconds), 0.05)
        c.pipeline_depth = max(_env_int("HOROVOD_PIPELINE_DEPTH",
                                        c.pipeline_depth), 0)
        c.data_prefetch = max(_env_int("HOROVOD_DATA_PREFETCH",
                                       c.data_prefetch), 0)
        c.fusion_donate = _env_int("HOROVOD_FUSION_DONATE", c.fusion_donate)
        c.autotune = _env_flag("HOROVOD_AUTOTUNE")
        c.autotune_log = os.environ.get("HOROVOD_AUTOTUNE_LOG", "")
        c.autotune_warmup_samples = _env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES",
                                             c.autotune_warmup_samples)
        c.autotune_steps_per_sample = _env_int("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE",
                                               c.autotune_steps_per_sample)
        c.elastic = _env_flag("HOROVOD_ELASTIC")
        c.elastic_timeout_seconds = _env_float(
            "HOROVOD_ELASTIC_TIMEOUT_SECONDS", c.elastic_timeout_seconds)
        c.elastic_settle_seconds = _env_float(
            "HOROVOD_ELASTIC_SETTLE_SECONDS", c.elastic_settle_seconds)
        c.elastic_grace_seconds = _env_float(
            "HOROVOD_ELASTIC_GRACE_SECONDS", c.elastic_grace_seconds)
        c.elastic_grace_dir = os.environ.get("HOROVOD_ELASTIC_GRACE_DIR",
                                             c.elastic_grace_dir)
        c.elastic_drain_seconds = _env_float(
            "HOROVOD_ELASTIC_DRAIN_SECONDS", c.elastic_drain_seconds)
        c.padding_algo = _env_int("PADDING_ALGO", 0)
        c.device_resident = _env_int("HOROVOD_DEVICE_RESIDENT",
                                     c.device_resident)
        c.step_program = _env_int("HOROVOD_STEP_PROGRAM", c.step_program)
        c.step_program_churn_limit = max(_env_int(
            "HOROVOD_STEP_PROGRAM_CHURN_LIMIT",
            c.step_program_churn_limit), 1)
        c.wire_profile = _env_flag("HOROVOD_WIRE_PROFILE")
        c.wire_profile_path = os.environ.get("HOROVOD_WIRE_PROFILE_PATH",
                                             c.wire_profile_path)
        c.profiler_path = os.environ.get("HOROVOD_PROFILER_PATH", c.profiler_path)
        c.profiler_disable = _env_flag("HOROVOD_PROFILER_DISABLE")
        c.metrics_dir = os.environ.get("HOROVOD_METRICS_DIR", "")
        c.metrics_port = _env_int("HOROVOD_METRICS_PORT", c.metrics_port)
        c.metrics_bind = os.environ.get("HOROVOD_METRICS_BIND",
                                        c.metrics_bind)
        c.metrics_interval = _env_float("HOROVOD_METRICS_INTERVAL",
                                        c.metrics_interval)
        c.flight_buffer = max(_env_int("HOROVOD_FLIGHT_BUFFER",
                                       c.flight_buffer), 0)
        c.stall_timeout_seconds = _env_float(
            "HOROVOD_STALL_TIMEOUT_SECONDS", c.stall_timeout_seconds)
        c.diag_dir = os.environ.get("HOROVOD_DIAG_DIR", c.diag_dir)
        c.xprof_steps = max(_env_int("HOROVOD_XPROF_STEPS",
                                     c.xprof_steps), 0)
        c.perf_sentry = _env_flag("HOROVOD_PERF_SENTRY")
        c.perf_sentry_threshold = max(_env_float(
            "HOROVOD_PERF_SENTRY_THRESHOLD", c.perf_sentry_threshold), 0.0)
        c.peak_flops = max(_env_float("HOROVOD_PEAK_FLOPS",
                                      c.peak_flops), 0.0)
        c.guard = _env_flag("HOROVOD_GUARD")
        c.guard_bad_step_limit = max(_env_int(
            "HOROVOD_GUARD_BAD_STEPS", c.guard_bad_step_limit), 1)
        c.guard_lr_backoff_steps = max(_env_int(
            "HOROVOD_GUARD_LR_BACKOFF_STEPS", c.guard_lr_backoff_steps), 1)
        c.guard_lr_backoff_factor = _env_float(
            "HOROVOD_GUARD_LR_BACKOFF_FACTOR", c.guard_lr_backoff_factor)
        c.guard_divergence_interval = max(_env_int(
            "HOROVOD_GUARD_DIVERGENCE_INTERVAL",
            c.guard_divergence_interval), 0)
        c.guard_retry = max(_env_int("HOROVOD_GUARD_RETRY",
                                     c.guard_retry), 0)
        c.guard_retry_deadline_seconds = _env_float(
            "HOROVOD_GUARD_RETRY_DEADLINE_SECONDS",
            c.guard_retry_deadline_seconds)
        c.guard_retry_base_seconds = _env_float(
            "HOROVOD_GUARD_RETRY_BASE_SECONDS", c.guard_retry_base_seconds)
        c.guard_inject = os.environ.get("HOROVOD_GUARD_INJECT",
                                        c.guard_inject)
        c.kv_retries = max(_env_int("HOROVOD_KV_RETRIES", c.kv_retries), 0)
        c.kv_retry_base_seconds = _env_float(
            "HOROVOD_KV_RETRY_BASE_SECONDS", c.kv_retry_base_seconds)
        c.expert_parallel = max(_env_int("HOROVOD_EXPERT_PARALLEL",
                                         c.expert_parallel), 1)
        c.model_parallel = max(_env_int("HOROVOD_MODEL_PARALLEL",
                                        c.model_parallel), 1)
        c.moe_chunks = max(_env_int("HOROVOD_MOE_CHUNKS",
                                    c.moe_chunks), 1)
        c.exchange_buckets = max(_env_int("HOROVOD_EXCHANGE_BUCKETS",
                                          c.exchange_buckets), 1)
        c.reduce_scatter_bucket = max(_env_int(
            "HOROVOD_REDUCE_SCATTER_BUCKET", c.reduce_scatter_bucket), 1)
        c.zero_stage = min(max(_env_int("HOROVOD_ZERO_STAGE",
                                        c.zero_stage), 0), 3)
        c.dcn_compression = os.environ.get("HOROVOD_DCN_COMPRESSION",
                                           c.dcn_compression)
        c.dcn_local_size = max(_env_int("HOROVOD_DCN_LOCAL_SIZE",
                                        c.dcn_local_size), 0)
        c.profiler_jit_callbacks = _env_flag("HOROVOD_PROFILER_JIT_CALLBACKS")
        c.serve_pages = max(_env_int("HOROVOD_SERVE_PAGES",
                                     c.serve_pages), 2)
        c.serve_page_size = max(_env_int("HOROVOD_SERVE_PAGE_SIZE",
                                         c.serve_page_size), 1)
        c.serve_max_batch = max(_env_int("HOROVOD_SERVE_MAX_BATCH",
                                         c.serve_max_batch), 1)
        c.serve_queue_depth = max(_env_int("HOROVOD_SERVE_QUEUE_DEPTH",
                                           c.serve_queue_depth), 1)
        c.serve_slo_p99_seconds = max(_env_float(
            "HOROVOD_SERVE_SLO_P99_SECONDS", c.serve_slo_p99_seconds),
            0.0)
        c.elastic_policy_dir = os.environ.get("HOROVOD_ELASTIC_POLICY_DIR",
                                              c.elastic_policy_dir)
        c.spark_start_timeout = max(_env_int(
            "HOROVOD_SPARK_START_TIMEOUT", c.spark_start_timeout), 1)
        c.tpu_local_size = _env_int("HOROVOD_TPU_LOCAL_SIZE",
                                    c.tpu_local_size)
        c.start_timeout = max(_env_int("HOROVOD_START_TIMEOUT",
                                       c.start_timeout), 1)
        c.launch_rpc = _env_flag("HOROVOD_LAUNCH_RPC")
        # The fork-parity dumps (profiler.txt / profiler.csv) default into
        # HOROVOD_METRICS_DIR when one is configured and no explicit path
        # overrides them — keeps test/bench runs from littering the CWD.
        # HOROVOD_DIAG_DIR is the second-choice home: diag-only runs
        # (bench/chaos smokes set it without a metrics dir) used to drop
        # profiler.txt in the CWD at shutdown, recreating the repo-root
        # stray PR 13 removed.
        if c.metrics_dir:
            if "HOROVOD_PROFILER_PATH" not in os.environ:
                c.profiler_path = os.path.join(c.metrics_dir,
                                               "profiler.txt")
            if "HOROVOD_WIRE_PROFILE_PATH" not in os.environ:
                c.wire_profile_path = os.path.join(c.metrics_dir,
                                                   "profiler.csv")
        elif c.diag_dir:
            if "HOROVOD_PROFILER_PATH" not in os.environ:
                c.profiler_path = os.path.join(c.diag_dir, "profiler.txt")
            if "HOROVOD_WIRE_PROFILE_PATH" not in os.environ:
                c.wire_profile_path = os.path.join(c.diag_dir,
                                                   "profiler.csv")
        c.log_level = os.environ.get("HOROVOD_LOG_LEVEL", c.log_level)
        c.log_hide_time = _env_flag("HOROVOD_LOG_HIDE_TIME")
        return c


def next_power_of_two(n):
    """Round up to the next power of two (fork padding experiment parity;
    reference: horovod/common/ops/mpi_operations.cc:24-40)."""
    if n <= 1:
        return 1
    return 1 << (int(n - 1).bit_length())
