"""horovod_tpu — a TPU-native distributed-training framework with the
capability surface of Horovod 0.16.2 (reference: /root/reference).

Public API parity map (reference: horovod/torch/__init__.py,
horovod/tensorflow/__init__.py, horovod/common/basics.py):

- ``init() / shutdown() / rank() / size() / local_rank() / local_size() /
  mpi_threads_supported()`` — runtime lifecycle over jax.distributed + a
  device Mesh instead of MPI (runtime.py).
- ``allreduce[_async] / allgather[_async] / broadcast[_async] / alltoall /
  poll / synchronize`` — eager handle-based collectives through the in-process
  engine (ops/engine.py); name-keyed, fused, cached, stall-checked like the
  reference coordinator.
- ``horovod_tpu.ops.*`` — the jit-native functional collectives for use inside
  ``jax.jit``/``shard_map`` programs (the fast path; XLA owns fusion and
  scheduling there).
- ``Compression`` — fp16/bf16 wire compression (ops/compression.py).
- ``DistributedOptimizer`` (optax) + ``broadcast_parameters`` /
  ``broadcast_optimizer_state`` — optimizer integration (optimizers.py).
- ``metrics_snapshot()`` — the process-wide runtime metrics registry
  (metrics.py; exporters configured via HOROVOD_METRICS_DIR /
  HOROVOD_METRICS_PORT — docs/observability.md).
- ``elastic`` — fault-tolerant training: worker-failure detection,
  commit/rollback state, re-rendezvous recovery (beyond the 0.16
  reference; the upstream analog is v0.20 Elastic Horovod —
  docs/elastic.md).
- ``data`` — the distributed input subsystem: deterministic
  seed-driven sharding with the equal-steps guarantee, background
  prefetch (``HOROVOD_DATA_PREFETCH``), and elastic-resumable iterator
  state (beyond the reference, whose examples hand-roll sharding; the
  upstream analog is Petastorm + tf.data prefetch — docs/data.md).
"""

import time as _time

_T_IMPORT = _time.perf_counter()  # the `import` span starts here

import numpy as np  # noqa: E402

from .version import __version__  # noqa: F401,E402
from . import ops  # noqa: F401
from .exceptions import (HorovodError, NotInitializedError, ShutDownError,  # noqa: F401
                         DuplicateNameError, MismatchError,
                         StalledTensorError, CoordinatorError,
                         TransientCollectiveError, CheckpointCorruptError,
                         WorkerLostError, HostsUpdatedError)
from .ops.compression import Compression  # noqa: F401
from .runtime import (init, shutdown, is_initialized, rank, size,  # noqa: F401
                      local_rank, local_size, cross_rank, cross_size,
                      mpi_threads_supported, mesh, expert_mesh,
                      expert_parallel_size, model_mesh,
                      model_parallel_size, state)
from .ops import engine as _engine_mod
from . import diag  # noqa: F401  (hvd.diag.spans(), hvd.diag.span)
from . import metrics as _metrics_mod


def metrics_snapshot():
    """Snapshot of the process-wide runtime metrics registry: engine cycle
    health, coordinator round latency, collective counters, step-time and
    straggler telemetry (metrics.py). Works before init() too — families
    are defined at import and simply read zero. See docs/observability.md
    for the metric name/label reference."""
    return _metrics_mod.snapshot()

# Auto-generated names for unnamed ops, parity with the reference's
# "allreduce.noname.%d" counters (torch/mpi_ops_v2.cc:58-62).
_noname_counters = {}


def _auto_name(op):
    n = _noname_counters.get(op, 0)
    _noname_counters[op] = n + 1
    return f"{op}.noname.{n + 1}"


def _engine():
    return state().engine


def _first(result):
    """Engine results are {rank: value}; eager API calls submit identical data
    for every local rank, so any value is THE value."""
    if isinstance(result, dict):
        return result[min(result)]
    return result


# ---------------------------------------------------------------- eager ops

def allreduce_async(tensor, average=True, name=None,
                    compression=Compression.none, rank=None, to_host=True):
    """Asynchronous allreduce; returns a handle for poll()/synchronize()
    (reference: torch/mpi_ops.py:85-120).

    ``to_host=False`` opts into the device-resident fast path
    (docs/performance.md): the handle resolves to a jax device array
    sliced out of the fused wire buffer inside the jitted wire program —
    no device->host readback, ``synchronize()`` waits on dispatch only.
    Default ``True`` keeps the exact legacy numpy-returning behavior, as
    does ``HOROVOD_DEVICE_RESIDENT=0`` regardless of this flag."""
    if name is None:
        name = _auto_name("allreduce")
    comp = None if compression is Compression.none else compression
    return _engine().enqueue(_engine_mod.ALLREDUCE, tensor, name, rank=rank,
                             average=average, compression=comp,
                             to_host=to_host)


def allreduce(tensor, average=True, name=None, compression=Compression.none,
              to_host=True):
    """Average (default) or sum of ``tensor`` over all ranks
    (reference: torch/mpi_ops.py:122-154). ``to_host=False`` returns a
    jax device array with zero host readback (see allreduce_async)."""
    return _first(synchronize(
        allreduce_async(tensor, average=average, name=name,
                        compression=compression, to_host=to_host)))


def allgather_async(tensor, name=None, rank=None):
    """Asynchronous allgather (reference: torch/mpi_ops.py:200-231)."""
    if name is None:
        name = _auto_name("allgather")
    return _engine().enqueue(_engine_mod.ALLGATHER, tensor, name, rank=rank)


def allgather(tensor, name=None):
    """Concatenation of every rank's tensor along dim 0; dim 0 may differ
    across ranks (reference: torch/mpi_ops.py:233-262)."""
    return _first(synchronize(allgather_async(tensor, name=name)))


def broadcast_async(tensor, root_rank, name=None, rank=None):
    """Asynchronous broadcast (reference: torch/mpi_ops.py:282-315)."""
    if name is None:
        name = _auto_name("broadcast")
    return _engine().enqueue(_engine_mod.BROADCAST, tensor, name, rank=rank,
                             root_rank=root_rank)


def broadcast(tensor, root_rank, name=None):
    """Every rank receives root_rank's tensor
    (reference: torch/mpi_ops.py:317-347)."""
    return _first(synchronize(broadcast_async(tensor, root_rank, name=name)))


def alltoall(tensor, name=None):
    """Scatter equal dim-0 slices to every rank, gather received slices.
    (Beyond the reference's 0.16 op set — see ops/collectives.py:alltoall.)"""
    if name is None:
        name = _auto_name("alltoall")
    h = _engine().enqueue(_engine_mod.ALLTOALL, tensor, name)
    return _first(synchronize(h))


def poll(handle):
    """True once the async op completed (reference: torch/mpi_ops.py:404-419)."""
    return _engine().poll(handle)


def synchronize(handle):
    """Wait for an async op; returns its output
    (reference: torch/mpi_ops.py:422-438)."""
    return _engine().synchronize(handle)


# --------------------------------------------------- optimizer / broadcast

def broadcast_parameters(params, root_rank=0):
    """Broadcast a pytree of parameters from root_rank to all ranks
    (reference: torch/__init__.py:211-241 broadcast_parameters; the TF analog
    is broadcast_global_variables, tensorflow/__init__.py:85-105).

    Accepts a dict of name->array (torch state_dict style) or any pytree; the
    broadcast itself is one masked-psum collective per tensor over ICI.
    """
    import jax
    leaves, treedef = jax.tree.flatten(params)
    _, out = _broadcast_leaves(leaves, root_rank, "broadcast_parameters")
    return jax.tree.unflatten(treedef, out)


def _broadcast_leaves(leaves, root_rank, prefix):
    """Pull every leaf to the host, async-submit them all, then
    synchronize: one engine cycle fuses the whole pytree into a few large
    batches instead of paying a blocking round-trip per tensor (the
    reference does the same — broadcast_async_ then synchronize,
    torch/__init__.py:211-241). Span ``bcast`` with its two parts,
    ``bcast.host_pull`` and ``bcast.engine`` (docs/diagnostics.md).
    Returns ``(host arrays, results)``."""
    with diag.span("bcast", leaves=len(leaves)) as sp:
        with diag.span("bcast.host_pull"):
            arrs = [np.asarray(leaf) for leaf in leaves]
        sp.set(bytes=int(sum(a.nbytes for a in arrs)))
        with diag.span("bcast.engine"):
            handles = [broadcast_async(arr, root_rank, name=f"{prefix}.{i}")
                       for i, arr in enumerate(arrs)]
            res = [_first(synchronize(h)) for h in handles]
    return arrs, res


def broadcast_optimizer_state(opt_state, root_rank=0):
    """Broadcast optimizer state (optax pytree) from root_rank
    (reference: torch/__init__.py:243-359 — which wraps scalars as tensors and
    recursively casts; optax states are already pytrees of arrays/scalars, so
    the same treatment is a plain pytree broadcast with scalar round-trip).
    """
    import jax
    leaves, treedef = jax.tree.flatten(opt_state)
    arrs, res = _broadcast_leaves(leaves, root_rank,
                                  "broadcast_optimizer_state")
    out = [r.item() if a.ndim == 0 and not hasattr(leaf, "shape") else r
           for leaf, a, r in zip(leaves, arrs, res)]
    return jax.tree.unflatten(treedef, out)


from .optimizers import (DistributedOptimizer, DistributedGradientTransform,  # noqa: F401,E402
                         exchange_gradients, guarded_apply_updates)
# Compiled hot loop: the whole train step (forward, backward, fused
# in-graph exchange, optimizer apply) as ONE jitted, buffer-donated XLA
# program — see docs/performance.md "Compiled hot loop".
from .ops.step_program import (CompiledTrainStep,  # noqa: F401,E402
                               compiled_train_step)
# On-demand XLA device tracing: capture + phase-attribute the next N
# compiled steps (docs/diagnostics.md "Seeing inside the compiled step").
from .diag.xla_trace import trace_steps  # noqa: F401,E402
# Step-integrity guard (skip/backoff/rollback ladder, divergence repair,
# chaos injection) — see docs/robustness.md. Inert unless HOROVOD_GUARD /
# HOROVOD_GUARD_INJECT opt in.
from . import guard  # noqa: F401,E402
# Elastic fault tolerance (worker-failure recovery): hvd.elastic.run /
# hvd.elastic.State — see docs/elastic.md. Imported last; its modules
# import horovod_tpu lazily inside functions. checkpoint rides along so
# hvd.checkpoint.CheckpointManager (the durable-commit tier) is
# reachable without a separate import.
from . import checkpoint  # noqa: F401,E402
from . import elastic  # noqa: F401,E402
from . import data  # noqa: F401,E402
# Inference serving (paged KV cache, continuous batching, SLO-driven
# elasticity): hvd.serve.Engine(model, params) — see docs/serving.md.
from . import serve  # noqa: F401,E402

diag.record_span("import", _T_IMPORT, _time.perf_counter())
