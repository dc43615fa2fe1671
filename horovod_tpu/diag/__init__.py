"""Collective flight recorder + cross-rank hang diagnosis.

The reference fork answers "where does collective time go" only when the
job *finishes* (profiler.txt at shutdown, operations.cc:219-317); a hung
or desynchronized collective produces a silent stall. This package closes
that gap:

- ``recorder.FlightRecorder``: an always-on, bounded-memory, lock-free
  per-rank ring buffer recording every collective's lifecycle (enqueue,
  negotiation submit, decision index, dispatch, wire end, readback,
  abort) plus input-wait and step marks. Off the steady-state critical
  path by construction: one GIL-atomic counter increment and one tuple
  store per event, no locks anywhere.
- ``recorder.span`` / ``spans()``: host spans inside the program
  (``import``, ``init``, ``bcast.*``, ``step.*``, ``jax.*``, ``data.*``)
  in the same ring, each also a ``TraceAnnotation("hvd_<name>")`` on the
  profiler's clock.
- ``recorder.HangWatchdog``: created only when
  ``HOROVOD_STALL_TIMEOUT_SECONDS > 0`` — dumps a durable post-mortem
  (``flight-rank<N>.json`` + all-thread stacks) for any collective
  in-flight past the timeout, publishes per-rank progress beacons over
  the coordination KV store, and (process 0) emits a desync report
  naming exactly which ranks entered the stalled collective and which
  are missing.
- ``xla_trace.StepTracer``: on-demand ``jax.profiler`` device capture of
  N compiled steps (``hvd.trace_steps(n)`` / ``HOROVOD_XPROF_STEPS``),
  parsed offline into per-phase device time via the step program's
  ``hvd_*`` named scopes — the view *inside* the single fused XLA
  dispatch the flight recorder cannot decompose.
- ``sentry.PerfSentry``: an EMA per-signature step-time/MFU baseline
  (``HOROVOD_PERF_SENTRY=1``) that flags regressions, records them in
  the flight ring, and auto-arms one trace window.
- ``python -m horovod_tpu.diag``: merges per-rank dumps into one
  clock-aligned Chrome trace (timeline.py's pid-space splicing) and
  prints a critical-path report (per-step phase breakdown, per-rank
  skew, slowest-rank ranking); ``--xla-trace`` splices a device capture
  into the same clock. See docs/diagnostics.md.
"""

from .recorder import (FlightRecorder, HangWatchdog, dump_post_mortem, get,
                       install, record_span, span, spans, start_watchdog,
                       uninstall)
from .sentry import PerfSentry
from .xla_trace import StepTracer, parse_trace_dir, trace_steps

__all__ = ["FlightRecorder", "HangWatchdog", "get", "install", "uninstall",
           "span", "spans", "record_span",
           "start_watchdog", "dump_post_mortem", "PerfSentry", "StepTracer",
           "parse_trace_dir", "trace_steps"]
