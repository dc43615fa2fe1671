"""Per-rank collective flight recorder and hang watchdog.

Design constraints (ISSUE 8 / docs/diagnostics.md):

- **Always on, off the critical path.** ``FlightRecorder.record`` is one
  GIL-atomic counter increment (``itertools.count``), two clock reads and
  one tuple store into a preallocated ring — no locks, no allocation
  beyond the tuple, safe from any thread including the device-resident
  fast path. Measured cost is ~1 µs/event; bench.py reports the resulting
  steady-state share as ``flight_overhead_frac``.
- **Bounded memory.** The ring holds ``HOROVOD_FLIGHT_BUFFER`` entries
  (default 4096, rounded up to a power of two); older events are
  overwritten, like an aircraft flight recorder.
- **Crash-durable on demand.** ``dump()`` writes ``flight-rank<N>.json``
  (ring + all-thread Python stacks + progress marks) atomically; the
  watchdog, elastic aborts and ``WorkerLostError`` paths call it
  automatically so every hang and worker loss leaves a post-mortem.
- **Inert by default.** The watchdog thread and its KV progress beacons
  exist only when ``HOROVOD_STALL_TIMEOUT_SECONDS > 0``; the recorder
  itself can be disabled with ``HOROVOD_FLIGHT_BUFFER=0``.

Event tuples are ``(seq, t_mono, t_wall, event, name, op, nbytes, dtype,
extra)`` — monotonic (``perf_counter``) for intra-rank spans, wall clock
for cross-rank alignment in the ``python -m horovod_tpu.diag`` merger.

**Spans.** ``span(name, **attrs)`` is the program's own measurement of
where host time goes (docs/diagnostics.md "Host spans"): one ring entry
per span — event ``"span"``, stored at its end, ``extra`` holding its
start (``t0``, ``perf_counter``), thread, id, the id of the span that
encloses it on that thread (``parent``, 0 at top level) and its
attributes — and, for the same interval, a
``jax.profiler.TraceAnnotation("hvd_" + name)``, so the span lies in the
xplane of ANY profiler capture on the profiler's clock. TraceMe's own
"is a session active" check is the only gate: with no capture a span
costs about 2 us in all, 0.4 us of it the annotation. ``spans()`` reads
them back as plain tuples.
"""

import itertools
import json
import os
import sys
import threading
import time
import traceback
from time import perf_counter

from jax.profiler import StepTraceAnnotation, TraceAnnotation

from .. import metrics
from ..utils.logging import get_logger

_logger = get_logger()

DUMP_VERSION = 1


def _pow2_at_least(n):
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length() if n & (n - 1) else n


class FlightRecorder:
    """Lock-free bounded ring of collective lifecycle events."""

    # hvdlint HVD002: the ring is deliberately NOT declared _GUARDED_BY.
    # Writers serialize through the atomic itertools.count() ticket and
    # each slot store is a single GIL-atomic list assignment; readers
    # (dump/snapshot) tolerate torn windows by design.  Only the dump
    # fan-out — which touches the filesystem — takes ``_dump_lock``.
    _GUARDED_BY = {}

    def __init__(self, capacity=4096, rank=0, process_index=0, digest="",
                 diag_dir=""):
        cap = _pow2_at_least(capacity or 1)
        self._ring = [None] * cap
        self._mask = cap - 1
        self._count = itertools.count()
        self.capacity = cap
        self.rank = int(rank)
        self.process_index = int(process_index)
        self.digest = digest
        self.diag_dir = diag_dir or ""
        # Progress marks for the watchdog beacons: plain attribute stores
        # (GIL-atomic), written by the coordinator / engine hot paths.
        self.last_decision_index = -1
        self.last_cycle_wall = 0.0
        self._dump_lock = threading.Lock()

    # ------------------------------------------------------------- hot path

    def record(self, ev, name="", op="", nbytes=0, dtype="", extra=None):
        """Append one lifecycle event. Hot-path safe: no locks, no I/O."""
        i = next(self._count)
        self._ring[i & self._mask] = (i, time.perf_counter(), time.time(),
                                      ev, name, op, nbytes, dtype, extra)

    @property
    def events_recorded(self):
        """Total events ever recorded (monotonic; ring holds the tail)."""
        # itertools.count has no peek; stash-and-restore would race.
        # Track via the newest ring slot instead (None ring = 0 events).
        newest = -1
        for e in self._ring:
            if e is not None and e[0] > newest:
                newest = e[0]
        return newest + 1

    # ------------------------------------------------------------ snapshots

    def snapshot(self):
        """Ring contents as ordered event dicts (oldest first)."""
        entries = [e for e in self._ring if e is not None]
        entries.sort(key=lambda e: e[0])
        out = []
        for seq, t_mono, t_wall, ev, name, op, nbytes, dtype, extra in entries:
            d = {"seq": seq, "t": round(t_mono, 6), "wall": round(t_wall, 6),
                 "ev": ev}
            if name:
                d["name"] = name
            if op:
                d["op"] = op
            if nbytes:
                d["nbytes"] = int(nbytes)
            if dtype:
                d["dtype"] = dtype
            if extra:
                d.update(extra)
            out.append(d)
        return out

    def phase_totals(self):
        """Aggregate phase attribution over the current ring: wire span,
        exposed readback wait, input wait, step wall time. The basis of
        bench.py's ``step_phase_breakdown`` and the TelemetryCallback
        phase gauges (``hvd_diag_phase_seconds``). Scans the ring off the
        hot path; events older than the ring are gone (bounded memory)."""
        wire = readback = input_w = step_s = 0.0
        steps = 0
        for e in self._ring:
            if e is None:
                continue
            ev, extra = e[3], e[8]
            if not extra:
                continue
            if ev == "wire_end":
                wire += extra.get("span", 0.0)
                readback += extra.get("wait", 0.0)
            elif ev == "input_wait":
                input_w += extra.get("wait", 0.0)
            elif ev == "step":
                step_s += extra.get("dt", 0.0)
                steps += 1
        return {"wire_s": wire, "readback_s": readback, "input_s": input_w,
                "step_s": step_s, "steps": steps,
                "events": self.events_recorded}

    # ----------------------------------------------------------------- dump

    def dump_path(self):
        return os.path.join(self.diag_dir or ".",
                            f"flight-rank{self.rank}.json")

    def dump(self, path=None, reason="manual", extra=None):
        """Durable post-mortem: ring + all-thread stacks + progress marks,
        written atomically. Returns the path, or None on failure (a dump
        must never take the job down with it)."""
        path = path or self.dump_path()
        payload = {
            "version": DUMP_VERSION,
            "reason": reason,
            "rank": self.rank,
            "pid": self.process_index,
            "wall_at_dump": time.time(),
            "mono_at_dump": time.perf_counter(),
            "membership_digest": self.digest,
            "last_decision_index": self.last_decision_index,
            "last_cycle_wall": self.last_cycle_wall,
            "events": self.snapshot(),
            "threads": _thread_stacks(),
        }
        if extra:
            payload.update(extra)
        try:
            with self._dump_lock:
                d = os.path.dirname(path)
                if d:
                    os.makedirs(d, exist_ok=True)
                tmp = f"{path}.tmp.{os.getpid()}"
                with open(tmp, "w") as f:
                    json.dump(payload, f, default=str)
                os.replace(tmp, path)
        except OSError as e:
            _logger.warning("flight recorder dump to %s failed: %s", path, e)
            return None
        metrics.DIAG_DUMPS.inc()
        _logger.warning("flight recorder dump (%s): %s", reason, path)
        return path


def _thread_stacks():
    """All-thread Python stacks, keyed by thread name (the post-mortem's
    'where was everyone' section)."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out = {}
    for ident, frame in sys._current_frames().items():
        label = f"{names.get(ident, 'unknown')}-{ident}"
        out[label] = [ln.rstrip() for ln in traceback.format_stack(frame)]
    return out


# ------------------------------------------------- process-wide installation

_recorder = None
_recorder_config = None
# The recorder ``spans()`` reads once ``uninstall()`` dropped the live one:
# a harness reads its run's spans after ``hvd.shutdown()``.
_retired = None


# ------------------------------------------------------------------- spans

#: Spans taken while no recorder is installed (the package import, anything
#: before ``hvd.init()``), as ring tuples; ``install()`` adopts them.
_early = []
_EARLY_CAP = 256
_span_ids = itertools.count(1)
_tls = threading.local()


def _store_span(name, t0, t1, parent, sid, extra):
    """One ring entry for a finished span; ``extra`` (the span's own
    attribute dict) gains the bookkeeping keys."""
    extra["t0"] = t0
    extra["tid"] = threading.get_ident()
    extra["id"] = sid
    extra["parent"] = parent
    rec = _recorder
    if rec is not None:
        i = next(rec._count)
        rec._ring[i & rec._mask] = (i, t1, time.time(), "span", name, "",
                                    0, "", extra)
    elif len(_early) < _EARLY_CAP:
        _early.append((-1, t1, time.time(), "span", name, "", 0, "",
                       extra))


class span:
    """``with diag.span("step.execute", step=n):`` — one ring entry and
    one ``TraceAnnotation("hvd_step.execute")`` for the enclosed
    interval. ``step_trace=n`` adds a
    ``StepTraceAnnotation("hvd_step", step_num=n)`` around the same
    interval, so xprof and the trace reducers can group device events by
    step. Attributes are small scalars; ``set(**attrs)`` adds some found
    while the span is open. Per-thread nesting gives each span its
    parent's id."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "_note",
                 "_step_note", "_stack")

    def __init__(self, name, step_trace=None, **attrs):
        self.name = name
        self.attrs = attrs
        self._note = TraceAnnotation("hvd_" + name)
        self._step_note = (None if step_trace is None else
                           StepTraceAnnotation("hvd_step",
                                               step_num=step_trace))

    def set(self, **attrs):
        self.attrs.update(attrs)

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._stack = stack
        self.parent = stack[-1] if stack else 0
        self.id = next(_span_ids)
        stack.append(self.id)
        if self._step_note is not None:
            self._step_note.__enter__()
        self._note.__enter__()
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = perf_counter()
        self._note.__exit__(*exc)
        if self._step_note is not None:
            self._step_note.__exit__(*exc)
        self._stack.pop()
        _store_span(self.name, self.t0, t1, self.parent, self.id,
                    self.attrs)
        return False


def record_span(name, t0, t1, **attrs):
    """A span whose interval was measured elsewhere (jax's own compile
    durations), as a child of whichever span is open on this thread."""
    stack = getattr(_tls, "stack", None)
    _store_span(name, t0, t1, stack[-1] if stack else 0, next(_span_ids),
                attrs)


def spans():
    """The spans the ring still holds, oldest first, as plain tuples
    ``(name, start, end, thread, id, parent, attrs)`` — ``start`` / ``end``
    on ``time.perf_counter``, ``parent`` the id of the enclosing span on
    that thread (0: none), ``attrs`` a dict (``step``, ``hit``, ``bytes``
    ...). Reads the live recorder, after ``hvd.shutdown()`` the one it
    retired, before ``hvd.init()`` the spans taken so far."""
    rec = _recorder or _retired
    out = []
    for e in (rec._ring if rec is not None else []) + _early:
        if e is not None and e[3] == "span":
            x = dict(e[8])
            out.append((e[4], x.pop("t0"), e[1], x.pop("tid"), x.pop("id"),
                        x.pop("parent"), x))
    out.sort(key=lambda s: s[1])
    return out


def install(config, rank=0, process_index=0, digest=""):
    """Create (or replace) the process recorder from config. Returns None —
    recorder disabled — when ``HOROVOD_FLIGHT_BUFFER`` is 0."""
    global _recorder, _recorder_config
    _recorder_config = config
    if int(getattr(config, "flight_buffer", 4096)) <= 0:
        _recorder = None
        metrics.registry().remove_collect_hook("diag")
        return None
    rec = FlightRecorder(capacity=config.flight_buffer, rank=rank,
                         process_index=process_index, digest=digest,
                         diag_dir=getattr(config, "diag_dir", ""))
    # adopt the spans taken before there was a ring (the import)
    for e in _early:
        i = next(rec._count)
        rec._ring[i & rec._mask] = (i,) + e[1:]
    del _early[:]
    _recorder = rec
    metrics.registry().set_collect_hook(
        "diag", lambda: metrics.DIAG_EVENTS.set(rec.events_recorded))
    return _recorder


def get():
    """The process recorder, or None when disabled / pre-init."""
    return _recorder


def uninstall():
    global _recorder, _recorder_config, _retired
    _retired = _recorder or _retired
    _recorder = None
    _recorder_config = None
    metrics.registry().remove_collect_hook("diag")


def _diag_active(config):
    """Whether automatic post-mortems are wanted: an explicit diag dir or
    a live stall timeout. Keeps ordinary runs (tier-1 tests, local
    notebooks) from littering the CWD with dump files on every elastic
    abort while still recording in memory."""
    return bool(getattr(config, "diag_dir", "")
                or float(getattr(config, "stall_timeout_seconds", 0)) > 0)


def dump_post_mortem(reason, extra=None, force=False):
    """Automatic dump hook for abort paths (elastic WorkerLostError,
    HostsUpdatedError): dump the process recorder when diagnostics are
    active. ``force=True`` (guard rollbacks/divergence, which are rare
    and always worth a post-mortem) dumps whenever a recorder exists,
    even with no diag dir or stall timeout configured. Never raises."""
    rec, cfg = _recorder, _recorder_config
    if rec is None or cfg is None or (not force and not _diag_active(cfg)):
        return None
    # diag_dir may have changed since install() (elastic re-init rebuilds
    # config; tests toggle it): honor the live value, not the captured one
    rec.diag_dir = getattr(cfg, "diag_dir", rec.diag_dir)
    try:
        return rec.dump(reason=reason, extra=extra)
    except Exception:  # noqa: BLE001 — post-mortems must never kill work
        _logger.debug("post-mortem dump failed", exc_info=True)
        return None


# ---------------------------------------------------------------- watchdog

def start_watchdog(engine, config):
    """Create + start the hang watchdog for ``engine``, or None when
    ``HOROVOD_STALL_TIMEOUT_SECONDS`` is 0 (fully inert: no thread, no
    beacons — the satellite contract)."""
    timeout = float(getattr(config, "stall_timeout_seconds", 0))
    if timeout <= 0 or _recorder is None:
        return None
    wd = HangWatchdog(engine, _recorder, config)
    wd.start()
    return wd


class HangWatchdog:
    """Background hang detector: any collective pending (negotiation) or
    in-flight (dispatched wire bucket) past ``stall_timeout_seconds``
    triggers a durable flight dump; ranks publish
    ``(last_decision_index, last_cycle)`` progress beacons over the
    coordination KV store so process 0 can name exactly which ranks
    entered the stalled collective and which are missing (the desync
    report, ``desync-report.json``)."""

    BEACON_KIND = "diag"

    def __init__(self, engine, recorder, config):
        self.engine = engine
        self.recorder = recorder
        self.timeout = float(config.stall_timeout_seconds)
        self.diag_dir = getattr(config, "diag_dir", "") or ""
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop,
                                        name="hvd-diag-watchdog", daemon=True)
        self._reported = set()   # stalled names already dumped this episode

    def start(self):
        self._thread.start()

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=2.0)

    @property
    def alive(self):
        return self._thread.is_alive()

    # ------------------------------------------------------------ main loop

    def _interval(self):
        return min(max(self.timeout / 4.0, 0.05), 1.0)

    def _loop(self):
        while not self._stop.wait(self._interval()):
            try:
                self._publish_beacon()
                stalled = self._find_stalled()
                if stalled:
                    self._report(stalled)
                elif self._reported:
                    self._reported.clear()  # recovered: re-arm
            except Exception:  # noqa: BLE001 — the watchdog must survive
                _logger.debug("watchdog tick failed", exc_info=True)

    # ------------------------------------------------------------- beacons

    def _beacon_payload(self):
        eng = self.engine
        try:
            pending = list(eng._table.keys())
        except RuntimeError:   # dict mutated mid-iteration: next tick
            pending = []
        rec = self.recorder
        return {"di": rec.last_decision_index,
                "cy": rec.last_cycle_wall,
                "pending": pending[:64],
                "inflight": len(eng._inflight),
                "t": time.time()}

    def _publish_beacon(self):
        coord = getattr(self.engine, "_coord", None)
        if coord is None:
            return
        try:
            blob = json.dumps(self._beacon_payload()).encode()
            coord._client.key_value_set_bytes(
                f"{coord._ns}/{self.BEACON_KIND}/{coord.pid}", blob,
                allow_overwrite=True)
        except Exception:  # noqa: BLE001 — best-effort beacon
            pass

    def _peer_beacons(self):
        """{pid: beacon} for every session participant (best-effort)."""
        coord = getattr(self.engine, "_coord", None)
        if coord is None:
            return {0: self._beacon_payload()}
        out = {}
        for p in coord._pid_list():
            if p == coord.pid:
                out[p] = self._beacon_payload()
                continue
            try:
                blob = coord._client.key_value_try_get_bytes(
                    f"{coord._ns}/{self.BEACON_KIND}/{p}")
                if blob is not None:
                    out[p] = json.loads(bytes(blob).decode())
            except Exception:  # noqa: BLE001 — a dead peer has no beacon
                pass
        return out

    # ------------------------------------------------------ stall detection

    def _find_stalled(self):
        """[(name, op, age_seconds, local_missing_ranks)] for collectives
        stuck past the timeout: negotiation-pending names from the request
        table, plus dispatched-but-unread wire buckets."""
        eng = self.engine
        now = time.perf_counter()
        stalled = []
        try:
            for name, pend in list(eng._table.items()):
                age = now - eng._first_seen.get(name, now)
                if age <= self.timeout:
                    continue
                op = next(iter(pend.values())).op if pend else ""
                missing = [r for r in range(eng.num_ranks) if r not in pend]
                stalled.append((name, op, age, missing))
            for rec in list(eng._inflight):
                age = now - rec.t_dispatch
                if age > self.timeout and rec.batch:
                    stalled.append((rec.batch[0][0], "ALLREDUCE", age, []))
        except RuntimeError:   # state mutated mid-scan: next tick
            return []
        return stalled

    def _report(self, stalled):
        fresh = [s for s in stalled if s[0] not in self._reported]
        if not fresh:
            return
        for name, _, _, _ in fresh:
            self._reported.add(name)
        metrics.DIAG_STALLS.inc(len(fresh))
        beacons = self._peer_beacons()
        coord = getattr(self.engine, "_coord", None)
        my_pid = coord.pid if coord is not None else 0
        info = {"stalled": [{"name": n, "op": op,
                             "age_seconds": round(age, 3),
                             "missing_local_ranks": missing}
                            for n, op, age, missing in fresh],
                "beacons": {str(p): b for p, b in beacons.items()}}
        self.recorder.record(
            "stall_detected", fresh[0][0], fresh[0][1],
            extra={"age": round(fresh[0][2], 3),
                   "n_stalled": len(fresh)})
        self.recorder.dump(
            os.path.join(self.diag_dir or ".",
                         f"flight-rank{self.recorder.rank}.json"),
            reason="stall", extra=info)
        if my_pid == 0:
            self._write_desync_report(fresh, beacons)

    def _write_desync_report(self, stalled, beacons):
        """Process 0 only: name exactly which participants entered each
        stalled collective and which are missing. Multi-host membership
        comes from the progress beacons (a rank that entered lists the
        name as pending — it is waiting inside the collective); the
        single-process fallback reads the local request table."""
        eng = self.engine
        multihost = getattr(eng, "_coord", None) is not None
        report = {"version": DUMP_VERSION, "reason": "stall",
                  "wall": time.time(), "timeout_seconds": self.timeout,
                  "pid": self.recorder.process_index,
                  "stalled": [], "beacons": {str(p): b
                                             for p, b in beacons.items()}}
        total_missing = 0
        for name, op, age, local_missing in stalled:
            if multihost:
                entered = sorted(p for p, b in beacons.items()
                                 if name in b.get("pending", ()))
                known = sorted(beacons)
                missing = [p for p in known if p not in entered]
                # A peer so wedged (or dead) it never published a beacon
                # is missing by definition.
                coord = eng._coord
                missing += [p for p in coord._pid_list() if p not in known]
            else:
                pend = eng._table.get(name, {})
                entered = sorted(pend)
                missing = local_missing
            total_missing = max(total_missing, len(missing))
            decision_index = {str(p): b.get("di", -1)
                              for p, b in beacons.items()}
            report["stalled"].append(
                {"name": name, "op": op, "age_seconds": round(age, 3),
                 "entered": entered, "missing": sorted(missing),
                 "decision_index": decision_index})
            _logger.error(
                "desync: collective %r stalled %.1fs past the %.1fs "
                "timeout at decision index %s; entered: %s; MISSING: %s "
                "(flight dumps + desync-report.json in %s)",
                name, age, self.timeout,
                self.recorder.last_decision_index, entered, sorted(missing),
                self.diag_dir or os.getcwd())
        metrics.DIAG_DESYNC_MISSING.set(total_missing)
        path = os.path.join(self.diag_dir or ".", "desync-report.json")
        try:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(report, f, default=str)
            os.replace(tmp, path)
        except OSError as e:
            _logger.warning("desync report write failed: %s", e)
