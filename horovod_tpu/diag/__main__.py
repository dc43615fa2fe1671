"""``python -m horovod_tpu.diag`` — merge per-rank flight dumps.

Takes ``flight-rank<N>.json`` dumps (files or directories to glob) and
produces:

- one clock-aligned Chrome/Perfetto trace (``--trace out.json``) by
  splicing each rank's events into a disjoint pid space through
  ``timeline.Timeline.merge_remote`` — the same machinery process 0 uses
  for live multi-host traces. Alignment uses the wall-clock timestamps
  every event carries: the earliest wall time across all dumps becomes
  t=0.
- a critical-path report on stdout: per-step phase breakdown (compute /
  wire / readback / input-wait), per-rank skew (max/median of mean step
  time) and a slowest-rank ranking. ``--json out.json`` writes the same
  numbers machine-readably.

With ``--xla-trace DIR`` (an ``xla-trace-<seq>/`` capture directory from
``hvd.trace_steps`` / ``HOROVOD_XPROF_STEPS``), the merge also splices
the XLA *device* trace into the same timeline — each device event
phase-labeled via the capture's ``xla-trace-meta.json`` sidecar and laid
on the flight view's clock through the sidecar's clock mapping (the
program's spans are in the ring and in the capture; the median
difference is the offset) — and the report gains a per-phase device-time
breakdown (forward / backward / exchange / optimizer / guard / other),
the named kernels, the collectives by message size, the clock mapping's
quality, device time by scope path and op class, and the fifteen matmul
fusions that lose most time against their FLOPs: the device-level
critical path next to the host-side flight view. DIR may also be a
capture without a sidecar beside the HLO text of the program that ran —
a directory holding ``*.xplane.pb`` and ``*.hlo.txt``, or one
``<name>.xplane.pb`` with ``<name>.hlo.txt`` next to it, as the
benchmark's ``--dump-dir`` writes them.

Usage::

    python -m horovod_tpu.diag $HOROVOD_DIAG_DIR --trace merged.json
    python -m horovod_tpu.diag flight-rank0.json flight-rank1.json
    python -m horovod_tpu.diag $HOROVOD_DIAG_DIR \\
        --xla-trace $HOROVOD_DIAG_DIR/xla-trace-001 --trace merged.json
"""

import argparse
import glob
import json
import os
import sys


def load_dumps(paths):
    """[(path, dump_dict)] from explicit files and/or directories."""
    files = []
    for p in paths:
        if os.path.isdir(p):
            files.extend(sorted(glob.glob(
                os.path.join(p, "flight-rank*.json"))))
        else:
            files.append(p)
    dumps = []
    for f in files:
        try:
            with open(f) as fh:
                d = json.load(fh)
        except (OSError, ValueError) as e:
            print(f"warning: skipping unreadable dump {f}: {e}",
                  file=sys.stderr)
            continue
        if not isinstance(d, dict) or "events" not in d:
            print(f"warning: {f} is not a flight dump; skipping",
                  file=sys.stderr)
            continue
        dumps.append((f, d))
    return dumps


def _chrome_events(dump):
    """One rank's dump as Chrome events with ts/dur in WALL microseconds
    (merge_remote then shifts them against the global epoch). Spans
    (wire, readback, input-wait, step, the program's own ``diag.span``s)
    become "X" complete events ending at their recorded wall time;
    lifecycle points become "i" instants."""
    out = []
    rank = dump.get("rank", 0)
    for tid, label in ((0, "wire"), (1, "readback"), (2, "input"),
                       (3, "step"), (4, "lifecycle")):
        out.append({"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                    "args": {"name": label}})
    out.append({"name": "process_name", "ph": "M", "pid": 0,
                "args": {"name": f"rank{rank} flight"}})
    span_tids = {}
    for ev in dump.get("events", ()):
        try:
            wall_us = int(float(ev["wall"]) * 1e6)
            kind = ev.get("ev", "")
        except (KeyError, TypeError, ValueError):
            continue
        name = ev.get("name") or ev.get("op") or kind
        args = {k: v for k, v in ev.items()
                if k not in ("seq", "t", "wall", "ev")}
        if kind == "wire_end":
            span_us = int(float(ev.get("span", 0)) * 1e6)
            out.append({"name": name, "cat": "wire", "ph": "X", "pid": 0,
                        "tid": 0, "ts": wall_us - span_us, "dur": span_us,
                        "args": args})
            wait_us = int(float(ev.get("wait", 0)) * 1e6)
            if wait_us > 0:
                out.append({"name": f"readback:{name}", "cat": "readback",
                            "ph": "X", "pid": 0, "tid": 1,
                            "ts": wall_us - wait_us, "dur": wait_us})
        elif kind == "input_wait":
            wait_us = int(float(ev.get("wait", 0)) * 1e6)
            out.append({"name": "INPUT_WAIT", "cat": "input", "ph": "X",
                        "pid": 0, "tid": 2, "ts": wall_us - wait_us,
                        "dur": wait_us})
        elif kind == "span" and "t0" in ev:
            # a program span (diag.span): stored at its end, one lane per
            # thread after the fixed ones
            dur_us = int((float(ev["t"]) - float(ev["t0"])) * 1e6)
            tid = span_tids.setdefault(ev.get("tid"), 5 + len(span_tids))
            out.append({"name": name, "cat": "span", "ph": "X", "pid": 0,
                        "tid": tid, "ts": wall_us - dur_us, "dur": dur_us,
                        "args": args})
        elif kind == "step":
            dt_us = int(float(ev.get("dt", 0)) * 1e6)
            out.append({"name": f"STEP {ev.get('step', '?')}",
                        "cat": "step", "ph": "X", "pid": 0, "tid": 3,
                        "ts": wall_us - dt_us, "dur": dt_us})
        else:
            out.append({"name": f"{kind}:{name}" if name != kind else kind,
                        "cat": "lifecycle", "ph": "i", "s": "t", "pid": 0,
                        "tid": 4, "ts": wall_us, "args": args})
    for tid in span_tids.values():
        out.append({"name": "thread_name", "ph": "M", "pid": 0, "tid": tid,
                    "args": {"name": f"spans {tid - 5}"}})
    return out


def load_xla_trace(trace_dir):
    """Device-trace view for ``--xla-trace``: per-phase totals (from the
    ``xla-trace-meta.json`` sidecar, re-reducing the raw capture when the
    sidecar is absent) plus phase-labeled Chrome events on wall-clock
    microseconds, ready for the same merge_remote splicing as the flight
    dumps. Returns None when the directory holds no device events.

    Clock: the sidecar's ``summary.clock.offset_ns`` maps the flight
    ring's ``perf_counter`` onto the profiler's clock (the median over
    every span that is in both, diag/xla_trace.py ``clock_map``), and its
    ``mono_start`` / ``wall_start`` are one instant on ``perf_counter``
    and on the wall clock the dumps are merged on. The events list is
    empty when the capture has no such pair (no program span ran in it):
    device timestamps alone cannot be aligned to the flight view."""
    from .xla_trace import (build_op_table, load_meta, read_capture,
                            summarize)
    meta = load_meta(trace_dir) or {}
    events = read_capture(trace_dir)
    summary = meta.get("summary")
    if summary is None:
        # no sidecar: join against the HLO text kept beside the capture
        table = {}
        for path in ([trace_dir[:-len(".xplane.pb")] + ".hlo.txt"]
                     if os.path.isfile(trace_dir) else sorted(glob.glob(
                         os.path.join(trace_dir, "*.hlo.txt")))):
            try:
                with open(path, encoding="utf-8") as fh:
                    table.update(build_op_table(fh.read()))
            except OSError:
                pass
        summary = summarize(events, table)
    if summary is None:
        print(f"warning: no parseable device events under {trace_dir}",
              file=sys.stderr)
        return None
    op_phases = meta.get("op_phases") or {}
    offset = (summary.get("clock") or {}).get("offset_ns")
    mono0, wall0 = meta.get("mono_start"), meta.get("wall_start")
    raw, lanes = [], {}
    if events and None not in (offset, mono0, wall0):
        for tid, key in enumerate(sorted(events["lanes"])):
            lanes[key] = tid
            for instr, start_ns, dur_ns, _ in events["lanes"][key]["ops"]:
                phase = (op_phases.get(instr) or [None])[0] or "other"
                mono = (start_ns - offset) * 1e-9
                raw.append({"name": f"{phase}:{instr}", "cat": phase,
                            "ph": "X", "pid": 0, "tid": tid,
                            "ts": (mono - mono0 + wall0) * 1e6,
                            "dur": dur_ns * 1e-3})
    evs = [{"name": "process_name", "ph": "M", "pid": 0,
            "args": {"name": "xla device trace"}}]
    evs += [{"name": "thread_name", "ph": "M", "pid": 0, "tid": t,
             "args": {"name": f"device lane {key}"}}
            for key, t in lanes.items()]
    return {"dir": trace_dir, "meta": meta, "summary": summary,
            "events": evs + raw, "aligned": bool(raw)}


def write_trace(dumps, out_path, xla=None):
    """Merge every dump into one Chrome trace via Timeline's pid-space
    splicing. Events carry wall-clock microsecond timestamps; setting the
    timeline epoch to the earliest wall time and passing epoch=0 per rank
    makes merge_remote's offset land every rank on a shared t=0."""
    from ..timeline import Timeline
    tl = Timeline(out_path, enabled=True)
    per_rank = [(path, dump, _chrome_events(dump)) for path, dump in dumps]
    groups = [(f"rank{dump.get('rank', os.path.basename(path))}", evs)
              for path, dump, evs in per_rank]
    if xla and xla["events"]:
        groups.append(("xla", xla["events"]))
    # Spans are end-timestamped in the ring, so the earliest *start*
    # (ts = wall - dur) across all ranks is the true t=0 — aligning on
    # the earliest event wall time would push long first spans negative.
    starts = [e["ts"] for _, evs in groups for e in evs if "ts" in e]
    tl.epoch = (min(starts) / 1e6) if starts else 0.0
    for label, evs in groups:
        tl.merge_remote(evs, epoch=0.0, label=label)
    tl.close()
    return out_path


def _phase_sums(dump):
    wire = readback = input_w = step_s = 0.0
    steps = 0
    for ev in dump.get("events", ()):
        kind = ev.get("ev")
        if kind == "wire_end":
            wire += float(ev.get("span", 0) or 0)
            readback += float(ev.get("wait", 0) or 0)
        elif kind == "input_wait":
            input_w += float(ev.get("wait", 0) or 0)
        elif kind == "step":
            step_s += float(ev.get("dt", 0) or 0)
            steps += 1
    return {"wire_s": wire, "readback_s": readback, "input_s": input_w,
            "step_s": step_s, "steps": steps}


def _median(xs):
    xs = sorted(xs)
    n = len(xs)
    if not n:
        return 0.0
    return xs[n // 2] if n % 2 else (xs[n // 2 - 1] + xs[n // 2]) / 2


def critical_path_report(dumps):
    """Per-rank phase attribution + skew from a set of flight dumps."""
    ranks = []
    for path, dump in dumps:
        p = _phase_sums(dump)
        steps = p["steps"]
        mean_step = p["step_s"] / steps if steps else 0.0
        compute = max(p["step_s"] - p["wire_s"] - p["readback_s"]
                      - p["input_s"], 0.0)
        ranks.append({
            "rank": dump.get("rank", 0),
            "dump": path,
            "reason": dump.get("reason", ""),
            "last_decision_index": dump.get("last_decision_index", -1),
            "steps": steps,
            "mean_step_ms": round(mean_step * 1e3, 3),
            "phase_ms_per_step": {
                "compute": round(compute / steps * 1e3, 3) if steps else 0,
                "wire": round(p["wire_s"] / steps * 1e3, 3) if steps else 0,
                "readback": round(p["readback_s"] / steps * 1e3, 3)
                if steps else 0,
                "input": round(p["input_s"] / steps * 1e3, 3)
                if steps else 0,
            },
            "totals_s": {k: round(v, 6) for k, v in p.items()
                         if k != "steps"},
        })
    means = [r["mean_step_ms"] for r in ranks if r["steps"]]
    med = _median(means)
    skew = (max(means) / med) if means and med > 0 else 0.0
    ranking = sorted((r for r in ranks if r["steps"]),
                     key=lambda r: r["mean_step_ms"], reverse=True)
    return {"ranks": sorted(ranks, key=lambda r: r["rank"]),
            "step_time_skew": round(skew, 4),
            "slowest_ranks": [r["rank"] for r in ranking],
            "n_dumps": len(dumps)}


def print_report(report, desync=None):
    print(f"flight dumps merged: {report['n_dumps']}")
    if desync:
        for st in desync.get("stalled", ()):
            print(f"DESYNC: {st['name']!r} stalled {st['age_seconds']}s "
                  f"— entered: {st['entered']}  MISSING: {st['missing']} "
                  f"(decision index {st.get('decision_index')})")
    for r in report["ranks"]:
        ph = r["phase_ms_per_step"]
        print(f"rank {r['rank']}: steps={r['steps']} "
              f"mean_step={r['mean_step_ms']}ms  "
              f"compute={ph['compute']}ms wire={ph['wire']}ms "
              f"readback={ph['readback']}ms input={ph['input']}ms  "
              f"decision_index={r['last_decision_index']} "
              f"[{r['reason']}]")
    if report["slowest_ranks"]:
        print(f"slowest ranks: {report['slowest_ranks']}  "
              f"step-time skew (max/median): {report['step_time_skew']}")


def print_xla_report(xla):
    """Per-phase device-time breakdown for a --xla-trace capture."""
    s = xla["summary"]
    steps = max(int(xla["meta"].get("steps") or s.get("step_runs") or 1), 1)
    lanes = max(int(s.get("lanes", 1) or 1), 1)
    print(f"xla device trace: {xla['dir']}  steps={steps} lanes={lanes} "
          f"events={s.get('events', 0)} "
          f"device_total={round(s['total_s'], 6)}s"
          + ("" if xla["aligned"] else "  (no sidecar — not clock-aligned)"))
    per = {p: round(v / steps / lanes * 1e3, 3)
           for p, v in s.get("phases", {}).items()}
    print("  device ms/step/lane: " + "  ".join(
        f"{p}={per[p]}" for p in ("forward", "backward", "exchange",
                                  "optimizer", "guard", "other")
        if p in per))
    stages = s.get("stages") or {}
    if any(stages.values()):
        print("  staged exchange: " + "  ".join(
            f"{k}={round(v / steps / lanes * 1e3, 3)}ms"
            for k, v in stages.items()))
    if s.get("kernels"):
        print("  kernels ms/step/lane: " + "  ".join(
            f"{k}={round(v['s'] / steps / lanes * 1e3, 3)}"
            f"({round(v['calls'] / steps / lanes, 1)} calls)"
            for k, v in sorted(s["kernels"].items())))
    for row in s.get("collectives") or ():
        calls = row["calls"] / steps / lanes
        print(f"  collective {row['op']} {row['bytes']} B: "
              f"{round(calls, 2)} calls/step/lane, "
              f"{round(row['device_s'] / row['calls'] * 1e3, 3)} ms a "
              f"call, {round(row['exposed_s'] / row['calls'] * 1e3, 3)} "
              "ms of it with nothing else running on the chip")
    idle = s.get("idle")
    if idle and idle["by"]:
        print("  idle ms/step/lane: " + "  ".join(
            f"{k}={round(v / steps / lanes * 1e3, 4)}"
            for k, v in sorted(idle["by"].items(), key=lambda kv: -kv[1])))
    clock = s.get("clock")
    if clock:
        print(f"  clock: {clock['pairs']} span pairs, spread "
              f"{round(clock['spread_ns'] * 1e-3, 1)} us, host-device "
              f"skew <= {clock.get('host_device_skew_bound_us')} us")
    _print_scope_table(s.get("classes") or {}, steps * lanes)
    _print_matmul_table(s.get("matmuls") or [], steps * lanes)


def _print_scope_table(classes, runs):
    """ms a step a lane by scope path (rows, the longest first) and op
    class (columns)."""
    if not classes:
        return
    from .xla_trace import CLASSES
    cols = [c for c in CLASSES if any(c in by for by in classes.values())]
    width = max(len(p) for p in classes)
    print(f"  device ms/step/lane by scope path and op class:\n  "
          f"{'scope':<{width}}  {'total':>9}"
          + "".join(f"  {c:>14}" for c in cols))
    for path, by in sorted(classes.items(),
                           key=lambda kv: -sum(kv[1].values())):
        print(f"  {path:<{width}}  {sum(by.values()) / runs * 1e3:9.3f}"
              + "".join(f"  {by.get(c, 0.0) / runs * 1e3:14.3f}"
                        for c in cols))


def _print_matmul_table(rows, runs, limit=15):
    """The matmul rows that lose most: calls and ms a step a lane, a
    call's GFLOP, its time at the peak and the time lost beside it."""
    if not rows:
        return
    total = sum(r["calls"] * r["flops"] for r in rows
                if r["flops"] is not None) / runs
    unknown = sum(r["flops"] is None for r in rows)
    print(f"  matmul fusions: {len(rows)} rows, {total * 1e-12:.4f} TFLOP"
          f"/step/lane" + (f", {unknown} without FLOPs" if unknown else "")
          + f"; the {min(limit, len(rows))} that lose most "
          "(calls, ms, ms at the peak, ms lost /step/lane; GFLOP a call):")
    def cell(value, scale, digits=3):
        return ("-" if value is None
                else f"{value * scale:.{digits}f}").rjust(8)

    per_run = 1e3 / runs
    for r in rows[:limit]:
        at_peak = r.get("at_peak_s")
        cells = [f"{r['calls'] / runs:6.1f}", cell(r["device_s"], per_run),
                 cell(at_peak and at_peak * r["calls"], per_run),
                 cell(r.get("lost_s"), per_run), cell(r["flops"], 1e-9, 2)]
        rides = ", ".join(r["rides"]
                          + ["all-reduce"] * r["carries_collective"])
        print("  " + " ".join(cells)
              + f"  {r['operands']}  {r['scope']}  {r['lhs']} * {r['rhs']}"
              f" -> {r['result']}" + (f"  rides: {rides}" if rides else ""))


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m horovod_tpu.diag", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("paths", nargs="+",
                    help="flight-rank*.json files or directories")
    ap.add_argument("--trace", metavar="OUT",
                    help="write a merged clock-aligned Chrome trace here")
    ap.add_argument("--json", metavar="OUT",
                    help="write the critical-path report as JSON here")
    ap.add_argument("--xla-trace", metavar="DIR",
                    help="an xla-trace-<seq>/ capture directory "
                         "(hvd.trace_steps / HOROVOD_XPROF_STEPS) to "
                         "phase-report and splice into the merged trace")
    args = ap.parse_args(argv)

    xla = load_xla_trace(args.xla_trace) if args.xla_trace else None
    dumps = load_dumps(args.paths)
    if not dumps and xla is None:
        print("error: no readable flight dumps found", file=sys.stderr)
        return 2

    desync = None
    for p in args.paths:
        cand = os.path.join(p, "desync-report.json") if os.path.isdir(p) \
            else None
        if cand and os.path.exists(cand):
            try:
                with open(cand) as fh:
                    desync = json.load(fh)
            except (OSError, ValueError):
                pass

    report = critical_path_report(dumps)
    if desync:
        report["desync"] = desync
    if xla:
        report["xla"] = {"dir": xla["dir"],
                         "steps": xla["meta"].get("steps"),
                         "lanes": xla["summary"].get("lanes"),
                         "phases": xla["summary"].get("phases"),
                         "stages": xla["summary"].get("stages"),
                         "total_s": xla["summary"].get("total_s"),
                         "aligned": xla["aligned"]}
    print_report(report, desync)
    if xla:
        print_xla_report(xla)
    if args.trace:
        write_trace(dumps, args.trace, xla=xla)
        print(f"merged trace: {args.trace}")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
        print(f"report JSON: {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
