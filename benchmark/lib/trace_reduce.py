"""From a profiler trace to per-device numbers: the benchmark's own reducer.

What a v5e trace looks like (looked at by hand, PR 22; jax 0.9.0, libtpu
0.0.34, read with ``jax.profiler.ProfileData``):

- one plane per chip, ``/device:TPU:<n>``, with the lines ``XLA Modules``
  (one event per execution of a compiled program, named
  ``jit_<fn>(<fingerprint>)``), ``XLA Ops`` (one event per HLO instruction
  that ran; an instruction that calls a computation, such as ``while``,
  encloses its body's events) and ``Async XLA Ops`` (start-to-done spans of
  asynchronous copies and collectives);
- an ``XLA Ops`` event's NAME is the instruction's HLO text without its
  metadata: ``%fusion.3 = f32[2048,2048]{...} fusion(...), kind=kOutput,
  calls=...``. A Pallas kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"``; ``pallas_call`` carries no
  ``name=`` in this repository, so the target is all that tells it apart;
- the ``jax.named_scope`` path (``hvd_forward`` ...) is NOT in the event: it
  is the ``op_name`` in the instruction's metadata in the compiled HLO text,
  and is joined on by instruction name (``scope_map``). The
  ``*.trace.json.gz`` the profiler writes beside the xplane does carry it,
  as the ``tf_op`` arg of each device event;
- ``jax.profiler.TraceAnnotation`` spans are on plane ``/host:CPU``, line
  ``python3``, on a clock about 1 ms off the device's.

The reduction is two steps, kept apart so that the second can be checked on
a small recorded trace (``benchmark/tests``): ``read_xplane`` turns the file
into plain lists, ``reduce_trace`` turns those into per-device numbers.
"""

import re
import statistics

_NAME_RE = re.compile(r"^%?([\w.\-]+) = ")
_OPCODE_RE = re.compile(r"[\]\})] ([a-z][a-z0-9\-]*)\(")
_TARGET_RE = re.compile(r'custom_call_target="([^"]*)"')
_LAYOUT_RE = re.compile(r"\{[^}]*\}")
_HLO_LINE_RE = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*metadata=\{[^}]*op_name="([^"]*)"')
_SCOPE_RE = re.compile(r"hvd_[a-z0-9_]+")


def scope_map(hlo_text):
    """``{instruction name: op_name}`` from optimized HLO text."""
    out = {}
    for line in (hlo_text or "").splitlines():
        m = _HLO_LINE_RE.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def parse_op(text):
    """``(name, opcode, custom-call target, result type)`` of an XLA Ops
    event name (HLO instruction text); the type without its layouts."""
    m = _NAME_RE.match(text)
    name = m.group(1) if m else text.split(" ")[0].lstrip("%")
    start = m.end() if m else 0
    m = _OPCODE_RE.search(text, start)
    opcode = m.group(1) if m else ""
    shape = _LAYOUT_RE.sub("", text[start:m.start() + 1]) if m else ""
    m = _TARGET_RE.search(text)
    return name, opcode, (m.group(1) if m else ""), shape


def read_xplane(path, host_prefix="bench_"):
    """The trace file as plain lists::

        {"devices": {"0": {"modules": [[name, start_ns, dur_ns], ...],
                           "ops": [[hlo text, start_ns, dur_ns], ...]}},
         "host": [[annotation, start_ns, dur_ns], ...]}
    """
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m:
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] = [[ev.name, ev.start_ns, ev.duration_ns]
                                for ev in line.events]
            devices[m.group(1)] = dev
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [[ev.name, ev.start_ns, ev.duration_ns]
                         for ev in line.events
                         if ev.name.startswith(host_prefix)]
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def _union(intervals):
    total, end = 0.0, None
    merged = []
    for s, e in sorted(intervals):
        if end is None or s > end:
            merged.append([s, e])
            end = e
        elif e > end:
            merged[-1][1] = end = e
    for s, e in merged:
        total += e - s
    return total, merged


def _self_times(ops):
    """Self time of each event on one line: its duration minus what the
    events it encloses cover. ``ops``: ``[start, end]`` sorted by start."""
    selfs = [e - s for s, e in ops]
    stack = []
    for i, (s, e) in enumerate(ops):
        while stack and ops[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1]:
            selfs[stack[-1]] -= e - s
        stack.append(i)
    return selfs


def _step_module(modules):
    """The program that took most device time: the train step."""
    totals = {}
    for name, _, dur in modules:
        base = name.split("(")[0]
        totals[base] = totals.get(base, 0.0) + dur
    return max(totals, key=totals.get) if totals else None


def reduce_trace(events, scopes, drop_first=1):
    """Per-device numbers over the steady steps of a traced window.

    The window on a device runs from the start of the second traced
    execution of the step program (the first starts from a drained queue)
    to the end of the last one. Returns ``{"devices": {id: {...}},
    "host": [...]}`` with, per device: ``steps``, ``window_ns``,
    ``busy_ns`` (union of the XLA Ops intervals in the window),
    ``step_span_ns`` (median duration of a step program execution) and
    ``ops``: dicts of ``name, opcode, target, shape, scope, start, dur,
    self`` for every op in the window."""
    out = {}
    for dev, lines in events["devices"].items():
        step_name = _step_module(lines["modules"])
        runs = sorted([m for m in lines["modules"]
                       if m[0].split("(")[0] == step_name],
                      key=lambda m: m[1])[drop_first:]
        if not runs:
            continue
        w0, w1 = runs[0][1], runs[-1][1] + runs[-1][2]
        inside = sorted([o for o in lines["ops"]
                         if o[1] >= w0 and o[1] + o[2] <= w1],
                        key=lambda o: (o[1], -o[2]))
        spans = [[o[1], o[1] + o[2]] for o in inside]
        selfs = _self_times(spans)
        busy, merged = _union(spans)
        ops = []
        for (text, start, dur), self_ns in zip(inside, selfs):
            name, opcode, target, shape = parse_op(text)
            ops.append({"name": name, "opcode": opcode, "target": target,
                        "shape": shape, "scope": scopes.get(name, ""),
                        "start": start, "dur": dur, "self": self_ns})
        out[dev] = {
            "module": step_name, "steps": len(runs),
            "window_ns": w1 - w0, "busy_ns": busy,
            "step_span_ns": statistics.median(m[2] for m in runs),
            "ops": ops, "busy_intervals": merged,
        }
    return {"devices": out, "host": events.get("host", [])}


def select(trace, field, pattern):
    """Per device, the self time (ns) of the ops whose ``field`` matches:
    ``scope`` is the op_name path; ``op`` is ``"<opcode> <custom-call
    target> <instruction name>"``."""
    rx = re.compile(pattern)
    out = {}
    for dev, d in trace["devices"].items():
        total = 0.0
        for o in d["ops"]:
            text = (o["scope"] if field == "scope"
                    else f"{o['opcode']} {o['target']} {o['name']}")
            if rx.search(text):
                total += o["self"]
        out[dev] = total
    return out


def mean_over_devices(trace, per_device_fn):
    vals = [per_device_fn(dev, d) for dev, d in trace["devices"].items()]
    return sum(vals) / len(vals) if vals else None


def _label(op):
    scopes = _SCOPE_RE.findall(op["scope"])
    tail = op["scope"].rsplit("/", 1)[-1] if op["scope"] else ""
    kind = op["target"] or tail or op["opcode"]
    return (f"{scopes[0] if scopes else 'no_scope'}/{kind} "
            f"{op['shape']}")[:120]


def breakdown(trace, limit=10):
    """What the next issue's writer sees of the trace: the device
    operations that took most time (self seconds per device over the
    window, grouped by scope, kind and result shape) and the idle gaps of
    the first device grouped by what the host was doing then."""
    n = max(len(trace["devices"]), 1)
    groups = {}
    for d in trace["devices"].values():
        for o in d["ops"]:
            key = _label(o)
            groups[key] = groups.get(key, 0.0) + o["self"] / n
    device_ops = sorted(groups.items(), key=lambda kv: -kv[1])[:limit]
    gaps = {}
    if trace["devices"]:
        d = trace["devices"][sorted(trace["devices"])[0]]
        iv = d["busy_intervals"]
        for (_, e0), (s1, _) in zip(iv, iv[1:]):
            mid = (e0 + s1) / 2
            doing = "host:" + next(
                (h[0] for h in reversed(trace["host"])
                 if h[1] <= mid <= h[1] + h[2]), "no_span")
            gaps[doing] = gaps.get(doing, 0.0) + (s1 - e0)
    idle_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:limit]
    return {"device_ops": [[k, v * 1e-9] for k, v in device_ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in idle_gaps]}
