"""On-demand XLA device tracing with per-phase attribution.

PRs 10-11 fused forward, backward, gradient exchange and optimizer apply
into ONE donated XLA program, so the flight recorder sees a single opaque
dispatch per step. This module opens that box without TensorBoard:

- The step-program builders wrap each region in ``jax.named_scope``
  labels (``hvd_forward`` / ``hvd_backward`` / ``hvd_exchange`` /
  ``hvd_optimizer`` / ``hvd_guard``, plus ``hvd_ici`` / ``hvd_dcn``
  inside the staged exchange); the Pallas kernels carry names of their
  own (``hvd_flash_fwd`` ...). The scopes survive compilation as the
  per-instruction ``op_name`` metadata in the optimized HLO.
- ``hvd.trace_steps(n)`` (or ``HOROVOD_XPROF_STEPS=n``) arms a one-shot
  :class:`StepTracer`. The next ``n`` compiled steps are captured with
  ``jax.profiler`` (python tracer off) into ``xla-trace-<seq>/`` under
  ``HOROVOD_DIAG_DIR``.
- :func:`read_capture` reads the capture's ``*.xplane.pb`` with
  ``jax.profiler.ProfileData`` into plain lists — one reader, two
  extractors. On a TPU each chip is a plane ``/device:TPU:<n>`` whose
  ``XLA Ops`` line has one event per instruction that ran, NAMED by the
  instruction's HLO text (``%fusion.3 = f32[...] fusion(...)``; the
  instruction name is parsed from it), whose ``XLA Modules`` line has one
  event per program execution and whose ``Async XLA Ops`` line has the
  start-to-done spans of asynchronous collectives. The CPU backend (what
  the tests run on) puts its ops on ``/host:CPU`` thread lines, named by
  instruction, with ``hlo_op`` / ``hlo_module`` / ``device_ordinal`` /
  ``run_id`` stats. The program's own host spans (``hvd_*``
  ``TraceAnnotation``s, diag/recorder.py) are on ``/host:CPU`` in both.
- :func:`summarize` joins the instruction names against the HLO text of
  the executable that RAN (:func:`live_hlo`, no further compile) and sums
  device SELF time (an enclosing ``while`` does not count its body twice)
  per phase; instructions outside any ``hvd_`` scope land in ``other``.
  It also reports the named kernels, the collectives by opcode and
  message size, the host spans in the window and the clock mapping —
  and, from the fused computations' bodies :func:`build_op_table` keeps,
  the same time by scope PATH (``backward/hvd_ffn/hvd_ffn_gate``) and op
  class (``matmul``, ``copy``, ...) and every matmul fusion's time
  against its FLOPs, with what rides along in it.
  The summary plus the clock mapping is written next to the capture as
  ``xla-trace-meta.json`` so the ``python -m horovod_tpu.diag
  --xla-trace`` merger can lay the device view on the flight-recorder
  timeline offline.

Inert by default: no tracer object exists until armed (mirroring the
guard's disabled-state contract), and the per-step cost with a tracer
installed but idle is one attribute check.
"""

import glob
import json
import math
import os
import re
import statistics
import time
import typing

from .. import metrics
from ..utils.logging import get_logger
from . import recorder

_logger = get_logger()

#: Step-program regions annotated by ops/step_program.py, plus the MoE
#: sub-phases annotated by models/moe.py (``hvd_dispatch`` /
#: ``hvd_expert`` / ``hvd_combine`` — dispatch/combine wrap ONLY the
#: alltoall collectives, expert wraps the FFN einsums, so their buckets
#: are pure wire vs pure compute), plus the serve programs' top-level
#: scopes (``hvd_prefill`` / ``hvd_decode``, serve/engine.py); the parse
#: buckets. ``other`` collects device time outside any hvd_ scope.
PHASES = ("forward", "backward", "exchange", "optimizer", "guard",
          "dispatch", "expert", "combine", "prefill", "decode")
#: Staged-exchange tiers annotated by ops/collectives.py.
STAGES = ("ici", "dcn")
#: Collective opcodes (``-start`` forms included by prefix) and the
#: ``stats`` / profiler.txt label each is written under.
COLLECTIVES = {"all-reduce": "allreduce_xla", "all-gather": "allgather_xla",
               "reduce-scatter": "reducescatter_xla",
               "all-to-all": "alltoall_xla",
               "collective-permute": "collectivepermute_xla"}

META_FILENAME = "xla-trace-meta.json"

_REGION_RE = re.compile(r"hvd_(forward|backward|exchange|optimizer|guard"
                        r"|prefill|decode)")
_MOE_RE = re.compile(r"hvd_(dispatch|expert|combine)")
# the labels above as whole names: what a scope PATH leaves out after its
# first component (the phase)
_PHASE_LABEL_RE = re.compile(
    r"hvd_(?:forward|backward|exchange(?:_bucket\d+)?|optimizer|guard"
    r"|prefill|decode|dispatch|expert|combine)")
_STAGE_RE = re.compile(r"hvd_(ici|dcn)")
_SCOPE_RE = re.compile(r"hvd_[a-z0-9_]+")
# An HLO instruction, as a line of the optimized HLO text and as the name
# of a TPU `XLA Ops` event: `%name = <result type> opcode(...), ...,
# metadata={... op_name="jit(f)/hvd_forward/dot_general" ...}`. The
# op_name carries the named_scope path.
_NAME_RE = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OPCODE_RE = re.compile(r"[\]\})] ([a-z][a-z0-9\-]*)\(")
_OP_NAME_RE = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
_ARRAY_RE = re.compile(r"\b([a-z]+[0-9]*[a-z0-9]*)\[([0-9,]*)\]")
_LAYOUT_RE = re.compile(r"\{[^}]*\}")
_MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
                "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}


def phase_of_op_name(op_name):
    """Phase bucket for an HLO ``op_name`` scope path, or None when the
    instruction sits outside every hvd_ scope. The FIRST step-region
    label wins: the backward of a custom-vjp kernel is named
    ``hvd_backward/transpose(hvd_forward)/...`` and remat's recomputed
    forward ``hvd_backward/.../rematted_computation/...`` — both are
    backward time. The MoE sub-phases are the exception: they are leaves
    nested inside forward/backward whose buckets are pure wire or pure
    compute, so the innermost of them wins over the region."""
    op_name = op_name or ""
    moe = _MOE_RE.findall(op_name)
    if moe:
        return moe[-1]
    m = _REGION_RE.search(op_name)
    return m.group(1) if m else None


def stage_of_op_name(op_name):
    """``ici`` / ``dcn`` tier for an op_name path, or None."""
    hits = _STAGE_RE.findall(op_name or "")
    return hits[-1] if hits else None


def kernel_of_op_name(op_name):
    """The name a custom call runs under: the innermost ``hvd_*`` scope
    that is not a phase or stage label (``hvd_flash_dq``), else None."""
    for label in reversed(_SCOPE_RE.findall(op_name or "")):
        if not (_REGION_RE.match(label) or _MOE_RE.match(label)
                or _STAGE_RE.match(label)):
            return label
    return None


def _match_instruction(text):
    """The name's and the opcode's matches in one instruction's text, or
    None: the result type lies between them, the operands after."""
    m = _NAME_RE.match(text)
    op = m and _OPCODE_RE.search(text, m.end() - 1)
    return (m, op) if op else None


def parse_instruction(text):
    """``(name, opcode, result type)`` of one HLO instruction's text (a
    line of HLO, or the name of a TPU ``XLA Ops`` event), ``None`` when
    the text is not an instruction."""
    found = _match_instruction(text)
    if not found:
        return None
    m, op = found
    return m.group(1), op.group(1), text[m.end():op.start() + 1]


def shape_bytes(shape, largest=False):
    """Bytes of a result type (``f32[8,128]{1,0}``; a tuple sums its
    arrays, or with ``largest`` takes the biggest — the output of an
    asynchronous start whose tuple also carries its operand)."""
    sizes = []
    for dtype, dims in _ARRAY_RE.findall(_LAYOUT_RE.sub("", shape)):
        width = _DTYPE_BYTES.get(dtype, 1 if dtype.startswith("f8") else 0)
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        sizes.append(n * width)
    if not sizes:
        return 0
    return max(sizes) if largest else sum(sizes)


_COMPUTATION_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")


class Op(typing.NamedTuple):
    """One HLO instruction as :func:`build_op_table` keeps it. ``body``:
    for a ``fusion``, the instructions of the computation it ``calls=``
    (those of the fusions nested in it too), else empty. ``attrs``: for a
    ``convolution`` / ``dot`` its ``dim_labels``, ``window``,
    ``feature_group_count``, ``batch_group_count`` and contracting /
    batch dimensions as the text gives them, for a ``custom-call`` its
    ``custom_call_target``; None for every other opcode."""
    opcode: str
    shape: str
    op_name: str
    body: tuple = ()
    operands: tuple = ()
    attrs: typing.Optional[dict] = None


_NO_OP = Op("", "", "")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_ATTR_RE = re.compile(
    r"\b(dim_labels|feature_group_count|batch_group_count"
    r"|lhs_contracting_dims|rhs_contracting_dims|lhs_batch_dims"
    r"|custom_call_target)=(?:\{([\d,]*)\}|\"([^\"]*)\"|([^\s,]+))")
_WINDOW_RE = re.compile(r"\bwindow=\{([^}]*)\}")
_KEPT_ATTRS = ("convolution", "dot", "custom-call")


def _operands(text, start):
    """The operand names between the parenthesis at ``start`` and its
    match (jax 0.9 prints them as ``%name``; a TPU event's text puts the
    type in front of each)."""
    i = text.find(")", start)
    if i < 0 or text.count("(", start + 1, i):   # a tuple type inside
        depth = 0
        for i in range(start, len(text)):
            c = text[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if not depth:
                    break
    return tuple(_OPERAND_RE.findall(text, start, i)), i


def _attrs(text, start):
    out = {m.group(1): next(g for g in m.groups()[1:] if g is not None)
           for m in _ATTR_RE.finditer(text, start)}
    m = _WINDOW_RE.search(text, start)
    if m:
        out["window"] = m.group(1)
    return out


def build_op_table(hlo_text):
    """``{instruction name: Op}`` from optimized-HLO text — every
    instruction of every computation, a fusion with the instructions of
    the computation it calls as its ``body`` (the text defines a
    computation before its caller). ``Op[:3]`` is ``(opcode, result
    type, op_name)``; ``op_name`` is "" where the compiler created the
    instruction without metadata, and the trace join tolerates an
    instruction the table lacks (it falls into ``other``)."""
    table, comps, comp = {}, {}, None
    for line in (hlo_text or "").splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION_RE.match(line)
            if m:
                comp = comps.setdefault(m.group(1), [])
            continue
        found = _match_instruction(line)
        if not found:
            continue
        m, op = found
        opcode = op.group(1)
        operands, end = _operands(line, op.end() - 1)
        body = []
        if opcode == "fusion":
            called = _CALLS_RE.search(line, end)
            for inner in comps.get(called.group(1), ()) if called else ():
                body.append(inner)
                body.extend(inner.body)
        meta = _OP_NAME_RE.search(line, end)
        row = table[m.group(1)] = Op(
            opcode, line[m.end():op.start() + 1],
            meta.group(1) if meta else "", tuple(body), operands,
            _attrs(line, end) if opcode in _KEPT_ATTRS else None)
        if comp is not None:
            comp.append(row)
    return table


# ------------------------------------------------- scope, class and FLOPs

#: Op classes of ``summarize``'s ``classes``; of what a fusion's body
#: holds the earlier one names it.
CLASSES = ("matmul", "kernel", "collective", "gather_scatter", "reduce",
           "copy", "elementwise", "other")
_MATMUL = frozenset(("convolution", "dot"))
_GATHER = frozenset(("gather", "scatter", "sort"))
_REDUCE = frozenset(("reduce", "reduce-window"))
_MOVES = frozenset(("copy", "transpose", "bitcast", "slice", "dynamic-slice",
                    "dynamic-update-slice", "concatenate", "pad", "broadcast",
                    "reshape", "reverse"))
# in a fusion's body these say nothing about what the fusion does
_NEUTRAL = frozenset(("parameter", "constant", "tuple", "get-tuple-element",
                      "iota", "fusion"))
_CONTROL = frozenset(("", "while", "conditional", "call", "tuple",
                      "get-tuple-element", "parameter", "async-start",
                      "async-done", "async-update"))
TRANSCENDENTAL = frozenset(("exponential", "exponential-minus-one", "tanh",
                            "logistic", "divide", "rsqrt", "sqrt", "log",
                            "log-plus-one", "power", "erf"))


def scope_path(op_name):
    """The scope PATH of an ``op_name``: its phase
    (:func:`phase_of_op_name`; ``other`` outside every region), then
    every finer ``hvd_*`` name in nesting order, each once, whatever
    ``transpose(jvp(...))``, ``checkpoint`` or ``rematted_computation``
    autodiff wrapped around them: ``backward/hvd_ffn/hvd_ffn_gate``."""
    parts = [phase_of_op_name(op_name) or "other"]
    for label in _SCOPE_RE.findall(op_name or ""):
        if not _PHASE_LABEL_RE.fullmatch(label) and label not in parts:
            parts.append(label)
    return "/".join(parts)


def _base(opcode):
    """``all-reduce-start`` / ``slice-done`` -> the opcode they split."""
    for tail in ("-start", "-done"):
        if opcode.endswith(tail):
            return opcode[:-len(tail)]
    return opcode


def op_class(op, text=None):
    """The class of one instruction (:data:`CLASSES`), read from the
    instruction itself and, for a fusion, from its body: ``matmul`` holds
    a convolution / dot, ``kernel`` is a Mosaic custom call (``text``: a
    TPU event's, for an instruction the table lacks), ``copy`` holds
    nothing but data movement, ``other`` is control flow's own self time,
    XLA's own small custom calls and what cannot be told (a fusion whose
    body was not registered)."""
    if op.opcode == "fusion" and not op.body:
        return "other"
    if op.opcode == "custom-call":
        target = (op.attrs or {}).get("custom_call_target")
        mosaic = (target == "tpu_custom_call" if target is not None
                  else bool(text and _MOSAIC_TARGET in text))
        return "kernel" if mosaic else "other"
    codes = {_base(o.opcode) for o in op.body or (op,)}
    if op.body:
        codes -= _NEUTRAL
    elif op.opcode in _CONTROL:
        return "other"
    if codes & _MATMUL:
        return "matmul"
    if any(c in COLLECTIVES for c in codes):
        return "collective"
    if codes & _GATHER:
        return "gather_scatter"
    if codes & _REDUCE:
        return "reduce"
    return "copy" if codes <= _MOVES else "elementwise"


def _dims(shape):
    """``(dtype, [dims])`` of the first array of a type."""
    m = _ARRAY_RE.search(_LAYOUT_RE.sub("", shape))
    if not m:
        return "", []
    return m.group(1), [int(d) for d in m.group(2).split(",") if d]


def _window(text, n):
    """A convolution's ``window={size=1x4 pad=0_0x3_3 ...}`` as lists
    over its ``n`` spatial dimensions: pad (low), stride, lhs_dilate,
    rhs_dilate."""
    fields = dict(f.split("=", 1) for f in (text or "").split() if "=" in f)

    def per_dim(key, default):
        vals = fields.get(key)
        return ([int(v.split("_")[0]) for v in vals.split("x")] if vals
                else [default] * n)

    return (per_dim("pad", 0), per_dim("stride", 1),
            per_dim("lhs_dilate", 1), per_dim("rhs_dilate", 1))


def matmul_flops(op, table):
    """FLOPs of one ``convolution`` / ``dot`` instruction: two for every
    multiply-add of one element of each operand. A window's taps that
    fall on padding or into the holes of a dilated operand are not
    counted, so a product that XLA wrote as a convolution — a padded
    window, or a batch dimension as a dilated one with a stride — counts
    what the dense product of the same operands needs. None when an
    operand's type is not in ``table`` (operand types are looked up by
    name: jax 0.9's text does not print them inline)."""
    attrs = op.attrs or {}
    types = [table.get(name, _NO_OP).shape for name in op.operands[:2]]
    if len(types) < 2 or not all(types):
        return None
    lhs, rhs, out = (_dims(t)[1] for t in (*types, op.shape))
    if op.opcode == "dot":
        n = 2
        for d in out:
            n *= d
        for c in (attrs.get("lhs_contracting_dims") or "").split(","):
            n *= lhs[int(c)] if c else 1
        return n
    try:
        lhs_l, rest = attrs.get("dim_labels", "").split("_")
        rhs_l, out_l = rest.split("->")
        lhs, rhs, out = (dict(zip(lab, dims)) for lab, dims in
                         ((lhs_l, lhs), (rhs_l, rhs), (out_l, out)))
        n = (2 * lhs["b"] // int(attrs.get("batch_group_count") or 1)
             * rhs["o"] * rhs["i"])
    except (KeyError, ValueError):
        return None
    spatial = sorted(k for k in rhs_l if k.isdigit())
    for k, pad, stride, ld, rd in zip(
            spatial, *_window(attrs.get("window"), len(spatial))):
        # (output position, tap) pairs that meet an element of the left
        # operand: tap t of output o reads position o * stride + t * rd -
        # pad of the operand dilated by ld, whose elements sit at the
        # multiples of ld up to (size - 1) * ld
        size, taps, steps = lhs[k], rhs[k], out[k]
        g = math.gcd(stride, ld)
        m = ld // g
        pairs = 0
        for t in range(taps):
            c = t * rd - pad
            first = max(-(c // stride), 0)
            last = min(((size - 1) * ld - c) // stride, steps - 1)
            if last >= first and c % g == 0:
                o = (-c // g) * pow(stride // g, -1, m) % m
                pairs += (last - o) // m - (first - 1 - o) // m
        n *= pairs
    return n


def _matmul_row(op, table):
    """What ``summarize``'s ``matmuls`` says of one instruction of class
    ``matmul`` apart from its time: the FLOPs of a call (the body's
    convolutions together), the operands of the largest, and what rides
    along in the fusion."""
    own = set(_SCOPE_RE.findall(op.op_name))
    flops, best, rides, carries = 0, None, set(), False
    for inner in op.body or (op,):
        if inner.opcode in _MATMUL:
            n = matmul_flops(inner, table)
            flops = None if n is None or flops is None else flops + n
            if best is None or (n or 0) > best[0]:
                best = (n or 0, inner)
        elif _base(inner.opcode) in COLLECTIVES:
            carries = True
        elif inner.opcode in TRANSCENDENTAL:
            rides.add(inner.opcode)
        rides.update(set(_SCOPE_RE.findall(inner.op_name)) - own)
    lhs, rhs = (_LAYOUT_RE.sub("", table.get(name, _NO_OP).shape)
                for name in (best[1].operands + ("", ""))[:2])
    return {"result": _LAYOUT_RE.sub("", op.shape), "lhs": lhs, "rhs": rhs,
            "operands": "×".join(_dims(t)[0] for t in (lhs, rhs)),
            "flops": flops, "rides": rides,
            "carries_collective": carries}


def live_hlo(module_names=None, executables=None):
    """``{module name: HLO text}`` of the executables this process holds
    (``client.live_executables()``, or just ``executables`` of them): the
    programs that RAN, so the instruction names are the trace's and no
    further compile is paid. ``module_names`` keeps only those (a big
    program's text is MBs; only the ones asked for are stringified)."""
    import jax
    out = {}
    if executables is None:
        executables = jax.devices()[0].client.live_executables()
    for exe in executables:
        try:
            module = exe.hlo_modules()[0]
            if module_names is None or module.name in module_names:
                out[module.name] = (out.get(module.name, "")
                                    + module.to_string())
        except Exception:  # noqa: BLE001 - an executable without HLO
            continue
    return out


# replica groups of one device each, listed or as an iota: the
# all-reduce of an axis of size 1, which a backend may leave in the text
_ALONE_RE = re.compile(r"replica_groups=(?:\{(?:\{\d+\},?)+\}|\[\d+,1\]<=)")


def exchange_async(hlo_text):
    """What the compiler made of a step program's all-reduces, read from
    its optimized HLO: ``{"all_reduces", "async_all_reduces", "bytes",
    "async_bytes", "async_bytes_share"}``. An all-reduce is asynchronous
    where it can run beside compute: on a TPU inside an
    ``async_collective_fusion`` computation (ONE all-reduce fused with a
    compute fusion, ops/step_program.py), elsewhere as an
    ``all-reduce-start``. Everything else — in ENTRY, a loop body or a
    plain fusion — holds the core for as long as it travels. The
    ``async-collective-start`` / ``-done`` fusions that bracket a fused
    all-reduce repeat its instruction; they are not counted again, and
    an all-reduce over groups of one device exchanges nothing and is not
    counted at all. The share is 0.0 where nothing is all-reduced (one
    device)."""
    comp, rows, caller = None, [], {}
    for line in (hlo_text or "").splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION_RE.match(line)
            comp = m.group(1) if m else comp
            continue
        inst = parse_instruction(line)
        if inst is None:
            continue
        name, opcode, shape = inst
        if opcode == "fusion":
            m = _CALLS_RE.search(line)
            if m:
                caller[m.group(1)] = name
        elif (_collective(opcode) == "all-reduce"
              and not _ALONE_RE.search(line)):
            rows.append((comp or "", opcode, shape_bytes(shape)))
    out = {"all_reduces": 0, "async_all_reduces": 0, "bytes": 0,
           "async_bytes": 0}
    for comp, opcode, nbytes in rows:
        if caller.get(comp, "").startswith(("async-collective-start",
                                            "async-collective-done")):
            continue
        out["all_reduces"] += 1
        out["bytes"] += nbytes
        if (comp.startswith("async_collective_fusion")
                or opcode == "all-reduce-start"):
            out["async_all_reduces"] += 1
            out["async_bytes"] += nbytes
    out["async_bytes_share"] = (out["async_bytes"] / out["bytes"]
                                if out["bytes"] else 0.0)
    return out


# ------------------------------------------------------------- the reader

def _tpu_lane(plane):
    """Extractor for a ``/device:TPU:<n>`` plane: the instruction name is
    parsed from the event's HLO text, which is kept (opcode and result
    type are in it even when no HLO text was registered)."""
    lane = {"ops": [], "modules": [], "async": []}
    for line in plane.lines:
        if line.name == "XLA Modules":
            lane["modules"] = [[ev.name.split("(")[0], ev.start_ns,
                                ev.duration_ns] for ev in line.events]
        elif line.name in ("XLA Ops", "Async XLA Ops"):
            rows = lane["ops" if line.name == "XLA Ops" else "async"]
            for ev in line.events:
                inst = parse_instruction(ev.name)
                rows.append([inst[0] if inst else ev.name, ev.start_ns,
                             ev.duration_ns, ev.name])
    return lane


def _host_plane(plane, lanes, host):
    """``/host:CPU`` in one pass: the program's ``hvd_*`` annotations go
    to ``host``; and — the CPU backend's extractor — the events that carry
    an ``hlo_op`` stat are ops, one lane per ``device_ordinal``, a program
    execution being the extent of one ``run_id``."""
    runs = {}
    for line in plane.lines:
        for ev in line.events:
            stats = dict(ev.stats)
            if ev.name.startswith("hvd_"):
                step = stats.get("step_num")
                host.append([ev.name, ev.start_ns, ev.duration_ns,
                             None if step is None else int(step)])
            op = stats.get("hlo_op")
            if not op:
                continue
            key = f"cpu:{stats.get('device_ordinal', line.name)}"
            lane = lanes.setdefault(key, {"ops": [], "modules": [],
                                          "async": []})
            end = ev.start_ns + ev.duration_ns
            lane["ops"].append([str(op), ev.start_ns, ev.duration_ns, None])
            run = runs.setdefault(
                (key, stats.get("hlo_module", ""), stats.get("run_id")),
                [ev.start_ns, end])
            run[0] = min(run[0], ev.start_ns)
            run[1] = max(run[1], end)
    for (key, module, _), (t0, t1) in runs.items():
        lanes[key]["modules"].append([str(module), t0, t1 - t0])


def read_capture(trace_dir):
    """A ``jax.profiler`` capture directory as plain lists, or None when
    it holds no readable xplane file::

        {"lanes": {lane: {"ops": [[instruction, start_ns, dur_ns,
                                   hlo text or None], ...],
                          "modules": [[module, start_ns, dur_ns], ...],
                          "async": [[instruction, start_ns, dur_ns,
                                     hlo text], ...]}},
         "host": [[annotation, start_ns, dur_ns, step_num or None], ...],
         "device_kind": "TPU v5 Lite" or None,
         "files": [paths]}

    ``host`` holds the program's ``hvd_*`` annotations; times are on the
    profiler's clock; ``device_kind`` is what a TPU plane says of its
    chip. ``trace_dir`` may also be one ``*.xplane.pb`` file. Unreadable
    files degrade to "no data", never a crash."""
    if not trace_dir or not os.path.exists(trace_dir):
        return None
    import jax
    lanes, host, files, kind = {}, [], [], None
    for path in ([trace_dir] if os.path.isfile(trace_dir) else sorted(
            glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True))):
        try:
            data = jax.profiler.ProfileData.from_file(path)
            planes = list(data.planes)
        except Exception:  # noqa: BLE001 - malformed capture, skip
            _logger.warning("xla_trace: skipping unreadable trace file %s",
                            path)
            continue
        files.append(path)
        for plane in planes:
            m = re.match(r"^/device:TPU:(\d+)$", plane.name)
            if m:
                lanes[f"tpu:{m.group(1)}"] = _tpu_lane(plane)
                kind = dict(plane.stats).get("device_type_string", kind)
            elif plane.name == "/host:CPU":
                _host_plane(plane, lanes, host)
    if not files:
        return None
    for lane in lanes.values():
        for rows in lane.values():
            rows.sort(key=lambda r: (r[1], -r[2]))
    host.sort(key=lambda r: r[1])
    return {"lanes": lanes, "host": host, "files": files,
            "device_kind": kind}


# ----------------------------------------------------------- the reduction

def _merge_intervals(ivs):
    """Union of (start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(ivs):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _overlap(iv, merged):
    """Length of ``iv``'s intersection with a merged interval union."""
    s, e = iv
    total = 0.0
    for ms, me in merged:
        if me <= s:
            continue
        if ms >= e:
            break
        total += min(e, me) - max(s, ms)
    return total


def _self_times(rows):
    """Self time of each event of one lane: its duration minus what the
    events it encloses cover. ``rows`` sorted by (start, -duration)."""
    selfs = [r[2] for r in rows]
    stack = []
    for i, (_, s, d, *_) in enumerate(rows):
        e = s + d
        while stack and rows[stack[-1]][1] + rows[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= rows[stack[-1]][1] + rows[stack[-1]][2]:
            selfs[stack[-1]] -= d
        stack.append(i)
    return selfs


def _collective(opcode):
    """The base collective opcode of ``opcode`` (``all-reduce-start`` ->
    ``all-reduce``), or None; ``-done`` halves are not collectives of
    their own (the async span covers them)."""
    for base in COLLECTIVES:
        if opcode == base or opcode == base + "-start":
            return base
    return None


def _in_window(ring_spans, window):
    """The ring spans that lie inside ``window`` (``(t0, t1)`` on
    ``perf_counter``; None: all of them)."""
    return [s for s in ring_spans or ()
            if window is None or (s[1] >= window[0] and s[2] <= window[1])]


def clock_map(ring_spans, host_events, window=None):
    """The offset that lays the flight ring (``perf_counter`` seconds) on
    the profiler's clock (ns): every span that is both in the ring and in
    the xplane is a pair — the k-th ``name`` span inside the capture
    ``window`` (``(t0, t1)`` on ``perf_counter``) against the k-th
    ``hvd_<name>`` annotation — and the offset is the median of
    ``annotation start - span start``. Names whose counts differ on the
    two sides are left out. Returns ``{"offset_ns", "pairs",
    "spread_ns"}`` or None without a pair."""
    by_name = {}
    for name, t0, *_ in _in_window(ring_spans, window):
        by_name.setdefault("hvd_" + name, []).append(t0)
    seen = {}
    for name, start_ns, _, step in host_events or ():
        if step is None:  # the StepTraceAnnotation doubles step.execute
            seen.setdefault(name, []).append(start_ns)
    diffs = []
    for name, starts in by_name.items():
        got = seen.get(name, ())
        if len(got) == len(starts):
            diffs += [ns - t0 * 1e9
                      for t0, ns in zip(sorted(starts), sorted(got))]
    if not diffs:
        return None
    offset = statistics.median(diffs)
    return {"offset_ns": offset, "pairs": len(diffs),
            "spread_ns": max(diffs) - min(diffs)}


def _host_self_seconds(inside):
    """Self time per span name over the spans ``inside`` the window: a
    span's duration minus its children's (by parent id)."""
    child = {}
    for _, t0, t1, _, _, parent, _ in inside:
        child[parent] = child.get(parent, 0.0) + (t1 - t0)
    out = {}
    for name, t0, t1, _, sid, _, _ in inside:
        key = "hvd_" + name
        out[key] = out.get(key, 0.0) + (t1 - t0) - child.get(sid, 0.0)
    return out


def summarize(events, op_table=None, ring_spans=None, window=None,
              peak_flops=None):
    """Reduce :func:`read_capture`'s lists. Returns None when no device
    op ran in the capture; otherwise a dict::

        {"phases": {phase: seconds, ..., "other": s},
         "scopes": {scope path: seconds},
         "classes": {scope path: {op class: seconds}},
         "matmuls": [{"scope", "result", "lhs", "rhs", "operands",
                      "calls", "device_s", "flops", "at_peak_s",
                      "lost_s", "rides", "carries_collective"}, ...],
         "stages": {"ici": s, "dcn": s},
         "moe": {...} or None, "exchange": {...} or None,
         "kernels": {name: {"s": seconds, "calls": n}},
         "collectives": [{"op", "bytes", "calls", "device_s",
                          "exposed_s"}, ...],
         "host": {"hvd_<span>": self seconds},
         "clock": {"offset_ns", "pairs", "spread_ns",
                   "host_device_skew_bound_us"} or None,
         "idle": {"idle_s": s, "by": {span or reason: s}},
         "total_s": s, "events": n, "lanes": n, "step_runs": n,
         "ts_min_us": t, "ts_max_us": t, "files": [paths]}

    Every device time is SELF time summed over the lanes (one lane per
    chip): per-step-per-chip time is ``phases[p] / steps / lanes``.
    ``op_table`` is :func:`build_op_table` of the programs that ran;
    without it TPU ops still have their opcode and result type (from the
    event's text) but no scope, so everything is ``other``.

    ``scopes`` splits ``phases`` by scope PATH (:func:`scope_path`:
    ``backward/hvd_ffn/hvd_ffn_gate``; summed by its first component it
    is ``phases``) and ``classes`` splits that by op class
    (:func:`op_class`: which part of a name's time the matmul unit could
    have had, and which is data movement). ``matmuls``: one row per scope
    path, result type and operand types of class ``matmul``, sorted by
    ``lost_s`` — ``calls`` and ``device_s`` over all lanes and captured
    steps like every other time, ``flops`` of ONE call
    (:func:`matmul_flops`),
    ``at_peak_s`` a call's time at ``peak_flops`` (FLOP/s of a chip: the
    argument, else the table entry of ``hardware.PEAK_BF16_FLOPS`` for
    the capture's device; both left out on a device it does not know),
    ``lost_s = device_s - calls * at_peak_s``, ``operands`` the two
    types the matmul unit was handed (``f32×bf16``), ``rides`` what else
    the fusion holds — the other ``hvd_*`` names on its body's
    instructions (an ``hvd_optimizer`` epilogue, an ``hvd_block_io`` norm
    as producer) and its transcendental opcodes — and
    ``carries_collective`` for an all-reduce fused with its matmul.

    ``moe`` appears when the capture contains MoE sub-phases
    (``hvd_dispatch``/``hvd_combine`` wrap only the dispatch/combine
    alltoalls, ``hvd_expert`` only the expert FFN): ``hidden_s`` is the
    device time the alltoall intervals spend overlapped with the union
    of expert-compute intervals across ALL lanes — an alltoall lane is
    stalled on peers, so any concurrent expert compute anywhere on the
    mesh is dispatch latency the chunked pipeline hid — and
    ``hidden_frac = hidden_s / alltoall_s`` is the overlap fraction
    ``bench_transformer.py --moe`` prints as ``alltoall_hidden_frac``
    (the smoke in ``.github/workflows/ci.yml`` asserts on it; nothing
    under ``benchmark/`` reads it) and the gauge
    ``hvd_moe_alltoall_hidden_frac`` carries.

    ``exchange`` appears when the capture contains gradient-exchange
    device time (``hvd_exchange`` scopes — one interval per bucketed psum
    under HOROVOD_EXCHANGE_BUCKETS > 1): the same interval fold, with the
    compute union taken over the forward/backward/optimizer/expert phases
    across ALL lanes. ``hidden_frac = hidden_s / exchange_s`` feeds
    the gauge ``hvd_exchange_hidden_frac`` and ``bench.py``'s
    ``exchange_hidden_frac`` (ci.yml's overlap smoke).

    ``kernels``: custom calls by the name they run under
    (:func:`kernel_of_op_name`; an unnamed Mosaic call by its
    instruction's). ``collectives``:
    one row per opcode and message size — calls and device seconds over
    all lanes, and ``exposed_s``, the part of them during which no other
    op ran on that chip (all of a synchronous collective; for an
    asynchronous one, its start-to-done span less the compute under it).
    ``host``: self time per program span inside ``window`` (from the
    flight ring, ``ring_spans``). ``clock``: :func:`clock_map` plus
    ``host_device_skew_bound_us`` — over the step program's executions,
    the least (device start - ``step.execute`` start on the mapped clock):
    the enqueue cannot precede the run, so the host-device skew is at
    most that, and an idle gap shorter than it cannot be given to a
    span: ``idle`` files each gap between two busy intervals of a chip
    under the innermost program span open at its middle, or under
    ``under_skew_bound`` (:func:`_idle_by_span`)."""
    if not events:
        return None
    op_table = op_table or {}
    phases = {p: 0.0 for p in PHASES}
    phases["other"] = 0.0
    stages = {s: 0.0 for s in STAGES}
    kernels, coll, classes, matmuls = {}, {}, {}, {}
    expert_iv, a2a_iv, exch_iv, compute_iv = [], [], [], []
    n_events, ts_min, ts_max = 0, None, None
    lanes_seen, step_runs, idle_lanes = 0, [], []
    cache = {}

    def info(instr, text):
        row = cache.get(instr)
        if row is None:
            op = op_table.get(instr, _NO_OP)
            if not op.opcode and text:
                inst = parse_instruction(text)
                if inst:
                    op = Op(inst[1], inst[2], "")
            path = scope_path(op.op_name)
            by_class = classes.setdefault(path, {})
            cls = op_class(op, text)
            mm = None
            if cls == "matmul":
                found = _matmul_row(op, op_table)
                mm = matmuls.setdefault(
                    (path, found["result"], found["lhs"], found["rhs"],
                     found["flops"]),
                    dict(found, scope=path, calls=0, device_s=0.0))
                mm["rides"] |= found["rides"]
            row = cache[instr] = (op.opcode, op.shape, op.op_name,
                                  path.split("/", 1)[0],
                                  stage_of_op_name(op.op_name),
                                  by_class, cls, mm)
        return row

    for lane in events["lanes"].values():
        ops = lane["ops"]
        if not ops:
            continue
        lanes_seen += 1
        n_events += len(ops)
        ts_min = ops[0][1] if ts_min is None else min(ts_min, ops[0][1])
        end = max(o[1] + o[2] for o in ops)
        ts_max = end if ts_max is None else max(ts_max, end)
        busy = []   # leaf, non-collective ops: what can hide a collective
        pending = []
        for (instr, start, dur, text), self_ns in zip(ops,
                                                      _self_times(ops)):
            (opcode, shape, op_name, phase, stage, by_class, cls,
             mm) = info(instr, text)
            iv = (start, start + dur)
            phases[phase] += self_ns
            by_class[cls] = by_class.get(cls, 0.0) + self_ns
            if mm is not None:
                mm["calls"] += 1
                mm["device_s"] += self_ns * 1e-9
            if stage in stages:
                stages[stage] += self_ns
            if phase == "expert":
                expert_iv.append(iv)
            elif phase in ("dispatch", "combine"):
                a2a_iv.append(iv)
            if phase == "exchange":
                exch_iv.append(iv)
            elif phase in ("forward", "backward", "optimizer", "expert"):
                compute_iv.append(iv)
            if opcode == "custom-call":
                # a kernel: a custom call under a kernel name, or Mosaic's
                # (XLA's own tiny custom calls are not worth a row)
                label = kernel_of_op_name(op_name)
                if label or (text and _MOSAIC_TARGET in text):
                    k = kernels.setdefault(label or instr,
                                           {"s": 0.0, "calls": 0})
                    k["s"] += self_ns * 1e-9
                    k["calls"] += 1
            base = _collective(opcode)
            if base and not opcode.endswith("-start"):
                pending.append((base, shape_bytes(shape), iv))
            elif not base and not opcode.endswith("-done") \
                    and self_ns == dur:
                busy.append(iv)
        for instr, start, dur, text in lane["async"]:
            opcode, shape, *_ = info(instr, text)
            base = _collective(opcode)
            if base:
                pending.append((base, shape_bytes(
                    shape, largest=base != "all-reduce"),
                    (start, start + dur)))
        idle_lanes.append(_merge_intervals(
            (o[1], o[1] + o[2]) for o in ops))
        busy = _merge_intervals(busy)
        for base, nbytes, iv in pending:
            row = coll.setdefault((base, nbytes), {
                "op": base, "bytes": nbytes, "calls": 0, "device_s": 0.0,
                "exposed_s": 0.0})
            row["calls"] += 1
            row["device_s"] += (iv[1] - iv[0]) * 1e-9
            row["exposed_s"] += (iv[1] - iv[0] - _overlap(iv, busy)) * 1e-9
        step_runs.append(_step_runs(lane["modules"]))
    if n_events == 0:
        return None
    moe = None
    a2a_ns = phases["dispatch"] + phases["combine"]
    if a2a_ns > 0.0:
        merged = _merge_intervals(expert_iv)
        hidden_ns = sum(_overlap(iv, merged) for iv in a2a_iv)
        moe = {
            "dispatch_s": phases["dispatch"] * 1e-9,
            "combine_s": phases["combine"] * 1e-9,
            "expert_s": phases["expert"] * 1e-9,
            "alltoall_s": a2a_ns * 1e-9,
            "hidden_s": hidden_ns * 1e-9,
            "hidden_frac": min(hidden_ns / a2a_ns, 1.0),
        }
    exchange = None
    exch_ns = phases["exchange"]
    if exch_ns > 0.0:
        merged = _merge_intervals(compute_iv)
        hidden_ns = sum(_overlap(iv, merged) for iv in exch_iv)
        exchange = {
            "exchange_s": exch_ns * 1e-9,
            "hidden_s": hidden_ns * 1e-9,
            "hidden_frac": min(hidden_ns / exch_ns, 1.0),
        }
    inside = _in_window(ring_spans, window)
    clock = clock_map(inside, events.get("host"))
    if clock is not None:
        clock["host_device_skew_bound_us"] = _skew_bound_us(
            step_runs, inside, clock["offset_ns"])
    idle = _idle_by_span(idle_lanes, inside, clock)
    to_s = 1e-9  # xplane times are nanoseconds
    classes = {path: {c: v * to_s for c, v in by.items()}
               for path, by in classes.items() if by}
    return {
        "phases": {k: v * to_s for k, v in phases.items()},
        "scopes": {path: sum(by.values()) for path, by in classes.items()},
        "classes": classes,
        "matmuls": _matmul_rows(matmuls.values(), peak_flops
                                or _peak_of(events.get("device_kind"))),
        "stages": {k: v * to_s for k, v in stages.items()},
        "moe": moe,
        "exchange": exchange,
        "kernels": kernels,
        "collectives": sorted(coll.values(),
                              key=lambda r: -r["device_s"]),
        "host": _host_self_seconds(inside),
        "clock": clock,
        "idle": idle,
        "total_s": sum(phases.values()) * to_s,
        "events": n_events,
        "lanes": max(lanes_seen, 1),
        "step_runs": max((len(r) for r in step_runs), default=0),
        "ts_min_us": ts_min * 1e-3,
        "ts_max_us": ts_max * 1e-3,
        "files": events.get("files", []),
    }


def _peak_of(device_kind):
    """Peak FLOP/s of the chip a capture names (``TPU v5 Lite``), None
    for one ``hardware.PEAK_BF16_FLOPS`` does not list."""
    from .. import hardware
    for kind, peak in hardware.PEAK_BF16_FLOPS.items():
        if kind.lower() == str(device_kind).lower():
            return peak
    return None


def _matmul_rows(rows, peak):
    """``summarize``'s ``matmuls`` from the rows it gathered: the time
    at the peak and the time lost beside each, the most lost first."""
    out = []
    for row in rows:
        if not row["calls"]:   # seen on the asynchronous line only
            continue
        row = dict(row, rides=sorted(row["rides"]))
        if peak and row["flops"] is not None:
            row["at_peak_s"] = row["flops"] / peak
            row["lost_s"] = row["device_s"] - row["calls"] * row["at_peak_s"]
        out.append(row)
    return sorted(out, key=lambda r: -r.get("lost_s", r["device_s"]))


def _step_runs(modules):
    """Start times (ns) of the executions of the program that took most
    device time on one lane: the train step."""
    totals = {}
    for name, _, dur in modules:
        totals[name] = totals.get(name, 0.0) + dur
    if not totals:
        return []
    step = max(totals, key=totals.get)
    return sorted(start for name, start, _ in modules if name == step)


def _skew_bound_us(step_runs, inside, offset_ns):
    """min over lanes and captured steps of (k-th step-program start on
    the device - k-th ``step.execute`` start mapped onto the profiler's
    clock), in us; None when the two sides cannot be matched. ``inside``:
    the ring spans of the capture window."""
    enq = sorted(s[1] * 1e9 + offset_ns for s in inside
                 if s[0] == "step.execute")
    gaps = [run - e for runs in step_runs if len(runs) == len(enq)
            for run, e in zip(runs, enq)]
    return min(gaps) * 1e-3 if gaps else None


def _idle_by_span(idle_lanes, ring_spans, clock):
    """``{"idle_s": s, "by": {label: s}}`` over the lanes: each gap
    between two busy intervals of a chip, filed under the innermost
    program span open at its middle on the loop's thread (on the mapped
    clock), ``no_span``
    when none was, ``under_skew_bound`` when the gap is shorter than the
    host-device skew bound (it cannot be given to a span) and
    ``unmapped`` without a clock."""
    by = {}
    bound_ns = None
    mapped = []
    if clock is not None:
        bound = clock.get("host_device_skew_bound_us")
        bound_ns = None if bound is None else bound * 1e3
        # the thread that enqueues the steps is the one the chip waits
        # for; the producer thread's spans overlap it and explain nothing
        loop = {s[3] for s in ring_spans or () if s[0] == "step.execute"}
        mapped = [(s[1] * 1e9 + clock["offset_ns"],
                   s[2] * 1e9 + clock["offset_ns"], "hvd_" + s[0])
                  for s in ring_spans or () if not loop or s[3] in loop]
    for merged in idle_lanes:
        for (_, e0), (s1, _) in zip(merged, merged[1:]):
            gap, mid = s1 - e0, (e0 + s1) / 2
            if clock is None:
                label = "unmapped"
            elif bound_ns is None or gap < bound_ns:
                label = "under_skew_bound"
            else:
                open_ = [m for m in mapped if m[0] <= mid <= m[1]]
                label = (max(open_, key=lambda m: m[0])[2] if open_
                         else "no_span")
            by[label] = by.get(label, 0.0) + gap * 1e-9
    return {"idle_s": sum(by.values()), "by": by}


def parse_trace_dir(trace_dir, op_table=None, ring_spans=None, window=None):
    """:func:`summarize` of :func:`read_capture`: a capture directory to
    its summary, None when it holds no device events."""
    return summarize(read_capture(trace_dir), op_table, ring_spans, window)


def load_meta(trace_dir):
    """The capture's ``xla-trace-meta.json`` sidecar, or None."""
    path = os.path.join(trace_dir, META_FILENAME)
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except Exception:  # noqa: BLE001 - absent/corrupt sidecar
        return None


# ------------------------------------------------------------- the tracer

class StepTracer:
    """One-shot, step-aligned ``jax.profiler`` capture.

    ``arm(n)`` requests a window; the training loop calls :meth:`tick`
    once per step (``CompiledTrainStep.__call__`` does it on the hot
    path, ``TelemetryCallback`` covers eager loops). The first tick
    after arming starts the device trace; after ``n`` further ticks the
    trace stops, parses, writes the sidecar meta and exports
    ``hvd_xla_phase_seconds`` / ``hvd_wire_stage_seconds``. A ticker that
    can wait for the device passes ``drain``: the tracer calls it right
    before the trace starts and right before it stops, so the capture
    holds ``n`` WHOLE steps, the first from an empty queue (which is what
    makes ``clock.host_device_skew_bound_us`` tight) — one pipeline
    bubble at each end of an on-demand capture. Single training-thread
    discipline: tick/arm race at worst delays a capture by a step, never
    corrupts state."""

    def __init__(self, diag_dir="", rank=0):
        self.diag_dir = diag_dir or "."
        self.rank = rank
        self.captures = 0
        self.last_summary = None
        self.last_dir = None
        self._want = 0
        self._n = 0
        self._seen = 0
        self._active = False
        self._owner = None
        self._seq = 0
        self._op_table = {}
        self._wall_start = 0.0
        self._mono_start = 0.0

    @property
    def active(self):
        return self._active

    @property
    def armed(self):
        return self._want > 0

    def register_hlo(self, hlo_text):
        """Add a program's instructions to the join table by hand. The
        tracer finds the HLO of what ran by itself (:func:`live_hlo`);
        this is for a program whose executable is gone by ``stop()``."""
        if hlo_text:
            self._op_table.update(build_op_table(hlo_text))

    def arm(self, n, out_dir=None):
        """Request a capture of the next ``n`` full steps (n >= 1)."""
        n = int(n)
        if n <= 0:
            return
        if out_dir:
            self.diag_dir = out_dir
        # A new window re-locks to whoever ticks first: without this a
        # tracer reused across program objects (bench A/B, successive
        # profiles) would silently ignore the new step's cadence.
        self._owner = None
        self._want = n

    def tick(self, owner=None, drain=None):
        """Step-boundary hook. ``owner`` locks the step cadence to the
        first caller that ticks (a compiled step and a telemetry
        callback in the same loop would otherwise double-count).
        ``drain`` is a zero-arg callable that returns once the device
        has finished every step enqueued so far; it is called only at
        the two ends of a capture."""
        if not self._want and not self._active:
            return
        if owner is not None:
            if self._owner is None:
                self._owner = owner
            elif self._owner is not owner:
                return
        if not self._active:
            self._start(drain)
            return
        self._seen += 1
        if self._seen >= self._n:
            self.stop(drain)

    @staticmethod
    def _drain(drain):
        if drain is not None:
            try:
                drain()
            except Exception:  # noqa: BLE001 - tracing must never kill a step
                _logger.warning("xla_trace: drain failed", exc_info=True)

    def _start(self, drain=None):
        import jax
        # Claim the first unused sequence dir: a tracer recreated after an
        # elastic re-init restarts _seq at 0, and blindly reusing
        # xla-trace-001 would mix two captures' event files and overwrite
        # the earlier sidecar meta with a join over both.
        for _ in range(1000):
            self._seq += 1
            out = os.path.join(self.diag_dir,
                               f"xla-trace-{self._seq:03d}")
            if not (os.path.isdir(out) and os.listdir(out)):
                break
        self._drain(drain)
        try:
            os.makedirs(out, exist_ok=True)
            # The python tracer would record every frame of the loop:
            # megabytes a step, and host time that is the tracer's own.
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(out, profiler_options=opts)
        except Exception:  # noqa: BLE001 - e.g. a foreign trace is active
            _logger.warning("xla_trace: could not start device trace",
                            exc_info=True)
            self._want = 0
            return
        self.last_dir = out
        self._n, self._want, self._seen = self._want, 0, 0
        self._wall_start = time.time()
        self._mono_start = time.perf_counter()
        self._active = True

    def stop(self, drain=None):
        """Stop and finalize the current capture (no-op when idle).
        Returns the parsed summary dict, or None."""
        self._owner = None
        if not self._active:
            self._want = 0
            return None
        import jax
        self._active = False
        self._drain(drain)
        mono_stop = time.perf_counter()
        try:
            jax.profiler.stop_trace()
        except Exception:  # noqa: BLE001
            _logger.warning("xla_trace: stop_trace failed", exc_info=True)
            return None
        wall_stop = time.time()
        steps = max(self._seen, 1)
        summary = self._summarize((self._mono_start, mono_stop))
        meta = {
            "version": 2,
            "rank": self.rank,
            "steps": steps,
            "wall_start": self._wall_start,
            "wall_stop": wall_stop,
            "wall_elapsed_s": wall_stop - self._wall_start,
            # one instant on both host clocks: with summary.clock's
            # offset it lays profiler ns on the flight dumps' wall clock
            "mono_start": self._mono_start,
            "trace_dir": self.last_dir,
            # the side file keeps the forty matmul rows that lose most
            "summary": summary and dict(
                summary, matmuls=summary["matmuls"][:40]),
            # Per-instruction phase/stage labels so the offline diag CLI
            # (--xla-trace) can phase-attribute individual device events
            # without the executable's HLO text.
            "op_phases": {instr: [phase_of_op_name(row[2]),
                                  stage_of_op_name(row[2])]
                          for instr, row in self._op_table.items()
                          if row[2]},
        }
        try:
            path = os.path.join(self.last_dir, META_FILENAME)
            tmp = f"{path}.tmp"
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(meta, f, indent=1, default=str)
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001
            _logger.warning("xla_trace: could not write %s", META_FILENAME,
                            exc_info=True)
        self.captures += 1
        self.last_summary = summary
        metrics.XLA_TRACE_CAPTURES.inc()
        if summary:
            self._export(summary, steps)
        rec = recorder.get()
        if rec is not None:
            rec.record("xla_trace", name=self.last_dir or "",
                       extra={"steps": steps,
                              "total_s": summary["total_s"] if summary
                              else 0.0})
        return summary

    def _summarize(self, window):
        """Read the capture, join it against the HLO of the programs
        that ran in it and reduce; None on an empty or unreadable one."""
        try:
            events = read_capture(self.last_dir)
            if events:
                names = {m[0] for lane in events["lanes"].values()
                         for m in lane["modules"]}
                for text in live_hlo(names).values():
                    self.register_hlo(text)
            return summarize(events, self._op_table, recorder.spans(),
                             window, self._peak_flops())
        except Exception:  # noqa: BLE001 - a capture that cannot be read
            _logger.warning("xla_trace: could not reduce %s", self.last_dir,
                            exc_info=True)
            return None

    @staticmethod
    def _peak_flops():
        """A chip's peak FLOP/s as the MFU gauges take it
        (``HOROVOD_PEAK_FLOPS``, else the table's entry for the device);
        None on the CPU and on a chip the table does not list."""
        from .. import hardware, runtime
        try:
            return hardware.peak_flops_per_chip(
                runtime.state().config if runtime.is_initialized()
                else None) or None
        except ValueError:
            return None

    @staticmethod
    def _export(summary, steps):
        """The summary into the gauges, and its collectives into the
        per-collective profile (stats.py -> profiler.txt) under their
        ``*_xla`` labels: per logical collective (one chip's calls), with
        the mean device time of a call."""
        lanes = summary["lanes"]
        for phase, sec in summary["phases"].items():
            metrics.XLA_PHASE_SECONDS.labels(phase=phase).set(sec)
        for stage, sec in summary["stages"].items():
            if sec > 0.0:
                metrics.WIRE_STAGE_SECONDS.labels(stage=stage).observe(
                    sec / steps / lanes)
        if summary.get("moe"):
            metrics.MOE_ALLTOALL_HIDDEN_FRAC.set(
                summary["moe"]["hidden_frac"])
        if summary.get("exchange"):
            metrics.EXCHANGE_HIDDEN_FRAC.set(
                summary["exchange"]["hidden_frac"])
        from .. import runtime
        st = runtime._state.stats if runtime.is_initialized() else None
        if st is not None:
            for row in summary["collectives"]:
                per_call = row["device_s"] / row["calls"]
                for _ in range(max(round(row["calls"] / lanes), 1)):
                    st.record(COLLECTIVES[row["op"]], row["bytes"],
                              per_call)


# --------------------------------------------------------- module plumbing

_tracer = None


def install(config, rank=0):
    """Create the process tracer at init. Returns None — and leaves NO
    tracer/profiler state behind — unless ``HOROVOD_XPROF_STEPS`` arms a
    capture (``hvd.trace_steps`` creates one on demand later)."""
    global _tracer
    steps = int(getattr(config, "xprof_steps", 0))
    if steps <= 0:
        _tracer = None
        return None
    _tracer = StepTracer(diag_dir=getattr(config, "diag_dir", ""), rank=rank)
    _tracer.arm(steps)
    return _tracer


def get():
    """The process tracer, or None when nothing ever armed one."""
    return _tracer


def uninstall():
    """Drop the tracer, stopping any still-active capture first."""
    global _tracer
    t, _tracer = _tracer, None
    if t is not None and t.active:
        try:
            t.stop()
        except Exception:  # noqa: BLE001
            _logger.debug("xla_trace: stop on uninstall failed",
                          exc_info=True)


def trace_steps(n, out_dir=None, rank=0):
    """Arm a one-shot device-trace capture of the next ``n`` compiled
    steps (the programmatic form of ``HOROVOD_XPROF_STEPS``). Creates
    the tracer on demand; ``out_dir`` overrides the capture directory
    (default: ``HOROVOD_DIAG_DIR``, else the CWD). Returns the tracer."""
    global _tracer
    if _tracer is None:
        diag_dir = out_dir
        if not diag_dir:
            from .. import runtime
            if runtime.is_initialized():
                diag_dir = getattr(runtime.state().config, "diag_dir", "")
        _tracer = StepTracer(diag_dir=diag_dir or "", rank=rank)
    _tracer.arm(n, out_dir)
    return _tracer
