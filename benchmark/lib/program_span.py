"""Reader source ``program_span``: per-layer metrics read from the spans
the PROGRAM records about itself (``hvd.diag.spans()``, the flight
recorder's ring: ``import``, ``init``, ``bcast.*``, ``step.*``, ``jax.*``,
``data.*`` — docs/diagnostics.md "Host spans"), not from the harness's
clock around its calls into the program.

A metric file names what it reads::

    {"source": "program_span", "pattern": "<regex on the span's name>",
     "phase": "setup" | "window" | "traced" (optional),
     "under": "<regex on an ancestor's name>" (optional),
     "under_first": true (optional: only the FIRST such ancestor),
     "reduce": "union_s" | "self_s" | "sum_ms_per_step" |
               "sum_ms_per_batch"}

``phase`` is the harness phase the span STARTS in. Both clocks are
``time.perf_counter`` of one process, so the harness's own rows
(``ctx["spans"]``) bound the phases: the window runs from its first row's
start to its last row's end, set-up is everything before it, the traced
window likewise from its rows. ``under`` follows the spans' parent ids.

``union_s`` is the time the matched intervals COVER, not their sum: jax
reports a trace of a jitted function inside another's as an event of its
own, inside the outer one. ``self_s`` is a span's duration less what its
children cover. A program without spans (a parent commit from
before they existed) reads as nothing: every metric is left out.
"""

import re


def program_spans(ctx):
    """``hvd.diag.spans()`` of this process — ``[(name, start, end,
    thread, id, parent, attrs)]`` — fetched once per run; ``[]`` from a
    program that has none."""
    if "program_spans" not in ctx:
        try:
            from horovod_tpu import diag
            ctx["program_spans"] = list(diag.spans())
        except Exception:  # noqa: BLE001 - a program from before the spans
            ctx["program_spans"] = []
    notes = ctx.setdefault("notes", {})
    if "program_spans" not in notes:
        # for --dump-dir: seconds and count per span name and phase
        bounds, table = phase_bounds(ctx["spans"]), {}
        for name, s, e, *_ in ctx["program_spans"]:
            phase = next((p for p, (lo, hi) in bounds.items()
                          if lo <= s <= hi), "other")
            row = table.setdefault(f"{phase}/{name}", [0.0, 0])
            row[0] += e - s
            row[1] += 1
        notes["program_spans"] = table
    return ctx["program_spans"]


def phase_bounds(rows):
    """``{phase: (start, end)}`` on ``perf_counter`` from the harness's
    rows ``(phase, name, start, end)``; ``setup`` ends where the window
    starts and has no beginning (the import precedes every row)."""
    out = {}
    for phase, _, s, e in rows:
        lo, hi = out.get(phase, (s, e))
        out[phase] = (min(lo, s), max(hi, e))
    if "window" in out:
        out["setup"] = (float("-inf"), out["window"][0])
    return out


def select(spans, reader, bounds):
    """The spans a reader file asks for."""
    rx = re.compile(reader["pattern"])
    hits = [s for s in spans if rx.search(s[0])]
    phase = reader.get("phase")
    if phase:
        if phase not in bounds:
            return []
        lo, hi = bounds[phase]
        hits = [s for s in hits if lo <= s[1] <= hi]
    if reader.get("under"):
        ux = re.compile(reader["under"])
        by_id = {s[4]: s for s in spans}
        roots = sorted((s for s in spans if ux.search(s[0])),
                       key=lambda s: s[1])
        if reader.get("under_first"):
            roots = roots[:1]
        root_ids = {s[4] for s in roots}

        def descends(s):
            seen = 0
            while s is not None and seen < 64:
                if s[5] in root_ids:
                    return True
                s, seen = by_id.get(s[5]), seen + 1
            return False

        hits = [s for s in hits if descends(s)]
    return hits


def _union_s(hits):
    total, end = 0.0, None
    for _, s, e, *_ in sorted(hits, key=lambda h: h[1]):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def read(reader, ctx):
    spans = program_spans(ctx)
    if not spans:
        return None
    hits = select(spans, reader, phase_bounds(ctx["spans"]))
    if not hits:
        return None
    reduce = reader["reduce"]
    if reduce == "union_s":
        return _union_s(hits)
    if reduce == "self_s":
        kids = {}
        for s in spans:
            kids.setdefault(s[5], []).append(s)
        return sum(h[2] - h[1] - _union_s(kids.get(h[4], ())) for h in hits)
    total_ms = 1e3 * sum(e - s for _, s, e, *_ in hits)
    if reduce == "sum_ms_per_step":
        return total_ms / ctx["steps"] if ctx["steps"] else None
    if reduce == "sum_ms_per_batch":
        batches = {h[6].get("batch") for h in hits}
        return total_ms / len(batches)
    raise SystemExit(f"program_span: unknown reduce {reduce!r}")
