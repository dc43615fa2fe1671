"""Per-layer metrics, read as their files say.

Each per-layer metric of ``BENCHMARK.json`` has a file
``benchmark/layer_metrics/<name>.json``::

    {"layer": "...", "moves": "...", "source": "host_span" | "counter" |
     "trace_scope" | "trace_kernel" | "trace_device", "pattern": "<regex>",
     "reduce": "...", "cells": [...optional...], "note": "..."}

and this module is the general reader: most new metrics are a new file and
no new code. A reader that finds nothing to read returns ``None`` and the
harness leaves the metric out of the line.

What a run hands the readers (``ctx``):

- ``spans``: ``[(phase, name, start_s, end_s)]`` host-clock spans the
  harness put around its own calls into the program; ``host_span`` matches
  ``"<phase>/<name>"`` (phases: ``setup``, ``window``, ``traced``);
- ``counters``: ``{family: increase over the window}`` from
  ``hvd.metrics_snapshot()``;
- ``trace``: ``trace_reduce.reduce_trace`` of the traced window, or None;
  ``trace_scope`` matches an op's ``jax.named_scope`` path,
  ``trace_kernel`` matches ``"<opcode> <custom-call target> <name>"``;
- ``steps`` (window), ``shape`` (for ``flops``), ``peaks``,
  ``seqs_per_chip``.
"""

import re

from . import flops, trace_reduce


def _host_span(reader, ctx):
    rx = re.compile(reader["pattern"])
    hits = [e - s for phase, name, s, e in ctx["spans"]
            if rx.search(f"{phase}/{name}")]
    if not hits:
        return None
    if reader["reduce"] == "sum_s":
        return sum(hits)
    if reader["reduce"] == "sum_ms_per_step":
        return 1e3 * sum(hits) / ctx["steps"] if ctx["steps"] else None
    raise SystemExit(f"host_span: unknown reduce {reader['reduce']!r}")


def _counter(reader, ctx):
    rx = re.compile(reader["pattern"])
    hits = [v for k, v in ctx["counters"].items() if rx.search(k)]
    if not hits or reader["reduce"] != "count":
        return None
    return sum(hits)


def _flash_work(ctx):
    """Least seconds per step per chip for the attention kernels: each of
    the three kernels once per layer on this chip's sequences, at the
    larger of FLOPs / peak and bytes / bandwidth."""
    work = flops.flash_kernel_work(ctx["shape"], ctx["seqs_per_chip"])
    total, bound_by = 0.0, set()
    for f, b in work.values():
        t, bound = flops.roofline_seconds(f, b, ctx["peaks"])
        total += t
        bound_by.add(bound)
    return ctx["shape"]["n_layers"] * total, "+".join(sorted(bound_by))


WORK = {"flash_attention": _flash_work}


def _trace_ops(reader, ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    field = "scope" if reader["source"] == "trace_scope" else "op"
    matched = trace_reduce.select(trace, field, reader["pattern"])
    reduce = reader["reduce"]
    if reduce == "sum_ms_per_step":
        return trace_reduce.mean_over_devices(
            trace, lambda dev, d: matched[dev] / d["steps"] * 1e-6)
    if reduce == "share_of_step":
        return trace_reduce.mean_over_devices(
            trace, lambda dev, d: 100.0 * matched[dev]
            / (d["steps"] * d["step_span_ns"]))
    if reduce == "roofline_share":
        least_s, bound_by = WORK[reader["work"]](ctx)
        ctx["notes"][reader["work"]] = bound_by
        spent = trace_reduce.mean_over_devices(
            trace, lambda dev, d: matched[dev] / d["steps"] * 1e-9)
        return 100.0 * least_s / spent if spent else None
    raise SystemExit(f"{reader['source']}: unknown reduce {reduce!r}")


def _trace_device(reader, ctx):
    trace = ctx.get("trace")
    if not trace or not trace["devices"]:
        return None
    if reader["reduce"] == "idle_share":
        return trace_reduce.mean_over_devices(
            trace, lambda dev, d: 100.0 * (1 - d["busy_ns"]
                                           / d["window_ns"]))
    if reader["reduce"] == "mfu":
        need = (flops.required_flops_per_token(ctx["shape"])
                * ctx["seqs_per_chip"] * ctx["shape"]["seq_len"])
        return trace_reduce.mean_over_devices(
            trace, lambda dev, d: 100.0 * need
            / (d["step_span_ns"] * 1e-9 * ctx["peaks"]["bf16_flops_per_s"]))
    raise SystemExit(f"trace_device: unknown reduce {reader['reduce']!r}")


READERS = {"host_span": _host_span, "counter": _counter,
           "trace_scope": _trace_ops, "trace_kernel": _trace_ops,
           "trace_device": _trace_device}


def read(metric, ctx):
    """One per-layer metric's value, or None."""
    reader = metric["reader"]
    if reader["source"] not in READERS:
        raise SystemExit(f"benchmark: layer metric {metric['name']!r} has "
                         f"unknown source {reader['source']!r}")
    return READERS[reader["source"]](reader, ctx)
