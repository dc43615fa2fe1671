"""Tests of what PR 23 added to the benchmark, all as new files: the
``program_span`` reader (metrics read from the program's own spans), the
per-kernel metrics and their roofline work, and the registration that
makes ``layer_metrics`` find them with no existing file edited. Run by
hand, on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
DATA = os.path.join(HERE, "data")

from benchmark.lib import (cells, kernel_work, layer_metrics,  # noqa: E402
                           program_span, trace_reduce)

NEW_METRICS = {
    "hvd_import_s", "bcast_host_pull_s", "bcast_engine_s",
    "first_step_trace_lower_s", "first_step_load_or_compile_s",
    "first_step_analyze_s", "step_signature_ms_per_step",
    "step_enqueue_ms_per_step", "data_wait_ms_per_step",
    "data_produce_ms_per_batch", "flash_fwd_ms", "flash_dq_ms",
    "flash_dkv_ms", "flash_fwd_recompute_ms", "flash_fwd_roofline",
    "flash_bwd_roofline", "dev_head_ce_ms", "dev_unscoped_ms"}


def _span(name, t0, t1, sid, parent=0, thread=1, **attrs):
    return (name, t0, t1, thread, sid, parent, attrs)


#: A recorded span list in the shape hvd.diag.spans() returns: import,
#: broadcast, a first step that lowers inside step.analyze and compiles
#: inside step.execute, two window steps with their loader spans.
SPANS = [
    _span("import", 0.0, 5.0, 1),
    _span("init", 6.0, 6.1, 2),
    _span("bcast", 10.0, 14.0, 3, leaves=4, bytes=1024),
    _span("bcast.host_pull", 10.0, 11.0, 4, parent=3),
    _span("bcast.engine", 11.0, 13.5, 5, parent=3),
    _span("step", 20.0, 26.0, 6, step=1, hit=False),
    _span("step.signature", 20.0, 20.1, 7, parent=6),
    _span("step.analyze", 20.2, 22.2, 8, parent=6),
    _span("jax.trace", 20.3, 21.3, 9, parent=8),
    _span("jax.trace", 20.5, 20.9, 10, parent=8),     # nested: inside 9
    _span("jax.lower", 21.3, 22.0, 11, parent=8),
    _span("step.execute", 22.3, 26.0, 12, parent=6),
    _span("jax.cache_load", 22.4, 24.4, 13, parent=12),
    _span("jax.compile", 24.4, 24.9, 14, parent=12),
    # a later compile that is not the first step's (the reference)
    _span("jax.compile", 300.0, 360.0, 15),
    # the window: harness rows below put it at [100, 102]
    _span("data.wait", 100.00, 100.01, 20, batch=5, depth=2),
    _span("step", 100.02, 100.10, 21, step=4, hit=True),
    _span("step.signature", 100.02, 100.05, 22, parent=21),
    _span("step.execute", 100.06, 100.10, 23, parent=21),
    _span("data.wait", 101.00, 101.03, 24, batch=6, depth=1),
    _span("step", 101.04, 101.10, 25, step=5, hit=True),
    _span("step.signature", 101.04, 101.05, 26, parent=25),
    _span("step.execute", 101.06, 101.10, 27, parent=25),
    _span("data.fetch", 100.2, 100.5, 30, thread=2, batch=7),
    _span("data.put", 100.5, 100.6, 31, thread=2, batch=7),
    _span("data.fetch", 101.2, 101.4, 32, thread=2, batch=8),
    _span("data.put", 101.4, 101.6, 33, thread=2, batch=8),
    # the traced window's steps are not the measured window's
    _span("step", 200.0, 200.5, 40, step=6, hit=True),
    _span("step.signature", 200.0, 200.4, 41, parent=40),
]
ROWS = [("setup", "hvd_init", 6.0, 6.1), ("setup", "first_step", 20.0, 26.5),
        ("window", "next_batch", 100.0, 100.01),
        ("window", "dispatch", 101.04, 101.1),
        ("window", "loss_readback", 101.1, 102.0),
        ("traced", "dispatch", 200.0, 200.5), ("post", "x", 250.0, 400.0)]


def _ctx(spans=SPANS):
    return {"spans": ROWS, "steps": 2, "program_spans": list(spans),
            "notes": {}, "trace": None, "counters": {}}


def _read(name, ctx):
    metric = next(m for m in cells.metrics_for("per_layer", "sc2-3b_s16k")
                  if m["name"] == name)
    return layer_metrics.read(metric, ctx)


def test_new_metric_files_are_found_by_name():
    """Every metric this PR appended to BENCHMARK.json has its reader
    file, a reader source and (where it names one) a work function the
    general reader knows — registered by benchmark/lib/__init__.py, no
    existing file edited."""
    bench = cells.load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    assert NEW_METRICS <= set(names)
    assert len(names) == len(set(names))
    assert "program_span" in layer_metrics.READERS
    for wl in ("cgpt13b_dp1", "cgpt13b_dp4", "sc2-3b_s4k", "sc2-3b_s16k"):
        for m in cells.metrics_for("per_layer", wl):
            r = m["reader"]
            assert r["source"] in layer_metrics.READERS, m["name"]
            assert r["layer"] == m["layer"] and r["moves"] == m["moves"]
            if "work" in r:
                assert r["work"] in layer_metrics.WORK, m["name"]
    # remat's second forward exists only in the StarCoder2 cells
    in_cell = {wl: {m["name"] for m in cells.metrics_for("per_layer", wl)}
               for wl in ("cgpt13b_dp1", "sc2-3b_s4k")}
    assert "flash_fwd_recompute_ms" in in_cell["sc2-3b_s4k"]
    assert "flash_fwd_recompute_ms" not in in_cell["cgpt13b_dp1"]
    # the contract's word for each source
    by = {m["name"]: m for m in bench["per_layer"]}
    assert by["hvd_import_s"]["source"] == "program_span"
    assert by["flash_fwd_ms"]["source"] == "device_trace"


def test_program_span_readers_on_a_recorded_span_list():
    ctx = _ctx()
    assert _read("hvd_import_s", ctx) == pytest.approx(5.0)
    assert _read("bcast_host_pull_s", ctx) == pytest.approx(1.0)
    assert _read("bcast_engine_s", ctx) == pytest.approx(2.5)
    # trace + lower: time COVERED under the first step (the nested trace
    # counts once): 20.3 -> 22.0
    assert _read("first_step_trace_lower_s", ctx) == pytest.approx(1.7)
    # load + compile under the first step only, not the reference's
    assert _read("first_step_load_or_compile_s", ctx) == pytest.approx(2.5)
    # step.analyze's self time: 2.0 less the 1.7 its children cover
    assert _read("first_step_analyze_s", ctx) == pytest.approx(0.3)
    # the window's steps only: (30 + 10) ms / 2 steps, (40 + 40) / 2
    assert _read("step_signature_ms_per_step", ctx) == pytest.approx(20.0)
    assert _read("step_enqueue_ms_per_step", ctx) == pytest.approx(40.0)
    assert _read("data_wait_ms_per_step", ctx) == pytest.approx(20.0)
    # (300 + 100 + 200 + 200) ms over the 2 batches produced
    assert _read("data_produce_ms_per_batch", ctx) == pytest.approx(400.0)
    # the table --dump-dir keeps: seconds and count per phase and name
    table = ctx["notes"]["program_spans"]
    assert table["window/step"] == [pytest.approx(0.14), 2]
    assert table["setup/jax.trace"][1] == 2
    assert table["traced/step.signature"] == [pytest.approx(0.4), 1]


def test_a_program_without_spans_reads_as_nothing(monkeypatch):
    """The parent commit has no spans: every program_span metric is left
    out, nothing raises."""
    ctx = _ctx(spans=())
    for name in ("hvd_import_s", "first_step_analyze_s",
                 "step_signature_ms_per_step", "data_produce_ms_per_batch"):
        assert _read(name, ctx) is None
    # ... also when the program has no hvd.diag.spans at all
    from horovod_tpu import diag
    monkeypatch.delattr(diag, "spans")
    ctx = _ctx()
    del ctx["program_spans"]
    assert _read("hvd_import_s", ctx) is None
    assert ctx["program_spans"] == []
    # spans there, but none of this metric's (no window ran)
    ctx = _ctx(spans=SPANS[:5])
    assert _read("bcast_engine_s", ctx) == pytest.approx(2.5)
    assert _read("step_enqueue_ms_per_step", ctx) is None


def _named_trace():
    """The v5e recording with the kernel names a program of this PR
    would give its ops: its one Pallas call stands in for the forward
    kernel, under forward in the first step and under backward (remat's
    second call) in the others; two fusions stand in for dQ and dK/dV."""
    events = trace_reduce.read_xplane(os.path.join(DATA,
                                                   "tiny_step.xplane.pb"))
    with open(os.path.join(DATA, "tiny_step.hlo.txt"),
              encoding="utf-8") as f:
        scopes = trace_reduce.scope_map(f.read())
    trace = trace_reduce.reduce_trace(events, scopes, drop_first=0)
    (dev,) = trace["devices"].values()
    seen = 0
    for op in dev["ops"]:
        if op["target"] == "tpu_custom_call":
            op["scope"] = ("jit(step)/hvd_forward/hvd_flash_fwd/pallas_call"
                           if seen == 0 else
                           "jit(step)/hvd_backward/transpose(hvd_forward)/"
                           "rematted_computation/hvd_flash_fwd/pallas_call")
            seen += 1
        elif op["name"] == "fusion":
            op["scope"] = "jit(step)/hvd_backward/hvd_flash_dq/pallas_call"
        elif op["name"] == "convolution_tanh_fusion":
            op["scope"] = "jit(step)/hvd_forward/hvd_head_ce/dot_general"
    return trace, dev


def test_kernel_readers_on_the_recorded_trace():
    trace, dev = _named_trace()
    shape = {"d_model": 256, "head_dim": 128, "n_heads": 2, "n_kv_heads": 2,
             "d_ff": 512, "n_layers": 1, "vocab_size": 512, "seq_len": 1024,
             "window": None}
    ctx = dict(_ctx(), trace=trace, shape=shape, seqs_per_chip=2,
               peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    steps = dev["steps"]

    def self_ms(pred):
        return sum(o["self"] for o in dev["ops"] if pred(o)) / steps * 1e-6

    fwd = self_ms(lambda o: "hvd_flash_fwd" in o["scope"])
    assert _read("flash_fwd_ms", ctx) == pytest.approx(fwd)
    assert _read("flash_fwd_recompute_ms", ctx) == pytest.approx(
        self_ms(lambda o: "hvd_backward" in o["scope"]
                and "hvd_flash_fwd" in o["scope"]))
    assert 0 < _read("flash_fwd_recompute_ms", ctx) < fwd
    assert _read("flash_dq_ms", ctx) == pytest.approx(
        self_ms(lambda o: "hvd_flash_dq" in o["scope"]))
    assert _read("flash_dkv_ms", ctx) == 0.0
    assert _read("dev_head_ce_ms", ctx) == pytest.approx(
        self_ms(lambda o: "hvd_head_ce" in o["scope"]))
    assert _read("dev_unscoped_ms", ctx) == pytest.approx(
        self_ms(lambda o: "hvd_" not in o["scope"]))
    # roofline: least time of ONE call x the calls made (3 in 3 steps ->
    # 1 a step) over the time spent: counting remat's call on both sides
    assert kernel_work.calls_per_step(trace, "hvd_flash_fwd") == 1.0
    from benchmark.lib import flops
    least, bound = flops.roofline_seconds(
        *flops.flash_kernel_work(shape, 2)["fwd"], ctx["peaks"])
    assert _read("flash_fwd_roofline", ctx) == pytest.approx(
        100.0 * least / (fwd * 1e-3))
    assert ctx["notes"]["flash_fwd"] == bound
    assert _read("flash_bwd_roofline", ctx) > 0.0
    # a trace of a program without the names matches nothing: left out
    for op in dev["ops"]:
        op["scope"] = op["scope"].replace("hvd_flash_", "hvd_x_")
    assert _read("flash_fwd_roofline", ctx) is None
    assert _read("flash_bwd_roofline", ctx) is None


def test_cpu_rehearsal_prints_the_host_span_metrics():
    """benchmark/run.py --cpu-rehearsal --trace 1 walks the program's own
    spans end to end: every program_span metric is on the line, none of
    the device ones (the CPU has no device plane)."""
    from test_benchmark import run_cell
    line = run_cell(ROOT, "sc2-3b_s4k", trace=1)
    assert line["correct"] is True
    got = set(line["metrics"])
    host = {m for m in NEW_METRICS
            if json.load(open(os.path.join(
                ROOT, "benchmark", "layer_metrics", m + ".json"),
                encoding="utf-8"))["source"] == "program_span"}
    assert len(host) == 10 and host <= got
    assert not (NEW_METRICS - host) & got
    v = {k: line["metrics"][k]["value"] for k in host}
    assert all(x >= 0.0 for x in v.values())
    # inside and outside views of the same run agree on what they share
    parts = (v["first_step_trace_lower_s"] + v["first_step_analyze_s"]
             + v["first_step_load_or_compile_s"])
    assert 0.5 * line["metrics"]["first_step_s"]["value"] <= parts \
        <= 1.05 * line["metrics"]["first_step_s"]["value"]
    assert v["step_signature_ms_per_step"] + v["step_enqueue_ms_per_step"] \
        <= line["metrics"]["dispatch_ms_per_step"]["value"]
