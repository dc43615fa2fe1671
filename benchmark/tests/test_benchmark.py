"""The benchmark's own tests. Run by hand, on the CPU, not by tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider

They walk ``benchmark/run.py`` end to end at a toy preset (kernels
interpreted, 1 and 4 virtual devices), hold the plain reference against
``tfm.loss_fn``, reduce a small trace recorded on a v5e, and show that a
cell, a traffic mix, a configuration and a per-layer metric dropped into a
copy as new files are found by name with no code edited.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny.json")
DATA = os.path.join(HERE, "data")


def run_cell(root, workload, trace, seconds=2):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", "3", "--seconds", str(seconds),
         "--trace", str(trace), "--cpu-rehearsal", TINY],
        cwd=root, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("workload,chips", [("sc2-3b_s16k", 1),
                                            ("cgpt13b_dp4", 4)])
def test_rehearsal_end_to_end(workload, chips):
    line = run_cell(ROOT, workload, trace=0)
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert line["device"]["count"] == chips
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                    "setup_s"}
    assert all(line["checks"].values()), line["checks"]


def test_rehearsal_traced_reports_host_layers_only():
    line = run_cell(ROOT, "cgpt13b_dp1", trace=1)
    # the CPU has no device plane: every trace reader returns nothing and
    # the harness leaves those metrics out instead of inventing a number
    assert {"init_s", "first_step_s", "input_wait_ms_per_step",
            "eager_ops_in_window"} <= set(line["metrics"])
    assert not {"flash_share", "mfu_device", "dev_forward_ms"} & set(
        line["metrics"])
    assert line["metrics"]["eager_ops_in_window"]["value"] == 0


def test_no_accelerator_is_an_error_and_prints_no_result():
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "cgpt13b_dp1", "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert out.returncode != 0
    assert "{" not in out.stdout


@pytest.mark.parametrize("kw,arch", [
    (dict(n_kv_heads=2, positional="rope", attention_window=96),
     dict(positional="rope", attention_window=96)),
    (dict(positional="learned"),
     dict(positional="learned", attention_window=None))])
def test_reference_agrees_with_the_program_in_float32(kw, arch):
    import jax
    import jax.numpy as jnp
    from benchmark.lib import reference
    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(
        vocab_size=300, d_model=64, n_heads=4, n_layers=2, d_ff=128,
        max_seq=256, dtype=jnp.float32, attention_impl="dense",
        loss_chunk=64, **kw)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tok = jax.random.randint(jax.random.PRNGKey(1), (3, 256), 0, 300)
    tgt = jnp.roll(tok, -1, 1)
    reference.Q_BLOCK = 64  # several query blocks, window across them
    try:
        with jax.default_matmul_precision("highest"):
            want, want_g = jax.value_and_grad(
                lambda p: tfm.loss_fn(p, tok, tgt, cfg))(params)
        paths = [("layers", 0, "wq" if "n_kv_heads" in kw else "wqkv"),
                 ("layers", 1, "w1"), ("lm_head",)]
        got, got_g = reference.loss_and_grads(params, tok, tgt, arch, paths)
    finally:
        reference.Q_BLOCK = 1024
    # float32 on both sides, different association only
    assert abs(float(want) - float(got)) < 1e-5
    for path, g in zip(paths, got_g):
        ref = reference.get_leaf(want_g, path)
        assert float(jnp.max(jnp.abs(ref - g)) / jnp.max(jnp.abs(ref))) < 1e-5


def test_trace_reduce_on_a_trace_recorded_on_a_v5e():
    from benchmark.lib import trace_reduce as tr
    events = tr.read_xplane(os.path.join(DATA, "tiny_step.xplane.pb"))
    with open(os.path.join(DATA, "tiny_step.hlo.txt"), encoding="utf-8") as f:
        scopes = tr.scope_map(f.read())
    assert list(events["devices"]) == ["0"]
    assert {e[0] for e in events["host"]} == {
        "bench_step", "bench_dispatch", "bench_readback"}
    trace = tr.reduce_trace(events, scopes)
    d = trace["devices"]["0"]
    # three executions traced, the first dropped: two steady steps of
    # ~1.05 ms device time in a ~3.55 ms window (the host read a result
    # back between them)
    assert d["module"] == "jit_step" and d["steps"] == 2
    assert 1.04e6 < d["step_span_ns"] < 1.06e6
    assert 0.55 < d["busy_ns"] / d["window_ns"] < 0.65
    fwd = tr.select(trace, "scope", r"^(?!.*hvd_backward).*hvd_forward")["0"]
    kernel = tr.select(trace, "op", r"^custom-call tpu_custom_call ")["0"]
    assert 0 < kernel < fwd < d["busy_ns"]
    # the Pallas kernel is a custom call inside hvd_forward: ~0.207 ms/step
    assert 0.20e6 < kernel / d["steps"] < 0.22e6
    top = tr.breakdown(trace)
    assert top["device_ops"][0][0].startswith("hvd_backward/dot_general")
    assert top["idle_gaps"][0][0] == "host:bench_readback"


def test_self_time_subtracts_enclosed_events():
    from benchmark.lib import trace_reduce as tr
    ev = {"devices": {"0": {
        "modules": [["jit_s(1)", 0, 10], ["jit_s(1)", 100, 100]],
        "ops": [["%while.1 = (f32[]) while(f32[] %a), body=%b", 100, 80],
                ["%fusion.1 = f32[4]{0} fusion(f32[4]{0} %x), kind=kLoop",
                 110, 30],
                ["%all-reduce.1 = f32[4]{0} all-reduce(f32[4]{0} %y)",
                 150, 20],
                ["%copy.1 = f32[4]{0} copy(f32[4]{0} %z)", 185, 10]]}},
        "host": []}
    trace = tr.reduce_trace(ev, {"fusion.1": "jit(s)/hvd_forward/mul"})
    ops = {o["name"]: o for o in trace["devices"]["0"]["ops"]}
    assert ops["while.1"]["self"] == 30 and ops["fusion.1"]["self"] == 30
    assert trace["devices"]["0"]["busy_ns"] == 90
    assert tr.select(trace, "op", "^all-reduce")["0"] == 20


def test_new_files_are_found_by_name(tmp_path):
    """What a later PR does: add files, append entries, edit nothing."""
    from benchmark.lib import cells
    root = str(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bdir = os.path.join(root, "benchmark")

    def write(rel, obj):
        with open(os.path.join(bdir, rel), "w", encoding="utf-8") as f:
            json.dump(obj, f)

    conf = cells.load_json(os.path.join(bdir, "configs",
                                        "starcoder2-3b.json"))
    conf["num_key_value_heads"] = 4  # "another model of the family"
    write("configs/starcoder2-7b.json", conf)
    write("traffic/s8192_gb2.json", {
        "seq_len": 8192, "global_batch": 2,
        "source": {"kind": "zipf_bigram", "zipf_exponent": 1.3,
                   "bigram_share": 0.25}})
    cell = cells.load_json(os.path.join(bdir, "workloads",
                                        "sc2-3b_s4k.json"))
    write("workloads/sc2-7b_s8k.json", cell)
    write("layer_metrics/readback_ms_per_step.json", {
        "layer": "step_program", "moves": "tokens_per_s_per_chip",
        "source": "host_span", "pattern": "^window/loss_readback$",
        "reduce": "sum_ms_per_step", "cells": ["sc2-7b_s8k"]})
    bench = cells.load_benchmark(root)
    bench["configs"].append({
        "name": "starcoder2-7b", "source": "https://example.invalid",
        "file": "benchmark/configs/starcoder2-7b.json",
        "reduced": ["num_hidden_layers"], "why": "drop-in test"})
    bench["workloads"].append({
        "name": "sc2-7b_s8k", "config": "starcoder2-7b",
        "traffic": "s8192_gb2", "chips": 1, "why": "drop-in test"})
    bench["per_layer"].append({
        "name": "readback_ms_per_step", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "step_program",
        "moves": "tokens_per_s_per_chip"})
    with open(os.path.join(root, "BENCHMARK.json"), "w",
              encoding="utf-8") as f:
        json.dump(bench, f)

    found = cells.load_cell("sc2-7b_s8k", root)
    assert found["config"]["num_key_value_heads"] == 4
    assert found["traffic"]["seq_len"] == 8192
    names = [m["name"] for m in cells.metrics_for(
        "per_layer", "sc2-7b_s8k", root)]
    assert "readback_ms_per_step" in names and "flash_share" in names
    assert "readback_ms_per_step" not in [
        m["name"] for m in cells.metrics_for("per_layer", "sc2-3b_s4k",
                                             root)]
    # and the copy runs the new cell, new metric included
    line = run_cell(root, "sc2-7b_s8k", trace=1)
    assert line["correct"] is True
    assert line["metrics"]["readback_ms_per_step"]["value"] > 0
