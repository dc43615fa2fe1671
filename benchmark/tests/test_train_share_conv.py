"""The benchmark's own tests of mode ``train_share_conv`` (PR 37). Run by
hand, on the CPU, not by tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import copy
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_lfm2.json")
CELL = "lfm2-24b-a2b_s16k"
NEW = {"dev_sconv_ms", "dev_sconv_proj_ms", "dev_sconv_gate_ms",
       "dev_qk_norm_ms", "attn_qknorm_ms", "dev_moe_top4_ms",
       "moe_top4_load_max_over_mean", "sconv_gate_roofline",
       "attn_qknorm_roofline", "mfu_device_sconv"}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_tool(script, *args):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", script), *args],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)


def run_cell(trace):
    out = run_tool("run.py", "--workload", CELL, "--seed", "3000000019",
                   "--seconds", "2", "--trace", str(trace),
                   "--cpu-rehearsal", TINY)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_end_to_end():
    line = run_cell(trace=0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                    "setup_s"}
    assert all(line["checks"].values()), line["checks"]


def test_rehearsal_traced_reports_what_needs_no_device():
    line = run_cell(trace=1)
    assert line["correct"] is True
    # no device plane on the CPU: the trace readers return nothing
    assert set(line["metrics"]) & NEW == {"moe_top4_load_max_over_mean"}
    assert {"init_s", "first_step_s", "dispatch_ms_per_step",
            "eager_ops_in_window"} <= set(line["metrics"])


def test_the_cell_reads_its_own_metrics_and_no_other_model_s():
    from benchmark.lib import cells
    names = {m["name"] for m in cells.metrics_for("per_layer", CELL)}
    assert NEW <= names
    assert {"flash_fwd_ms", "flash_share", "dev_head_ce_ms",
            "dev_unscoped_ms", "device_idle_share"} <= names
    assert not {"mfu_device", "mfu_device_layers", "mfu_device_hybrid",
                "mfu_device_linear", "flash_roofline", "dev_moe_ms",
                "dev_moe_sigmoid_ms", "dev_ssm_ms", "dev_kda_ms",
                "attn_full_ms", "attn_full_roofline"} & names
    for other in ("sc2-3b_s16k", "laguna-s21_s8k", "granite4h-micro_s16k",
                  "kimi-linear_s16k"):
        assert not NEW & {m["name"] for m in cells.metrics_for("per_layer",
                                                               other)}


def test_work_per_layer_kind_is_the_arithmetic_of_the_cell():
    """ISSUE 37's count, forward MFLOP a token: a conv mixer's two
    projections 33.6, an attention layer's projections 21.0 and its causal
    triangle at 16k 67.1, a sparse FFN 9.7 at the uniform 0.5 assignments
    a token, the dense MLP 144.7, the tied head 33.6; over the eight layers
    that stand (6 conv : 2 attention, 7 sparse) 623.6 in all, 30.7 TFLOP a
    step; and the gate chain's least time is its bytes, 0.33 ms forward
    and 0.57 ms backward a layer."""
    from benchmark.lib import cells, work_conv
    from benchmark.modes import train_share_conv as mode
    shape = mode.lfm2_work_shape(cells.load_cell(CELL))
    assert [(l["mixer"], l["mlp"]) for l in shape["layers"]] == [
        ("sconv", "dense"), ("attention", "sparse"), ("sconv", "sparse"),
        ("sconv", "sparse"), ("sconv", "sparse"), ("attention", "sparse"),
        ("sconv", "sparse"), ("sconv", "sparse")]
    total, parts = work_conv.required_flops_per_token(shape, 0.5)
    first, attn, conv, head = parts[0], parts[1], parts[2], parts[-1]
    assert round(first["projections"] / 1e6, 1) == 33.6
    assert round(first["mlp"] / 1e6, 1) == 144.7
    assert round(attn["projections"] / 1e6, 1) == 21.0
    assert round(attn["attention"] / 1e6, 1) == 67.1
    assert "shared" not in conv
    assert round((conv["router"] + conv["routed"]) / 1e6, 1) == 9.7
    assert round(head["head"] / 1e6, 1) == 33.6
    assert round(total / 3e6, 1) == 623.6
    assert round(total * 16384 / 1e12, 1) == 30.7
    fwd, bound = work_conv.gate_seconds(shape, 16384, 4, PEAKS)
    bwd, _ = work_conv.gate_seconds(shape, 16384, 7, PEAKS)
    assert bound == "memory"
    assert abs(fwd - 4 * 16384 * 2048 * 2 / 819e9) < 1e-12
    assert (round(fwd * 1e3, 2), round(bwd * 1e3, 2)) == (0.33, 0.57)
    least, _ = work_conv.sconv_gate({"work": {
        "shape": shape, "remat": True, "tokens_per_chip": 16384},
        "peaks": PEAKS})
    assert abs(least - 6 * (2 * fwd + bwd)) < 1e-12


def test_the_attention_layers_least_time_is_granite_s_work_function():
    """``attn_qknorm_roofline``'s numerator is ``work_hybrid.attn_full``
    (imported, not copied) at 32 / 8 heads of 64 over the whole causal
    triangle, at the calls the trace shows."""
    from benchmark.lib import (cells, flops, layer_metrics, work_conv,
                               work_hybrid)
    from benchmark.modes import train_share_conv as mode
    assert layer_metrics.WORK["attn_qknorm"] is work_hybrid.attn_full
    assert layer_metrics.WORK["sconv_gate"] is work_conv.sconv_gate
    shape = mode.lfm2_work_shape(cells.load_cell(CELL))
    scopes = (["hvd_forward/hvd_attn_full/hvd_flash_fwd"]
              + ["hvd_backward/hvd_attn_full/hvd_flash_" + k
                 for k in ("fwd", "dq", "dkv")]) * 2 + ["hvd_sconv_gate"]
    trace = {"devices": {0: {"steps": 1, "ops": [{"scope": s}
                                                  for s in scopes]}}}
    least, bound = layer_metrics.WORK["attn_qknorm"]({
        "work": {"shape": shape, "seqs_per_chip": 1}, "trace": trace,
        "peaks": PEAKS})
    one = flops.flash_kernel_work(
        {"seq_len": 16384, "head_dim": 64, "n_heads": 32, "n_kv_heads": 8},
        1)
    want = 2 * sum(n * flops.roofline_seconds(*one[k], PEAKS)[0]
                   for k, n in (("fwd", 2), ("dq", 1), ("dkv", 1)))
    assert abs(least - want) < 1e-9 and bound == "compute"


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_vocab():
    """Every number of the catalog row's ``config`` under the same key,
    but the five ``reduced`` ones; the nested group whole."""
    from benchmark.lib import cells
    bench = cells.load_benchmark()
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-24b-a2b")
    conf = cells.load_cell(CELL)["config"]
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "layer_types", "num_dense_layers",
        "num_experts", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_dense_layers"],
            conf["num_experts"], conf["vocab_size"]) == (8, 1, 8, 8192)
    assert conf["layer_types"] == [
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv"]
    published = {
        "hidden_size": 2048, "intermediate_size": 11776,
        "moe_intermediate_size": 1536, "num_attention_heads": 32,
        "num_key_value_heads": 8, "num_experts_per_tok": 4,
        "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-05,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
    assert {k: conf[k] for k in published} == published
    assert conf["published"]["num_experts"] == 64
    assert conf["deployment"]["chips_per_layer"] == 8
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(json.loads(l) for l in f if '"LFM2-24B-A2B"' in l)
        assert entry["source"] == conf["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in conf["reduced"]:
                assert conf[key] == value, key
        # the layers held are published layers 1-8
        assert conf["layer_types"] == row["config"]["layer_types"][1:9]


def _reading(seed=5):
    """A reference reading at toy sizes and the limits' shape."""
    import jax

    from benchmark.lib import cells, data
    from benchmark.modes import train_share_conv as mode
    jax.config.update("jax_platforms", "cpu")
    cell = mode.apply_tiny(cells.load_cell(CELL), cells.load_json(TINY))
    cfg = mode.LFM2.model_config(cell, interpret=True)
    source = data.make_source(cell["traffic"], seed, cfg.vocab_size)
    return mode, cfg, mode.reference_reading(mode.LFM2, cfg, cell, seed,
                                             source)


TOL = {"loss0_abs": 1e-3, "sign_agreement_min": 0.999,
       "assignments_moved_share_max": 0.01}


Q_NORM = "sign_agreement_min:layers/1/q_norm"


@pytest.mark.parametrize("lr, doctored, limit", [
    (3e-4, None, None), (3e-4, "loss", "loss0_abs"),
    (3e-4, "load", "assignments_moved_share_max"),
    (3e-4, "update", "sign_agreement_min:layers/0/sconv/conv_w"),
    (3e-4, "norm", Q_NORM),
    (3e-4 / 16000, None, None), (3e-4 / 16000, "moment", Q_NORM),
    (3e-4 / 16000, "moved", Q_NORM),
    (3e-4 / 16000, "update", "sign_agreement_min:layers/0/sconv/conv_w")])
def test_the_comparison_refuses_a_doctored_result(lr, doctored, limit):
    """The reference's own reading passes its comparison; a loss off by
    0.01, half of an expert's assignments on its neighbour, or a leaf (the
    first layer's taps, the 64 floats of a q norm) updated with the
    gradient's sign is each refused, by its own limit and no other. At the
    first rate of the 16,000-step warm-up float32 holds no step on a norm
    weight of 1.0: the leaf is read from the optimizer's first moment, and
    a moment of the wrong sign or an element that moved all the same is
    refused; the taps are still read where they can move."""
    import numpy as np
    mode, cfg, want = _reading()
    got = copy.deepcopy(want)
    updates = [-np.float32(lr) * np.sign(g) for g in want["grads"]]
    moments = [(1 - mode.ADAM_B1) * g for g in want["grads"]]
    if doctored == "loss":
        got["loss"] += 0.01
    elif doctored == "load":
        load = got["aux"]["expert_load"]
        moved = 0.5 * load[0, 0]
        load[0, 0] -= moved
        load[0, 1] += moved
    elif doctored == "update":
        updates[1] = -updates[1]
    elif doctored == "norm":
        updates[4] = -updates[4]
    elif doctored == "moment":
        moments[4] = -moments[4]
    elif doctored == "moved":   # 16 times the rate: float32 holds that
        updates[4] = 16 * updates[4]
    p1 = [b + u for b, u in zip(want["before"], updates)]
    ok, out = mode.compare_with_reference(
        mode.LFM2, cfg, want, got["loss"], got["aux"], p1, moments, TOL, lr)
    assert out["limits_missed"] == ([limit] if limit else [])
    assert ok == (limit is None)
    assert set(out["sign_agreement"]) == {
        "layers/0/sconv/w_in", "layers/0/sconv/conv_w",
        "layers/2/sconv/w_out", "layers/1/wq", "layers/1/q_norm",
        "layers/1/moe/w1", "layers/1/moe/w_router", "embed"}
    share = out["first_moment_share"]
    if lr == 3e-4:
        assert not any(share.values())
    else:   # the norm weight whole, the taps of 0.5 and more
        assert share["layers/1/q_norm"] == 1.0
        assert 0.0 < share["layers/0/sconv/conv_w"] < 0.5
        assert share["layers/0/sconv/w_in"] == 0.0
        assert share["embed"] < 1e-3   # toy rows reach 0.5 at 4 sigma
    if doctored is None:
        assert max(out["grad_rel_err"].values()) < 1e-6


def test_the_loop_takes_the_model_as_an_argument():
    """``run`` reads every model-specific part off its ``model`` argument:
    a ``Model`` whose program check fails stops the run before jax's
    backend is opened, with the model's own words."""
    import dataclasses

    from benchmark.lib import cells
    from benchmark.modes import train_share_conv as mode
    other = dataclasses.replace(
        mode.LFM2, lacks=lambda tfm: "a layer nobody has written")
    with pytest.raises(SystemExit, match="a layer nobody has written"):
        mode.run(cells.load_cell(CELL), None, 0.0, model=other)
    assert mode.LFM2.lacks(__import__(
        "horovod_tpu.models.transformer", fromlist=["x"])) is None


def test_the_precision_control_feeds_the_harness_s_own_comparison():
    """``benchmark/tools/precision_control_conv.py`` at toy sizes: the
    float32 reading passes against itself with a gradient error of zero,
    and every lower precision reads an error above it in the loss and in
    the first gradient. (Which limits a variant misses is a chip reading
    at the timed size: PERF.md section 6 PR 37.)"""
    out = os.path.join(ROOT, ".bench_out", "precision_control_conv.json")
    run = run_tool(os.path.join("tools", "precision_control_conv.py"),
                   CELL, "--seeds", "2147483777", "--cpu-rehearsal", TINY,
                   "--out", out)
    assert run.returncode == 0, run.stderr[-3000:]
    with open(out, encoding="utf-8") as f:
        rows = {row["variant"]: row for row in json.load(f)}
    os.remove(out)
    assert set(rows) == {"fp32", "bf16", "fp8", "gate_bf16"}
    assert rows["fp32"]["correct"] and not rows["fp32"]["failed_limits"]
    assert rows["fp32"]["loss0_abs_err"] == 0.0
    assert max(rows["fp32"]["grad_rel_err"].values()) < 1e-6
    # the preset keeps the cell's warm-up: the norm weight is read from the
    # first moment in every variant
    assert rows["fp32"]["first_moment_share"]["layers/1/q_norm"] == 1.0
    for name in ("bf16", "fp8", "gate_bf16"):
        assert rows[name]["correct"] == (not rows[name]["failed_limits"])
        assert rows[name]["loss0_abs_err"] > 0.0
        assert rows[name]["grad_rel_err"]["layers/0/sconv/w_in"] > 1e-4
