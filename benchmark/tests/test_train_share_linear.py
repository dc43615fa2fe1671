"""The benchmark's own tests of mode ``train_share_linear`` (PR 33). Run by
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
TINY = os.path.join(HERE, "tiny_kimi_linear.json")
CELL = "kimi-linear_s16k"
NEW = {"dev_kda_ms", "dev_kda_proj_ms", "dev_kda_conv_ms",
       "dev_kda_scan_ms", "dev_kda_norm_ms", "attn_mla_ms",
       "dev_mla_proj_ms", "dev_moe_sigmoid_ms",
       "moe_sigmoid_load_max_over_mean", "kda_scan_roofline",
       "attn_mla_roofline", "mfu_device_linear"}
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
    assert set(line["metrics"]) & NEW == {"moe_sigmoid_load_max_over_mean"}
    assert {"init_s", "first_step_s", "dispatch_ms_per_step",
            "eager_ops_in_window"} <= set(line["metrics"])


def test_the_cell_reads_its_own_metrics_and_no_other_model_s():
    from benchmark.lib import cells
    names = {m["name"] for m in cells.metrics_for("per_layer", CELL)}
    assert NEW <= names
    assert {"flash_fwd_ms", "flash_share", "dev_head_ce_ms",
            "dev_unscoped_ms", "device_idle_share"} <= names
    assert not {"mfu_device", "mfu_device_layers", "mfu_device_hybrid",
                "flash_roofline", "dev_moe_ms", "dev_ssm_ms",
                "attn_full_ms", "attn_full_roofline"} & names
    for other in ("sc2-3b_s16k", "laguna-s21_s8k", "granite4h-micro_s16k"):
        assert not NEW & {m["name"] for m in cells.metrics_for("per_layer",
                                                               other)}


def test_work_per_layer_kind_is_the_arithmetic_of_the_cell():
    """ISSUE 33's count, forward MFLOP a token: a KDA layer's projections
    78.9 and its recurrence 3.67, the latent-attention layer's projections
    58.2 and its causal triangle at 16k 167.8, a sparse FFN 18.9 at the
    uniform 0.25 assignments a token, the dense MLP 127.4, the head 94.4:
    853.6 in all, 42.0 TFLOP a step; and the recurrence's least time is
    set by its bytes, 0.82 ms a layer."""
    from benchmark.lib import cells, work_linear
    from benchmark.modes import train_share_linear as mode
    shape = mode.work_shape(cells.load_cell(CELL))
    assert [(l["mixer"], l["mlp"]) for l in shape["layers"]] == [
        ("kda", "dense"), ("kda", "sparse"), ("kda", "sparse"),
        ("mla", "sparse"), ("kda", "sparse")]
    total, parts = work_linear.required_flops_per_token(shape, 0.25)
    first, sparse, mla, head = parts[0], parts[1], parts[3], parts[-1]
    assert round(first["projections"] / 1e6, 1) == 78.9
    assert round(first["recurrence"] / 1e6, 2) == 3.67
    assert round(first["mlp"] / 1e6, 1) == 127.4
    assert round((sparse["router"] + sparse["routed"] + sparse["shared"])
                 / 1e6, 1) == 18.9
    assert round(mla["projections"] / 1e6, 1) == 58.2
    assert round(mla["attention"] / 1e6, 1) == 167.8
    assert round(head["head"] / 1e6, 1) == 94.4
    assert round(total / 3e6, 1) == 853.6
    assert round(total * 16384 / 1e12, 1) == 42.0
    t, bound = work_linear.recurrence_seconds(shape, 16384, PEAKS)
    assert bound == "memory"
    assert abs(t - 16384 * (2 * 5 * 4096 + 4 * 32) / 819e9) < 1e-12
    assert round(t * 1e3, 2) == 0.82
    least, _ = work_linear.kda_scan({"work": {
        "shape": shape, "remat": True, "tokens_per_chip": 16384},
        "peaks": PEAKS})
    assert abs(least - 4 * 4 * t) < 1e-12


def test_the_latent_attention_layer_s_least_time_counts_both_head_sizes():
    """``attn_mla_roofline``'s numerator: forward 2 (192 + 128), dQ 2 (384
    + 128), dK/dV 2 (384 + 256) FLOPs over the causal triangle's 16,384 x
    16,385 / 2 pairs x 32 heads, at the calls the trace shows (two
    forwards under remat); compute-bound."""
    from benchmark.lib import cells, work_linear
    from benchmark.modes import train_share_linear as mode
    shape = mode.work_shape(cells.load_cell(CELL))
    scopes = (["fwd/hvd_forward/hvd_attn_full/hvd_flash_fwd"]
              + ["hvd_backward/hvd_attn_full/hvd_flash_" + k
                 for k in ("fwd", "dq", "dkv")] + ["hvd_kda/hvd_kda_scan"])
    trace = {"devices": {0: {"steps": 1, "ops": [{"scope": s}
                                                  for s in scopes]}}}
    least, bound = work_linear.attn_mla({
        "work": {"shape": shape, "seqs_per_chip": 1}, "trace": trace,
        "peaks": PEAKS})
    pairs = (16384 * 16385 // 2) * 32
    assert bound == "compute"
    want = (2 * 640 + 1024 + 1280) * pairs / 197e12
    assert abs(least - want) < 1e-9
    assert round(least * 1e3, 1) == 78.1


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_vocab():
    """Every number of the catalog row's ``config`` under the same key,
    but the three ``reduced`` ones; the nested group whole."""
    from benchmark.lib import cells
    bench = cells.load_benchmark()
    entry = next(c for c in bench["configs"]
                 if c["name"] == "kimi-linear-48b-a3b")
    conf = cells.load_cell(CELL)["config"]
    assert conf["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert (conf["num_hidden_layers"], conf["num_experts"],
            conf["vocab_size"]) == (5, 8, 20480)
    assert conf["published"] == {"num_hidden_layers": 27,
                                 "num_experts": 256, "vocab_size": 163840}
    published = {
        "hidden_size": 2304, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_attention_heads": 32, "num_key_value_heads": 32,
        "head_dim": 72, "num_experts_per_token": 8,
        "num_shared_experts": 1, "routed_scaling_factor": 2.446,
        "first_k_dense_replace": 1, "rms_norm_eps": 1e-05,
        "q_lora_rank": None, "mla_use_nope": True,
        "moe_router_activation_func": "sigmoid"}
    assert {k: conf[k] for k in published} == published
    lin = conf["linear_attn_config"]
    assert (lin["num_heads"], lin["head_dim"],
            lin["short_conv_kernel_size"]) == (32, 128, 4)
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(lin["kda_layers"]) == 20
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(json.loads(l) for l in f
                       if '"Kimi-Linear-48B-A3B-Instruct"' in l)
        assert entry["source"] == conf["source"] == row["source_url"]
        for key, value in row["config"].items():
            if key not in conf["reduced"]:
                assert conf[key] == value, key


def _reading(seed=5):
    """A reference reading at toy sizes and the limits' shape."""
    import jax

    from benchmark.lib import cells, data
    from benchmark.modes import train_share_linear as mode
    jax.config.update("jax_platforms", "cpu")
    cell = mode.apply_tiny(cells.load_cell(CELL), cells.load_json(TINY))
    cfg = mode.model_config(cell, interpret=True)
    source = data.make_source(cell["traffic"], seed, cfg.vocab_size)
    return mode, cfg, mode.reference_reading(cfg, cell, seed, source)


TOL = {"loss0_abs": 1e-3, "sign_agreement_min": 0.999,
       "kda_state_rms_rel_max": 0.05, "assignments_moved_share_max": 0.01}


@pytest.mark.parametrize("doctored, limit", [
    (None, None), ("loss", "loss0_abs"), ("state", "kda_state_rms_rel_max"),
    ("load", "assignments_moved_share_max"),
    ("update", "sign_agreement_min:layers/2/kda/conv_w")])
def test_the_comparison_refuses_a_doctored_result(doctored, limit):
    """The reference's own reading passes its comparison; a loss off by
    0.01, one head's state 10 % too large, half of an expert's assignments
    on its neighbour, or a leaf updated with the gradient's sign is each
    refused, by its own limit and no other."""
    import numpy as np
    mode, cfg, want = _reading()
    got = copy.deepcopy(want)
    lr = 3e-4
    updates = [-lr * np.sign(g) for g in want["grads"]]
    if doctored == "loss":
        got["loss"] += 0.01
    elif doctored == "state":
        got["rms"][1, 0] *= 1.1
    elif doctored == "load":
        moved = 0.5 * got["load"][0, 0]
        got["load"][0, 0] -= moved
        got["load"][0, 1] += moved
    elif doctored == "update":
        updates[2] = -updates[2]
    p1 = [b + u for b, u in zip(want["before"], updates)]
    ok, out = mode.compare_with_reference(
        cfg, want, got["loss"], got["rms"], got["load"], p1, TOL)
    assert out["limits_missed"] == ([limit] if limit else [])
    assert ok == (limit is None)
    assert set(out["sign_agreement"]) == {
        "layers/0/kda/wq", "layers/0/kda/w_fb", "layers/2/kda/conv_w",
        "layers/2/kda/wo", "layers/3/mla/w_kvb", "layers/3/mla/wq",
        "layers/1/moe/w1", "layers/1/moe/w_router", "embed"}


def test_the_precision_control_feeds_the_harness_s_own_comparison():
    """``benchmark/tools/precision_control_linear.py`` at toy sizes: the
    float32 reading passes against itself and every lower precision reads
    an error above it in the loss and in the state statistic (a KDA state
    feeds every output, so a bfloat16 state moves the loss too). (Which
    limits a variant misses is a chip reading at the timed size: PERF.md
    section 6 PR 33.)"""
    out = os.path.join(ROOT, ".bench_out", "precision_control_linear.json")
    run = run_tool(os.path.join("tools", "precision_control_linear.py"),
                   CELL, "--seeds", "2147483777", "--cpu-rehearsal", TINY,
                   "--out", out)
    assert run.returncode == 0, run.stderr[-3000:]
    with open(out, encoding="utf-8") as f:
        rows = {row["variant"]: row for row in json.load(f)}
    os.remove(out)
    assert set(rows) == {"fp32", "bf16", "fp8", "state_bf16"}
    assert rows["fp32"]["correct"] and not rows["fp32"]["failed_limits"]
    assert rows["fp32"]["kda_state_rms_rel_err_max"] == 0.0
    for name in ("bf16", "fp8", "state_bf16"):
        assert rows[name]["kda_state_rms_rel_err_max"] > 0.0
        assert rows[name]["correct"] == (not rows[name]["failed_limits"])
        assert rows[name]["loss0_abs_err"] > 0.0
    assert rows["state_bf16"]["kda_state_rms_rel_err_max"] > 1e-3
