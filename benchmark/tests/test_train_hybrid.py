"""The benchmark's own tests of mode ``train_hybrid`` (PR 31). Run by hand,
on the CPU, not by tier-1:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests -q -p no:cacheprovider
"""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)
TINY = os.path.join(HERE, "tiny_granite.json")
CELL = "granite4h-micro_s16k"
NEW = {"dev_ssm_ms", "dev_ssm_proj_ms", "dev_ssm_conv_ms", "dev_ssm_scan_ms",
       "dev_ssm_norm_ms", "ssm_scan_roofline", "mfu_device_hybrid",
       "attn_full_roofline"}


def run_cell(trace):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--cpu-rehearsal", TINY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
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
    assert not (NEW | {"mfu_device", "mfu_device_layers", "flash_roofline",
                       "dev_moe_ms"}) & set(line["metrics"])
    assert {"init_s", "first_step_s", "dispatch_ms_per_step",
            "eager_ops_in_window"} <= set(line["metrics"])


def test_the_cell_reads_its_own_metrics_and_no_other_model_s():
    from benchmark.lib import cells
    names = {m["name"] for m in cells.metrics_for("per_layer", CELL)}
    assert NEW <= names
    assert {"flash_fwd_ms", "flash_share", "dev_head_ce_ms",
            "dev_unscoped_ms", "device_idle_share"} <= names
    assert not {"mfu_device", "mfu_device_layers", "flash_roofline",
                "flash_fwd_roofline", "dev_moe_ms",
                "attn_window_roofline"} & names
    for other in ("sc2-3b_s16k", "laguna-s21_s8k"):
        assert not NEW & {m["name"] for m in cells.metrics_for("per_layer",
                                                               other)}


def test_work_per_layer_kind_is_the_arithmetic_of_the_cell():
    """ISSUE 31's count, forward MFLOP a token: a Mamba-2 layer's
    projections + MLP 152.3 and its scan 4.3, the attention layer 121.6 +
    67.1 of attention, the head 51.4: 1,649 in all; and the scan's least
    time is set by its bytes (the float32 chunk states)."""
    from benchmark.lib import cells, work_hybrid
    from benchmark.modes import train_hybrid
    shape = train_hybrid.work_shape(cells.load_cell(CELL))
    total, parts = work_hybrid.required_flops_per_token(shape)
    assert shape["layers"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    mamba, attn, head = parts[0], parts[5], parts[-1]
    assert round((mamba["projections"] + mamba["mlp"]) / 1e6, 1) == 152.3
    assert round(mamba["scan"] / 1e6, 1) == 4.3
    assert round((attn["projections"] + attn["mlp"]) / 1e6, 1) == 121.6
    assert round(attn["attention"] / 1e6, 1) == 67.1
    assert round(head["head"] / 1e6, 1) == 51.4
    assert round(total / 3e6) == 1649
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work_hybrid.scan_seconds(shape, 16384, peaks)
    assert bound == "memory"
    nbytes = 16384 * (2 * (2 * 4096 + 256) + 4 * 64) + 64 * 2 * 4 * 4096 * 128
    assert abs(t - nbytes / 819e9) < 1e-9
    least, _ = work_hybrid.ssm_scan({"work": {
        "shape": shape, "remat": True, "tokens_per_chip": 16384},
        "peaks": peaks})
    assert abs(least - 9 * 4 * t) < 1e-9


def test_the_attention_layer_s_least_time_is_the_kernels_at_heads_of_64():
    """``attn_full_roofline``'s numerator: 2 / 3 / 4 tile matmuls of 2 x
    64 FLOPs over the causal triangle's 16,384 x 16,385 / 2 pairs x 32
    heads, at the calls the trace shows (two forwards under remat): 5.58 x
    2 + 8.37 + 11.16 ms; compute-bound."""
    from benchmark.lib import cells, work_hybrid
    from benchmark.modes import train_hybrid
    shape = train_hybrid.work_shape(cells.load_cell(CELL))
    scopes = (["fwd/hvd_forward/hvd_attn_full/hvd_flash_fwd"]
              + ["hvd_backward/hvd_attn_full/hvd_flash_" + k
                 for k in ("fwd", "dq", "dkv")] + ["hvd_ssm/hvd_ssm_scan"])
    trace = {"devices": {0: {"steps": 1, "ops": [{"scope": s}
                                                  for s in scopes]}}}
    least, bound = work_hybrid.attn_full({
        "work": {"shape": shape, "seqs_per_chip": 1}, "trace": trace,
        "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}})
    per_matmul = 2 * 64 * (16384 * 16385 // 2) * 32
    assert bound == "compute"
    assert abs(least - (2 * 2 + 3 + 4) * per_matmul / 197e12) < 1e-9
    assert round(least * 1e3, 1) == 30.7


def test_the_precision_control_feeds_the_harness_s_own_comparison():
    """``benchmark/tools/precision_control.py`` at toy sizes: the float32
    reading passes against itself, every lower precision reads an error
    above it, and the bfloat16 state shows in the state statistic and not
    in the loss. (Which limits a variant misses is a chip reading at the
    timed size: PERF.md section 6 PR 31.)"""
    out = os.path.join(ROOT, ".bench_out", "precision_control_test.json")
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    run = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "benchmark", "tools", "precision_control.py"),
         CELL, "--seeds", "2147483777", "--cpu-rehearsal", TINY,
         "--out", out],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert run.returncode == 0, run.stderr[-3000:]
    with open(out, encoding="utf-8") as f:
        rows = {row["variant"]: row for row in json.load(f)}
    os.remove(out)
    assert set(rows) == {"fp32", "bf16", "fp8", "state_bf16"}
    assert rows["fp32"]["correct"] and not rows["fp32"]["failed_limits"]
    assert rows["fp32"]["ssm_state_rms_rel_err_max"] == 0.0
    for name in ("bf16", "fp8", "state_bf16"):
        assert rows[name]["ssm_state_rms_rel_err_max"] > 0.0
        assert rows[name]["correct"] == (not rows[name]["failed_limits"])
    assert rows["state_bf16"]["ssm_state_rms_rel_err_max"] > 0.02
    assert rows["state_bf16"]["loss0_abs_err"] < 1e-4 \
        < rows["bf16"]["loss0_abs_err"]


def test_the_configuration_is_the_catalog_row_cut_in_depth_and_vocabulary():
    from benchmark.lib import cells
    conf = cells.load_cell(CELL)["config"]
    assert conf["reduced"] == ["num_hidden_layers", "layer_types",
                               "vocab_size"]
    assert (conf["num_hidden_layers"], conf["vocab_size"]) == (10, 12544)
    assert conf["layer_types"].count("attention") == 1
    assert conf["layer_types"].index("attention") == 5
    assert conf["published"]["num_hidden_layers"] == 40
    assert conf["published"]["vocab_size"] == 8 * conf["vocab_size"]
    for key, value in {
            "hidden_size": 2048, "mamba_n_heads": 64, "mamba_d_head": 64,
            "mamba_d_state": 128, "mamba_chunk_size": 256,
            "mamba_d_conv": 4, "mamba_expand": 2, "mamba_n_groups": 1,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "shared_intermediate_size": 8192,
            "attention_multiplier": 0.015625, "embedding_multiplier": 12,
            "residual_multiplier": 0.22, "logits_scaling": 8,
            "rms_norm_eps": 1e-05, "tie_word_embeddings": True,
            "position_embedding_type": "nope"}.items():
        assert conf[key] == value, key
