"""The benchmark's own tests of mode ``train_share`` (PR 27). Run by hand,
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
TINY = os.path.join(HERE, "tiny_laguna.json")


def run_cell(workload, trace):
    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds", "2",
         "--trace", str(trace), "--cpu-rehearsal", TINY],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_end_to_end():
    line = run_cell("laguna-s21_s8k", trace=0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 3
    assert line["device"]["platform"] == "cpu"  # a rehearsal says so
    assert set(line["metrics"]) == {"tokens_per_s_per_chip", "peak_hbm_gib",
                                    "setup_s"}
    assert all(line["checks"].values()), line["checks"]


def test_rehearsal_traced_reports_what_needs_no_device():
    line = run_cell("laguna-s21_s8k", trace=1)
    # no device plane on the CPU: the trace readers return nothing; the
    # routing counters come from the step's own aux
    assert 1.0 <= line["metrics"]["moe_load_max_over_mean"]["value"] <= 4.0
    assert not {"dev_moe_ms", "moe_experts_roofline", "mfu_device_layers",
                "mfu_device", "flash_roofline"} & set(line["metrics"])


def test_the_four_homogeneous_work_metrics_skip_the_new_cell():
    from benchmark.lib import cells
    names = {m["name"] for m in cells.metrics_for("per_layer",
                                                  "laguna-s21_s8k")}
    assert not {"mfu_device", "flash_roofline", "flash_fwd_roofline",
                "flash_bwd_roofline"} & names
    assert {"mfu_device_layers", "attn_window_roofline", "dev_moe_ms",
            "flash_fwd_ms", "dev_unscoped_ms"} <= names
    old = {m["name"] for m in cells.metrics_for("per_layer",
                                                "sc2-3b_s16k_noremat")}
    assert {"mfu_device", "flash_roofline"} <= old
    assert not {"dev_moe_ms", "flash_fwd_recompute_ms"} & old


def test_work_per_layer_kind_is_the_arithmetic_of_the_cell():
    """ISSUE 27's count: layer 0 250 M forward FLOPs a token (its dense
    MLP 226 M), a sliding sparse layer 44.4 M, the full sparse layer
    50.0 M, the head 77 M: 510 M under uniform routing."""
    from benchmark.lib import cells, work_layers
    from benchmark.modes import train_share
    shape = train_share.work_shape(cells.load_cell("laguna-s21_s8k"))
    total, parts = work_layers.required_flops_per_token(shape, 10 * 8 / 256)
    per_layer = [sum(p.values()) for p in parts]
    assert round(parts[0]["mlp"] / 1e6) == 226
    assert [round(x / 1e6, 1) for x in per_layer] == [
        250.1, 44.4, 44.4, 44.4, 50.0, 77.1]
    assert abs(total - 3 * sum(per_layer)) < 1
    assert round(sum(per_layer) / 1e6) == 510
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = work_layers.expert_matmul_seconds(shape, 5120, peaks)
    assert bound == "compute" and abs(t - 2 * 5120 * 3072 * 1024 / 197e12) \
        < 1e-9
