"""Smoke for the flagship transformer MFU harness (bench_transformer.py).

Protocol analog of tests/test_eager_bench.py: the harness must run
end-to-end on the virtual CPU mesh and emit the JSON contract the docs'
family table is built from. MFU itself is only meaningful on a real chip
(peak-FLOPs table keys on TPU device kinds), so here it must be null, not
a number fabricated from a CPU rate.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_bench_transformer_smoke():
    # --cpu-devices: the harness's own explicit request for the virtual
    # CPU mesh (it never moves to the CPU unasked)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench_transformer.py"),
         "--cpu-devices", "2",
         "--d-model", "32", "--layers", "1", "--heads", "2",
         "--kv-heads", "0",
         "--vocab", "128", "--seq-len", "64", "--batch-per-chip", "2",
         "--loss-chunk", "32", "--dense", "--iters", "1"],
        cwd=REPO, env=dict(os.environ), capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["metric"] == "transformer_tokens_per_sec_per_chip"
    assert payload["value"] > 0
    assert payload["unit"] == "tokens/sec"
    assert payload["mfu_pct"] is None  # no fabricated MFU off-TPU
    assert payload["flops_per_token"] > 0
    assert payload["attention"] == "dense"
    # the line names the device it ran on (the suite's XLA_FLAGS may
    # already hold more virtual devices than the 2 asked for)
    assert (payload["platform"], payload["device_kind"]) == ("cpu", "cpu")
    assert payload["device_count"] >= 2
