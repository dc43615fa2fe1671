#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process: set-up, warm-up, a measured window of ``--seconds``, checks,
and as the LAST line of stdout one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced). ``--trace 0`` prints the cell's ``end_to_end`` metrics, ``--trace
1`` its ``per_layer`` metrics. No accelerator, or another number of chips
than the cell asks for: a non-zero exit and no result line.
``--cpu-rehearsal <preset.json>`` walks the same path on virtual CPU devices
at the preset's toy sizes with interpreted kernels and says ``"platform":
"cpu"``; it is for finding faults in the harness, never a measurement.

The cell's files are found by name (``benchmark/lib/cells.py``); its
``mode`` names the file under ``benchmark/modes/`` that runs it.
"""

import time

T_START = time.perf_counter()  # the process's clock starts here

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                         "BENCHMARK.json run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu-rehearsal", metavar="PRESET",
                    help="NOT a measurement: toy sizes from this preset "
                         "file on virtual CPU devices, kernels interpreted")
    ap.add_argument("--dump-dir", help="also write the whole result (and, "
                    "traced, the xplane file and the step's HLO) here")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    from benchmark.lib import cells, layer_metrics
    cell = cells.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cells.load_benchmark()["run_seconds"])
    if args.cpu_rehearsal:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
    # The compile cache sits at a fixed path inside the checkout unless it
    # is placed from outside; hvd.init() follows the same rule.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(cells.ROOT, ".jax_cache"))
    mode = importlib.import_module(f"benchmark.modes.{cell['cell']['mode']}")
    result = mode.run(cell, args, T_START)

    metrics = {}
    if args.trace:
        for m in cells.metrics_for("per_layer", args.workload):
            value = layer_metrics.read(m, result["ctx"])
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cells.metrics_for("end_to_end", args.workload):
            metrics[m["name"]] = {"value": result["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": metrics,
            "device": result["device"]}
    if args.trace and result.get("breakdown"):
        line["breakdown"] = result["breakdown"]
    line["checks"] = result["checks"]
    if args.dump_dir:
        os.makedirs(args.dump_dir, exist_ok=True)
        tag = f"{args.workload}-seed{args.seed}-trace{args.trace}"
        full = dict(line, end_to_end=result["end_to_end"],
                    detail=result["detail"],
                    notes=result["ctx"].get("notes"))
        with open(os.path.join(args.dump_dir, f"{tag}.json"), "w",
                  encoding="utf-8") as f:
            json.dump(full, f, indent=1, default=str)
    sys.stdout.flush()
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
