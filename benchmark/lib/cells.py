"""Find a cell's files by name. Nothing here knows any cell, configuration,
traffic mix or metric: ``BENCHMARK.json`` lists them, and each one's
parameters live in a file of its own that is found by its name.

    BENCHMARK.json workloads[].name     -> benchmark/workloads/<name>.json
    BENCHMARK.json workloads[].traffic  -> benchmark/traffic/<traffic>.json
    BENCHMARK.json workloads[].config   -> configs[].file
    BENCHMARK.json per_layer[].name     -> benchmark/layer_metrics/<name>.json

A later PR adds a cell, a traffic mix, a configuration or a per-layer metric
by adding files and appending entries; no file that exists is edited.
"""

import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def _entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    known = ", ".join(e["name"] for e in entries)
    raise SystemExit(f"benchmark: no {what} named {name!r} in "
                     f"BENCHMARK.json (known: {known})")


def load_cell(name, root=ROOT):
    """Everything one cell is made of, each part from its own file."""
    bench = load_benchmark(root)
    entry = _entry(bench["workloads"], name, "workload")
    cfg_entry = _entry(bench["configs"], entry["config"], "config")
    bdir = os.path.join(root, "benchmark")
    return {
        "name": name,
        "chips": int(entry["chips"]),
        "entry": entry,
        "cell": load_json(os.path.join(bdir, "workloads", f"{name}.json")),
        "traffic": load_json(os.path.join(
            bdir, "traffic", f"{entry['traffic']}.json")),
        "config_name": entry["config"],
        "config": load_json(os.path.join(root, cfg_entry["file"])),
    }


def metrics_for(kind, workload, root=ROOT):
    """The ``end_to_end`` or ``per_layer`` entries that apply to
    ``workload`` (an entry with a ``workloads`` list applies only to
    those), in the order of BENCHMARK.json. Per-layer entries are joined
    with their reader file's fields."""
    bench = load_benchmark(root)
    out = []
    for m in bench[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        m = dict(m)
        if kind == "per_layer":
            path = os.path.join(root, "benchmark", "layer_metrics",
                                f"{m['name']}.json")
            m["reader"] = load_json(path)
            cells = m["reader"].get("cells")
            if cells is not None and workload not in cells:
                continue
        out.append(m)
    return out
