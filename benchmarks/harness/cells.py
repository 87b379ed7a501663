"""Everything is found by name: BENCHMARK.json names a cell's config and
mix, and each per-layer metric names itself; their files lie under
benchmarks/. A later PR adds files and entries and edits none."""
from __future__ import annotations

import importlib
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def resolve(workload: str, bench: dict | None = None) -> dict:
    """The cell's entry, its configuration, mix and limits, and the
    metrics it reports."""
    bench = bench or benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = cells[workload]
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    # how the configuration is laid over that many chips (mesh, sharding
    # stage) is the configuration's to say
    config["step"].update(config["layouts"][str(cell["chips"])])
    limits = load_json("limits", workload + ".json")

    def reported(metric):
        return workload in metric.get("workloads", cells)

    end_to_end = [m for m in bench["end_to_end"] if reported(m)]
    e2e_names = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if reported(m) and m["moves"] in e2e_names]
    return {"cell": cell, "config": config, "mix": mix, "limits": limits,
            "end_to_end": end_to_end, "per_layer": per_layer}


def family(config: dict):
    """(adapter, reference) modules of the configuration's family. Only
    the adapter imports the program."""
    name = config["family"]
    return (importlib.import_module(f"benchmarks.families.{name}"),
            importlib.import_module(f"benchmarks.families.{name}_reference"))


def reader(metric_name: str):
    """(read function, parameters) of one per-layer metric."""
    spec = load_json("metrics", metric_name + ".json")
    mod = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return mod.read, spec.get("params", {})
