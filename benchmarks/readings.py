#!/usr/bin/env python3
"""The readings a cell's limits are set from (not part of a benchmark run).

    python3 benchmarks/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 4,5,6] [--out chiprun_out/readings.jsonl]
        [--leaves 1]

For every seed the program's first steps against the reference (the lower
reading is the largest of these over a dozen seeds or more); for every
control seed the reference in fp8 in the program's place and the
half-batch fault planted there, against the same reference (the upper
reading is the smallest of those). A state left unchanged reads 1 by the
measure of `check` and needs no run. One JSON line per reading, each
judged by the cell's committed limits (`correct`: a control or a fault
has to read false). `--leaves` keeps both records leaf by leaf: the look
for the cause, where a few seeds read far off.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def program(spec: dict, seeds, devices, peak, leaves: bool) -> list:
    from benchmarks.harness import loop
    rows = []
    for seed in seeds:
        kept = {}
        out = loop.run_cell(spec, seed, 0.0, False, devices, peak,
                            time.perf_counter(), keep=kept)
        rows.append({"seed": seed, "what": "program",
                     "correct": out["correct"],
                     **{k: c["value"] for k, c in out["compared"].items()},
                     "at": {k: c.get("at")
                            for k, c in out["compared"].items()},
                     **(kept if leaves else {})})
    return rows


def controls(spec: dict, seeds, devices, leaves: bool) -> list:
    from benchmarks.harness import cells, check, data, reference_train
    from benchmarks.harness.loop import CHECK_STEPS, reference_blocks
    config, mix = spec["config"], spec["mix"]
    blocks = reference_blocks(mix)
    _, reference = cells.family(config)
    rows = []
    for seed in seeds:
        pool = data.make_pool(mix, config["vocab_size"], seed)[:CHECK_STEPS]
        ref = reference_train.run(reference, config, pool, seed,
                                  blocks=blocks, devices=devices)
        sides = {
            "control_fp8": reference_train.run(
                reference, config, pool, seed, precision="fp8",
                blocks=blocks, devices=devices),
            # with blocks, half of them: the rows of the other chips
            "fault_half_batch": reference_train.run(
                reference, config, pool, seed,
                rows=slice(0, mix["batch"] // 2),
                blocks=max(blocks // 2, 1), devices=devices),
        }
        for what, got in sides.items():
            ok, compared = check.compare(got, ref, spec["limits"])
            rows.append({"seed": seed, "what": what, "correct": ok,
                         **{k: c["value"] for k, c in compared.items()},
                         "at": {k: c["at"] for k, c in compared.items()},
                         **({"got": got, "ref": ref} if leaves else {})})
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", default="")
    ap.add_argument("--leaves", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from benchmarks import run as entry
    from benchmarks.harness import cells
    spec = cells.resolve(args.workload)

    def ints(text):
        return [int(x) for x in text.split(",") if x]
    # the control and the planted fault are the reference alone: one chip
    seeds = ints(args.seeds)
    devices, peak = entry.require_tpu(spec["cell"]["chips"] if seeds else 1)
    entry.enable_cache()
    rows = program(spec, seeds, devices, peak, bool(args.leaves))
    rows += controls(spec, ints(args.control_seeds), devices,
                     bool(args.leaves))
    for r in rows:
        print(json.dumps({k: v for k, v in r.items()
                          if k not in ("got", "ref")}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "a") as f:
            for r in rows:
                f.write(json.dumps({"workload": args.workload, **r}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
