#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

ONE process, the only one that touches JAX. It runs on the machine it is
started on and needs the chips the cell asks for: without a TPU, or with
fewer chips, it exits non-zero and prints no result; it never falls back
to the CPU. The last line of standard output is the result, one JSON
object; what was compared for `correct` stands beside its limits at the
end of standard error and last in the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def require_tpu(chips: int) -> tuple:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"benchmark: JAX found no TPU (platform {devs[0].platform!r}, "
            f"kind {devs[0].device_kind!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devs)}")
    from benchmarks.harness import peaks
    # an unknown kind is an error, not a default
    return devs[:chips], peaks.peaks(devs[0].device_kind)


def enable_cache() -> str:
    """The program's own rule (`JAX_COMPILATION_CACHE_DIR`, else
    <checkout>/.jax_cache), and every program of a run kept, the small
    ones too: the second run of a cell compiles nothing."""
    import jax
    from paddle_tpu.core import compile_cache
    path = compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.harness import cells, loop
    spec = cells.resolve(args.workload)
    devices, peak = require_tpu(spec["cell"]["chips"])
    loop.log(f"[device] {devices[0].platform} {devices[0].device_kind} x"
             f"{len(devices)}; compile cache {enable_cache()}")
    result = loop.run_cell(spec, args.seed, args.seconds, bool(args.trace),
                           devices, peak, T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
