"""JAX's persistent compilation cache, configured in one place.

Every cold process recompiles the whole train step (GPT-345M on a v5e:
11-13 s cold against 2 s from this cache, `chip_smoke.py` runs of PR 23;
larger models and the tools' sweeps cost more), so the entry points that
run on the chip
(`chip_smoke.py`, `bench.py`, `tools/perf_sweep.py`,
`tools/conv_profile.py`, `paddle_tpu.tools.op_bench`) call `enable()`
before their first compile. The rule:

* `JAX_COMPILATION_CACHE_DIR` set in the environment: JAX reads it by
  itself, and this module sets nothing.
* unset: one fixed directory inside the checkout (git-ignored). The
  path is part of the cache key, so it is never built from a temp
  name, a pid or the time.

`tests/conftest.py` applies the same rule through the environment
variable (it has to, before `jax` is imported, so that the tests'
subprocesses inherit it).
"""
from __future__ import annotations

import os

# <checkout>/.jax_cache — the directory that holds the paddle_tpu package
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on and return the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
