"""JAX's persistent compilation cache, configured in one place.

Every cold process recompiles the whole train step (GPT-345M on a v5e:
11-13 s cold against 2 s from this cache, `chip_smoke.py` runs of PR 23;
larger models and the tools' sweeps cost more), so the entry points that
run on the chip
(`chip_smoke.py`, `benchmarks/run.py`, `tools/conv_profile.py`, `paddle_tpu.tools.op_bench`) call `enable()`
before their first compile. The rule:

* `JAX_COMPILATION_CACHE_DIR` set in the environment: JAX reads it by
  itself, and this module sets nothing.
* unset: one fixed directory inside the checkout (git-ignored). The
  path is part of the cache key, so it is never built from a temp
  name, a pid or the time.

`tests/conftest.py` applies the same rule through the environment
variable (it has to, before `jax` is imported, so that the tests'
subprocesses inherit it).

`enable()` also makes every compile of the process visible: JAX reports
what tracing, lowering and the backend compile (or the read from this
cache) of each program took, and each report becomes a span of the
program's profiler, `compile.trace`, `compile.lower` or
`compile.backend`, with the function's name as its `detail`; the counters
`compile.requests` and `compile.cache_hits` of `profiler.stats.REGISTRY`
count them.
"""
from __future__ import annotations

import os
import time

# <checkout>/.jax_cache — the directory that holds the paddle_tpu package
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


_SPAN_OF_EVENT = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
    "/jax/core/compile/backend_compile_duration": "compile.backend",
}
_listening = False


def _listen() -> None:
    """Register the listeners, once a process (JAX has no way to take
    one back)."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax

    from .. import profiler

    counters = profiler.stats.REGISTRY

    def duration(event, seconds, fun_name="", **_kw):
        name = _SPAN_OF_EVENT.get(event)
        if name is None:
            return
        now = time.perf_counter_ns()
        profiler.record_span(name, now - int(seconds * 1e9), now,
                             detail=str(fun_name))
        if name == "compile.backend":
            counters.counter("compile.requests").add()

    def event(name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            counters.counter("compile.cache_hits").add()

    jax.monitoring.register_event_duration_secs_listener(duration)
    jax.monitoring.register_event_listener(event)


def enable() -> str:
    """Turn the persistent cache on and return the directory in use."""
    _listen()
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
