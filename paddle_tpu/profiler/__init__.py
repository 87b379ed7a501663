"""`paddle.profiler` equivalent, and the one place where the program names
its own work.

Host spans: `RecordEvent` keeps every finished span in memory, on
`time.perf_counter_ns` (the clock a caller's own timings use), and enters
a `jax.profiler.TraceAnnotation`, so that while a jax trace runs the same
span lies on the host plane of the `.xplane.pb`, on the device events'
clock. While `start_profiler()` is on it also feeds the native ring
(csrc/ptpu_runtime.cc Profiler ≈ `platform/profiler.h:127` RecordEvent),
whose chrome://tracing dump the serving tools read (`tools/timeline.py`
parity). Device-side timing comes from `jax.profiler` (XLA's tracer
replaces the reference's CUPTI `DeviceTracer`,
`platform/device_tracer.h:43`).

Step records: a jitted step's blocks may hand out traced counters
(`count`, collected per block by `counting()` while the step builder
traces it), and the step's callable keeps what each call returned, with
its host dispatch on the same `time.perf_counter_ns`, in a bounded record
(`step_records()`). The counters, packed into one array, are copied to
the host asynchronously: keeping them never waits on the device.

Device names: the constants below are the names of the step programs, of
the Pallas kernels and of the `jax.named_scope`s on the training path.
They end up in every HLO instruction's `op_name`, and a kernel's name is
its compiled instruction's name too, which is what the benchmark's trace
readers match (`benchmarks/metrics/*.json`;
`tests/test_train_path_names.py` holds the two together).
"""
from __future__ import annotations

import collections
import contextlib
import functools
import itertools
import math
import threading
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core import native
from . import stats  # noqa: F401  (re-export: profiler.stats registry)

# step programs (`jax.jit` of a function of this name: module `jit_<name>`)
GPT_TRAIN_STEP = "gpt_train_step"
GPT_OFFLOAD_GRAD = "gpt_offload_grad"
GPT_OFFLOAD_CHUNK = "gpt_offload_chunk"
GPT_OFFLOAD_OUTER = "gpt_offload_outer"
# Pallas kernels (`pallas_call(name=...)`: the innermost component of the
# kernel's `op_name`, and the compiled instruction's name, "%flash_fwd.16")
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
# under a per-(q, k) selection: the forward and ONE backward, the dk/dv
# walk, out of which dq comes too; and the indexer that makes the
# selection: its scores, and the exact top-k over them
FLASH_SEL_FWD = "flash_sel_fwd"
FLASH_SEL_BWD_DKV = "flash_sel_bwd_dkv"
INDEX_SCORES = "index_scores"
INDEX_TOPK = "index_topk"
# the same two for latent attention: scores over a wider head (with one
# rotary key head shared by every query head) than the values read
FLASH_MLA_FWD = "flash_mla_fwd"
FLASH_MLA_BWD_DKV = "flash_mla_bwd_dkv"
# the causal three under a sliding window: their grid visits only the key
# blocks the band meets
FLASH_WIN_FWD = "flash_win_fwd"
FLASH_WIN_BWD_DQ = "flash_win_bwd_dq"
FLASH_WIN_BWD_DKV = "flash_win_bwd_dkv"
KERNELS = (FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV)
MLA_KERNELS = (FLASH_MLA_FWD, FLASH_MLA_BWD_DKV)
SEL_KERNELS = (FLASH_SEL_FWD, FLASH_SEL_BWD_DKV)
WIN_KERNELS = (FLASH_WIN_FWD, FLASH_WIN_BWD_DQ, FLASH_WIN_BWD_DKV)
# not ours to choose: the instruction the TPU compiler makes of
# `jax.lax.ragged_dot` (the expert layer's grouped product) is a custom
# call of this name, "%ragged-dot-none.3", forward and backward alike
RAGGED_DOT = "ragged-dot-none"
# scopes, outermost first: the step's phases, a block's two halves, the
# attention dispatch inside `attn`, the clip inside `optimizer`
EMBED = "embed"
DECODER = "decoder"
LM_LOSS = "lm_loss"
MLM_HEAD = "mlm_head"
OPTIMIZER = "optimizer"
CLIP = "clip"
ATTN = "attn"
MLP = "mlp"
ATTENTION = "attention"
# the sparse-attention indexer inside `attn` and its exact top-k, the
# expert layer's two halves inside `mlp`
INDEXER = "indexer"
INDEXER_SELECT = "indexer.select"
MOE_ROUTE = "moe.route"
MOE_EXPERTS = "moe.experts"
# latent attention's projections and norm inside `attn`, an expert layer's
# shared expert inside `mlp`
ATTN_LATENT = "attn.latent"
MOE_SHARED = "moe.shared"
# a sliding-window layer's attention dispatch, and an attention output's
# sigmoid gate, inside `attn`
ATTN_WINDOW = "attn.window"
ATTN_GATE = "attn.gate"


class Span(NamedTuple):
    """One finished host span, nanoseconds of `time.perf_counter_ns`.
    `parent` is the id of the span that was open on the same thread when
    this one started (0: none); `detail` says which of several (the
    function a `compile.*` span compiled)."""
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: int
    detail: str = ""


# the spans are few (set-up, compiles, whatever a caller scopes); a
# caller that scopes every step of a long job keeps its first MAX_SPANS
# and a count of the rest, until `reset()`
MAX_SPANS = 1 << 16
_spans: list = []
_ids = itertools.count(1)
_native_on = False   # between start_profiler() and stop_profiler()


class _Open(threading.local):
    """Ids of the spans open on this thread, outermost first."""

    def __init__(self):
        self.ids = []


_open = _Open()


def _keep(span: Span) -> None:
    if len(_spans) < MAX_SPANS:
        _spans.append(span)
    else:
        stats.REGISTRY.counter("profiler.spans_dropped").add()


def record_span(name: str, start_ns: int, end_ns: int,
                detail: str = "") -> None:
    """Keep a span that was timed by other means (an import, a duration
    JAX reports); its parent is the span open on this thread now."""
    stack = _open.ids
    _keep(Span(name, start_ns, end_ns, next(_ids),
               stack[-1] if stack else 0, detail))


def spans() -> list:
    """Every span finished since the process started or `reset()`, in
    the order they ended."""
    return list(_spans)


class RecordEvent:
    """Scoped host span (reference: platform/profiler.h:127), as context
    manager, `begin()` / `end()` pair or decorator.

    Always: one `Span` kept in memory (`spans()`), parent taken from the
    spans open on this thread, and a `TraceAnnotation` of the same name,
    which costs a fraction of a microsecond and shows in a jax trace when
    one is running. Between `start_profiler()` and `stop_profiler()` the
    span also goes to the native ring; outside it no native call is made.
    Meant for work that runs once per process or per compile, not inside
    a jitted step (there `jax.named_scope` names the device's work).
    """

    def __init__(self, name: str):
        self.name = name
        self._native_t0 = None

    def __enter__(self):
        stack = _open.ids
        self._parent = stack[-1] if stack else 0
        self._id = next(_ids)
        stack.append(self._id)
        if _native_on:
            self._native_t0 = native.lib().ptpu_profiler_now_us()
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        if self._native_t0 is not None:
            l = native.lib()
            l.ptpu_profiler_record(self.name.encode(), self._native_t0,
                                   l.ptpu_profiler_now_us())
            self._native_t0 = None
        stack = _open.ids
        try:
            # also closes children left open by an exception
            del stack[stack.index(self._id):]
        except ValueError:      # ended on another thread than it began
            pass
        _keep(Span(self.name, self._t0, t1, self._id, self._parent))
        return False

    begin = __enter__

    def end(self):
        self.__exit__(None, None, None)

    def __call__(self, fn):
        """Decorator form: every call of `fn` runs inside a scoped
        event named after this RecordEvent (reference:
        `platform/profiler.py` RecordEvent's decorator usage)."""
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            # a fresh scope per call — decorating with ONE RecordEvent
            # instance must stay reentrant/nestable
            with RecordEvent(self.name):
                return fn(*args, **kwargs)
        return wrapped


def start_profiler(tracer_option: str = "Default"):
    """Reference: fluid/profiler.py start_profiler."""
    global _native_on
    if native.available():
        native.lib().ptpu_profiler_enable()
        _native_on = True


def stop_profiler(sorted_key: Optional[str] = None,
                  profile_path: str = "/tmp/profile"):
    """Dump host events as a chrome trace (reference writes profiler.proto;
    chrome trace is the rendered form both end up in)."""
    global _native_on
    _native_on = False
    if native.available():
        l = native.lib()
        l.ptpu_profiler_disable()
        l.ptpu_profiler_dump(str(profile_path).encode())


@contextlib.contextmanager
def profiler(tracer_option: str = "Default",
             profile_path: str = "/tmp/profile"):
    """Reference: fluid/profiler.py profiler context manager."""
    start_profiler(tracer_option)
    try:
        yield
    finally:
        stop_profiler(profile_path=profile_path)


def event_count() -> int:
    """Events in the native ring (those recorded while profiling was on)."""
    return int(native.lib().ptpu_profiler_count()) if native.available() \
        else 0


class Counters:
    """A step's counters `{name: int32 [blocks]}` packed into ONE int32
    array (`pack`, in the traced step), so that keeping them costs one
    copy to the host a step; `unpack` gives them back by name. A pytree
    whose one leaf is the packed array and whose layout is static."""

    def __init__(self, packed, layout):
        self.packed, self.layout = packed, layout

    @classmethod
    def pack(cls, counters: dict) -> "Counters":
        names = sorted(counters)
        return cls(jnp.concatenate([jnp.ravel(counters[n]) for n in names]),
                   tuple((n, tuple(counters[n].shape)) for n in names))

    def unpack(self) -> dict:
        flat, out, at = np.asarray(self.packed), {}, 0
        for name, shape in self.layout:
            size = math.prod(shape)
            out[name], at = flat[at:at + size].reshape(shape), at + size
        return out


jax.tree_util.register_pytree_node(
    Counters, lambda c: ((c.packed,), c.layout),
    lambda layout, leaves: Counters(leaves[0], layout))


class StepRecord(NamedTuple):
    """One call of a step: its index among the calls of that step
    callable, its host dispatch on `time.perf_counter_ns`, and the
    counters its blocks handed out (`Counters`; in `step_records()`
    `{name: int32 [blocks]}`, empty where no block counts)."""
    step: int
    begin_ns: int
    end_ns: int
    counters: object


# a few minutes of steps at any step time; the oldest go first
MAX_STEP_RECORDS = 512
_steps: collections.deque = collections.deque(maxlen=MAX_STEP_RECORDS)


class _Counting(threading.local):
    """The counters of the block being traced on this thread, or None."""

    def __init__(self):
        self.open = None


_counting = _Counting()


@contextlib.contextmanager
def counting():
    """Collect what `count` is given while the block inside is traced:
    yields the dict `{name: traced value}` it fills."""
    outer, _counting.open = _counting.open, {}
    try:
        yield _counting.open
    finally:
        _counting.open = outer


def count(name: str, value) -> None:
    """Add a traced value to the counter `name` of the block being traced
    under `counting()`; anywhere else, nothing."""
    got = _counting.open
    if got is not None:
        got[name] = got[name] + value if name in got else value


def record_step(step: int, begin_ns: int, end_ns: int, counters) -> None:
    """Keep one call of a step; its `Counters` (or `{}`: none) start
    their way to the host now and are not waited for."""
    if counters:
        counters.packed.copy_to_host_async()
    _steps.append(StepRecord(step, begin_ns, end_ns, counters))


def step_records() -> list:
    """The kept calls, oldest first, their counters by name as numpy
    arrays."""
    return [r._replace(counters=r.counters.unpack() if r.counters else {})
            for r in list(_steps)]


def reset():
    """Forget the spans and step records kept in memory and clear the
    native ring."""
    del _spans[:]
    _steps.clear()
    if native.available():
        native.lib().ptpu_profiler_clear()


# Device-side (XLA) tracing — jax.profiler passthrough
def start_trace(log_dir: str):
    import jax
    jax.profiler.start_trace(log_dir)


def stop_trace():
    import jax
    jax.profiler.stop_trace()


@contextlib.contextmanager
def trace(log_dir: str):
    start_trace(log_dir)
    try:
        yield
    finally:
        stop_trace()
