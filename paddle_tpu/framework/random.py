"""RNG state management.

Bridges paddle's stateful global-seed model (`paddle.seed`,
`fluid/framework/generator.py`) onto JAX's explicit-key PRNG:

- Eager code: a process-global stateful key, advanced on every draw.
- Traced (jit) code: callers seed a scope with `rng_guard(key)` where `key`
  is a traced value threaded into the step function; layers draw sub-keys via
  `next_key()`. Trace-order determinism makes this reproducible.
- `RNGStatesTracker` mirrors the reference's model-parallel dropout seed
  tracker (`fleet/meta_parallel/parallel_layers/random.py:24`): named states
  so tensor-parallel ranks use identical or distinct dropout masks on demand.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax

_state = threading.local()


def _global():
    if not hasattr(_state, "key"):
        _state.key = jax.random.key(0)
    return _state


def seed(seed):
    """paddle.seed equivalent (ref: framework/random.py:22)."""
    _global().key = jax.random.key(int(seed))
    return _global().key


def next_key():
    """Draw a fresh PRNG key.

    Inside an `rng_guard` scope (e.g. within a jitted step) keys come from the
    scoped traced key; otherwise from the process-global eager state.
    """
    st = _global()
    scoped = getattr(st, "scoped", None)
    if scoped:
        key, sub = jax.random.split(scoped[-1])
        scoped[-1] = key
        return sub
    # the global key stays a concrete value even when a trace draws from
    # it unscoped (jit.save of a layer with hard-wired training dropout):
    # left to the trace, the split would store a tracer here and every
    # later eager draw of the process would fail on it
    with jax.ensure_compile_time_eval():
        st.key, sub = jax.random.split(st.key)
    return sub


@contextlib.contextmanager
def rng_guard(key):
    """Scope a (possibly traced) PRNG key for layers that draw randomness."""
    st = _global()
    if not hasattr(st, "scoped"):
        st.scoped = []
    st.scoped.append(key)
    try:
        yield
    finally:
        st.scoped.pop()


def get_rng_state():
    return _global().key


def set_rng_state(key):
    _global().key = key


class RNGStatesTracker:
    """Named RNG states for tensor-parallel dropout.

    Reference: `RNGStatesTracker`
    (`fleet/meta_parallel/parallel_layers/random.py:24`). `add` registers a
    named seed; `rng_state(name)` scopes draws to that state so e.g.
    'local_seed' differs per mp rank while 'global_seed' matches.
    """

    def __init__(self):
        self.states_ = {}
        self.seeds_ = set()

    def reset(self):
        self.states_ = {}
        self.seeds_ = set()

    def add(self, name: str, seed_val: int):
        if seed_val in self.seeds_:
            raise ValueError(f"seed {seed_val} already exists")
        if name in self.states_:
            raise ValueError(f"state {name} already exists")
        self.seeds_.add(seed_val)
        self.states_[name] = jax.random.key(int(seed_val))

    @contextlib.contextmanager
    def rng_state(self, name: str = "global_seed"):
        if name not in self.states_:
            raise ValueError(f"state {name} does not exist")
        st = _global()
        saved_scoped = getattr(st, "scoped", None)
        saved_key = st.key
        st.scoped = []
        st.key = self.states_[name]
        try:
            yield
        finally:
            self.states_[name] = st.key
            st.key = saved_key
            if saved_scoped is None:
                del st.scoped
            else:
                st.scoped = saved_scoped


_MODEL_PARALLEL_TRACKER: Optional[RNGStatesTracker] = None


def get_rng_state_tracker() -> RNGStatesTracker:
    global _MODEL_PARALLEL_TRACKER
    if _MODEL_PARALLEL_TRACKER is None:
        _MODEL_PARALLEL_TRACKER = RNGStatesTracker()
    return _MODEL_PARALLEL_TRACKER


def model_parallel_random_seed(seed_val: int, mp_rank: int = 0):
    """Reference: `model_parallel_random_seed` (parallel_layers/random.py)."""
    tracker = get_rng_state_tracker()
    tracker.reset()
    tracker.add("global_seed", seed_val)
    tracker.add("local_seed", seed_val + 1024 + mp_rank)
