"""`paddle.utils` equivalent (reference: python/paddle/utils/ —
download.py, install_check.py, deprecated.py, op_version.py)."""
from __future__ import annotations

import functools
import os
import warnings


def run_check():
    """Reference: utils/install_check.py `paddle.utils.run_check` — a
    sanity forward/backward on the available device(s)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ..nn.layer_common import Linear
    from ..nn.layer import functional_call, trainable_state

    lin = Linear(4, 2)
    x = jnp.ones((2, 4))

    def loss(p):
        out, _ = functional_call(lin, p, x)
        return jnp.sum(out ** 2)

    g = jax.grad(loss)(trainable_state(lin))
    assert all(np.isfinite(np.asarray(v)).all() for v in g.values())
    n = len(jax.devices())
    print(f"paddle_tpu is installed successfully! "
          f"{n} {jax.default_backend()} device(s) available.")
    return True


def deprecated(update_to="", since="", reason="", level=0):
    """Reference: utils/deprecated.py decorator. level 0/1 warn on call;
    level 2 raises (the reference's hard-removal stage)."""

    def decorator(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            hint = f"; use {update_to} instead" if update_to else ""
            msg = (f"{fn.__name__} is deprecated since {since or 'n/a'}"
                   f"{hint}. {reason}")
            if level >= 2:
                raise RuntimeError(msg)
            warnings.warn(msg, DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)
        return wrapper
    return decorator


def get_weights_path_from_url(url, md5sum=None):
    """Reference: utils/download.py — zero-egress environment: only a
    pre-populated cache hit can succeed."""
    cache = os.path.join(os.path.expanduser("~"), ".cache", "paddle_tpu",
                         "weights", os.path.basename(url))
    if os.path.exists(cache):
        return cache
    raise RuntimeError(
        f"no network egress and {cache} not pre-populated; place the "
        "weights file there manually")


def try_import(module_name: str):
    import importlib
    try:
        return importlib.import_module(module_name)
    except ImportError as e:
        raise ImportError(
            f"{module_name} is required but not installed (and this "
            "environment installs nothing)") from e


def require_version(min_version, max_version=None):
    """Reference: fluid/framework.py require_version — assert the installed
    framework version is in [min_version, max_version]."""
    import itertools
    import re

    from .. import __version__

    def parse(v):
        # leading digits of each dot segment; '1rc0' -> 1, 'dev' -> 0
        out = []
        for p in str(v).split("."):
            m = re.match(r"\d+", p)
            out.append(int(m.group()) if m else 0)
        return out

    def cmp(a, b):
        for x, y in itertools.zip_longest(a, b, fillvalue=0):
            if x != y:
                return -1 if x < y else 1
        return 0

    cur = parse(__version__)
    if cmp(parse(min_version), cur) > 0:
        raise RuntimeError(
            f"requires version >= {min_version}, installed {__version__}")
    if max_version is not None and cmp(parse(max_version), cur) < 0:
        raise RuntimeError(
            f"requires version <= {max_version}, installed {__version__}")


class OpLastCheckpointChecker:
    """Reference: utils/op_version.py — queries op-version compatibility
    checkpoints. Ops here version with the package, so every op reports
    the package version with no extra attrs."""

    def get_op_attrs(self, op_name):
        return {}

    def get_version(self, op_name):
        from .. import __version__
        return __version__


# profiler facade (reference: utils/profiler.py over fluid profiler)
class ProfilerOptions:
    def __init__(self, options=None):
        self.options = {
            "state": "All", "sorted_key": "default", "tracer_level": "Default",
            "batch_range": [0, 100], "output_thread_detail": False,
            "profile_path": "/tmp/profile",
            "timeline_path": "/tmp/timeline", "op_summary_path": None,
        }
        if options is not None:
            self.options.update(options)

    def with_state(self, state):
        new = ProfilerOptions(dict(self.options))
        new.options["state"] = state
        return new

    def __getitem__(self, name):
        return self.options[name]


class Profiler:
    """Reference: utils/profiler.py Profiler — start/stop facade over the
    native profiler (the ring `RecordEvent` feeds while this runs, and
    its chrome-trace export)."""

    def __init__(self, enabled=True, options=None):
        self.enabled = enabled
        self.profiler_options = options or ProfilerOptions()
        self._running = False

    def start(self):
        if self.enabled and not self._running:
            from .. import profiler as prof
            prof.start_profiler(self.profiler_options["tracer_level"])
            self._running = True

    def stop(self):
        if self._running:
            from .. import profiler as prof
            prof.stop_profiler(self.profiler_options["sorted_key"],
                               self.profiler_options["profile_path"])
            self._running = False

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False

    def record_step(self, change_profiler_status=True):
        # nothing to mark: a step is whatever `RecordEvent` scope the
        # caller puts around it, which keeps the span in memory
        # (`profiler.spans()`) and, while this profiler runs, in the
        # native ring's chrome dump
        pass


_profiler_singleton = None


def get_profiler():
    global _profiler_singleton
    if _profiler_singleton is None:
        _profiler_singleton = Profiler()
    return _profiler_singleton


class unique_name:  # namespace-style module shim (reference: utils/unique_name)
    """Reference: `paddle.utils.unique_name` (fluid/unique_name.py):
    generate/guard/switch over a process-wide name registry."""
    _counters = {}

    @staticmethod
    def generate(key):
        n = unique_name._counters.get(key, 0)
        unique_name._counters[key] = n + 1
        return f"{key}_{n}"

    @staticmethod
    def switch(new_generator=None):
        old = dict(unique_name._counters)
        unique_name._counters = {} if new_generator is None \
            else new_generator
        return old

    @staticmethod
    def guard(new_generator=None):
        import contextlib

        @contextlib.contextmanager
        def _guard():
            old = unique_name.switch({} if new_generator is None
                                     else new_generator)
            try:
                yield
            finally:
                unique_name._counters = old
        return _guard()


class image_util:  # namespace shim (reference: utils/image_util.py)
    """Reference: utils/image_util.py — PIL-based image resize/crop helpers
    used by old detection reader scripts."""

    @staticmethod
    def resize_image(img, target_size):
        from PIL import Image
        return img.resize((target_size, target_size), Image.BILINEAR)

    @staticmethod
    def crop_image(img, box):
        return img.crop(tuple(int(v) for v in box))


from . import cpp_extension  # noqa: F401,E402
from . import download  # noqa: F401,E402
