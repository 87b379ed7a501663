"""Tensor method-surface patching.

Reference: `fluid/layers/math_op_patch.py monkey_patch_variable` and
`fluid/dygraph/math_op_patch.py monkey_patch_math_varbase` — paddle
installs its Tensor methods onto the runtime tensor class at import.
Here the runtime tensor IS `jax.Array`; operators already work natively,
but reference scripts also use the METHOD spellings (`t.numpy()`,
`t.unsqueeze(0)`, `t.add(y)`, `t.stop_gradient = True`). This module
adds the missing ones onto the jax Array class — never overriding
anything jax already defines.

Known hole: `x.flatten(start_axis, stop_axis)` keeps jax's native
`flatten(order)` (overriding it could break jax internals); use
`paddle_tpu.flatten(x, start, stop)` or the `x.flatten_(...)` alias for
paddle flatten semantics.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

_PATCHED = False


def _to_cpu(x):
    try:
        return jax.device_put(x, jax.devices("cpu")[0])
    except RuntimeError:   # no CPU backend registered
        return x


def _methods():
    """Method table. Ops that exist as tensor-module functions DELEGATE
    to them so the method and function spellings share one paddle-
    semantics implementation (norm's p='fro' default, expand's -1 dims,
    argsort's descending/stable, t's ndim<2 passthrough, ...)."""
    from ..tensor import linalg as L
    from ..tensor import manipulation as M
    from ..tensor import math as TM
    from ..tensor import search as S

    def unary(fn):
        return lambda self: fn(self)

    def binary(fn):
        return lambda self, other: fn(self, other)

    simple = {
        # torch/paddle-style conversions
        "numpy": lambda self: np.asarray(self),
        "clone": lambda self: jnp.array(self, copy=True),
        "detach": lambda self: jax.lax.stop_gradient(self),
        "cpu": _to_cpu,
        "cuda": lambda self, *a, **k: self,   # accelerator-resident
        "pin_memory": lambda self: self,
        "numel": lambda self: int(np.prod(self.shape)),
        "dim": lambda self: self.ndim,
        "ndimension": lambda self: self.ndim,
        "element_size": lambda self: self.dtype.itemsize,
        "cast": lambda self, dtype: M.cast(self, dtype),
        "scale": lambda self, scale=1.0, bias=0.0: self * scale + bias,
        # elementwise method spellings
        "add": binary(jnp.add),
        "subtract": binary(jnp.subtract),
        "multiply": binary(jnp.multiply),
        "divide": binary(jnp.divide),
        "floor_divide": binary(jnp.floor_divide),
        "mod": binary(jnp.mod),
        "remainder": binary(jnp.mod),
        "pow": binary(jnp.power),
        "matmul": binary(jnp.matmul),
        "maximum": binary(jnp.maximum),
        "minimum": binary(jnp.minimum),
        "equal": binary(jnp.equal),
        "not_equal": binary(jnp.not_equal),
        "greater_than": binary(jnp.greater),
        "greater_equal": binary(jnp.greater_equal),
        "less_than": binary(jnp.less),
        "less_equal": binary(jnp.less_equal),
        "logical_and": binary(jnp.logical_and),
        "logical_or": binary(jnp.logical_or),
        "logical_not": unary(jnp.logical_not),
        "abs": unary(jnp.abs),
        "exp": unary(jnp.exp),
        "log": unary(jnp.log),
        "sqrt": unary(jnp.sqrt),
        "rsqrt": unary(lambda x: 1.0 / jnp.sqrt(x)),
        "square": unary(jnp.square),
        "tanh": unary(jnp.tanh),
        "sigmoid": unary(jax.nn.sigmoid),
        "floor": unary(jnp.floor),
        "ceil": unary(jnp.ceil),
        "sign": unary(jnp.sign),
        "neg": unary(jnp.negative),
        "reciprocal": unary(jnp.reciprocal),
        "isnan": unary(jnp.isnan),
        "isinf": unary(jnp.isinf),
        "isfinite": unary(jnp.isfinite),
        # shape method spellings — delegate to the function surface
        "unsqueeze": lambda self, axis: M.unsqueeze(self, axis),
        "t": lambda self: TM.t(self),
        "tile": lambda self, reps: M.tile(self, reps),
        "expand": lambda self, shape: M.expand(self, shape),
        "broadcast_to": lambda self, shape: M.broadcast_to(self, shape),
        "flatten_": lambda self, *a, **k: M.flatten(self, *a, **k),
        "unbind": lambda self, axis=0: M.unbind(self, axis),
        # reductions missing from the native surface
        "norm": lambda self, p="fro", axis=None, keepdim=False:
            L.norm(self, p=p, axis=axis, keepdim=keepdim),
        "argsort": lambda self, axis=-1, descending=False:
            S.argsort(self, axis=axis, descending=descending),
    }
    return simple


def _backward(self, *a, **k):
    raise RuntimeError(
        "Tensor.backward() is unsupported: autograd is functional on "
        "TPU (no tape). Write the computation as a function and use "
        "paddle_tpu.grad(fn) / value_and_grad(fn).")


def _tracer_class():
    """The Tracer base class — patched too so `x.add(y)` works inside
    jit-traced code, not just eagerly."""
    try:
        from jax._src.core import Tracer
        return Tracer
    except ImportError:
        return None


def monkey_patch_tensor():
    """Install the missing paddle Tensor methods on jax's Array base
    class (and the Tracer base, for inside-jit use). Idempotent;
    existing jax attributes are never overridden.

    IMPORTANT: runs at package import — must not instantiate any array
    or otherwise initialize a jax backend (every importing subprocess
    would claim the chip before it could pin the CPU)."""
    global _PATCHED
    if _PATCHED:
        return
    classes = [jax.Array]
    tracer = _tracer_class()
    if tracer is not None:
        classes.append(tracer)
    methods = _methods()
    for cls in classes:
        for name, fn in methods.items():
            if not hasattr(cls, name):
                try:
                    setattr(cls, name, fn)
                except (TypeError, AttributeError):
                    break  # immutable class on this jax version
        if not hasattr(cls, "backward"):
            try:
                cls.backward = _backward
            except (TypeError, AttributeError):
                pass
    for cls in classes:   # Tracer is NOT a jax.Array subclass: both
        if not hasattr(cls, "stop_gradient"):
            try:
                # eager arrays are constants: reads are True; writes are
                # accepted and ignored so `x.stop_gradient = True` runs
                # unchanged, eagerly AND inside traced code
                cls.stop_gradient = property(lambda self: True,
                                             lambda self, v: None)
            except (TypeError, AttributeError):
                pass
    _PATCHED = True
