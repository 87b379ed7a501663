"""Layer/Parameter system — the imperative module API.

TPU-native equivalent of the reference's dygraph layer stack
(`python/paddle/fluid/dygraph/layers.py` `Layer`, 1507 lines; `ParamBase`;
hooks). Eager forward runs ops op-by-op exactly like dygraph; training uses
the **functional bridge** (`functional_call`) that swaps a params/buffers
pytree into the layer tree, runs forward under trace, and captures updated
buffers — replacing the reference's C++ `Tracer`/`BasicEngine` autograd
(`imperative/tracer.cc:144`, `basic_engine.cc:305`) with `jax.grad` over a
pure function. XLA then compiles the whole step; no per-op dispatch hot loop
survives.
"""
from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import enforce
from ..core.dtypes import convert_dtype, get_default_dtype
from ..profiler import RecordEvent


class Parameter:
    """A named, trainable array slot (reference: `ParamBase`).

    Holds a `jax.Array`; during `functional_call` the value is temporarily a
    tracer. `stop_gradient=True` marks the slot non-trainable (excluded from
    `trainable_params`), mirroring paddle's `param.stop_gradient` /
    `trainable` flag.
    """

    __slots__ = ("value", "name", "stop_gradient", "_is_buffer",
                 "optimize_attr", "sharding_spec", "regularizer")

    def __init__(self, value, name: str = "", stop_gradient: bool = False,
                 is_buffer: bool = False):
        self.value = jnp.asarray(value)
        self.name = name
        self.stop_gradient = stop_gradient
        self._is_buffer = is_buffer
        self.optimize_attr = {"learning_rate": 1.0}
        # PartitionSpec for hybrid-parallel training (set by mp/pp layers;
        # consumed by the distributed train-step to build NamedShardings).
        self.sharding_spec = None
        # per-param weight-decay override (reference: ParamAttr.regularizer)
        self.regularizer = None

    @property
    def trainable(self) -> bool:
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v: bool):
        self.stop_gradient = not v

    @property
    def shape(self):
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def ndim(self):
        return self.value.ndim

    def numpy(self):
        return np.asarray(self.value)

    def set_value(self, v):
        v = jnp.asarray(v, dtype=self.value.dtype)
        if tuple(v.shape) != tuple(self.value.shape):
            raise ValueError(
                f"set_value shape mismatch for {self.name!r}: parameter is "
                f"{tuple(self.value.shape)}, got {tuple(v.shape)}")
        self.value = v

    def astype(self, dtype):
        return self.value.astype(convert_dtype(dtype))

    def __repr__(self):
        kind = "Buffer" if self._is_buffer else "Parameter"
        return (f"{kind}(name={self.name!r}, shape={tuple(self.value.shape)}, "
                f"dtype={self.value.dtype.name}, trainable={self.trainable})")

    # Arithmetic convenience so `param * x` works in eager code.
    def __array__(self, dtype=None):
        return np.asarray(self.value, dtype=dtype)

    def __jax_array__(self):
        return self.value


# Make Parameter transparently usable where an array is expected.
jax.tree_util.register_pytree_node(
    Parameter,
    lambda p: ((p.value,), (p.name, p.stop_gradient, p._is_buffer)),
    lambda aux, children: Parameter(children[0], name=aux[0],
                                    stop_gradient=aux[1], is_buffer=aux[2]),
)


_name_counters: Dict[str, int] = {}


def _unique_name(prefix: str) -> str:
    i = _name_counters.get(prefix, 0)
    _name_counters[prefix] = i + 1
    return f"{prefix}_{i}"


class HookRemoveHelper:
    def __init__(self, hooks: dict, hook_id: int):
        self._hooks = hooks
        self._hook_id = hook_id

    def remove(self):
        self._hooks.pop(self._hook_id, None)


class Layer:
    """Base building block (reference: dygraph `Layer`, layers.py).

    Subclasses define parameters in `__init__` (via attribute assignment or
    `create_parameter`) and computation in `forward`. The layer tree is
    introspectable exactly like the reference: `named_parameters`,
    `sublayers`, `state_dict`, forward pre/post hooks, `train`/`eval`.
    """

    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        self.training = True
        self._dtype = convert_dtype(dtype) or get_default_dtype()
        self._full_name = _unique_name(name_scope or
                                       self.__class__.__name__.lower())
        self._parameters: "OrderedDict[str, Parameter]" = OrderedDict()
        self._buffers: "OrderedDict[str, Parameter]" = OrderedDict()
        self._sub_layers: "OrderedDict[str, Layer]" = OrderedDict()
        self._forward_pre_hooks: Dict[int, Callable] = {}
        self._forward_post_hooks: Dict[int, Callable] = {}
        self._hook_id = 0

    # --- construction helpers ---

    def create_parameter(self, shape, dtype=None, is_bias=False,
                         default_initializer=None, attr=None) -> Parameter:
        """Reference: `Layer.create_parameter` → `LayerHelper` param creation
        (`fluid/layer_helper.py`). `attr` accepts a `ParamAttr` (or a
        name/initializer it normalizes from) whose initializer overrides
        `default_initializer` and whose regularizer/trainable/lr hints land
        on the created Parameter."""
        from . import initializer as I
        from ..framework.param_attr import ParamAttr
        attr = ParamAttr._to_attr(attr)
        dtype = convert_dtype(dtype) or self._dtype
        if isinstance(attr, ParamAttr) and attr.initializer is not None:
            default_initializer = attr.initializer
        if default_initializer is None:
            glob = I._global_initializer   # set_global_initializer hook
            if glob is not None and (glob[1] if is_bias else glob[0]) \
                    is not None:
                default_initializer = glob[1] if is_bias else glob[0]
            else:
                default_initializer = I.Constant(0.0) if is_bias \
                    else I.XavierUniform()
        value = default_initializer(tuple(int(s) for s in shape), dtype)
        param = Parameter(value, name=_unique_name(self._full_name + ".w"))
        if isinstance(attr, ParamAttr):
            attr.apply_to(param)
        return param

    def register_buffer(self, name: str, tensor, persistable: bool = True):
        buf = Parameter(tensor, name=f"{self._full_name}.{name}",
                        stop_gradient=True, is_buffer=True)
        self._buffers[name] = buf
        object.__setattr__(self, name, buf)
        return buf

    def add_sublayer(self, name: str, sublayer: "Layer") -> "Layer":
        self._sub_layers[name] = sublayer
        object.__setattr__(self, name, sublayer)
        return sublayer

    def add_parameter(self, name: str, parameter: Parameter) -> Parameter:
        self._parameters[name] = parameter
        object.__setattr__(self, name, parameter)
        return parameter

    # --- attribute interception (mirrors layers.py __setattr__) ---

    def __setattr__(self, name: str, value: Any):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter) and params is not None:
            if value._is_buffer:
                buffers[name] = value
            else:
                params[name] = value
        elif isinstance(value, Layer) and layers is not None:
            layers[name] = value
        elif params is not None and name in params and not isinstance(value, Parameter):
            # assigning an array to a parameter slot updates its value
            params[name].set_value(value)
            return
        object.__setattr__(self, name, value)

    def __delattr__(self, name):
        self._parameters.pop(name, None)
        self._buffers.pop(name, None)
        self._sub_layers.pop(name, None)
        object.__delattr__(self, name)

    # --- forward & hooks ---

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            out = hook(self, inputs)
            if out is not None:
                inputs = out if isinstance(out, tuple) else (out,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, outputs)
            if res is not None:
                outputs = res
        return outputs

    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # --- traversal ---

    def children(self) -> Iterator["Layer"]:
        yield from self._sub_layers.values()

    def named_children(self) -> Iterator[Tuple[str, "Layer"]]:
        yield from self._sub_layers.items()

    def sublayers(self, include_self: bool = False) -> Iterator["Layer"]:
        if include_self:
            yield self
        for l in self._sub_layers.values():
            yield from l.sublayers(include_self=True)

    def named_sublayers(self, prefix: str = "", include_self: bool = False):
        if include_self:
            yield prefix, self
        for name, l in self._sub_layers.items():
            p = f"{prefix}.{name}" if prefix else name
            yield from l.named_sublayers(prefix=p, include_self=True)

    def parameters(self, include_sublayers: bool = True):
        return [p for _, p in self.named_parameters()] if include_sublayers \
            else list(self._parameters.values())

    def named_parameters(self, prefix: str = ""):
        for name, p in self._parameters.items():
            yield (f"{prefix}.{name}" if prefix else name), p
        for lname, l in self._sub_layers.items():
            sub_prefix = f"{prefix}.{lname}" if prefix else lname
            yield from l.named_parameters(prefix=sub_prefix)

    def named_buffers(self, prefix: str = ""):
        for name, b in self._buffers.items():
            yield (f"{prefix}.{name}" if prefix else name), b
        for lname, l in self._sub_layers.items():
            sub_prefix = f"{prefix}.{lname}" if prefix else lname
            yield from l.named_buffers(prefix=sub_prefix)

    def buffers(self):
        return [b for _, b in self.named_buffers()]

    def apply(self, fn: Callable[["Layer"], None]) -> "Layer":
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # --- mode / dtype ---

    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    def to(self, device=None, dtype=None):
        dtype = convert_dtype(dtype)
        for p in list(self.parameters()) + list(self.buffers()):
            v = p.value
            if dtype is not None and jnp.issubdtype(v.dtype, jnp.floating):
                v = v.astype(dtype)
            if device is not None:
                v = jax.device_put(v, device.jax_device()
                                   if hasattr(device, "jax_device") else device)
            p.value = v
        if dtype is not None:
            for l in self.sublayers(include_self=True):
                l._dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # --- state dict (reference: layers.py state_dict/set_state_dict) ---

    def state_dict(self, include_sublayers=True, keep_vars=False):
        out = OrderedDict()
        for name, p in self.named_parameters():
            out[name] = p if keep_vars else p.value
        for name, b in self.named_buffers():
            out[name] = b if keep_vars else b.value
        return out

    @RecordEvent("set_state_dict")
    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], set(state_dict.keys())
        for name, slot in list(self.named_parameters()) + \
                list(self.named_buffers()):
            if name in state_dict:
                slot.set_value(state_dict[name])
                unexpected.discard(name)
            else:
                missing.append(name)
        return missing, sorted(unexpected)

    load_dict = set_state_dict

    def full_name(self):
        return self._full_name

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{self.__class__.__name__}({extra}"] if extra else \
            [f"{self.__class__.__name__}("]
        for name, child in self._sub_layers.items():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        return "\n".join(lines) + "\n)" if len(lines) > 1 else \
            f"{self.__class__.__name__}({extra})"


# --- functional bridge -------------------------------------------------------

def _slots(layer: Layer):
    slots = OrderedDict()
    for name, p in layer.named_parameters():
        slots[name] = p
    for name, b in layer.named_buffers():
        slots[name] = b
    return slots


def _assign(slots, values: Optional[Dict[str, Any]]):
    for name, v in (values or {}).items():
        if name in slots:
            slots[name].value = v


@contextlib.contextmanager
def swap_state(layer: Layer, params: Dict[str, Any],
               buffers: Optional[Dict[str, Any]] = None):
    """Inside the block the layer's Parameter (and buffer) slots hold the
    given values, which may be tracers of a `jax.jit` / `jax.grad` trace;
    on the way out, by an exception too, every slot holds again what it
    held before. Names the layer does not have are passed over."""
    slots = _slots(layer)
    saved = {name: s.value for name, s in slots.items()}
    try:
        _assign(slots, params)
        _assign(slots, buffers)
        yield
    finally:
        for name, s in slots.items():
            s.value = saved[name]


def functional_call(layer: Layer, params: Dict[str, Any], *args,
                    buffers: Optional[Dict[str, Any]] = None,
                    **kwargs):
    """Run `layer` as a pure function of (params, buffers, inputs).

    Swaps the given values into the layer's Parameter slots
    (`swap_state`), runs forward, captures (possibly updated) buffer
    values, then restores the originals.

    Returns `(outputs, new_buffers)`.

    This is the TPU replacement for the reference's dygraph execution: the
    per-op C++ `Tracer` (`imperative/tracer.cc:144`) becomes a jax trace of
    the whole forward.
    """
    with swap_state(layer, params, buffers):
        out = layer(*args, **kwargs)
        new_buffers = {name: b.value for name, b in layer.named_buffers()}
    return out, new_buffers


def trainable_state(layer: Layer) -> Dict[str, Any]:
    """Params pytree to differentiate w.r.t. (excludes frozen + buffers).

    Plain dicts (insertion-ordered) — OrderedDict is a distinct pytree node
    type and would break structure equality across lax.cond branches."""
    return {n: p.value for n, p in layer.named_parameters() if p.trainable}


def frozen_state(layer: Layer) -> Dict[str, Any]:
    return {n: p.value for n, p in layer.named_parameters()
            if not p.trainable}


def buffer_state(layer: Layer) -> Dict[str, Any]:
    return {n: b.value for n, b in layer.named_buffers()}


def load_state(layer: Layer, params: Dict[str, Any],
               buffers: Optional[Dict[str, Any]] = None):
    """Write arrays back into the layer (post-step sync in training loops)."""
    slots = _slots(layer)
    _assign(slots, params)
    _assign(slots, buffers)


@contextlib.contextmanager
def no_init():
    yield
