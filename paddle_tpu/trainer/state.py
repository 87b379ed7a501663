"""The training state's layout: `state = (outer, stacked, opt_state)`.

`outer` holds the parameters outside the blocks by their names in the
model, `stacked` one leaf `[L, ...]` for each parameter of a block, and
`opt_state` is the optimizer's over ONE flat dict of both, the stacked
leaves under `"blocks." + name` (`flatten` / `unflatten`). A model of
several groups of alike blocks (`contract.block_groups`) has one such
leaf `[n, ...]` for each parameter of each group's block, under
`"g<i>." + name` (`group_keys`, `of_group`); a one-group model's leaves
carry no such prefix. What is decided here and nowhere else: that naming,
the residency of parameters and master weights, and the partition spec of
every leaf by ZeRO stage.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed.meta_parallel.sharding_optimizer import shard_spec_for
from ..distributed.meta_parallel.stacked_pipeline import stack_stage_params
from ..nn.layer import Layer
from ..profiler import RecordEvent
from .contract import block_groups, group_keys, split_parameters

_BLOCKS = "blocks."

Tree = Dict[str, Any]


def flatten(outer: Tree, stacked: Tree) -> Tree:
    """One dict over both: what the optimizer sees, and how its slots are
    keyed."""
    flat = dict(outer)
    flat.update({_BLOCKS + n: v for n, v in stacked.items()})
    return flat


def unflatten(flat: Tree) -> Tuple[Tree, Tree]:
    """`flatten`, undone: `(outer, stacked)` of any dict keyed as it keys
    (parameters, gradients, specs, the optimizer's slots)."""
    outer, stacked = {}, {}
    for n, v in flat.items():
        if n.startswith(_BLOCKS):
            stacked[n[len(_BLOCKS):]] = v
        else:
            outer[n] = v
    return outer, stacked


def of_group(stacked: Tree, key: str) -> Tree:
    """`{rel: leaf}` of the group whose leaves are called `key + rel`
    (`contract.group_keys`; the whole dict of a one-group model)."""
    if not key:
        return stacked
    return {n[len(key):]: v for n, v in stacked.items()
            if n.startswith(key)}


def stack_params(model: Layer, param_dtype) -> Tuple[Tree, Tree,
                                                     Optional[Tuple]]:
    """`(outer, stacked, masters)` from the model's trainable parameters.

    With `param_dtype` the floating leaves rest in it (O2-style: bf16
    halves parameter and gradient HBM; reference: pure-fp16 + master
    weights, `contrib/mixed_precision/decorator.py`) and `masters` is the
    pair as it was BEFORE the cast, which the float32 master weights must
    come from: fp32(bf16(w)) throws away the mantissa bits they exist to
    keep. Else `masters` is None. The eager model's copy of the blocks is
    given up: the arrays are deleted once stacked."""
    with RecordEvent("build_train_step.stack"):
        outer_ps, block_ps = split_parameters(model)
        outer = {n: p.value for n, p in outer_ps.items()}
        groups = block_groups(model)
        stacked, first = {}, 0
        for key, (_, count) in zip(group_keys(groups), groups):
            stacked.update(stack_stage_params(
                [{key + n: p.value for n, p in blk.items()}
                 for blk in block_ps[first:first + count]]))
            first += count
    masters = None
    if param_dtype is not None:
        masters = (outer, stacked)

        def cast(v):
            return (v.astype(param_dtype)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v)
        outer = {n: cast(v) for n, v in outer.items()}
        stacked = {n: cast(v) for n, v in stacked.items()}
    for blk in block_ps:
        for p in blk.values():
            p.value.delete()
    return outer, stacked, masters


def init_opt_state(optimizer, outer: Tree, stacked: Tree, masters):
    """The optimizer's state over the flat parameters, its master weights
    (where it keeps any) taken from `masters`."""
    with RecordEvent("build_train_step.opt_init"):
        opt_state = optimizer.init_state(flatten(outer, stacked))
    if masters is not None:
        source = flatten(*masters)
        for n, slots in opt_state["slots"].items():
            if "master" in slots:
                slots["master"] = source[n].astype(jnp.float32)
    return opt_state


class Layout:
    """Where each leaf of the state lies on the mesh.

    A parameter's own spec is the model's (`Parameter.sharding_spec`:
    tensor parallelism), a stacked leaf's that of the template block's
    parameter behind the layer dim, which the 'pipe' axis splits. A slot
    of the optimizer is further split over 'sharding' on its largest free
    dim (ZeRO), and from `zero_stage` 3 the PARAMETERS rest under their
    slots' specs too: XLA all-gathers each layer's weights where the layer
    scan uses them, parameter memory at rest = 1 / the axis' size.
    Reference bar: static ShardingOptimizer is ZeRO-2 + offload
    (`sharding_optimizer.py:87-1385`)."""

    def __init__(self, model: Layer, mesh, outer: Tree, stacked: Tree,
                 zero_stage: int):
        self.mesh = mesh
        self.shard_axis = mesh.shape.get("sharding", 1)
        lead = "pipe" if mesh.shape.get("pipe", 1) > 1 else None
        named = dict(model.named_parameters())
        groups = block_groups(model)
        self._own = flatten(
            {n: named[n].sharding_spec or P() for n in outer},
            {key + n: P(lead, *(p.sharding_spec or P()))
             for key, (template, _) in zip(group_keys(groups), groups)
             for n, p in template.named_parameters() if p.trainable})
        if zero_stage >= 3 and self.shard_axis > 1:
            specs = {n: self.slot_spec(n, v)
                     for n, v in flatten(outer, stacked).items()}
        else:
            specs = self._own
        self.outer, self.stacked = (
            {n: self.ns(s) for n, s in part.items()}
            for part in unflatten(specs))

    def ns(self, spec: P, memory_kind: Optional[str] = None
           ) -> NamedSharding:
        return NamedSharding(self.mesh, spec, memory_kind=memory_kind)

    def slot_spec(self, name: str, v) -> P:
        """Spec of a slot (or a gradient) of the flat parameter `name`
        with `v`'s shape."""
        if jnp.ndim(v) == 0:
            return P()
        own = self._own.get(name) or P()
        if self.shard_axis > 1:
            return shard_spec_for(v.shape, self.shard_axis, "sharding", own)
        return own

    def slot_shardings(self, slots: Tree,
                       memory_kind: Optional[str] = None) -> Tree:
        """Shardings of `{flat name: {slot: array or shape}}`."""
        return {n: {sn: self.ns(self.slot_spec(n, v), memory_kind)
                    for sn, v in per.items()} for n, per in slots.items()}

    def grad_shardings(self, outer: Tree, stacked: Tree) -> Tuple[Tree, Tree]:
        return unflatten({n: self.ns(self.slot_spec(n, v))
                          for n, v in flatten(outer, stacked).items()})

    def grads_as_slots(self, flat_g: Tree) -> Tree:
        """ZeRO-2: pin gradients to the optimizer-state layout, so that
        XLA reduce-scatters them over 'sharding' (not all-reduce) and
        runs the update sharded; fresh parameters all-gather on the way
        out. Reference bar: grad sharding in static ShardingOptimizer
        (`sharding_optimizer.py:87-1385`)."""
        if self.shard_axis == 1:
            return flat_g
        return {n: (jax.lax.with_sharding_constraint(
                        v, self.ns(self.slot_spec(n, v)))
                    if jnp.ndim(v) else v) for n, v in flat_g.items()}

    def batch(self, seq_axis: Optional[str]) -> Tuple[NamedSharding, ...]:
        """`(input_ids, labels)`: the 'sharding' axis IS data parallelism
        with sharded states, so the rows split over data x sharding
        jointly (reference: `sharding_optimizer.py:968 _build_groups`)."""
        rows = self.ns(P(("data", "sharding"), seq_axis))
        return rows, rows

    def state(self, opt_state: Tree) -> Tuple[Tree, Tree, Tree]:
        return (self.outer, self.stacked,
                {"step": self.ns(P()),
                 "slots": self.slot_shardings(opt_state["slots"])})

    def place(self, state, shardings):
        with RecordEvent("build_train_step.place"):
            return jax.device_put(state, shardings)
