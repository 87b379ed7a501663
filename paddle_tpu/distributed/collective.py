"""Collective communication API.

Mirrors `python/paddle/distributed/collective.py:166-1455` (all_reduce,
broadcast, all_gather, reduce, scatter, alltoall, send/recv, barrier,
new_group) whose reference backends are the `operators/collective/c_*` NCCL
kernels keyed by `ring_id` (`c_allreduce_op.h:253-322`).

TPU-native semantics: a "group" is a named mesh axis. Inside a traced
`shard_map` region the ops lower to XLA collectives over ICI
(psum/all_gather/ppermute/all_to_all); in eager single-process code they
operate on the global (replicated) view, so reductions over a size-1 or
replicated axis are identity — matching how the reference's ops behave with
ring size 1. No stream-sync ops exist: XLA schedules communication.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .env import get_rank, get_world_size

# op codes (parity with paddle.distributed.ReduceOp)
class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A named-axis handle (replaces NCCL ring_id)."""

    def __init__(self, axis_name: str, ranks=None):
        self.axis_name = axis_name
        self.ranks = ranks

    @property
    def nranks(self):
        # lazy: get_world_size() touches jax.process_count(), which
        # initializes a backend — must NOT happen at import time (a
        # module-level Group would claim the chip on every import)
        return len(self.ranks) if self.ranks else get_world_size()

    def __repr__(self):
        return f"Group(axis={self.axis_name!r})"


_DEFAULT_GROUP = Group("data")


def new_group(ranks=None, backend=None, axis_name: str = "data") -> Group:
    """Reference: collective.py:206 — creates an extra NCCL ring. Here: a
    handle onto a mesh axis (create the axis via topology.build_mesh)."""
    return Group(axis_name, ranks)


def _axis(group) -> Optional[str]:
    if group is None:
        return "data"
    if isinstance(group, Group):
        return group.axis_name
    return str(group)


def _in_trace(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _eager_multiproc_guard(op_name: str):
    """Eager collectives in a multi-process job are a silent semantic
    divergence (VERDICT r5 item 7): the reference's eager ops REALLY
    communicate (`collective.py:413` NCCL rings), while the TPU-native
    eager path only sees this process's replicated view — returning the
    input unchanged would silently skip the cross-rank reduction. Raise
    with guidance instead. Single-process (world 1) keeps the identity
    semantics: there is nothing to communicate."""
    world = get_world_size()
    if world > 1:
        raise RuntimeError(
            f"paddle_tpu.distributed.{op_name}: called OUTSIDE a traced "
            f"computation in a {world}-process job. Eager collectives "
            f"do not communicate across processes here (the op would "
            f"silently return its input). Run the op inside the traced "
            f"step so it lowers to an XLA collective over the mesh "
            f"axis (see MIGRATION.md 'Collectives'), or exchange host "
            f"data explicitly via the PS KV store "
            f"(paddle_tpu.distributed.ps).")


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               use_calc_stream=True):
    """Reference: c_allreduce_{sum,max,min,prod}."""
    axis = _axis(group)
    if _in_trace(tensor):
        try:
            if op == ReduceOp.SUM:
                return lax.psum(tensor, axis)
            if op == ReduceOp.MAX:
                return lax.pmax(tensor, axis)
            if op == ReduceOp.MIN:
                return lax.pmin(tensor, axis)
            if op == ReduceOp.AVG:
                return lax.pmean(tensor, axis)
            if op == ReduceOp.PROD:
                return jnp.exp(lax.psum(jnp.log(tensor), axis))
        except NameError:
            return tensor  # axis not mapped here → group of size 1
    _eager_multiproc_guard("all_reduce")
    return tensor  # eager global view: already reduced/replicated


def all_gather(tensor_list, tensor=None, group=None, sync_op=True,
               use_calc_stream=True, axis: int = 0):
    """Reference: c_allgather. Functional form returns the gathered array;
    the paddle list-out form appends to `tensor_list`."""
    if isinstance(tensor_list, list):
        t = tensor
        out = _all_gather_impl(t, group, axis)
        n = out.shape[axis] // t.shape[axis] if t.shape else 1
        tensor_list.extend(jnp.split(out, n, axis=axis))
        return tensor_list
    return _all_gather_impl(tensor_list, group, axis)


def _all_gather_impl(tensor, group, axis):
    ax = _axis(group)
    if _in_trace(tensor):
        try:
            return lax.all_gather(tensor, ax, axis=axis, tiled=True)
        except NameError:
            return tensor
    _eager_multiproc_guard("all_gather")
    return tensor


def reduce_scatter(tensor, op=ReduceOp.SUM, group=None, axis: int = 0):
    """Reference: c_reducescatter."""
    ax = _axis(group)
    if _in_trace(tensor):
        try:
            return lax.psum_scatter(tensor, ax, scatter_dimension=axis,
                                    tiled=True)
        except NameError:
            return tensor
    _eager_multiproc_guard("reduce_scatter")
    return tensor


def broadcast(tensor, src=0, group=None, sync_op=True,
              use_calc_stream=True):
    """Reference: c_broadcast. Under SPMD every device computes the same
    program, so broadcast is realized by selecting src's shard."""
    ax = _axis(group)
    if _in_trace(tensor):
        try:
            idx = lax.axis_index(ax)
            full = lax.all_gather(tensor, ax)
            return full[src]
        except NameError:
            return tensor
    _eager_multiproc_guard("broadcast")
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True,
           use_calc_stream=True):
    """Reference: c_reduce_*. SPMD form: psum everywhere (result only
    meaningful on dst, same contract as NCCL reduce)."""
    return all_reduce(tensor, op=op, group=group)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True,
            use_calc_stream=True):
    ax = _axis(group)
    if tensor_list is not None and not _in_trace(tensor):
        return tensor_list[get_rank()]
    if _in_trace(tensor):
        try:
            idx = lax.axis_index(ax)
            n = lax.axis_size(ax)
            chunk = tensor.shape[0] // n
            return lax.dynamic_slice_in_dim(tensor, idx * chunk, chunk)
        except NameError:
            return tensor
    _eager_multiproc_guard("scatter")
    return tensor


def alltoall(in_tensor_list, out_tensor_list=None, group=None,
             sync_op=True, use_calc_stream=True):
    """Reference: alltoall_op. Traced form over a mesh axis uses
    lax.all_to_all; this is the building block for Ulysses sequence
    parallelism (see distributed/sequence_parallel.py)."""
    ax = _axis(group)
    if isinstance(in_tensor_list, (list, tuple)):
        stacked = jnp.stack(list(in_tensor_list), axis=0)
    else:
        stacked = in_tensor_list
    if _in_trace(stacked):
        try:
            out = lax.all_to_all(stacked, ax, split_axis=0, concat_axis=0,
                                 tiled=False)
            if out_tensor_list is not None:
                out_tensor_list.extend(list(out))
                return out_tensor_list
            return out
        except NameError:
            pass   # traced, axis unmapped: group of size 1 — identity
    else:
        _eager_multiproc_guard("alltoall")
    if out_tensor_list is not None:
        out_tensor_list.extend(list(stacked))
        return out_tensor_list
    return stacked


def all_to_all_single(tensor, group=None, split_axis=0, concat_axis=0):
    ax = _axis(group)
    if _in_trace(tensor):
        try:
            return lax.all_to_all(tensor, ax, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=True)
        except NameError:
            return tensor
    _eager_multiproc_guard("all_to_all_single")
    return tensor


def send(tensor, dst=0, group=None, sync_op=True, use_calc_stream=True):
    """Reference: send_v2. SPMD equivalent is a collective_permute — use
    `p2p_push` with an explicit perm inside shard_map."""
    if not _in_trace(tensor):
        _eager_multiproc_guard("send")
    return tensor


def recv(tensor, src=0, group=None, sync_op=True, use_calc_stream=True):
    if not _in_trace(tensor):
        _eager_multiproc_guard("recv")
    return tensor


def p2p_push(tensor, perm, group=None):
    """collective_permute over the group axis (reference: send_v2/recv_v2
    pairs in pipeline parallelism). `perm`: list of (src, dst)."""
    ax = _axis(group)
    if _in_trace(tensor):
        try:
            return lax.ppermute(tensor, ax, perm)
        except NameError:
            return tensor
    _eager_multiproc_guard("p2p_push")
    return tensor


def barrier(group=None):
    """Reference: barrier_op. Host-level sync across processes."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils
        multihost_utils.sync_global_devices("paddle_tpu_barrier")


def get_group(id=0):
    return _DEFAULT_GROUP


def wait(tensor, group=None, use_calc_stream=True):
    """Reference: c_wait_comm / c_sync_comm_stream — XLA schedules comm, so
    this only blocks the host until `tensor` is ready."""
    if hasattr(tensor, "block_until_ready"):
        tensor.block_until_ready()
    return tensor


def split(x, num_partitions, axis=0):
    return jnp.split(x, num_partitions, axis=axis)
