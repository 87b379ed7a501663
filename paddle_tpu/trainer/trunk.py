"""All L blocks applied to the embedded batch: each group of alike blocks
as its template block under a scan over the group's stacked leaves, one
group after the other, over two streams of the batch where a 'model' axis
has sums to hide, or (one group only) under a pipeline schedule where the
mesh has a 'pipe' axis; and the sequence-parallel layout of a batch.
What the blocks count (`profiler.count`) comes out of the scans stacked
by block, outside a pipeline schedule.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.meta_parallel.mp_layers import TP_SUM, _constrain
from ..distributed.meta_parallel.sequence_parallel import (
    make_sp_attention, zigzag_permutation)
from ..distributed.meta_parallel.stacked_pipeline import pipelined_apply
from ..framework.random import rng_guard
from ..nn.layer import Layer, functional_call
from ..profiler import DECODER, counting, stats
from .contract import group_keys
from .state import of_group

# remat policies that keep one named residual beside the dots:
# "dots_attn" the attention output (+16 MB a layer at GPT-345M buys
# skipping the flash-forward replay in the backward), "dots_sel" a learned
# key selection (int8 [b, s, s] a layer: the indexer and its exact top-k
# are not replayed)
_SAVED_BESIDE_DOTS = {"dots_attn": "attn_out", "dots_sel": "attn_selection"}


def checkpoint_policy(remat_policy: str):
    """The `jax.checkpoint` policy of a block, None for "full" (everything
    is rematerialized).

    "dots" is selective remat: keep the weight-matmul outputs (no batch
    dims in the dot), recompute elementwise + attention (whose einsums
    carry batch dims): full per-block checkpoint alone cost ~25% of
    achievable MFU. A row-parallel product summed by an explicit exchange
    (`mp_layers._row_product`) is a weight matmul's output too, but sits
    where the policy cannot see a dot: saved by its name, else the
    backward would run product and exchange again."""
    policies = jax.checkpoint_policies
    if remat_policy == "full":
        return None
    dots = policies.save_from_both_policies(
        policies.dots_with_no_batch_dims_saveable,
        policies.save_only_these_names(TP_SUM))
    if remat_policy == "dots":
        return dots
    if remat_policy in _SAVED_BESIDE_DOTS:
        return policies.save_from_both_policies(
            dots, policies.save_only_these_names(
                _SAVED_BESIDE_DOTS[remat_policy]))
    raise ValueError(f"unknown remat_policy {remat_policy!r}")


def keyed(key):
    """`rng_guard(key)` where there is a key to scope."""
    return contextlib.nullcontext() if key is None else rng_guard(key)


def require_key(dropout: float, rng):
    if dropout > 0.0 and rng is None:
        # without a key the dropout draws would fall back to the
        # process-global RNG: one constant mask baked into the compiled
        # program + a tracer leaked into eager state
        raise ValueError(
            "cfg.dropout > 0 requires step(state, batch, rng_key) — "
            "pass a fresh jax.random key every step")


def sequence_parallel(mesh, mode: str, zigzag: bool
                      ) -> Tuple[Optional[Callable], Callable]:
    """`(attention, layout)` of a mesh: the blocks' attention over the
    'sequence' axis (None without one) and `layout(input_ids, labels) ->
    (input_ids, labels, position_ids)`, which zigzag-reorders the tokens
    so that each rank gets an equal share of causal-mask work; the
    position ids carry the original positions (the loss is a
    position-wise mean, invariant to the reorder)."""
    sp = mesh.shape.get("sequence", 1)
    if sp > 1 and mode == "ulysses":
        # all-to-all resharding: every chip sees the FULL sequence for
        # its head slice, so the contiguous layout is already
        # causal-balanced — no zigzag
        zigzag = False
    attention = None if sp == 1 else make_sp_attention(
        mesh, mode=mode, causal=True, zigzag=zigzag, jit=False)

    def layout(input_ids, labels):
        if sp == 1 or not zigzag:
            return input_ids, labels, None
        zperm = jnp.asarray(
            zigzag_permutation(input_ids.shape[1], sp), jnp.int32)
        ids_z = jnp.take(input_ids, zperm, axis=1)
        labels_z = jnp.take(labels, zperm, axis=1)
        return ids_z, labels_z, jnp.broadcast_to(zperm[None, :], ids_z.shape)
    return attention, layout


class Trunk:
    """`trunk(stacked_p, x, key=None) -> (h, counters)`: the blocks, by
    the mesh's axes, and what they counted, `{<group key><name>: [blocks]}`
    (`contract.group_keys`; summed over the streams; empty where no block
    counts, and under a pipeline schedule, which hands out none).

    `groups` is the model's `[(template, blocks), ...]`: every group is a
    scan of its own over its own leaves, under the one remat policy, the
    one `decoder` scope and the same streams. A 'pipe' axis takes a
    one-group model only: stages would have to end where groups do, and
    no schedule here cuts them so.

    Under a 'model' axis (and no 'pipe' axis, whose microbatches already
    are such streams) each block is applied to the two halves of a chip's
    rows as two streams of one scan body, so that one half's row-parallel
    sums cross the link while the other half computes."""

    def __init__(self, groups: List[Tuple[Layer, int]], mesh, *,
                 remat: bool, remat_policy: str, num_microbatches: int,
                 sp_attention: Optional[Callable]):
        axis = mesh.shape
        self.groups = groups
        self.pp = axis.get("pipe", 1)
        if self.pp > 1 and len(groups) > 1:
            raise NotImplementedError(
                f"a 'pipe' axis of {self.pp} over {len(groups)} groups of "
                f"blocks ({'+'.join(str(n) for _, n in groups)}): the "
                f"pipeline schedules stack one group's stages")
        self.layers_per_stage = sum(n for _, n in groups) // self.pp
        self.microbatches = max(num_microbatches, self.pp)
        self.seq_axis = "sequence" if axis.get("sequence", 1) > 1 else None
        self._tp = axis.get("model", 1) > 1
        self._row_groups = axis.get("data", 1) * axis.get("sharding", 1)
        self._sp_attention = sp_attention
        policy = checkpoint_policy(remat_policy)
        self._blocks = [
            (jax.checkpoint(apply, policy=policy) if remat else apply)
            for apply in (functools.partial(self.block_apply, template)
                          for template, _ in groups)]

    def block_apply(self, template, bparams, x, key=None):
        # _sp_attention is scoped to THIS trace (set/restore, not a
        # permanent template mutation): the model stays usable eagerly
        # and under other meshes after the step is built. The guard sits
        # INSIDE the checkpointed function: it pushes and pops the scoped
        # key within one trace, so no inner-trace key tracer survives in
        # the thread-local scope (leak otherwise)
        template._sp_attention = self._sp_attention
        try:
            with keyed(key), counting() as counted:
                out, _ = functional_call(template, bparams, x)
        finally:
            template._sp_attention = None
        return out, counted

    def stage_blocks(self, stage_p, h, key=None, group: int = 0):
        """`scan_blocks` without the counters: a pipeline stage (shared by
        the gpipe and 1f1b schedules)."""
        return self.scan_blocks(stage_p, h, key, group)[0]

    @jax.named_scope(DECODER)
    def scan_blocks(self, stage_p, h, key=None, group: int = 0):
        """One group's blocks, or one pipeline stage of them = scan over
        its L/pp blocks, and their counters stacked by block. `key`
        (when dropout > 0) is split into one sub-key per block, and a
        block's into one per stream, so masks decorrelate across layers —
        a closure draw would bake a single mask into the scanned body. `h` is the batch, or a tuple of
        streams of it (`streams`): the body then applies the block to
        each, one after the other in the program and independent in its
        data, so that one stream's row-parallel sum is on the link while
        the other computes."""
        keys = None
        if key is not None:
            keys = jax.random.split(key, jax.tree.leaves(stage_p)[0].shape[0])
        block = self._blocks[group]

        def body(carry, xs):
            bp, k = xs
            if not isinstance(carry, tuple):
                return block(bp, carry, k)
            ks = (None,) * len(carry) if k is None else \
                jax.random.split(k, len(carry))
            outs, counted = zip(*(block(bp, c, ki)
                                  for c, ki in zip(carry, ks)))
            return outs, jax.tree.map(lambda *v: sum(v), *counted)
        return jax.lax.scan(body, h, (stage_p, keys))

    def streams(self, x):
        """x [B, ...] as the streams the blocks are applied to: the two
        halves of each chip's rows (split WITHIN a data x sharding group,
        so no row changes chip) where the mesh has a 'model' axis, whose
        sums a stream's partner can hide, and a chip holds an even number
        of rows; else x itself."""
        groups = self._row_groups
        rows = x.shape[0] // groups
        n = 2 if (self._tp and x.shape[0] % groups == 0
                  and rows % 2 == 0) else 1
        stats.static("tp.streams", n)
        if n == 1:
            return x
        parts = x.reshape((groups, n, rows // n) + x.shape[1:])
        return tuple(
            _constrain(parts[:, i].reshape((-1,) + x.shape[1:]),
                       ("data", "sharding"), self.seq_axis, None)
            for i in range(n))

    def join(self, h):
        """The batch back in its order from `streams`' streams."""
        if not isinstance(h, tuple):
            return h
        parts = jnp.stack([c.reshape((self._row_groups, -1) + c.shape[1:])
                           for c in h], axis=1)
        return _constrain(parts.reshape((-1,) + h[0].shape[1:]),
                          ("data", "sharding"), self.seq_axis, None)

    def to_staged(self, stacked_p):
        """Leaves [L, ...] -> [pp, L/pp, ...]."""
        return jax.tree.map(
            lambda a: a.reshape((self.pp, self.layers_per_stage)
                                + a.shape[1:]), stacked_p)

    def from_staged(self, staged):
        """`to_staged`, undone."""
        return jax.tree.map(
            lambda a: a.reshape((self.pp * self.layers_per_stage,)
                                + a.shape[2:]), staged)

    def all_groups(self, stacked_p, h, key=None):
        """Every group's blocks in order, each a scan over its own
        leaves; with dropout a key a group. Returns `(h, counters)`."""
        stats.static("trunk.groups", len(self.groups))
        for g, (_, blocks) in enumerate(self.groups):
            stats.static(f"trunk.groups.g{g}", blocks)
        if len(self.groups) == 1:
            return self.scan_blocks(stacked_p, h, key)
        keys = [None] * len(self.groups) if key is None else \
            jax.random.split(key, len(self.groups))
        counters = {}
        for g, name in enumerate(group_keys(self.groups)):
            h, counted = self.scan_blocks(of_group(stacked_p, name), h,
                                          keys[g], g)
            counters.update({name + k: v for k, v in counted.items()})
        return h, counters

    def __call__(self, stacked_p, x, key=None):
        if self.pp == 1:
            h, counters = self.all_groups(stacked_p, self.streams(x), key)
            return self.join(h), counters
        return pipelined_apply(self.stage_blocks, self.to_staged(stacked_p),
                               x, num_stages=self.pp,
                               num_microbatches=self.microbatches,
                               remat=False, rng_key=key), {}
