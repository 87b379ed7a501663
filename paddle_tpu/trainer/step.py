"""`build_train_step`: a model, an optimizer and a mesh made into the one
compiled hybrid-parallel training step and its state."""
from __future__ import annotations

import itertools
import time
import warnings

import jax
import jax.numpy as jnp

from ..distributed.meta_parallel.mp_layers import _constrain
from ..distributed.meta_parallel.stacked_pipeline import one_f_one_b
from ..distributed.topology import mesh_scope
from ..framework.random import next_key, rng_guard
from ..nn.layer import Layer, swap_state
from ..profiler import (EMBED, GPT_TRAIN_STEP, LM_LOSS, Counters,
                        RecordEvent, record_step)
from .contract import block_groups, check_model
from .offload import build_offload_step
from .state import Layout, flatten, init_opt_state, stack_params, unflatten
from .trunk import Trunk, keyed, require_key, sequence_parallel


class _Forward:
    """Loss and gradients of `(outer, stacked)` on a batch: embedding ->
    trunk -> loss head, differentiated whole or by the 1F1B schedule."""

    def __init__(self, model: Layer, mesh, trunk: Trunk, sp_layout,
                 loss_chunks: int, one_f_one_b: bool):
        self.model = model
        self.mesh = mesh
        self.trunk = trunk
        self.sp_layout = sp_layout
        self.loss_chunks = loss_chunks
        self.one_f_one_b = one_f_one_b

    def embed(self, outer_p, input_ids, position_ids, base):
        """The embedded batch; `base` (dropout > 0) keys its masks."""
        key = None if base is None else jax.random.fold_in(base, 0)
        with swap_state(self.model, outer_p), keyed(key), \
                jax.named_scope(EMBED):
            x = self.model.embed(input_ids, position_ids)
            return _constrain(x, ("data", "sharding"), self.trunk.seq_axis,
                              None)

    def head(self, outer_p, hidden, labels):
        with swap_state(self.model, outer_p):
            return self.lm_loss(hidden, labels)

    @jax.named_scope(LM_LOSS)
    def lm_loss(self, hidden, labels):
        """ln_f → logits → CE. With loss_chunks > 1 the [B,S,V] fp32
        logits tensor never materializes: a checkpointed scan over
        sequence chunks computes logits+CE per chunk and the backward
        rematerializes each chunk's logits (the full tied-head logit
        tensor was the largest HBM round-trip in the step)."""
        model = self.model
        hidden = model.final_norm(hidden)
        if self.loss_chunks <= 1:
            return model.criterion(model.logits(hidden), labels)
        b, s, d = hidden.shape
        c = self.loss_chunks
        assert s % c == 0, f"seq {s} not divisible by loss_chunks {c}"
        hs = jnp.moveaxis(hidden.reshape(b, c, s // c, d), 1, 0)
        ls = jnp.moveaxis(labels.reshape(b, c, s // c), 1, 0)

        def chunk(tot, xs):
            h, lab = xs
            loss = model.criterion.ce(model.logits(h), lab)[..., 0]
            return tot + jnp.sum(loss.astype(jnp.float32)), None

        tot, _ = jax.lax.scan(jax.checkpoint(chunk),
                              jnp.zeros((), jnp.float32), (hs, ls))
        return tot / (b * s)

    def loss(self, params, batch):
        """`(loss, counters)`: what the trunk's blocks counted rides
        beside the loss."""
        outer_p, stacked_p = params
        input_ids, labels, pos_ids = self.sp_layout(*batch)
        # with dropout, one base key from the ambient rng_guard scope
        # keys the embedding's and the trunk's masks explicitly — the
        # SAME derivation the 1F1B path uses, so gpipe and 1f1b draw
        # identical masks (exact loss parity between schedules)
        base = next_key() if self.model.config.dropout > 0.0 else None
        x = self.embed(outer_p, input_ids, pos_ids, base)
        x, counters = self.trunk(stacked_p, x, None if base is None
                                 else jax.random.fold_in(base, 1))
        return self.head(outer_p, x, labels), counters

    def value_and_grad_1f1b(self, params, batch, rng):
        """Loss + grads via the 1F1B schedule (SectionWorker mode 1,
        `section_worker.cc:144-156`): embedding vjp outside the schedule,
        per-microbatch head (ln_f + logits + CE) inside it so backward
        starts S-1 ticks after forward. With rng set, dropout keys are
        threaded per (microbatch, stage) through the schedule."""
        outer_p, stacked_p = params
        trunk = self.trunk
        input_ids, labels, pos_ids = self.sp_layout(*batch)
        B, M = input_ids.shape[0], trunk.microbatches
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"
        base = None
        if rng is not None:
            with rng_guard(rng):
                base = next_key()   # same derivation as `loss`

        x, embed_vjp = jax.vjp(
            lambda op: self.embed(op, input_ids, pos_ids, base), outer_p)
        mb = x.reshape((M, B // M) + tuple(x.shape[1:]))
        labels_mb = labels.reshape((M, B // M) + tuple(labels.shape[1:]))

        def head_grad(op, y, lab):
            loss_v, vjp_fn = jax.vjp(
                lambda op_, y_: self.head(op_, y_, lab), op, y)
            # global loss = mean over microbatches → seed cotangent 1/M
            dop, dy = vjp_fn(jnp.asarray(1.0 / M, loss_v.dtype))
            return loss_v, dy, dop

        loss_sum, dx_stream, g_staged, g_outer_head = one_f_one_b(
            trunk.stage_blocks, trunk.to_staged(stacked_p), mb, head_grad,
            outer_p, labels_mb, num_stages=trunk.pp,
            rng_key=(jax.random.fold_in(base, 1) if base is not None
                     else None))
        dx = dx_stream.reshape((B,) + tuple(x.shape[1:]))
        (g_outer_embed,) = embed_vjp(dx)
        grads = (jax.tree.map(jnp.add, g_outer_head, g_outer_embed),
                 trunk.from_staged(g_staged))
        return (loss_sum / M, {}), grads

    def loss_and_grads(self, params, batch, rng):
        """`((loss, counters), grads)` of `params` on `batch`."""
        # all model code of the step (plain and offloaded) is traced in
        # here: it shards for THIS step's mesh, not for whatever mesh is
        # the global one by the time of the first call
        with mesh_scope(self.mesh):
            if self.one_f_one_b:
                return self.value_and_grad_1f1b(params, batch, rng)

            def loss(params_, batch_):
                # the traced key is scoped so that Dropout draws fresh
                # masks per step (an unscoped next_key() inside jit would
                # bake one constant mask into the compiled program)
                with keyed(rng):
                    return self.loss(params_, batch_)
            return jax.value_and_grad(loss, has_aux=True)(params, batch)


@RecordEvent("build_train_step")   # one frame more: warnings below say 3
def build_train_step(model: Layer, optimizer, mesh,
                     num_microbatches: int = 1, remat: bool = True,
                     donate: bool = True, pipeline_schedule: str = "gpipe",
                     remat_policy: str = "dots", loss_chunks: int = 0,
                     zero_stage: int = 2, sequence_zigzag: bool = True,
                     sequence_mode: str = "ring", offload: bool = False,
                     offload_memory_kind: str = "pinned_host",
                     param_dtype=None):
    """Build the one compiled hybrid-parallel training step.

    `model` is any decoder-only LM made of groups of alike blocks that
    gives the builder its pieces (the contract: `trainer/contract.py`).

    The eager model's copy of the blocks' weights is given up once they
    are stacked into the state (the arrays are deleted: 1.3 GiB at 345M
    parameters, 2.3 GB at the Keye decoder's 581M in blocks, that no step
    reads); `sync_params_to_model` brings the model back for save / eval.

    Parallelism comes entirely from the mesh axes: 'data' (DP — batch dim),
    'model' (TP — weight PartitionSpecs), 'pipe' (PP — stacked blocks via
    the CollectivePermute schedule), 'sharding' (ZeRO — optimizer-state
    specs), 'sequence' (SP — activations sharded on the seq dim with
    zigzag-balanced causal ring attention in every decoder layer;
    composes with dp×tp×zero AND pp — the schedules split the batch
    dim into microbatches, orthogonal to the sequence shard). This
    replaces the reference's whole meta-optimizer chain
    (`fleet_base.py:1288` → StrategyCompiler → program rewriting).
    Under a 'model' axis (and no 'pipe' axis) the layer scan applies each
    block to two streams of the batch (`Trunk`; the count is the static
    counter `tp.streams`).

    Returns (step_fn, state) where state = (outer, stacked_blocks,
    opt_state) and step_fn(state, batch) -> (state, loss);
    batch = (input_ids, labels) int32 [B, S]. When cfg.dropout > 0 the
    signature is step_fn(state, batch, rng_key) — pass a fresh key per
    step. Every call is kept in `profiler.step_records()` with what the
    blocks counted (`recorded`; the expert layers' rows and rounds).

    offload=True keeps the optimizer slots (Adam m/v, master weights) at
    rest in HOST memory (`memory_kind="pinned_host"`): the step streams
    them to device for the update and back out, trading PCIe bandwidth
    for ~2/3 of optimizer HBM (`trainer/offload.py`).
    """
    check_model(model)
    cfg = model.config
    pp = mesh.shape.get("pipe", 1)
    sp = mesh.shape.get("sequence", 1)
    groups = block_groups(model)
    assert sum(n for _, n in groups) % pp == 0, \
        "num_layers must divide pipe axis"
    if pipeline_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline_schedule {pipeline_schedule!r}")
    if pp > 1 and num_microbatches < pp:
        warnings.warn(
            f"num_microbatches={num_microbatches} < pipeline stages "
            f"{pp}: the schedule needs at least one microbatch per stage; "
            f"using {pp}", stacklevel=3)
    if sp > 1 and loss_chunks > 1:
        warnings.warn("loss_chunks disabled under sequence "
                      "parallelism (the chunk scan would re-slice the "
                      "sequence-sharded dim)", stacklevel=3)
        loss_chunks = 0
    if param_dtype is not None and \
            not getattr(optimizer, "_multi_precision", False):
        # pair a low-precision residency with multi_precision=True, so
        # that fp32 master weights live in the (host-offloadable) slots
        warnings.warn(
            "param_dtype set without optimizer multi_precision=True: "
            "no fp32 master weights — low-precision updates will "
            "accumulate rounding error", stacklevel=3)
    for template, _ in groups:
        if sp > 1 and not hasattr(type(template), "_sp_attention"):
            raise NotImplementedError(
                f"{type(template).__name__} has no sequence-parallel "
                f"attention")

    outer, stacked, masters = stack_params(model, param_dtype)

    # sequence parallelism composes with dp x tp x zero AND pp: the
    # pipeline schedules split the BATCH dim into microbatches while SP
    # shards the SEQUENCE dim — orthogonal. Ring attention is a shard_map
    # over only the 'sequence' axis, so it vmaps over the stacked stage
    # dim inside the schedules
    sp_attention, sp_layout = sequence_parallel(mesh, sequence_mode,
                                                sequence_zigzag)
    trunk = Trunk(groups, mesh, remat=remat, remat_policy=remat_policy,
                  num_microbatches=num_microbatches,
                  sp_attention=sp_attention)
    forward = _Forward(model, mesh, trunk, sp_layout, loss_chunks,
                       one_f_one_b=pipeline_schedule == "1f1b" and pp > 1)

    layout = Layout(model, mesh, outer, stacked, zero_stage)
    batch_sharding = layout.batch(trunk.seq_axis)
    if offload:
        if len(groups) > 1:
            raise NotImplementedError(
                f"offload=True over {len(groups)} groups of blocks: the "
                f"offloaded update streams chunks of ONE stack of blocks")
        return build_offload_step(
            optimizer=optimizer, outer=outer, stacked=stacked,
            masters=masters, layout=layout,
            memory_kind=offload_memory_kind,
            loss_and_grads=forward.loss_and_grads,
            batch_sharding=batch_sharding, dropout=cfg.dropout,
            donate=donate)

    def train_step(state, batch, rng=None):
        require_key(cfg.dropout, rng)
        outer_p, stacked_p, opt_state = state
        (loss, counters), grads = forward.loss_and_grads(
            (outer_p, stacked_p), batch, rng)
        new_flat, new_opt = optimizer.apply(
            flatten(outer_p, stacked_p),
            layout.grads_as_slots(flatten(*grads)), opt_state)
        # one array, one copy to the host; a model that counts nothing
        # returns nothing more than it did
        return (*unflatten(new_flat), new_opt), loss, \
            Counters.pack(counters) if counters else {}

    # the jitted function's name is the compiled module's ("jit_<name>"),
    # which is how a trace or a compile log tells the step program from
    # every other; without dropout it is called without a key
    train_step.__name__ = getattr(model, "step_name", GPT_TRAIN_STEP)
    state = (outer, stacked, init_opt_state(optimizer, outer, stacked,
                                            masters))
    shardings = layout.state(state[2])
    step = jax.jit(
        train_step,
        in_shardings=(shardings, batch_sharding)
        + ((None,) if cfg.dropout > 0.0 else ()),
        out_shardings=(shardings, None, None),
        donate_argnums=(0,) if donate else ())
    return recorded(step), layout.place(state, shardings)


def recorded(jitted):
    """`step(state, batch[, rng]) -> (state, loss)` around the jitted
    step, which also returns what its blocks counted: each call is kept
    in `profiler.step_records()` with its host dispatch, and lies on a
    trace's host plane as a `StepTraceAnnotation` of its number. Nothing
    here waits on the device. `lower` is the jitted step's."""
    calls = itertools.count()

    def step(state, batch, *rng):
        n = next(calls)
        with jax.profiler.StepTraceAnnotation(jitted.__name__, step_num=n):
            begin = time.perf_counter_ns()
            state, loss, counters = jitted(state, batch, *rng)
            end = time.perf_counter_ns()
        if not isinstance(loss, jax.core.Tracer):   # not inside a trace
            record_step(n, begin, end, counters)
        return state, loss
    step.lower = jitted.lower
    return step
