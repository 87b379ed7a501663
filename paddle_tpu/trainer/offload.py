"""The host-offloaded train step: optimizer slots at rest in host memory,
streamed through the device a chunk of blocks at a time.

The reference's sharding offload (`fleet/meta_optimizers/sharding/
offload_helper.py:1`) keeps Adam slots in host memory and streams them
through device memory parameter-group by parameter-group, by program
rewriting; here it is XLA host-memory shardings. A single-jit version of
that (slots device_put'd in one go) is useless: XLA counts the whole
optimizer state against peak HBM and an ERNIE-1.3B step OOMs exactly as
if there were no offload. So there are three compiled programs:

  1. grad phase — loss + grads (+ global-norm clip, + ZeRO grad layout),
     params resident, slots untouched;
  2. one chunk-update program, reused for every chunk of k decoder
     blocks: dynamic-slice the [L, ...] param/grad stacks at a TRACED
     offset (one compile for all chunks), update against that chunk's
     slots, write params back with dynamic-update-slice;
  3. outer update — embeddings/final-LN slots the same way.

Slots cross the host<->device boundary OUTSIDE the jits, as plain
transfers in the orchestrator: in-jit memory-space changes
(annotate_device_placement) break the SPMD partitioner on multi-device
meshes, and outside-jit copies dispatch async anyway, pipelining chunk
i+1's upload behind chunk i's compute. All COMPUTE stays in device memory
space, so the step runs on the CPU backend too — CI proves step parity
there.

Peak HBM = params + grads + up to ~TWO chunks of slots: the backpressure
sync waits on chunk ci-2, deliberately leaving two chunks' transfers in
flight to overlap copy with compute, and chunk sizing uses the
conservative UNSHARDED byte estimate — so budget ~2x
`_OFFLOAD_CHUNK_BYTES` of slot residency when capacity planning at
10B-class sizes. The largest trainable size is still bounded by
params+grads+activations — the offload promise. Slots at rest are tuples
of per-chunk arrays in host memory; they never exist stacked on device.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as onp
from jax.sharding import PartitionSpec as P

from ..profiler import CLIP, OPTIMIZER, RecordEvent
from .state import Layout, flatten, unflatten
from .trunk import require_key

# per-chunk optimizer-slot bytes allowed on device at once in the
# offloaded update (the streaming window, not a model-size limit)
_OFFLOAD_CHUNK_BYTES = 1 << 30


def _chunk_layers(stacked_slots, num_layers: int) -> int:
    """Blocks a chunk: the largest divisor of L whose slots fit the
    window, by the conservative (unsharded) byte estimate:
    `shard_spec_for` may leave a leaf replicated, so dividing by the
    'sharding' axis here could pick a chunk that many times over budget
    on some device."""
    per_layer = sum(int(onp.prod(v.shape[1:])) * v.dtype.itemsize
                    for slots in stacked_slots.values()
                    for v in slots.values())
    return max(d for d in range(1, num_layers + 1)
               if num_layers % d == 0
               and (d == 1 or d * per_layer <= _OFFLOAD_CHUNK_BYTES))


def _resting_slots(optimizer, params, masters, to_host, chunks=None):
    """The initial slots at rest on the host, built without an HBM detour,
    of the parameters `to_host` (`{name: {slot: host sharding}}`) names:
    `{name: {slot: array}}`, or with `chunks = (k, n_chunks)` `{slot:
    tuple of n_chunks arrays [k, ...]}`. `_init_slot` runs on the CPU
    backend so non-zero initial values (e.g. Adagrad's
    initial_accumulator_value) are honored exactly as in the resident
    path, without materializing [L, ...] on the TPU. The slot template is
    the RESIDENT (possibly cast) parameter, so that moment dtypes match
    the optimizer's own; master weights initialize FROM the pre-cast
    parameters (`masters`), not from zeros."""
    try:
        cpu0 = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu0 = None  # no CPU backend: chunk-sized device transient is fine

    def master(w):
        return onp.asarray(jax.device_get(w), onp.float32)

    out = {}
    for name, shardings in to_host.items():
        src, shape = params[name], tuple(params[name].shape)
        if chunks is not None:
            k, n_chunks = chunks
            shape = (k,) + shape[1:]
        with jax.default_device(cpu0):   # None leaves the default
            init = optimizer._init_slot(jnp.zeros(shape, src.dtype))
        out[name] = per = {}
        for sn, sharding in shardings.items():
            if chunks is None:
                per[sn] = jax.device_put(
                    master(masters[name]) if sn == "master"
                    else onp.asarray(init[sn]), sharding)
            elif sn == "master":
                per[sn] = tuple(
                    jax.device_put(master(masters[name][c * k:(c + 1) * k]),
                                   sharding) for c in range(n_chunks))
            else:
                # one transfer, shared by every chunk slot: jax arrays
                # are immutable and each slot is wholesale-replaced by
                # the first step's update
                per[sn] = (jax.device_put(onp.asarray(init[sn]),
                                          sharding),) * n_chunks
    return out


def _programs(optimizer, layout, loss_and_grads, batch_sharding, grads_sh,
              outer_dev, chunk_dev, k, dropout, donate):
    """The three compiled programs `(grad, chunk, outer)`, named as
    `build_train_step`'s step is, one name a program."""
    scalar = layout.ns(P())
    g_outer_sh, g_stacked_sh = grads_sh

    def gpt_offload_grad(params_pair, opt_step, batch, rng=None):
        # the blocks' counters are not handed out by this path
        (loss, _), grads = loss_and_grads(params_pair, batch, rng)
        flat_g = layout.grads_as_slots(flatten(*grads))
        if optimizer._grad_clip is not None:
            # global-norm clip sees the FULL grad set here; the per-chunk
            # updates below must not clip again
            with jax.named_scope(OPTIMIZER), jax.named_scope(CLIP):
                flat_g = optimizer._grad_clip(flat_g)
        return (loss, *unflatten(flat_g), opt_step + 1)

    grad_jit = jax.jit(
        gpt_offload_grad,
        in_shardings=((layout.outer, layout.stacked), scalar, batch_sharding)
        + ((None,) if dropout > 0.0 else ()),
        out_shardings=(None, g_outer_sh, g_stacked_sh, scalar))

    @jax.named_scope(OPTIMIZER)
    def gpt_offload_chunk(stacked_p, g_stacked, slots_chunk, new_step,
                          start):
        def chunk(tree):
            return flatten({}, {
                n: jax.lax.dynamic_slice_in_dim(tree[n], start, k, 0)
                for n in stacked_p})
        new_p_c, new_slots = optimizer.apply_named(
            chunk(stacked_p), chunk(g_stacked), slots_chunk, new_step)
        new_p = unflatten(new_p_c)[1]
        new_stacked = {
            n: jax.lax.dynamic_update_slice_in_dim(
                v, new_p[n].astype(v.dtype), start, 0)
            for n, v in stacked_p.items()}
        return new_stacked, new_slots

    chunk_jit = jax.jit(
        gpt_offload_chunk,
        in_shardings=(layout.stacked, g_stacked_sh, chunk_dev, scalar, None),
        out_shardings=(layout.stacked, chunk_dev),
        donate_argnums=(0, 2) if donate else ())

    @jax.named_scope(OPTIMIZER)
    def gpt_offload_outer(outer_p, g_outer, outer_slots, new_step):
        return optimizer.apply_named(outer_p, g_outer, outer_slots,
                                     new_step)

    outer_jit = jax.jit(
        gpt_offload_outer,
        in_shardings=(layout.outer, g_outer_sh, outer_dev, scalar),
        out_shardings=(layout.outer, outer_dev),
        donate_argnums=(0, 2) if donate else ())
    return grad_jit, chunk_jit, outer_jit


def build_offload_step(*, optimizer, outer, stacked, masters,
                       layout: Layout, memory_kind: str, loss_and_grads,
                       batch_sharding, dropout: float, donate: bool):
    """`(step_fn, state)` as `build_train_step` returns them, the slots
    of `state[2]` in `memory_kind` memory: 'pinned_host' is the
    reference-offload default (DMA-able); some workers cap the pinned pool
    well below their RAM — 'unpinned_host' rests slots in ordinary host
    memory instead (staged transfers)."""
    if layout.mesh.shape.get("pipe", 1) != 1:
        raise ValueError(
            "offload=True requires pipe=1: the chunked update slices the "
            "block stack, which the pipeline axis partitions")
    if not optimizer._elementwise_update:
        raise ValueError(
            f"offload=True cannot stream {type(optimizer).__name__}: its "
            "update is a whole-tensor norm (trust ratio), so per-chunk "
            "streaming would change the numerics. Use an elementwise "
            "rule (Adam/AdamW/Momentum/...) or offload=False")
    params = flatten(outer, stacked)
    masters = params if masters is None else flatten(*masters)
    # structure only: materializing the full [L, ...] slot zeros on device
    # before moving them to host would transiently cost the whole
    # optimizer HBM the offload exists to avoid
    outer_struct, stacked_struct = unflatten(
        jax.eval_shape(optimizer.init_state, params)["slots"])
    num_layers = jax.tree.leaves(stacked)[0].shape[0]
    k = _chunk_layers(stacked_struct, num_layers)
    n_chunks = num_layers // k
    starts = [onp.int32(ci * k) for ci in range(n_chunks)]
    # a stacked parameter's slots are held, moved and updated a chunk
    # [k, ...] at a time
    chunk_struct = flatten({}, {
        n: {sn: jax.ShapeDtypeStruct((k,) + tuple(sd.shape[1:]), sd.dtype)
            for sn, sd in slots.items()}
        for n, slots in stacked_struct.items()})
    outer_dev = layout.slot_shardings(outer_struct)
    chunk_dev = layout.slot_shardings(chunk_struct)
    outer_host = layout.slot_shardings(outer_struct, memory_kind=memory_kind)
    chunk_host = layout.slot_shardings(chunk_struct, memory_kind=memory_kind)
    slots_host = _resting_slots(optimizer, params, masters, outer_host)
    slots_host.update(_resting_slots(optimizer, params, masters, chunk_host,
                                     (k, n_chunks)))
    grad_jit, chunk_jit, outer_jit = _programs(
        optimizer, layout, loss_and_grads, batch_sharding,
        layout.grad_shardings(outer, stacked), outer_dev, chunk_dev, k,
        dropout, donate)

    def step_fn(state, batch, rng=None):
        # the spans are the host's side of each phase (dispatch, the
        # transfers it starts and the wait for room); the device's side
        # is the three named programs in a trace
        require_key(dropout, rng)
        outer_p, stacked_p, opt_state = state
        with RecordEvent("offload.grad"):
            loss, g_outer, g_stacked, new_step = grad_jit(
                (outer_p, stacked_p), opt_state["step"], batch,
                *((rng,) if dropout > 0.0 else ()))
        slots = opt_state["slots"]
        new_stacked = stacked_p
        chunk_results = []
        for ci in range(n_chunks):
            with RecordEvent("offload.chunk"):
                if ci >= 2:
                    # backpressure: dispatch is async, so without this the
                    # Python loop uploads EVERY chunk's slots before the
                    # first update frees any — the whole optimizer state
                    # lands on device at once and the step OOMs exactly
                    # like the unchunked version. Once chunk ci-2's new
                    # slots are back at rest on the host, its update has
                    # executed and its donated device buffers are free, so
                    # at most ~2 chunks of slots are in flight on device
                    jax.block_until_ready(chunk_results[ci - 2])
                slots_chunk = jax.device_put(
                    {n: {sn: slots[n][sn][ci] for sn in slots[n]}
                     for n in chunk_dev}, chunk_dev)
                new_stacked, new_chunk = chunk_jit(
                    new_stacked, g_stacked, slots_chunk, new_step, starts[ci])
                # back to host residence; dropping the device ref frees the
                # chunk's HBM before chunk ci+2 uploads
                chunk_results.append(jax.device_put(new_chunk, chunk_host))
        with RecordEvent("offload.outer"):
            new_outer, new_outer_slots = outer_jit(
                outer_p, g_outer,
                jax.device_put({n: slots[n] for n in outer_dev}, outer_dev),
                new_step)
            new_slots = jax.device_put(new_outer_slots, outer_host)
        new_slots.update({
            n: {sn: tuple(cr[n][sn] for cr in chunk_results)
                for sn in slots[n]} for n in chunk_dev})
        return (new_outer, new_stacked,
                {"step": new_step, "slots": new_slots}), loss

    state = (jax.device_put(outer, layout.outer),
             jax.device_put(stacked, layout.stacked),
             {"step": jax.device_put(jnp.zeros((), jnp.int32),
                                     layout.ns(P())),
              "slots": slots_host})
    return step_fn, state
