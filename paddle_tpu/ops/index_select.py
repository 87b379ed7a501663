"""Learned key selection for sparse attention (DeepSeek-V3.2's lightning
indexer): index scores and the exact top-k over them.

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        (j: index heads)
    keep[t, s] = s <= t and I[t, s] is among the `topk` largest of row t
                 (ties to the smaller s; every s <= t where t < topk)

`index_scores` is a Pallas TPU kernel: a [tile, tile] block of I is the
sum of `heads` small products, each through a relu and a per-row weight,
which XLA would compute as a [heads, t, s] tensor in HBM (at 16 heads and
8K tokens 17 GB a layer) before adding it up. In the kernel the partial
products never leave VMEM. Blocks above the diagonal are not computed.

`select_topk` is exact and sort-free: the k-th largest score of a row is
found digit by digit over the scores' bit patterns (an order-preserving
map of float32 to uint32; a loop of 8 passes of 15 counted thresholds each), ties
at the threshold are cut by position the same way, and all of it is
compares and row sums, which XLA fuses into passes over the scores. A
sort of every 8K row costs more than the attention it feeds; an
approximate top-k would not be the model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..profiler import INDEX_SCORES
from . import flash_attention as _fa

_TILE = 512     # the published q_chunk_size / kv_chunk_size
_DIGIT = 4      # bits found per counting pass
_NT = (((1,), (1,)), ((), ()))


def _scores_kernel(q_ref, k_ref, w_ref, o_ref, *, heads):
    @pl.when(pl.program_id(2) <= pl.program_id(1))
    def _():
        k = k_ref[0]
        w = w_ref[0]
        acc = jnp.zeros(o_ref.shape[1:], jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[0, j], k, _NT,
                                    preferred_element_type=jnp.float32)
            acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
        o_ref[0] = acc

    @pl.when(pl.program_id(2) > pl.program_id(1))
    def _():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], jnp.float32)


def index_scores(q, k, w):
    """q [b, s, heads, d], k [b, s, d], w [b, s, heads] -> I [b, s, s]
    float32; entries above the diagonal (s > t) are 0 and mean nothing.
    Products take the operands as they come (bf16 on the training path)
    and accumulate in float32."""
    b, s, heads, d = q.shape
    # float32 operands take twice the room in VMEM: half the tile
    tile = _TILE if q.dtype.itemsize <= 2 else _TILE // 2
    tile = tile if s % tile == 0 else 128
    if s % tile:
        raise ValueError(f"index_scores needs seq % 128 == 0, got {s}")
    n = s // tile
    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_scores_kernel, heads=heads),
        grid=(b, n, n),
        in_specs=[
            spec((1, heads, tile, d), lambda r, i, j: (r, 0, i, 0)),
            # above the diagonal nothing is computed: fetch nothing new
            spec((1, tile, d), lambda r, i, j: (r, jnp.minimum(j, i), 0)),
            spec((1, tile, heads), lambda r, i, j: (r, i, 0))],
        out_specs=spec((1, tile, tile), lambda r, i, j: (r, i, j)),
        out_shape=jax.ShapeDtypeStruct((b, s, s), jnp.float32),
        interpret=_fa._interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        name=INDEX_SCORES,
    )(jnp.swapaxes(q, 1, 2), k, w.astype(jnp.float32))


def index_scores_xla(q, k, w):
    """The same scores as plain XLA (off the TPU)."""
    s = jnp.einsum("btjd,bsd->btjs", q, k,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("btj,btjs->bts", w.astype(jnp.float32),
                      jnp.maximum(s, 0.0))


def _ordered(x):
    """float32 -> uint32 with the same order (-0.0 == +0.0 kept equal)."""
    x = jnp.where(x == 0, 0.0, x)
    i = jax.lax.bitcast_convert_type(x, jnp.int32)
    i = jnp.where(i < 0, i ^ jnp.int32(0x7FFFFFFF), i)
    return jax.lax.bitcast_convert_type(i, jnp.uint32) ^ jnp.uint32(1 << 31)


def _climb(count, good, bits: int, shape):
    """The largest uint32 value v (below 2**bits) with good(count(v)),
    where good(count(.)) holds at 0 and never again once it has failed:
    `_DIGIT` bits a pass, each pass one fused count of 2**_DIGIT - 1
    thresholds. `count(v)`: [..., n] thresholds -> [..., n] counts. The
    passes are one loop, traced and lowered once, not a body a pass."""
    digits = jnp.arange(1, 1 << _DIGIT, dtype=jnp.uint32)
    passes = -(-bits // _DIGIT)

    def one(i, v):
        shift = ((passes - 1 - i) * _DIGIT).astype(jnp.uint32)
        ok = good(count(v[..., None] + (digits << shift)))
        return v + (jnp.sum(ok, -1).astype(jnp.uint32) << shift)
    return jax.lax.fori_loop(0, passes, one, jnp.zeros(shape, jnp.uint32))


def select_topk(scores, topk: int, t0: int = 0):
    """scores [..., rows, s] of the queries t0 .. t0 + rows -> int8
    [..., rows, s], 1 at the `topk` keys s <= t with the largest score,
    ties to the smaller s; at every s <= t where t < topk."""
    rows, s = scores.shape[-2:]
    t = t0 + jnp.arange(rows)[:, None]
    pos = jnp.arange(s, dtype=jnp.uint32)
    seen = pos[None, :] <= t
    if t0 + rows <= topk:
        return jnp.broadcast_to(seen, scores.shape).astype(jnp.int8)
    u = jnp.where(seen, _ordered(scores.astype(jnp.float32)), 0)
    lead = u.shape[:-1]

    def at_least(c):        # keys with u >= c, for [..., n] thresholds
        return jnp.sum(u[..., None, :] >= c[..., None], -1)

    # the k-th largest: the largest threshold that `topk` keys still reach
    kth = _climb(at_least, lambda n: n >= topk, 32, lead)[..., None]
    above = u > kth
    ties = (u == kth) & seen
    room = topk - jnp.sum(above, -1)

    def cut_ties(_):
        # the first `room` ties by position: the largest p with no more
        # than `room` ties before it
        def before(c):
            return jnp.sum(ties[..., None, :] & (pos < c[..., None]), -1)
        p = _climb(before, lambda n: n <= room[..., None],
                   max(s, 2).bit_length(), lead)
        return ties & (pos < p[..., None])

    # scores that differ: exactly one key sits at the threshold
    ties = jax.lax.cond(jnp.all(jnp.sum(ties, -1) <= room),
                        lambda _: ties, cut_ties, None)
    return ((above | ties) & seen).astype(jnp.int8)


def topk_selection(q, k, w, topk: int, block: int = 1024):
    """The selection of every query of [b, s]: int8 [b, s, s]. Scores by
    the kernel on a TPU, by XLA elsewhere; the top-k over `block` query
    rows at a time against the keys up to the block's last row. Carries
    no gradient."""
    # a selection is not a value: nothing here is differentiated
    q, k, w = (jax.lax.stop_gradient(x) for x in (q, k, w))
    b, s = q.shape[:2]
    on_tpu = jax.default_backend() == "tpu" or _fa._interpret()
    scores = (index_scores if on_tpu and s % 128 == 0
              else index_scores_xla)(q, k, w)
    block = min(block, s)
    out = []
    for t0 in range(0, s, block):
        rows = min(block, s - t0)
        keys = t0 + rows
        sel = select_topk(scores[:, t0:t0 + rows, :keys], topk, t0)
        out.append(jnp.pad(sel, ((0, 0), (0, 0), (0, s - keys))))
    return jnp.concatenate(out, 1)
