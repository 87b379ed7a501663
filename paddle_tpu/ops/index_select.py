"""Learned key selection for sparse attention (DeepSeek-V3.2's lightning
indexer): index scores and the exact top-k over them.

    I[t, s] = sum_j w[t, j] * relu(q[t, j] . k[s])        (j: index heads)
    keep[t, s] = s <= t and I[t, s] is among the `topk` largest of row t
                 (ties to the smaller s; every s <= t where t < topk)

On a TPU both halves are Pallas kernels, and `topk_selection` is one call
of each; elsewhere (the CPU, lengths that are no multiple of 128) XLA
computes the same selection, and is the oracle of the kernels' tests.

`index_scores`: a [tile, tile] block of I is the sum of `heads` small
products, each through a relu and a per-row weight, which XLA would
compute as a [heads, t, s] tensor in HBM (at 16 heads and 8K tokens 17 GB
a layer) before adding it up. In the kernel the partial products never
leave VMEM. Blocks above the diagonal are not computed. `index_scores_xla`
is the same as plain XLA.

The top-k is exact and sort-free. The k-th largest score of a row is the
largest threshold that `topk` keys still reach, found from the top bit
down over the scores' bit patterns (an order-preserving map of float32 to
integers, signed zeros one value); ties at the threshold are cut by
position, and all of it is compares and row sums. A sort of every 8K row
costs more than the attention it feeds (`lax.top_k` and a sort take three
times the digit search: PERF.md, PR 29); an approximate top-k would not
be the model.

`index_topk` is that search as one kernel over the whole [b, s, s]: a
block of 256 query rows and all their keys stays in VMEM while 32 passes
count it, one bit a pass, each pass straight-line code over the columns
up to the block's last row; the scores are read from HBM once and the
int8 selection, the causal mask of the rows under `topk` included, is
written once. `select_topk` is the search in XLA, 4 bits a pass (8 passes
of 15 counted thresholds, each pass one fusion over HBM), and
`_select_blocks` runs it over the rows 1024 at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..profiler import INDEX_SCORES, INDEX_TOPK
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


_LOW = -(1 << 31)    # the ordered pattern of a key a query may not see
_BLOCK_BYTES = 8 << 20      # of float32 scores a grid step of `index_topk`


def _topk_kernel(x_ref, o_ref, u_ref, *, topk, t0, digit, panels, walks):
    """One grid step: `R` query rows from `r0` against all `s` keys, the
    keys in `panels` panels of `C` columns of which only those with a key
    the rows may see are walked (`walks`: every such count the grid has,
    each a branch whose counting passes are straight-line code)."""
    R, s = u_ref.shape
    C = s // panels
    i32 = jnp.int32
    r0 = t0 + pl.program_id(1) * R
    query = r0 + jax.lax.broadcasted_iota(i32, (R, 1), 0)
    few = query < topk      # no more keys than room: every key is kept

    def panel(k):           # (columns, their keys [1, C]) of panel k
        return (pl.ds(pl.multiple_of(k * C, 128), C),
                k * C + jax.lax.broadcasted_iota(i32, (1, C), 1))

    def walk(lo, hi, body):
        jax.lax.fori_loop(lo, hi, lambda k, _: body(k), None)

    def keep(k, kept=None):
        """Write panel k: `kept(columns, keys)` where a query has more keys
        than room, every key it may see where not."""
        cols, key = panel(k)
        out = key <= query
        if kept is not None:
            out = (few & out) | (~few & kept(cols, key))
        o_ref[0, :, cols] = out.astype(jnp.int8)

    @pl.when(r0 + R <= topk)
    def _():
        walk(0, panels, keep)

    def kth_largest(n):
        """The k-th largest pattern of each row over the first n panels:
        the largest threshold that `topk` keys still reach, `digit` bits
        a pass from the top, each count a row sum over VMEM; and `reach`,
        the count at that threshold."""
        def one_pass(i, found):
            base, reach = found
            kth = base
            shift = (32 - digit) - digit * i
            for j in range(1, 1 << digit):
                c = base + (i32(j) << shift)
                count = jnp.sum((u_ref[:, :n * C] >= c).astype(i32), -1,
                                keepdims=True)
                kth = jnp.where(count >= topk, c, kth)
                reach = jnp.where(count >= topk, count, reach)
            return kth, reach
        return jax.lax.fori_loop(
            0, 32 // digit, one_pass,
            (jnp.full((R, 1), _LOW, i32), jnp.full((R, 1), s, i32)))

    def cut_ties(n, kth):
        """The rare path, as loops: of the keys at the threshold the first
        `room` by position, `select_topk`'s rule."""
        def count(hit):
            def body(k, acc):
                cols, key = panel(k)
                return acc + jnp.sum(hit(u_ref[:, cols], key).astype(i32),
                                     -1, keepdims=True)
            return jax.lax.fori_loop(0, n, body, jnp.zeros((R, 1), i32))

        room = topk - count(lambda u, _: u > kth)
        bits = max(s, 2).bit_length()

        def one_pass(i, p):     # the largest p with <= room ties before it
            c = p + (i32(1) << (bits - 1 - i))
            before = count(lambda u, key: (u == kth) & (key < c))
            return jnp.where(before <= room, c, p)
        p = jax.lax.fori_loop(0, bits, one_pass, jnp.zeros((R, 1), i32))

        def kept(cols, key):
            u = u_ref[:, cols]
            return (u > kth) | ((u == kth) & (key < p))
        walk(0, n, lambda k: keep(k, kept))

    def search():
        n = (r0 + R + C - 1) // C       # panels with a key the rows may see

        # 1. float32 -> int32 of the same order (`_ordered` with the top
        # bit flipped back), keys above the diagonal at the lowest value
        def to_ordered(k):
            cols, key = panel(k)
            x = x_ref[0, :, cols]
            x = jnp.where(x == 0, 0.0, x)
            i = jax.lax.bitcast_convert_type(x, i32)
            i = jnp.where(i < 0, i ^ i32(0x7FFFFFFF), i)
            u_ref[:, cols] = jnp.where(key <= query, i, i32(_LOW))
        walk(0, n, to_ordered)

        # 2. the search, straight-line code for each count of panels
        kth, reach = jax.lax.switch(
            sum((n > w).astype(i32) for w in walks),
            [functools.partial(kth_largest, w) for w in walks])

        # 3. more keys at the threshold than room, in any row that has a
        # threshold at all: only then are ties cut by position
        crowded = jnp.sum(((reach > topk) & ~few).astype(i32))

        @pl.when(crowded == 0)
        def _():
            walk(0, n, lambda k: keep(
                k, lambda cols, _: u_ref[:, cols] >= kth))

        @pl.when(crowded > 0)
        def _():
            cut_ties(n, kth)

        def blank(k):
            o_ref[0, :, panel(k)[0]] = jnp.zeros((R, C), jnp.int8)
        walk(n, panels, blank)

    if walks:       # else no block has more keys than room
        pl.when(r0 + R > topk)(search)


def index_topk(scores, topk: int, t0: int = 0, *, block: int | None = None,
               digit: int = 1):
    """`select_topk` as one Pallas TPU kernel: scores float32 [b, rows, s]
    of the queries t0 .. t0 + rows -> the same int8 [b, rows, s], bit for
    bit. A block of query rows stays in VMEM across every pass of the
    threshold search; the scores are read once. `block` (query rows a grid
    step; the largest of 256 .. 32 that fits) and `digit` (bits found a
    pass) are what `tools/index_topk_step0.py` measured, not options."""
    b, rows, s = scores.shape
    R = block or next((r for r in (256, 128, 64, 32) if rows % r == 0
                       and r * s * 4 <= _BLOCK_BYTES), None)
    if R is None or rows % R or s % 128 or t0 + rows > s:
        raise ValueError(f"index_topk takes rows % 32 == 0 of s % 128 == 0 "
                         f"keys, got {scores.shape} at {t0}")
    panels = next(p for p in range(8, 0, -1) if (s // 128) % p == 0)
    C = s // panels
    ends = [t0 + R * (i + 1) for i in range(rows // R)]
    # blocks with no more keys than room read no score: fetch nothing new
    # until the first block that searches
    first = next((i for i, e in enumerate(ends) if e > topk), 0)
    spec = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(
            _topk_kernel, topk=topk, t0=t0, digit=digit, panels=panels,
            walks=sorted({-(-e // C) for e in ends if e > topk})),
        grid=(b, rows // R),
        in_specs=[spec((1, R, s), lambda r, i: (r, jnp.maximum(i, first), 0))],
        out_specs=spec((1, R, s), lambda r, i: (r, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, rows, s), jnp.int8),
        scratch_shapes=[pltpu.VMEM((R, s), jnp.int32)],
        interpret=_fa._interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the scores and the selection twice each (the pipeline's two
            # buffers), the patterns once, and room for the compiler's own
            vmem_limit_bytes=R * s * 14 + (16 << 20)),
        name=INDEX_TOPK,
    )(scores)


def _select_blocks(scores, topk: int, block: int = 1024):
    """`select_topk` over scores [b, s, s], `block` query rows at a time
    against the keys up to the block's last row."""
    s = scores.shape[-1]
    block = min(block, s)
    out = []
    for t0 in range(0, s, block):
        rows = min(block, s - t0)
        keys = t0 + rows
        sel = select_topk(scores[:, t0:t0 + rows, :keys], topk, t0)
        out.append(jnp.pad(sel, ((0, 0), (0, 0), (0, s - keys))))
    return jnp.concatenate(out, 1)


def topk_selection(q, k, w, topk: int, block: int = 1024):
    """The selection of every query of [b, s]: int8 [b, s, s]. On a TPU
    two kernels, the scores and one exact top-k over all of them;
    elsewhere (and at lengths the kernels do not take) XLA, the top-k
    over `block` query rows at a time. Carries no gradient."""
    # a selection is not a value: nothing here is differentiated
    q, k, w = (jax.lax.stop_gradient(x) for x in (q, k, w))
    s = q.shape[1]
    on_tpu = jax.default_backend() == "tpu" or _fa._interpret()
    if on_tpu and s % 128 == 0:
        return index_topk(index_scores(q, k, w), topk)
    return _select_blocks(index_scores_xla(q, k, w), topk, block)
