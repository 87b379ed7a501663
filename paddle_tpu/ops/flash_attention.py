"""Flash attention — Pallas TPU kernel (training-capable, custom VJP).

Replaces the reference's inference-only fused attention CUDA kernels
(`operators/fused/multihead_matmul_op.cu`,
`operators/math/bert_encoder_functor.cu`) with a fused kernel that works in
both directions: the S×S score matrix lives only chunk-by-chunk in VMEM, so
long sequences never materialize O(S²) in HBM.

Layout contract: [batch, seq, heads, head_dim] (paddle 2.x attention
layout); internally [b·h, s, d]. All kernels (fwd, dq, dk/dv, and the fused
backward further down) run on a grid (bh, out_block, reduce_block) and
share one skeleton (`_walk`). The
resident block is the whole sequence while an operand of one head fits
`_RESIDENT_BYTES` (2K tokens in bf16), so at 1K tokens the grid is
(bh, 1, 1): K and V (dk/dv: Q and dO) are fetched once a head, nothing is
carried between grid steps, and the running max / sum / accumulator are
values written once. Longer sequences keep the outer axes with smaller
blocks and carry that state between grid steps in fp32 scratch, so VMEM
use is O(block·d) regardless of S and one kernel body serves 1K and 64K
tokens. Inside a block the plane is walked in chunks of up to 1024 rows,
one or two a side, as straight-line code, and a chunk's body is cut into
groups of out-side rows (`_plan`); under a causal mask the walk stops
(dk/dv: starts) at the chunk the diagonal crosses (`_chunk_spans`), only
that chunk's body builds a mask, and each of its groups stops at its own
diagonal (`_groups`): computed / needed scores at 1K tokens is 1.25
(`score_work`), where 512 x 512 tiles computed 1.50.

What sets the time, measured on v5e (PERF.md, PR 28): not the vector work
per score element (dropping the mask, the scale or the `exp` from the
old 512-tile kernels moved nothing) but how much independent work one
basic block holds. A grid step or a `fori_loop` step drains the pipeline:
128-row chunks in a loop took twice the time of 512-row chunks although
they compute a quarter less, and the whole head as straight-line code
takes half the time of 512-tiles on a (bh, 2, 2) grid. Around that:
dk/dv computes the scores transposed (k qᵀ) so that pᵀ·dO and dsᵀ·q are
plain matmuls, with lse and delta delivered along lanes (XLA lays lse out
so, a small fusion a call outside the kernels). Matmul operands stay bf16
(fp32 operands run the MXU at 1/8 rate); accumulation and softmax
statistics are fp32. Row statistics (logsumexp/delta) ride an 8-lane
broadcast between forward and dq because TPU block layouts need a
lane-divisible trailing dim.

A sliding window (`window=`: query p reads keys p - window + 1 .. p) is a
second diagonal of the causal family, `window` positions below the first.
The kernels (`flash_win_fwd`, `flash_win_bwd_dq`, `flash_win_bwd_dkv`)
keep the causal walk and add that edge at every level: the grid's reduce
axis holds only the blocks the band meets (`_band_blocks` + 1, a static
offset between the out block and the reduce block at each step, `_specs`
`band`), so a block below the band costs no DMA and no grid step; inside a
block `_chunk_spans` leaves out the chunks below it, `_groups` starts each
group's reduce rows at its lower edge, and `_keep_tri` masks the groups
that edge crosses. At 8192 tokens, 1024-row blocks and a window of 2048 a
query block meets 3 of the 8 key blocks (`score_work`).

Latent attention (further down) and attention under a per-pair selection
have two kernels, not three: their backward is the dk/dv walk alone
(`_fused_bwd`), and dq comes out of it, because a dq kernel of its own
computes the scores, their mask and selection, their `exp` and dO vᵀ of
every (q, k) pair a second time (640 of the pair's 1408 lanes of MXU work
at 192-wide scores, 384 of 896 at 128). The head's whole dq waits in a
float32 VMEM accumulator while the walk passes the head's k blocks. The
causal and the k-side-masked family keep a dq kernel and a dk/dv kernel.

Which kernels walk their scores transposed (sᵀ = k qᵀ, k along sublanes,
q along lanes), and where the running statistics lie. Every dk/dv walk
(`_dkv_kernel`, `_fused_bwd_kernel`) is transposed, for the plain matmuls
above; it reads lse and delta as rows along lanes and reduces nothing.
The forwards and dq keep q along sublanes. There a row's running max and
sum are reductions across lanes, and `m`, `l`, `corr` were columns
[rows, 1]: a vreg for every 8 numbers with one live lane, and a lane
broadcast a vreg row wherever one meets the scores or the accumulator
(`s - m`, `acc * corr`). Measured on the latent forward at 8k tokens
(PERF.md, PR 36, ms a call): 13.80 with columns; 11.08 with the
reductions replaced by a constant and 11.05 with no statistics at all;
11.54 with the statistics REPLICATED over a vreg's 128 lanes, the same
numbers, and 11.18 with lse written as rows besides; 13.14 with the
scores transposed and the statistics as rows along lanes (10.50 with no
statistics: the transposed walk is the faster one, but its statistics
cost 2.4 ms however they are held or folded). The columns were the cost,
not the reductions across lanes. So `_mla_fwd_kernel` keeps q along sublanes and holds its
statistics [rows, 128], alike in every lane (`_STAT_LANES`,
`_over_lanes`), and writes lse once a q block as the rows the backward
reads. `_fwd_kernel` and `_dq_kernel` (causal, masked, selected) still
carry columns: at 1k tokens their time goes with basic blocks, not vector
work, and the selected forward's cell cannot show a gain yet (ROADMAP L1).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..profiler import (FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD,
                        FLASH_MLA_BWD_DKV, FLASH_MLA_FWD, FLASH_SEL_BWD_DKV,
                        FLASH_SEL_FWD, FLASH_WIN_BWD_DKV, FLASH_WIN_BWD_DQ,
                        FLASH_WIN_FWD)

# Row statistics (lse/delta) ride an 8-lane broadcast: TPU block layouts
# need the last two dims (sublane, lane) to divide (8, 128) or equal the
# array dims — a trailing dim of 8 equals itself, keeping the stat arrays
# at 8x logical size instead of 128x.
LANE = 8
NEG_INF = -1e30

# The most one resident operand block may take of VMEM, counted as it lies
# there (rows x the head dim padded to 128 lanes x the operand's bytes).
# The causal and masked family's dq kernel holds five such blocks and two
# columns of statistics, all double-buffered: 5 MiB + 4 MiB of the 16 MiB
# a kernel may use on a v5e unasked, beside a group's scores. The fused
# backward of the selected and the latent family holds a head's whole dq
# besides, and asks for its VMEM by its shapes (`_fused_bwd_vmem`).
_RESIDENT_BYTES = 512 * 1024


class Plan(NamedTuple):
    """How the kernels walk the (q, k) plane of one head."""
    block: int      # rows of q and of k resident in VMEM per grid step
    chunk: int      # rows of q and of k per chunk of the walk inside
    sub: int        # out-side rows per group of a chunk


def _plan(s: int, d: int, dtype, causal: bool) -> Plan:
    """Resident block, chunk and group from what the call can see.

    The block is the whole sequence while an operand of one head fits
    `_RESIDENT_BYTES`, else the largest 128-multiple divisor of `s` that
    fits half of it. The chunk is as large as divides the block, up to
    `_CHUNK`, and a block is one or two chunks a side, walked as
    straight-line code, which the scheduler overlaps where a loop step
    drains the pipeline (PERF.md, PR 28: 128-row chunks in a `fori_loop`
    took twice the time of 512-row chunks over a smaller area); a block
    that would take more chunks (s = 1152) gives way to blocks of one
    chunk on the grid. A chunk's body is cut into groups of out-side rows,
    so that what it holds at a time is a group's scores, [sub, chunk] fp32
    within `_GROUP_SCORES`. Under a causal mask the groups also win the
    area, since each stops at its own diagonal (`_groups`), and are
    `_CAUSAL_SUB` rows at most."""
    if s % 128 != 0 or s < 128:
        raise ValueError(f"flash_attention needs seq % 128 == 0, got {s}")
    row_bytes = -(-d // 128) * 128 * jnp.dtype(dtype).itemsize
    rows = _RESIDENT_BYTES // row_bytes
    if s > rows:    # several blocks: their carried state needs room too
        rows //= 2
    block = _divisor(s, rows)
    chunk = _divisor(block, _CHUNK)
    if block > 2 * chunk:
        block = chunk
    sub = _divisor(chunk, _GROUP_SCORES // chunk)
    return Plan(block, chunk, _divisor(sub, _CAUSAL_SUB) if causal else sub)


def _divisor(n: int, most: int) -> int:
    """The largest multiple of 128 that divides `n` and is at most `most`
    (128 where there is none)."""
    return max(b for b in range(128, max(128, min(n, most)) + 1, 128)
               if n % b == 0)


# Measured at [128, 1024, 64] causal and [256, 512, 64] masked on a v5e
# (PERF.md, PR 28).
_CHUNK = 1024
_GROUP_SCORES = 256 * 1024
_CAUSAL_SUB = 256


def _chunk_spans(o0, c, n, causal, before, window=None, shift=0):
    """The reduce-side chunks (of `c` rows, `n` in all) that the out-side
    rows [o0, o0 + c) meet, in rising order: (j, delta), `delta` the q
    chunk's first position less the k chunk's, None without a causal mask
    (every chunk, nothing masked). `before`: the reduce side is k and the
    out side q (forward, dq); else the reduce side is q (dk/dv). `shift`:
    the q block's first position less the k block's, so that `delta` is
    in positions of the sequence. Under the causal mask a chunk wholly above the
    diagonal is left out, and under a `window` one wholly below the band's
    lower edge, `window` positions back (query p reads keys p - window + 1
    .. p). The kernels' walk and `score_work` both come from here."""
    if not causal:
        return [(j, None) for j in range(n)]
    out = []
    for j in range(n):
        delta = shift + o0 - j * c if before else shift + j * c - o0
        if delta <= -c or (window is not None and delta - c + 1 >= window):
            continue
        out.append((j, delta))
    return out


def _diffs(rows, cols, off, before):
    """(least, most) of q - k over a group's [rows, cols] scores whose
    mask offset is `off` (`_keep_tri`)."""
    if before:
        return off - cols + 1, off + rows - 1
    return off - rows + 1, off + cols - 1


def _groups(c, sub, before, delta, window=None):
    """A chunk pair (c x c, `_chunk_spans`' delta) cut into groups of
    `sub` out-side rows: (out_lo, red_lo, red_hi, off) — out rows [out_lo,
    out_lo + sub) against the reduce rows [red_lo, red_hi), and the offset
    `_keep_tri` masks them by, None where the group keeps every pair. A
    group needs nothing beyond its own diagonal and, under a window,
    nothing before its band's lower edge; its reduce rows are whole groups
    of `sub`, and a group that needs none is left out. Static, so a
    chunk's body is straight-line code."""
    if delta is None:
        return [(g, 0, c, None) for g in range(0, c, sub)]
    out = []
    for g in range(0, c, sub):
        if before:      # q rows g.., k rows with q - window < k <= q
            lo = 0 if window is None else delta + g - window + 1
            hi = delta + g + sub
        else:           # k rows g.., q rows with k <= q < k + window
            lo = g - delta
            hi = c if window is None else g + sub - 1 - delta + window
        lo = min(max(lo // sub * sub, 0), c)
        hi = min(max(-(-hi // sub) * sub, 0), c)
        if lo >= hi:
            continue
        off = delta + g - lo if before else delta + lo - g
        least, most = _diffs(sub, hi - lo, off, before)
        masked = least < 0 or (window is not None and most >= window)
        out.append((g, lo, hi, off if masked else None))
    return out


class ScoreWork(NamedTuple):
    chunks: int         # chunk bodies one head runs
    masked_chunks: int  # of them, bodies that apply a mask
    ratio: float        # computed / needed score elements
    needed: int         # score elements one head needs


def score_work(s: int, causal: bool, d: int = 64, dtype=jnp.bfloat16,
               transposed: bool = False, window=None) -> ScoreWork:
    """What one head costs under `_plan`'s schedule, counted from the same
    spans and groups the kernels are built from. `transposed`: the dk/dv
    walk (k chunks over q chunks) instead of the forward's and dq's.
    `window`: the causal band of that many keys a query (`flash_attention`
    `window=`). A windowed kernel's grid visits the k blocks the band
    meets (`_band_blocks`) and, inside them, these chunks."""
    _, c, sub = _plan(s, d, dtype, causal)
    before = not transposed
    chunks = masked = computed = 0
    for o0 in range(0, s, c):
        for _, delta in _chunk_spans(o0, c, s // c, causal, before, window):
            groups = _groups(c, sub, before, delta, window)
            chunks += 1
            masked += any(off is not None for *_, off in groups)
            computed += sum(sub * (hi - lo) for _, lo, hi, _ in groups)
    if not causal:
        needed = s * s
    elif window is None or window >= s:
        needed = s * (s + 1) // 2
    else:
        needed = window * (window + 1) // 2 + (s - window) * window
    return ScoreWork(chunks, masked, computed / needed, needed)


def _band_blocks(window: int, block: int, n: int) -> int:
    """How many k blocks before its own a q block's band reaches: the
    first query of a block reads back to `window` - 1 positions."""
    return min(n - 1, -(-(window - 1) // block))


def window_kblocks(s: int, d: int, dtype, window: int) -> int:
    """The key blocks one query block's grid steps visit under a window
    (the windowed kernels' reduce axis): its own and the `_band_blocks`
    before it, of `_plan`'s resident blocks. 3 of 8 at 8192 tokens of
    head dim 128 in bf16 and a window of 2048."""
    block = _plan(s, d, dtype, True).block
    return _band_blocks(window, block, s // block) + 1


def _interpret() -> bool:
    """Whether `pallas_call` runs the kernels in the Pallas interpreter.

    Never in the library: these are TPU programs, and a backend that
    cannot lower them raises. The CPU tests that check the kernels'
    arithmetic off-chip ask for the interpreter by patching this
    function (tests/test_flash_attention.py)."""
    return False


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))

_NT = (((1,), (1,)), ((), ()))      # a bᵀ: contract the last dim of both
_NN = (((1,), (0,)), ((), ()))      # a b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _rows(ref, start, size):
    """Rows [start, start + size) of a [1, rows, d] block."""
    return ref[0, pl.ds(start, size), :]


def _keep_tri(rows, cols, off, before, window=None):
    """The mask of a group (`_groups`): keep where 0 <= q - k, and q - k <
    `window` under a window. `before`: q along rows, k along columns, q -
    k = row + off - column; else k along rows and q along columns, q - k =
    column + off - row. Only an edge that crosses the group is built."""
    a = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
    b = jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1)
    q, k = (a, b) if before else (b, a)
    least, most = _diffs(rows, cols, off, before)
    keep = None
    if least < 0:
        keep = q + off >= k if before or off else q >= k
    if window is not None and most >= window:
        band = k > q + (off - window)
        keep = band if keep is None else keep & band
    return keep


def _walk(plan, n, causal, before, out_id, red_id, init, scratch, prep,
          piece, finalize, window=None):
    """The skeleton the three kernels share. The resident out block is cut
    into chunks; each takes its carry (from `scratch` where the reduce
    side has more than one block, else `init`: (fill, width) per value),
    meets the resident reduce chunks `_chunk_spans` names in rising
    order, group by group (`_groups`), through `piece(ctx, j, g, lo, hi,
    off, rows)` -> the group's new rows of the carry (`off`: the mask's
    offset, None for none), and hands the carry to `finalize(ctx, r,
    carry)` after its last reduce block. `ctx = prep(r)` is what a chunk
    of out rows needs once. Of several reduce blocks under a causal mask
    only the one on the diagonal is walked causally and the ones clear of
    it in full, so the spans inside a block are static either way. Under
    a `window` the grid's reduce axis counts the blocks the band meets
    (`_band_blocks` + 1, `_specs`), and each step's offset between the
    out block and the reduce block is static too: the forward and dq meet
    k blocks out_id - m .. out_id, dk/dv q blocks out_id .. out_id + m,
    and a step whose block lies outside the sequence computes nothing."""
    block, c, sub = plan
    if n > 1:
        @pl.when(red_id == 0)
        def _init():
            for ref, (fill, _) in zip(scratch, init):
                ref[:] = jnp.full_like(ref, fill)

    def meet(ctx, j, delta, carry):
        kept = {g: rest for g, *rest in _groups(c, sub, before, delta,
                                                window)}
        parts = []
        for g in range(0, c, sub):
            rows = tuple(x[g:g + sub] for x in carry)
            parts.append(piece(ctx, j, g, *kept[g], rows) if g in kept
                         else rows)
        return tuple(jnp.concatenate(xs, axis=0) for xs in zip(*parts))

    def out_chunk(r, shift, last):
        ctx = prep(r)
        spans = _chunk_spans(r, c, block // c, shift is not None, before,
                             window, shift or 0)
        if n > 1:
            carry = tuple(ref[pl.ds(r, c), :] for ref in scratch)
        else:
            carry = tuple(jnp.full((c, w), fill, jnp.float32)
                          for fill, w in init)
        for j, delta in spans:
            carry = meet(ctx, j, delta, carry)
        if n > 1:
            for ref, x in zip(scratch, carry):
                ref[pl.ds(r, c), :] = x
        if isinstance(last, bool):
            if last:
                finalize(ctx, r, carry)
        else:
            pl.when(last)(lambda: finalize(ctx, r, carry))

    def walk(shift, last):
        """`shift`: the q block's first position less the k block's, None
        without a causal mask."""
        for r in range(0, block, c):
            out_chunk(r, shift, last)

    if not causal:
        walk(None, True if n == 1 else red_id == n - 1)
    elif n == 1:
        walk(0, True)
    elif window is None:
        # forward and dq end a q block at its diagonal k block; dk/dv
        # begins a k block there and ends at the last q block. A block
        # clear of the diagonal is walked as one a block away.
        pl.when(red_id == out_id)(
            lambda: walk(0, True if before else red_id == n - 1))
        pl.when(red_id < out_id if before else red_id > out_id)(
            lambda: walk(block, False if before else red_id == n - 1))
    else:
        m = _band_blocks(window, block, n)
        for j in range(m + 1):
            if before:      # k block out_id - m + j; the last is out_id
                real = red_id == j
                if j < m:
                    real = real & (out_id >= m - j)
                shift, last = (m - j) * block, j == m
            else:           # q block out_id + j, up to the last block
                real = (red_id == j) & (out_id + j <= n - 1)
                shift = j * block
                last = True if j == m else out_id + j == n - 1
            pl.when(real)(functools.partial(walk, shift, last))


# ---------------------------------------------------------------- forward

def _selected(sel_ref, row0, rows, col0, cols):
    """Where the selection operand keeps a score: rows [row0, row0 + rows)
    against columns [col0, col0 + cols) of the resident [1, out, red]
    block of 0/1 bytes."""
    return sel_ref[0, pl.ds(row0, rows),
                   pl.ds(col0, cols)].astype(jnp.int32) > 0


def _fwd_kernel(q_ref, k_ref, v_ref, *refs, causal, scale, plan, n, masked,
                selected=False, window=None):
    refs = list(refs)
    mask_ref = refs.pop(0) if masked else None
    sel_ref = refs.pop(0) if selected else None
    o_ref, lse_ref = refs[:2]
    _, c, sub = plan
    d = q_ref.shape[-1]

    def prep(r):
        return _rows(q_ref, r, c), r

    def piece(ctx, j, g, lo, hi, off, carry):
        """One online-softmax step of a group's q rows over k/v rows."""
        q, r = ctx
        m, l, acc = carry
        k0 = j * c + lo
        k, v = _rows(k_ref, k0, hi - lo), _rows(v_ref, k0, hi - lo)
        s = _dot(q[g:g + sub], k, _NT) * scale       # [sub, hi - lo] fp32
        if off is not None:
            s = jnp.where(_keep_tri(sub, hi - lo, off, True, window), s,
                          NEG_INF)
        if masked:
            # k-side padding mask (1=keep), a row [1, k]: k runs along
            # lanes like the scores' columns
            s = jnp.where(mask_ref[0, 0, pl.ds(j, 1), :hi] > 0, s, NEG_INF)
        if selected:
            # per-(q, k) selection, the same for every head of a row
            s = jnp.where(_selected(sel_ref, r + g, sub, j * c, hi), s,
                          NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        if masked or selected:
            # fully-masked row guard: m_new == NEG_INF would make the
            # masked exp(s - m_new) = 1; clamp so p stays 0 and the row
            # sums to 0. Without a k-side mask a row's first chunk holds
            # its own position, so m_new is finite from there on; under a
            # window a row may see no key of its first chunk (the band's
            # lower edge), and what that chunk adds to l and acc is wiped
            # by corr = exp(NEG_INF - m) = 0 at the row's next chunk,
            # which holds keys it reads.
            m_new = jnp.where(m_new > 0.5 * NEG_INF, m_new, 0.0)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * corr + _dot(p.astype(v.dtype), v, _NN)
        return m_new, l, acc

    def finalize(ctx, r, carry):
        m, l, acc = carry
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, pl.ds(r, c), :] = (acc / l_safe).astype(o_ref.dtype)
        lse_ref[0, pl.ds(r, c), :] = jnp.broadcast_to(
            m + jnp.log(l_safe), (c, LANE))

    _walk(plan, n, causal, True, pl.program_id(1), pl.program_id(2),
          ((NEG_INF, 1), (0.0, 1), (0.0, d)), refs[2:], prep, piece,
          finalize, window)


def _specs(plan, d, causal, heads, out_is_q, group=1, band=None):
    """Block specs of a grid (bh, out_block, reduce_block): operand rows of
    the out side and of the reduce side, the q side's statistics as
    columns (`stat_out`: the forward writes them, the causal and masked
    dq kernel reads them) or one row a group (`stat_rows`: every dk/dv
    walk reads them; `stat_rows_out`: the latent forward, whose out side
    they are, writes them), the k-side mask one row a chunk (`mask_rows`)
    or as a column (`mask_col`), each along the axis its side lies on, and
    the (out, reduce) block of a per-pair selection (`sel`: [q, k] to the
    forward,
    [k, q] of the transposed operand to the fused backward, which is the
    one backward kernel that reads it), which like the mask is one per
    batch row. `kv_out` / `kv_red` are the key/value side where `group` query
    heads share one key/value head: the grid runs over query heads and
    the index map folds them (b // group), so no copy is made. Under a
    causal mask the reduce side stops (dk/dv: starts) at the out block,
    so a grid step that computes nothing fetches nothing either. `band`
    (m, n): under a window the reduce axis counts the m + 1 blocks the
    band meets, k blocks i - m .. i (dk/dv: q blocks i .. i + m), held
    inside the sequence, so that a step beyond it fetches nothing new."""
    block, c, sub = plan

    def red(b, i, j):
        if not causal:
            return j
        if band is not None:
            m, n = band
            return jnp.maximum(i - m + j, 0) if out_is_q else \
                jnp.minimum(i + j, n - 1)
        return jnp.minimum(j, i) if out_is_q else jnp.maximum(j, i)

    def kv(b):
        return b if group == 1 else b // group

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    return dict(
        out=spec((1, block, d), lambda b, i, j: (b, i, 0)),
        red=spec((1, block, d), lambda *g: (g[0], red(*g), 0)),
        kv_out=spec((1, block, d), lambda b, i, j: (kv(b), i, 0)),
        kv_red=spec((1, block, d), lambda *g: (kv(g[0]), red(*g), 0)),
        stat_out=spec((1, block, LANE), lambda b, i, j: (b, i, 0)),
        stat_rows=spec((1, 1, block // sub, sub),
                       lambda *g: (g[0], red(*g), 0, 0)),
        stat_rows_out=spec((1, 1, block // sub, sub),
                           lambda b, i, j: (b, i, 0, 0)),
        mask_rows=spec((1, 1, block // c, c),
                       lambda *g: (g[0] // heads, red(*g), 0, 0)),
        mask_col=spec((1, block, LANE),
                      lambda b, i, j: (b // heads, i, 0)),
        sel=spec((1, block, block),
                 lambda *g: (g[0] // heads, g[1], red(*g))))


def _band(plan, n, window):
    """(`_band_blocks`, n) of a windowed call, for `_specs`; None without
    a window."""
    return None if window is None else (
        _band_blocks(window, plan.block, n), n)


def _grid(bh, n, band):
    """(b h, out blocks, reduce blocks): every block, or under a window
    the blocks its band meets."""
    return (bh, n, n if band is None else band[0] + 1)


def _fwd(q3, k3, v3, causal, scale, mask3=None, heads=1, sel=None, group=1,
         window=None):
    bh, s, d = q3.shape
    plan = _plan(s, d, q3.dtype, causal)
    n = s // plan.block
    band = _band(plan, n, window)
    sp = _specs(plan, d, causal, heads, out_is_q=True, group=group,
                band=band)
    in_specs = [sp["out"], sp["kv_red"], sp["kv_red"]]
    args = [q3, k3, v3]
    if mask3 is not None:
        # k-side mask [batch, 1, s] as rows of one k chunk each; every head
        # of a batch row reads the same block via the b // heads index map
        # (heads is static)
        in_specs.append(sp["mask_rows"])
        args.append(mask3.reshape(-1, n, plan.block // plan.chunk,
                                  plan.chunk))
    if sel is not None:
        in_specs.append(sp["sel"])
        args.append(sel)
    carried = [pltpu.VMEM((plan.block, w), jnp.float32)
               for w in (1, 1, d)] if n > 1 else []
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          plan=plan, n=n, masked=mask3 is not None,
                          selected=sel is not None, window=window),
        grid=_grid(bh, n, band),
        in_specs=in_specs,
        out_specs=[sp["out"], sp["stat_out"]],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, s, LANE), jnp.float32)],
        scratch_shapes=carried,
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        # the name is the innermost component of the kernel's `op_name`,
        # and with it the compiled instruction's name ("%flash_fwd.N"):
        # a trace tells the three kernels apart without knowing shapes,
        # and the selected and the windowed path from the plain one
        name=(FLASH_SEL_FWD if sel is not None else
              FLASH_WIN_FWD if window is not None else FLASH_FWD),
    )(*args)
    return o, lse


# --------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
               causal, scale, plan, n, masked, window=None):
    refs = list(refs)
    mask_ref = refs.pop(0) if masked else None
    dq_ref = refs[0]
    _, c, sub = plan
    d = q_ref.shape[-1]

    def prep(r):
        return (_rows(q_ref, r, c), _rows(do_ref, r, c),
                _rows(lse_ref, r, c)[:, 0:1],
                _rows(delta_ref, r, c)[:, 0:1])

    def piece(ctx, j, g, lo, hi, off, carry):
        q, do, lse, delta = (x[g:g + sub] for x in ctx)
        k0 = j * c + lo
        k, v = _rows(k_ref, k0, hi - lo), _rows(v_ref, k0, hi - lo)
        s = _dot(q, k, _NT) * scale
        if off is not None:
            s = jnp.where(_keep_tri(sub, hi - lo, off, True, window), s,
                          NEG_INF)
        if masked:
            s = jnp.where(mask_ref[0, 0, pl.ds(j, 1), :hi] > 0, s, NEG_INF)
        ds = jnp.exp(s - lse) * (_dot(do, v, _NT) - delta) * scale
        return (carry[0] + _dot(ds.astype(k.dtype), k, _NN),)

    def finalize(ctx, r, carry):
        dq_ref[0, pl.ds(r, c), :] = carry[0].astype(dq_ref.dtype)

    _walk(plan, n, causal, True, pl.program_id(1), pl.program_id(2),
          ((0.0, d),), refs[1:], prep, piece, finalize, window)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                causal, scale, plan, n, masked, window=None):
    """dk and dv of the resident k block, a chunk of k rows at a time over
    the chunks of the resident q block. The scores are computed
    transposed, sᵀ = k qᵀ: k runs along sublanes and q along lanes, so
    lse and delta arrive as rows [1, q], the k-side mask as a column, and
    pᵀ dO and dsᵀ q contract over lanes like any matmul."""
    refs = list(refs)
    mask_ref = refs.pop(0) if masked else None
    dk_ref, dv_ref = refs[:2]
    _, c, sub = plan
    d = q_ref.shape[-1]

    def prep(r):
        keep = _rows(mask_ref, r, c)[:, 0:1] > 0 if masked else None
        return _rows(k_ref, r, c), _rows(v_ref, r, c), keep

    def piece(ctx, i, g, lo, hi, off, carry):
        k, v, keep = (x[g:g + sub] if x is not None else None for x in ctx)
        dk, dv = carry
        q0, nq = i * c + lo, hi - lo
        q, do = _rows(q_ref, q0, nq), _rows(do_ref, q0, nq)
        st = _dot(k, q, _NT) * scale                      # [sub, nq] fp32
        if off is not None:
            st = jnp.where(_keep_tri(sub, nq, off, False, window), st,
                           NEG_INF)
        if masked:
            st = jnp.where(keep, st, NEG_INF)
        pt = jnp.exp(st - _stat_row(lse_ref, q0, nq, sub))
        dv = dv + _dot(pt.astype(do.dtype), do, _NN)
        dst = pt * (_dot(v, do, _NT)
                    - _stat_row(delta_ref, q0, nq, sub)) * scale
        return dk + _dot(dst.astype(q.dtype), q, _NN), dv

    def finalize(ctx, r, carry):
        dk, dv = carry
        dk_ref[0, pl.ds(r, c), :] = dk.astype(dk_ref.dtype)
        dv_ref[0, pl.ds(r, c), :] = dv.astype(dv_ref.dtype)

    _walk(plan, n, causal, False, pl.program_id(1), pl.program_id(2),
          ((0.0, d), (0.0, d)), refs[2:], prep, piece, finalize, window)


def _stat_row(ref, start, size, sub):
    """[1, size] of a q-side statistic that lies along lanes in rows of
    `sub` (`_specs` `stat_rows`): a row whose lanes are cut after the load
    keeps its lane offset, which no broadcast accepts."""
    return jnp.concatenate(
        [ref[0, 0, pl.ds((start + u) // sub, 1), :]
         for u in range(0, size, sub)], axis=1)


def _bwd_impl(causal, scale, res, g, mask3=None, heads=1, sel=None,
              group=1, window=None):
    """dq, dk, dv. The causal and the k-side-masked family: a dq kernel
    and a dk/dv kernel, each of which computes the scores, their `exp`
    and dO vᵀ of every pair it visits. Under a selection: the ONE fused
    kernel further down (`_fused_bwd`), which the latent family shares."""
    q3, k3, v3, o3, lse = res
    if sel is not None:
        if not causal:
            raise NotImplementedError(
                "a selection without causal=True: the fused backward ends "
                "a query block's dq at its diagonal key block")
        # the selection arrives transposed, [k, q], like the scores; q, k
        # and v one head an array row, k and v read by `group` query heads
        d = q3.shape[-1]
        parts = Parts(q_lays=(Lay(),), k_lays=(Lay(group), Lay(group)),
                      q=((0, 0, d),), k=((0, 0, d),), v=(1, 0, d))
        (dq,), (dk, dv) = _fused_bwd(
            FLASH_SEL_BWD_DKV, scale, (q3,), (k3, v3), o3, g, lse, parts,
            sel_t=jnp.swapaxes(sel, 1, 2))
        return dq, dk, dv
    bh, s, d = q3.shape
    plan = _plan(s, d, q3.dtype, causal)
    block, c, sub = plan
    n = s // block
    band = _band(plan, n, window)
    do3 = g
    # softmax delta rowsum(dO·O), precomputed once (not per k chunk). dq
    # reads it, like lse, as a column over the stat-lane layout; dk/dv
    # reads both along lanes, one row a diagonal group.
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    delta3 = jnp.broadcast_to(delta[..., None], (bh, s, LANE))
    delta_rows = delta.reshape(bh, n, block // sub, sub)
    lse_rows = lse[..., 0].reshape(bh, n, block // sub, sub)
    masked = mask3 is not None
    acc = [pltpu.VMEM((block, d), jnp.float32)] if n > 1 else []

    # dq grid: (bh, q_block, k_block) — the k-side mask follows axis 2
    sp = _specs(plan, d, causal, heads, out_is_q=True, group=group,
                band=band)
    dq_in = [sp["out"], sp["kv_red"], sp["kv_red"], sp["out"],
             sp["stat_out"], sp["stat_out"]] + (
        [sp["mask_rows"]] if masked else [])
    dq_args = [q3, k3, v3, do3, lse, delta3] + (
        [mask3.reshape(-1, n, block // c, c)] if masked else [])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          plan=plan, n=n, masked=masked, window=window),
        grid=_grid(bh, n, band),
        in_specs=dq_in,
        out_specs=[sp["out"]],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype)],
        scratch_shapes=acc,
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        name=FLASH_BWD_DQ if window is None else FLASH_WIN_BWD_DQ,
    )(*dq_args)[0]

    # grid dims: (bh, k_block, q_block) — q is the reduce (innermost) dim;
    # the k-side mask follows axis 1 here, as a column beside k's rows.
    # Where `group` query heads share a key/value head the grid still runs
    # over query heads: each writes its own dk, dv in fp32 and XLA adds a
    # group's up, which costs one pass over [bh, s, d] where a second
    # reduce axis would cost the kernels their static walk.
    sp = _specs(plan, d, causal, heads, out_is_q=False, group=group,
                band=band)
    dkv_in = [sp["red"], sp["kv_out"], sp["kv_out"], sp["red"],
              sp["stat_rows"], sp["stat_rows"]] + (
        [sp["mask_col"]] if masked else [])
    dkv_args = [q3, k3, v3, do3, lse_rows, delta_rows] + (
        [jnp.broadcast_to(mask3.reshape(-1, s, 1), (mask3.shape[0], s, LANE))]
        if masked else [])
    part = jnp.float32 if group > 1 else None
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          plan=plan, n=n, masked=masked, window=window),
        grid=_grid(bh, n, band),
        in_specs=dkv_in,
        out_specs=[sp["out"], sp["out"]],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), part or k3.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), part or v3.dtype)],
        scratch_shapes=acc * 2,
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        name=FLASH_BWD_DKV if window is None else FLASH_WIN_BWD_DKV,
    )(*dkv_args)
    if group > 1:
        dk, dv = (x.reshape(bh // group, group, s, d).sum(1).astype(k3.dtype)
                  for x in (dk, dv))
    return dq, dk, dv


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash3(q3, k3, v3, mask3, sel, causal, scale, heads, group,
            window=None):
    """The one differentiable wrapper of the forward kernel and its
    backward: a dq and a dk/dv kernel, or under a selection the one fused
    kernel (`_bwd_impl`). `mask3` ([batch, 1, s] float, or None): the
    k-side padding mask; `sel` ([batch, s_q, s_k] 0/1 bytes, or None): a
    per-pair selection; a row's `heads` query heads share both. `group`
    query heads share one key/value head. `window` (or None): the causal
    band's keys a query."""
    o, _ = _fwd(q3, k3, v3, causal, scale, mask3=mask3, heads=heads, sel=sel,
                group=group, window=window)
    return o


def _flash3_fwd(q3, k3, v3, mask3, sel, causal, scale, heads, group,
                window=None):
    o, lse = _fwd(q3, k3, v3, causal, scale, mask3=mask3, heads=heads,
                  sel=sel, group=group, window=window)
    return o, (q3, k3, v3, o, lse, mask3, sel)


def _flash3_bwd(causal, scale, heads, group, window, res, g):
    import numpy as np
    *res, mask3, sel = res
    dq, dk, dv = _bwd_impl(causal, scale, res, g, mask3=mask3, heads=heads,
                           sel=sel, group=group, window=window)
    # neither is a value: the mask's cotangent is zero, an integer
    # operand's is float0
    dmask = None if mask3 is None else jnp.zeros_like(mask3)
    dsel = None if sel is None else np.zeros(sel.shape, jax.dtypes.float0)
    return dq, dk, dv, dmask, dsel


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


def flash_attention(query, key, value, causal: bool = False,
                    scale=None, kv_mask=None, selection=None, window=None):
    """[b, s, h, d] fused attention. Requires s % 128 == 0.

    kv_mask ([b, s], bool/0-1, optional): k-side padding mask — 1 keeps
    the key position, 0 masks it for every query (the padded-batch BERT
    attention mask; reference: the mask input of
    `operators/fused/multihead_matmul_op.cu:1`). Fully-masked rows
    return 0. Mask cotangent is zero (it is a selection, not a value).

    key/value may hold fewer heads, [b, s, h_kv, d] with h % h_kv == 0:
    query head i reads key/value head i // (h / h_kv), through the
    kernels' index maps and without a copy (dk, dv: one partial sum a
    query head, added up outside the kernel).

    selection ([b, s_q, s_k] int8, optional): 1 where query position q may
    read key position k, the same for every head of a row (a learned
    top-k key selection) and lies under the diagonal: it is passed WITH
    causal=True, which says which blocks the walk may skip and where the
    backward has a query block's dq whole. Rows that select nothing
    return 0.

    window (int, optional, with causal=True): query position p reads the
    keys p - window + 1 .. p alone (a sliding window). The kernels' grid
    visits only the key blocks that band meets, and their walk only the
    chunks and groups inside it (`score_work`); they are named apart from
    the causal ones (`flash_win_fwd`, `flash_win_bwd_dq`,
    `flash_win_bwd_dkv`).
    """
    b, s, h, d = query.shape
    if s % 128 != 0:
        raise ValueError(f"flash_attention needs seq % 128 == 0, "
                         f"got {s}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def to3(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * x.shape[2], s, d)

    h_kv = key.shape[2]
    if h % h_kv:
        raise ValueError(f"{h} query heads over {h_kv} key/value heads")
    if kv_mask is not None and (selection is not None or h_kv != h):
        raise NotImplementedError(
            "kv_mask with grouped heads or a selection: fold the padding "
            "into the selection")
    if selection is not None and not causal:
        raise NotImplementedError(
            "a selection without causal=True: the selected backward ends a "
            "query block's dq at its diagonal key block")
    if window is not None and (not causal or kv_mask is not None
                               or selection is not None or window < 1):
        raise NotImplementedError(
            f"window={window}: a band of at least one key under "
            f"causal=True, without kv_mask or a selection")
    # [batch, 1, s] / [batch, s_q, s_k]: heads share the batch row via the
    # kernels' b // heads index map (no h-fold HBM duplication)
    m3 = None if kv_mask is None else jnp.asarray(
        kv_mask, jnp.float32).reshape(b, 1, s)
    sel = None if selection is None else jnp.asarray(selection, jnp.int8)
    o3 = _flash3(to3(query), to3(key), to3(value), m3, sel, causal, scale,
                 h, h // h_kv, None if window is None else int(window))
    return jnp.swapaxes(o3.reshape(b, h, s, d), 1, 2)


# ----------------------------------------------------- latent attention
#
# DeepSeek's latent attention scores over a wider head than its values
# have (192 = 128 without position + 64 rotary, against 128), and its
# rotary key is ONE head that every query head reads. The two latent
# kernels below stand on the same `_walk` as the three above, causal
# only, with q and the key given in PARTS along the score width, paired:
# the score is the sum of the parts' products, dq and dk are written part
# by part. One part of the full width is the key concatenated in HBM
# beforehand.
#
# Where a head's rows lie is the caller's to say (`Lay`, `Parts`): the
# grid runs over query heads (b h), and each operand's index map finds a
# head's block where the projections left it. At the published widths
# (`flash_attention_latent`) q without position is read from `[b, s, h
# 128]`, the key without position and the values from `kv_b_proj`'s own
# `[b, s, h (128 + 128)]`, one 256-lane block a head, o and dO as `[b, s,
# h 128]`, which `o_proj` takes as it is; dq comes out in q's two parts,
# and the key's and the values' gradient as ONE `[b, s, h 256]` in kv's
# layout, so no operand and no gradient is transposed to or from `[b h,
# s, w]`. The rotary query part is head-major, `[b h, s, 64]`, as the
# rotary fusion writes it, and the one rotary key head `[b, s, 64]` is
# read by the row's heads at b // h (no copy); its gradient comes out one
# float32 partial a query head, added up outside.
#
# The forward (`_mla_fwd_kernel`) holds its running max, sum and correction
# replicated over a vreg's lanes and hands lse to the backward as rows
# along lanes (PERF.md, PR 36).
#
# Two kernels, forward and backward: dq rides the dk/dv walk. A (q, k)
# pair's scores, `exp` and dO vT are what dq and dk/dv both need, so the
# one walk makes them once and adds the pair's share of dq beside
# dst q and pT dO. A q block's dq is added to by every k block up to its
# own, grid steps that do not follow each other, so it cannot be a carried
# output block: the head's WHOLE dq stays in VMEM in float32 (6 MiB at
# 8192 tokens) and each q block is rounded once, when its diagonal k block
# has passed (PERF.md, PR 34). The selected family's backward (`_bwd_impl`)
# is the same kernel with ONE key part of the full width, which with the
# values `group` query heads share, and the selection beside the causal
# mask (PERF.md, PR 35).

class Lay(NamedTuple):
    """Where row g of the grid, query head g % h of batch row g // h,
    finds its block of an operand [rows, s, lanes] of the latent kernels
    and of the fused backward: at array row g // fold, and at lane block
    g % fold where a row's `fold` heads lie side by side along lanes
    (`side`: a projection's own layout, [b, s, h w]), else at lane block 0,
    a block `fold` query heads share (the one rotary key head of a row, a
    key/value head of a group), whose gradient leaves as one float32
    partial a query head, added up outside. fold 1: one head an array
    row, [b h, s, w]."""
    fold: int = 1
    side: bool = False

    def width(self, x) -> int:
        """Lanes of one head's block of `x`."""
        return x.shape[-1] // self.fold if self.side else x.shape[-1]


class Parts(NamedTuple):
    """How the latent kernels and the fused backward find their operands:
    the `Lay` of each q-side and of each k-side array, and where q's parts,
    the key's parts (paired with q's) and the values lie in them: (array,
    first lane, width) within a head's block."""
    q_lays: tuple
    k_lays: tuple
    q: tuple
    k: tuple
    v: tuple

    @property
    def o(self) -> Lay:
        """o's and dO's: side by side where the values are, else one head
        an array row."""
        lay = self.k_lays[self.v[0]]
        return lay if lay.side else Lay()


def _lanes(ref, start, size, lo, w):
    """Rows [start, start + size) and lanes [lo, lo + w) of a [1, rows,
    lanes] block."""
    return ref[0, pl.ds(start, size), pl.ds(lo, w)]


def _mla_scores(q_parts, keys, row0, rows, scale):
    """sum over the parts of q_part k_partT: [q rows, rows] fp32. `keys`:
    (ref, first lane, width) of each key part."""
    s = None
    for qp, (k_ref, lo, w) in zip(q_parts, keys):
        t = _dot(qp, _lanes(k_ref, row0, rows, lo, w), _NT)
        s = t if s is None else s + t
    return s * scale


# The lanes a running statistic of the latent forward is replicated over:
# a vreg's.
_STAT_LANES = 128


def _over_lanes(x, w):
    """A statistic [rows, _STAT_LANES], alike in every lane, over `w`
    lanes: whole vregs side by side, which costs nothing, where a column
    [rows, 1] costs a lane broadcast a vreg row."""
    reps = -(-w // x.shape[1])
    x = jnp.tile(x, (1, reps)) if reps > 1 else x
    return x if x.shape[1] == w else x[:, :w]


def _mla_fwd_kernel(*refs, parts, scale, plan, n):
    """The latent forward. Its running statistics `m`, `l` and the
    correction `corr` are NOT columns [rows, 1] but [rows, _STAT_LANES],
    alike in every lane: a column is a vreg for every 8 numbers with one
    live lane, and each use of it against the scores or the accumulator
    (`s - m`, `acc * corr`) is a lane broadcast a vreg row, which was a
    sixth of this kernel's time (PERF.md, PR 36: 13.80 ms a call with
    columns, 11.54 so, 11.18 with lse as rows too; the scores transposed,
    with the statistics as rows along lanes, read 13.14). lse leaves as
    the backward reads it, rows along lanes (`_specs` `stat_rows_out`),
    transposed once a q block."""
    n_q, n_k = len(parts.q_lays), len(parts.k_lays)
    q_refs, k_refs = refs[:n_q], refs[n_q:n_q + n_k]
    o_ref, lse_ref = refs[n_q + n_k:n_q + n_k + 2]
    keys = [(k_refs[a], lo, w) for a, lo, w in parts.k]
    v_a, v_lo, dv = parts.v
    _, c, sub = plan

    def prep(r):
        return tuple(_lanes(q_refs[a], r, c, lo, w) for a, lo, w in parts.q)

    def piece(ctx, j, g, lo, hi, off, carry):
        m, l, acc = carry
        s = _mla_scores([q[g:g + sub] for q in ctx], keys, j * c, hi,
                        scale)
        if off is not None:
            s = jnp.where(_keep_tri(sub, hi, off, True), s, NEG_INF)
        v = _lanes(k_refs[v_a], j * c, hi, v_lo, dv)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _over_lanes(m_new, hi))
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=1, keepdims=True)
        return m_new, l, (acc * _over_lanes(corr, dv)
                          + _dot(p.astype(v.dtype), v, _NN))

    def finalize(ctx, r, carry):
        m, l, acc = carry
        l_safe = jnp.maximum(l, 1e-30)
        o_ref[0, pl.ds(r, c), :] = (acc / _over_lanes(l_safe, dv)).astype(
            o_ref.dtype)
        lse = (m + jnp.log(l_safe)).T[0:1]                # [1, c]
        for u in range(0, c, sub):
            lse_ref[0, 0, pl.ds((r + u) // sub, 1), :] = lse[:, u:u + sub]

    _walk(plan, n, True, True, pl.program_id(1), pl.program_id(2),
          ((NEG_INF, _STAT_LANES), (0.0, _STAT_LANES), (0.0, dv)),
          refs[n_q + n_k + 2:], prep, piece, finalize)


def _fused_bwd_kernel(*refs, parts, scale, plan, n, selected):
    """The backward as ONE kernel, of the latent family and of the
    selected: dk of every key part and dv of the resident k block as
    `_dkv_kernel` makes them (the scores transposed, k qT; a per-pair
    selection arrives transposed too, [k, q]), and out of the same scores
    and dO vT the pair's share of dq, added into a float32 accumulator
    that holds the HEAD's whole dq across all its k blocks (`dq_acc`, one
    scratch a key part). The accumulator holds dq TRANSPOSED, [blocks, w,
    block]: the share is then k_partT dst, a plain product of what the
    walk already has (dst as it stands; k_partT made once a k chunk), and
    a 64-wide part is the product's rows and not its lanes, which pad to
    128 (PERF.md, PR 34: 23.9 ms a call against 25.2 for the latent
    family; PR 35: a tie where every part is 128 wide, 16.40 against
    16.39, so the one layout serves both). A q block's dq is whole once
    its own (diagonal) k block has passed, which is that k block's first
    step on the grid: there it is transposed back and rounded, once, into
    its part's lanes of the q side's gradient. Each k-side array's
    gradient block takes its parts' lanes (dk without position and dv side
    by side in one block of kv's gradient)."""
    n_q, n_k = len(parts.q_lays), len(parts.k_lays)
    q_refs, k_refs = refs[:n_q], refs[n_q:n_q + n_k]
    do_ref, lse_ref, delta_ref = refs[n_q + n_k:n_q + n_k + 3]
    refs = refs[n_q + n_k + 3:]
    sel_ref = refs[0] if selected else None
    refs = refs[selected:]
    dq_refs, dk_refs = refs[:n_q], refs[n_q:n_q + n_k]
    dq_acc = refs[n_q + n_k:n_q + n_k + len(parts.k)]
    v_a, v_lo, v_w = parts.v
    _, c, sub = plan
    kb, qb = pl.program_id(1), pl.program_id(2)

    @pl.when(kb == 0)
    def _first_k_block():
        for acc in dq_acc:
            acc[qb] = jnp.zeros(acc.shape[1:], jnp.float32)

    def prep(r):
        ks = tuple(_lanes(k_refs[a], r, c, lo, w) for a, lo, w in parts.k)
        return ks, tuple(k.T for k in ks), _lanes(k_refs[v_a], r, c, v_lo,
                                                  v_w), r

    def piece(ctx, i, g, lo, hi, off, carry):
        ks, kts, v, r = ctx
        *dks, dv = carry
        q0, nq = i * c + lo, hi - lo
        qs = [_lanes(q_refs[a], q0, nq, l0, w) for a, l0, w in parts.q]
        do = _rows(do_ref, q0, nq)
        st = None
        for k, q in zip(ks, qs):
            t = _dot(k[g:g + sub], q, _NT)
            st = t if st is None else st + t
        st = st * scale                                   # [sub, nq] fp32
        if off is not None:
            st = jnp.where(_keep_tri(sub, nq, off, False), st, NEG_INF)
        if selected:
            st = jnp.where(_selected(sel_ref, r + g, sub, q0, nq), st,
                           NEG_INF)
        pt = jnp.exp(st - _stat_row(lse_ref, q0, nq, sub))
        dv = dv + _dot(pt.astype(do.dtype), do, _NN)
        dst = (pt * (_dot(v[g:g + sub], do, _NT)
                     - _stat_row(delta_ref, q0, nq, sub))
               * scale).astype(do.dtype)
        for acc, kt in zip(dq_acc, kts):
            acc[qb, :, pl.ds(q0, nq)] += _dot(kt[:, g:g + sub], dst, _NN)
        return (*(dk + _dot(dst, q, _NN) for dk, q in zip(dks, qs)), dv)

    def finalize(ctx, r, carry):
        for (a, lo, w), x in zip((*parts.k, parts.v), carry):
            ref = dk_refs[a]
            ref[0, pl.ds(r, c), pl.ds(lo, w)] = x.astype(ref.dtype)

    _walk(plan, n, True, False, kb, qb,
          (*((0.0, w) for _, _, w in parts.k), (0.0, v_w)),
          refs[n_q + n_k + len(parts.k):], prep, piece, finalize)

    @pl.when(qb == kb)
    def _diagonal():
        for (a, lo, w), acc in zip(parts.q, dq_acc):
            ref = dq_refs[a]
            ref[0, :, pl.ds(lo, w)] = acc[kb].T.astype(ref.dtype)


def _parts_specs(plan, out_is_q, heads=1):
    """Block specs of the latent kernels' and the fused backward's
    operands on a grid (b h, out block, reduce block), under the causal
    mask: an array `x` laid as `lay` (`Lay`) on the q side (`q(x, lay)`),
    on the k side (`k`) or as an output (`out`), and the statistics and
    the selection as `_specs` lays them (`heads` query heads a row share a
    selection)."""
    block = plan.block
    # of `_specs` only the statistics' and the selection's, which no
    # operand's width enters
    laid = _specs(plan, 0, True, heads, out_is_q)

    def red(i, j):
        return jnp.minimum(j, i) if out_is_q else jnp.maximum(j, i)

    def spec(x, lay, seq):
        shift = lay.fold.bit_length() - 1

        def index(b, i, j):
            if lay.side and lay.fold == 1 << shift:
                # a power of two of heads side by side: row and lane block
                # by shift and mask, where an integer division costs the
                # pipeline about 20 ns an operand a grid step (0.07 ms a
                # forward call of 4096 steps on a v5e: PERF.md section 6)
                return (jax.lax.shift_right_logical(b, shift), seq(i, j),
                        b & (lay.fold - 1))
            return (b if lay.fold == 1 else b // lay.fold, seq(i, j),
                    b % lay.fold if lay.side else 0)
        return pl.BlockSpec((1, block, lay.width(x)), index,
                            memory_space=pltpu.VMEM)

    def out_side(x, lay):
        return spec(x, lay, lambda i, j: i)

    def red_side(x, lay):
        return spec(x, lay, red)
    q_side, k_side = (out_side, red_side) if out_is_q else \
        (red_side, out_side)
    return dict(q=q_side, k=k_side, out=out_side,
                stat_rows=laid["stat_rows"],
                stat_rows_out=laid["stat_rows_out"], sel=laid["sel"])


def _values_plan(s: int, dv: int, dtype) -> Plan:
    """`_plan` by the VALUES' width, of the latent kernels and of the
    fused backward (`_fused_bwd`), which the selected family shares: there
    scores and values are one width and this is `_plan(s, d, dtype,
    True)`, the selected forward's. Of the latent kernels' operands only
    q and dq are wider (and 64 of their 256 lanes are padding), and what
    dq holds at that block, q and dq at 256 lanes and the key's parts, v
    and dO at 128, is 8 operand blocks of `_RESIDENT_BYTES` / 2 where the
    budget allows 5 of `_RESIDENT_BYTES`. Measured (PERF.md, PR 33): 1024
    resident rows at 8192 tokens take 58.4 ms a layer forward and
    backward where the 512 that the score width would give take 68.2."""
    return _plan(s, dv, dtype, True)


def _grid_rows(qs, parts):
    """(query heads b h, s) of the grid, from q's first array."""
    return qs[0].shape[0] * parts.q_lays[0].fold, qs[0].shape[1]


def _mla_fwd(qs, ks, parts, scale):
    """o, laid as `parts.o`, and lse as the fused backward reads it, [b h,
    blocks, groups a block, rows a group] (`_specs` `stat_rows`). `qs`,
    `ks`: the q-side and the k-side arrays (`Parts`)."""
    bh, s = _grid_rows(qs, parts)
    dv = parts.v[2]
    plan = _values_plan(s, dv, qs[0].dtype)
    n = s // plan.block
    sp = _parts_specs(plan, out_is_q=True)
    o = jax.ShapeDtypeStruct((bh // parts.o.fold, s, parts.o.fold * dv),
                             qs[0].dtype)
    carried = [pltpu.VMEM((plan.block, w), jnp.float32)
               for w in (_STAT_LANES, _STAT_LANES, dv)] if n > 1 else []
    return pl.pallas_call(
        functools.partial(_mla_fwd_kernel, parts=parts, scale=scale,
                          plan=plan, n=n),
        grid=(bh, n, n),
        in_specs=[*map(sp["q"], qs, parts.q_lays),
                  *map(sp["k"], ks, parts.k_lays)],
        out_specs=[sp["out"](o, parts.o), sp["stat_rows_out"]],
        out_shape=[o, jax.ShapeDtypeStruct(
            (bh, n, plan.block // plan.sub, plan.sub), jnp.float32)],
        scratch_shapes=carried,
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        name=FLASH_MLA_FWD,
    )(*qs, *ks)


# The most a head's whole dq may take of VMEM in float32 (a v5e core has
# 128 MiB; at 8192 tokens the kanana cell's takes 6 MiB, the Keye cell's 4).
_DQ_BYTES = 32 << 20


def _fused_bwd_vmem(s, block, parts, dv, selected=False):
    """(bytes of the dq accumulator, `vmem_limit_bytes`) of the fused
    backward, from its shapes (`parts`: the key parts' widths): the
    accumulator (dq is held transposed, so the widths lie along sublanes
    and pad to 8, not to 128 lanes), the selection's int8 block twice
    where there is one, every operand and output block twice (the
    pipeline's two buffers) and the carried dk, dv once, each counted as
    float32 rows of 128-lane tiles, and 16 MiB for a group's scores and
    the compiler's own."""
    def tiles(*widths):
        return sum(-(-w // 128) * 128 * 4 for w in widths)
    acc = s * sum(-(-w // 8) * 8 * 4 for w in parts)
    blocks = block * (2 * tiles(sum(parts), *parts, dv, dv)
                      + 2 * tiles(sum(parts), *parts, dv)
                      + tiles(*parts, dv))
    sel = 2 * block * block if selected else 0
    return acc, acc + sel + blocks + (16 << 20)


def _rowsum(do, o, lay, bh):
    """rowsum(dO·O) a query head, [b h, s], of o laid as `lay`."""
    x = do.astype(jnp.float32) * o.astype(jnp.float32)
    if not lay.side:
        return jnp.sum(x, axis=-1)
    x = jnp.sum(x.reshape(*o.shape[:2], lay.fold, -1), axis=-1)  # [b, s, h]
    return jnp.swapaxes(x, 1, 2).reshape(bh, -1)


def _fused_bwd(name, scale, qs, ks, o, do, lse, parts, sel_t=None):
    """The gradients of the q-side and of the k-side arrays (`qs`, `ks`,
    laid as `parts` says), from ONE `pallas_call` named `name`; `lse` as
    the family's forward writes it, the rows the kernel reads (`_mla_fwd`)
    or the columns [b h, s, LANE] of `_fwd`; `sel_t` ([b, s_k, s_q] 0/1
    bytes, or None): a per-pair selection, transposed. Causal."""
    bh, s = _grid_rows(qs, parts)
    dv = parts.v[2]
    plan = _values_plan(s, dv, qs[0].dtype)
    block, _, sub = plan
    n = s // block
    widths = [w for _, _, w in parts.k]
    selected = sel_t is not None
    acc_bytes, vmem = _fused_bwd_vmem(s, block, widths, dv, selected)
    if acc_bytes > _DQ_BYTES:
        raise ValueError(
            f"{name} keeps a head's whole dq in VMEM: {s} rows of {widths} "
            f"lanes take {acc_bytes} bytes, over {_DQ_BYTES}")
    delta_rows = _rowsum(do, o, parts.o, bh).reshape(bh, n, block // sub,
                                                     sub)
    if lse.ndim == 3:       # columns of the stat-lane layout: laid out here
        lse = lse[..., 0].reshape(bh, n, block // sub, sub)

    # grid (b h, k block, q block). A k-side array whose block several
    # query heads share: its gradient is one partial sum a query head in
    # float32, added up outside, which costs one pass over [b h, s, w]
    # where a second reduce axis would cost the kernel its static walk.
    # The k blocks of a head follow each other ("arbitrary"): the head's
    # dq crosses them in the scratch.
    sp = _parts_specs(plan, out_is_q=False,
                      heads=bh // sel_t.shape[0] if selected else 1)
    shared = [lay.fold > 1 and not lay.side for lay in parts.k_lays]
    k_out = [jax.ShapeDtypeStruct((bh, s, x.shape[-1]), jnp.float32) if sh
             else jax.ShapeDtypeStruct(x.shape, x.dtype)
             for x, sh in zip(ks, shared)]
    carried = [pltpu.VMEM((block, w), jnp.float32)
               for w in (*widths, dv)] if n > 1 else []
    grads = pl.pallas_call(
        functools.partial(_fused_bwd_kernel, parts=parts, scale=scale,
                          plan=plan, n=n, selected=selected),
        grid=(bh, n, n),
        in_specs=[*map(sp["q"], qs, parts.q_lays),
                  *map(sp["k"], ks, parts.k_lays), sp["q"](do, parts.o),
                  sp["stat_rows"], sp["stat_rows"],
                  *([sp["sel"]] if selected else [])],
        out_specs=[*map(sp["out"], qs, parts.q_lays),
                   *(sp["out"](x, Lay() if sh else lay)
                     for x, lay, sh in zip(k_out, parts.k_lays, shared))],
        out_shape=[*(jax.ShapeDtypeStruct(x.shape, x.dtype) for x in qs),
                   *k_out],
        scratch_shapes=[*(pltpu.VMEM((n, w, block), jnp.float32)
                          for w in widths), *carried],
        interpret=_interpret(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=vmem),
        name=name,
    )(*qs, *ks, do, lse, delta_rows, *([sel_t] if selected else []))
    dks = (g.reshape(bh // lay.fold, lay.fold, s, -1).sum(1).astype(x.dtype)
           if sh else g
           for g, x, lay, sh in zip(grads[len(qs):], ks, parts.k_lays,
                                    shared))
    return tuple(grads[:len(qs)]), tuple(dks)


def _mla_bwd(parts, scale, res, do):
    qs, ks, o, lse = res
    return _fused_bwd(FLASH_MLA_BWD_DKV, scale, qs, ks, o, do, lse, parts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _mla3(qs, ks, parts, scale):
    """The differentiable wrapper of the two latent kernels: o laid as
    `parts.o`. `qs`, `ks`: the q-side and the k-side arrays, laid as
    `parts` says (module comment above)."""
    return _mla_fwd(qs, ks, parts, scale)[0]


def _mla3_fwd(qs, ks, parts, scale):
    o, lse = _mla_fwd(qs, ks, parts, scale)
    return o, (qs, ks, o, lse)


_mla3.defvjp(_mla3_fwd, _mla_bwd)


def flash_attention_latent(q_nope, q_rope, kv, k_rope, scale=None):
    """Causal latent attention on the arrays the projections make. q_nope
    [b, s, h, dn]; q_rope [b, h, s, dr], the rotary part head-major, as
    the rotary fusion writes it; kv [b, s, h, dn + dv], a head's key
    without position and its values side by side (`kv_b_proj`'s output);
    k_rope [b, s, 1, dr], ONE head that every query head reads. score =
    (q_nope . k_nope + q_rope . k_rope) x scale (default 1 / sqrt(dn +
    dr)). Returns [b, s, h, dv]. Requires s % 128 == 0.

    Where dn and dv are whole 128-lane tiles (the published 128 and 128)
    the kernels read q_nope, kv and dO and write o, dq_nope and kv's
    gradient where they lie, `[b, s, h w]`, through their index maps
    (`Lay` side): nothing is transposed and nothing is sliced. Else each
    is laid out `[b h, s, w]` first. The rotary key is read through the
    index map, not concatenated in HBM beforehand (step 0 on the chip:
    tools/flash_mla_step0.py; PERF.md, PR 33)."""
    b, s, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    if s % 128 != 0:
        raise ValueError(f"flash_attention_latent needs seq % 128 == 0, "
                         f"got {s}")
    qs, ks, parts = _laid(q_nope, q_rope, kv, k_rope)
    scale = scale if scale is not None else 1.0 / math.sqrt(dn + dr)
    o = _mla3(qs, ks, parts, scale)
    if parts.o.side:
        return o.reshape(b, s, h, -1)
    return jnp.swapaxes(o.reshape(b, h, s, -1), 1, 2)


def _laid(q_nope, q_rope, kv, k_rope):
    """The latent kernels' q-side and k-side arrays and their `Parts`,
    from `flash_attention_latent`'s operands: where a head's parts without
    position and its values are whole 128-lane tiles, the projections'
    arrays as they are, [b, s, h w] (free reshapes); else laid out [b h,
    s, w]. The rotary parts as they come, [b h, s, dr] and [b, s, dr]."""
    b, s, h, dn = q_nope.shape
    dr = q_rope.shape[-1]
    dv = kv.shape[-1] - dn
    if q_rope.shape != (b, h, s, dr) or kv.shape[:3] != (b, s, h) \
            or dv <= 0 or k_rope.shape != (b, s, 1, dr):
        raise ValueError(
            f"key parts {kv.shape} + {k_rope.shape} against queries "
            f"{q_nope.shape} + {q_rope.shape}")
    if dn % 128 == 0 and dv % 128 == 0:
        lay = Lay(h, side=True)
        q3, kv3 = q_nope.reshape(b, s, -1), kv.reshape(b, s, -1)
    else:
        lay = Lay()

        def to3(x):
            return jnp.swapaxes(x, 1, 2).reshape(b * h, s, x.shape[-1])
        q3, kv3 = to3(q_nope), to3(kv)
    parts = Parts(q_lays=(lay, Lay()), k_lays=(lay, Lay(h)),
                  q=((0, 0, dn), (1, 0, dr)), k=((0, 0, dn), (1, 0, dr)),
                  v=(0, dn, dv))
    return ((q3, q_rope.reshape(b * h, s, dr)),
            (kv3, k_rope.reshape(b, s, dr)), parts)
