"""Flash attention — Pallas TPU kernel (training-capable, custom VJP).

Replaces the reference's inference-only fused attention CUDA kernels
(`operators/fused/multihead_matmul_op.cu`,
`operators/math/bert_encoder_functor.cu`) with a fused kernel that works in
both directions: the S×S score matrix lives only tile-by-tile in VMEM, so
long sequences never materialize O(S²) in HBM.

Layout contract: [batch, seq, heads, head_dim] (paddle 2.x attention
layout); internally [b·h, s, d]. All three kernels (fwd, dq, dk/dv) walk a
3-D grid (bh, out_tile, reduce_tile) with square seq tiles in VMEM and
fp32 scratch accumulators — VMEM use is O(BLOCK·(BLOCK+d)) regardless of
S, so the same kernel serves 1K and 64K tokens (and each ring-attention
shard, sequence_parallel.py). The tile edge adapts to the sequence
(512 → 256 → 128): big tiles keep the MXU busy and amortize the per-tile
softmax bookkeeping (measured on v5e: 512-tiles ≈ 2x over 128-tiles at
seq 1024). Matmul operands stay bf16 (fp32 operands run the MXU at 1/8
rate); accumulation and softmax statistics are fp32. Row statistics
(logsumexp/delta) ride an 8-lane broadcast because TPU block layouts need
a lane-divisible trailing dim.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..profiler import FLASH_BWD_DKV, FLASH_BWD_DQ, FLASH_FWD

# Row statistics (lse/delta) ride an 8-lane broadcast: TPU block layouts
# need the last two dims (sublane, lane) to divide (8, 128) or equal the
# array dims — a trailing dim of 8 equals itself, keeping the stat arrays
# at 8x logical size instead of 128x.
LANE = 8
NEG_INF = -1e30


def _block_for(s: int) -> int:
    for b in (512, 256, 128):
        if s % b == 0 and s >= b:
            return b
    raise ValueError(f"flash_attention needs seq % 128 == 0, got {s}")


def _interpret() -> bool:
    """Whether `pallas_call` runs the kernels in the Pallas interpreter.

    Never in the library: these are TPU programs, and a backend that
    cannot lower them raises. The CPU tests that check the kernels'
    arithmetic off-chip ask for the interpreter by patching this
    function (tests/test_flash_attention.py)."""
    return False


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, *refs, causal, scale, nk,
                masked=False):
    if masked:
        mask_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    else:
        o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
        mask_ref = None
    iq, jk = pl.program_id(1), pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(jnp.logical_or(not causal, jk <= iq))
    def _compute():
        q = q_ref[0]                                      # [BQ, d] bf16
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        bq, bk = s.shape
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = jk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if mask_ref is not None:
            # k-side padding mask (1=keep), [1, BK]: k runs along lanes
            # like the score tile's columns, so this is a row broadcast
            s = jnp.where(mask_ref[0] > 0, s, NEG_INF)
        m_prev = m_ref[:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # fully-masked row guard: m_new == NEG_INF would make the masked
        # exp(s - m_new) = 1; clamp so p stays 0 and the row sums to 0
        m_new = jnp.where(m_new > 0.5 * NEG_INF, m_new, 0.0)
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jax.lax.broadcast_in_dim(m_new[:, 0], m_ref.shape, (0,))

    @pl.when(jk == nk - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:, 0:1], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        lse = m_ref[:, 0:1] + jnp.log(l_safe)
        lse_ref[0] = jax.lax.broadcast_in_dim(lse[:, 0],
                                              lse_ref.shape[1:], (0,))


def _fwd(q3, k3, v3, causal, scale, mask3=None, heads=1):
    bh, s, d = q3.shape
    blk = _block_for(s)
    n = s // blk
    qt = pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0),
                      memory_space=pltpu.VMEM)
    kt = pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0),
                      memory_space=pltpu.VMEM)
    in_specs = [qt, kt, kt]
    args = [q3, k3, v3]
    if mask3 is not None:
        # k-side mask is [batch, 1, s], tiled by the K index along lanes;
        # every head of a batch row reads the same block via the
        # b // heads index map (heads is static)
        in_specs.append(pl.BlockSpec((1, 1, blk),
                                     lambda b, i, j: (b // heads, 0, j),
                                     memory_space=pltpu.VMEM))
        args.append(mask3)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale, nk=n,
                          masked=mask3 is not None),
        grid=(bh, n, n),
        in_specs=in_specs,
        out_specs=[qt,
                   pl.BlockSpec((1, blk, LANE), lambda b, i, j: (b, i, 0),
                                memory_space=pltpu.VMEM)],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, s, LANE), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, 128), jnp.float32),
                        pltpu.VMEM((blk, 128), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        # the name is the innermost component of the kernel's `op_name`,
        # and with it the compiled instruction's name ("%flash_fwd.N"):
        # a trace tells the three kernels apart without knowing shapes
        name=FLASH_FWD,
    )(*args)
    return o, lse


# --------------------------------------------------------------- backward

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
               causal, scale, nk, masked=False):
    if masked:
        mask_ref, dq_ref, acc_ref = refs
    else:
        dq_ref, acc_ref = refs
        mask_ref = None
    iq, jk = pl.program_id(1), pl.program_id(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_or(not causal, jk <= iq))
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        bq, bk = s.shape
        if causal:
            q_pos = iq * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = jk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if mask_ref is not None:
            s = jnp.where(mask_ref[0] > 0, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k.dtype)
        acc_ref[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _finalize():
        dq_ref[0] = acc_ref[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *refs,
                causal, scale, nq, masked=False):
    if masked:
        mask_ref, dk_ref, dv_ref, dk_acc, dv_acc = refs
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs
        mask_ref = None
    jk, i = pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(jnp.logical_or(not causal, i >= jk))
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0][:, 0:1]
        delta = delta_ref[0][:, 0:1]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        bq, bk = s.shape
        if causal:
            q_pos = i * bq + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 0)
            k_pos = jk * bk + jax.lax.broadcasted_iota(
                jnp.int32, (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        if mask_ref is not None:
            s = jnp.where(mask_ref[0] > 0, s, NEG_INF)
        p = jnp.exp(s - lse)                              # [BQ, BK]
        pc = p.astype(do.dtype)
        dv_acc[:] += jax.lax.dot_general(
            pc, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_impl(causal, scale, res, g, mask3=None, heads=1):
    q3, k3, v3, o3, lse = res
    bh, s, d = q3.shape
    blk = _block_for(s)
    n = s // blk
    do3 = g
    # softmax delta rowsum(dO·O), precomputed once (not per k-tile) and
    # broadcast over the stat-lane layout like lse
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)
    delta3 = jnp.broadcast_to(delta[..., None], (bh, s, LANE))

    def tile_i(b, i, j):
        return (b, i, 0)

    def tile_j(b, i, j):
        return (b, j, 0)

    ti = pl.BlockSpec((1, blk, d), tile_i, memory_space=pltpu.VMEM)
    tj = pl.BlockSpec((1, blk, d), tile_j, memory_space=pltpu.VMEM)
    lse_i = pl.BlockSpec((1, blk, LANE), tile_i, memory_space=pltpu.VMEM)
    lse_j = pl.BlockSpec((1, blk, LANE), tile_j, memory_space=pltpu.VMEM)

    masked = mask3 is not None
    mj = pl.BlockSpec((1, 1, blk), lambda b, i, j: (b // heads, 0, j),
                      memory_space=pltpu.VMEM)
    mi = pl.BlockSpec((1, 1, blk), lambda b, i, j: (b // heads, 0, i),
                      memory_space=pltpu.VMEM)
    # dq grid: (bh, q_tile, k_tile) — the k-side mask follows axis 2
    dq_in = [ti, tj, tj, ti, lse_i, lse_i] + ([mj] if masked else [])
    dq_args = [q3, k3, v3, do3, lse, delta3] + ([mask3] if masked else [])
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, nk=n,
                          masked=masked),
        grid=(bh, n, n),
        in_specs=dq_in,
        out_specs=[ti],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q3.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        name=FLASH_BWD_DQ,
    )(*dq_args)[0]

    # grid dims: (bh, k_tile, q_tile) — q is the reduce (innermost) dim;
    # the k-side mask follows axis 1 here
    dkv_in = [tj, ti, ti, tj, lse_j, lse_j] + ([mi] if masked else [])
    dkv_args = [q3, k3, v3, do3, lse, delta3] + ([mask3] if masked else [])
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, nq=n,
                          masked=masked),
        grid=(bh, n, n),
        in_specs=dkv_in,
        out_specs=[ti, ti],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_COMPILER_PARAMS,
        name=FLASH_BWD_DKV,
    )(*dkv_args)
    return dq, dk, dv


def _bwd(causal, scale, res, g):
    return _bwd_impl(causal, scale, res, g)


# ------------------------------------------------------------- public op

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash3(q3, k3, v3, causal, scale):
    o, _ = _fwd(q3, k3, v3, causal, scale)
    return o


def _flash3_fwd(q3, k3, v3, causal, scale):
    o, lse = _fwd(q3, k3, v3, causal, scale)
    return o, (q3, k3, v3, o, lse)


_flash3.defvjp(_flash3_fwd, _bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash3m(q3, k3, v3, mask3, causal, scale, heads):
    o, _ = _fwd(q3, k3, v3, causal, scale, mask3=mask3, heads=heads)
    return o


def _flash3m_fwd(q3, k3, v3, mask3, causal, scale, heads):
    o, lse = _fwd(q3, k3, v3, causal, scale, mask3=mask3, heads=heads)
    return o, (q3, k3, v3, o, lse, mask3)


def _flash3m_bwd(causal, scale, heads, res, g):
    q3, k3, v3, o3, lse, mask3 = res
    dq, dk, dv = _bwd_impl(causal, scale, (q3, k3, v3, o3, lse), g,
                           mask3=mask3, heads=heads)
    return dq, dk, dv, jnp.zeros_like(mask3)


_flash3m.defvjp(_flash3m_fwd, _flash3m_bwd)


def flash_attention(query, key, value, causal: bool = False,
                    scale=None, kv_mask=None):
    """[b, s, h, d] fused attention. Requires s % 128 == 0.

    kv_mask ([b, s], bool/0-1, optional): k-side padding mask — 1 keeps
    the key position, 0 masks it for every query (the padded-batch BERT
    attention mask; reference: the mask input of
    `operators/fused/multihead_matmul_op.cu:1`). Fully-masked rows
    return 0. Mask cotangent is zero (it is a selection, not a value).
    """
    b, s, h, d = query.shape
    if s % 128 != 0:
        raise ValueError(f"flash_attention needs seq % 128 == 0, "
                         f"got {s}")
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    def to3(x):
        return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)

    if kv_mask is None:
        o3 = _flash3(to3(query), to3(key), to3(value), causal, scale)
    else:
        # [batch, 1, s] — heads share the batch row via the kernels'
        # b // heads index map (no h-fold HBM duplication)
        m3 = jnp.asarray(kv_mask, jnp.float32).reshape(b, 1, s)
        o3 = _flash3m(to3(query), to3(key), to3(value), m3, causal, scale,
                      h)
    return jnp.swapaxes(o3.reshape(b, h, s, d), 1, 2)
