"""Sequence/context parallelism — ring attention + Ulysses (all-to-all).

BEYOND-REFERENCE capability (SURVEY.md §5 "Long-context / sequence
parallelism: Absent ... The TPU build must therefore add SP/CP"). The only
reference hook is the `alltoall` collective
(`operators/collective/alltoall_op.cc`), which is the Ulysses building
block.

Two schemes over the 'sequence' mesh axis, both used inside
`jax.shard_map`:

* **ring_attention** — q/k/v sharded on the sequence dim; K/V blocks
  rotate around the ring via `lax.ppermute` over ICI while each chip
  accumulates its queries' attention in flash style (running max /
  normalizer — the S×S score matrix never materializes globally).
  Communication overlaps compute; memory per chip is O(S/sp · S/sp).
* **ulysses_attention** — `lax.all_to_all` reshards [B, S/sp, H, D] →
  [B, S, H/sp, D], runs dense per-head attention locally, then reshards
  back. Cheaper collectives for moderate S; requires heads % sp == 0.

Both are reverse-differentiable (scan + ppermute/all_to_all transpose
rules) so they drop straight into training.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def ring_attention(q, k, v, axis_name: str = "sequence",
                   causal: bool = False, scale: Optional[float] = None,
                   positions=None):
    """Blockwise ring attention on per-chip shards.

    q, k, v: [b, s_local, h, d] — the local sequence shard (call inside
    shard_map with in_specs sharding dim 1 over `axis_name`).
    Returns [b, s_local, h, d].

    `positions` ([s_local] int32, optional): GLOBAL sequence position of
    each local token, for non-contiguous layouts — zigzag load balancing
    (`zigzag_permutation`) hands every rank an early and a late chunk so
    the causal mask wastes no rank. Defaults to the contiguous layout
    rank*s + arange(s). K positions travel around the ring with their
    K/V blocks.
    """
    sp = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, s, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    # [b, h, s, d] compute layout
    qh = jnp.swapaxes(q, 1, 2).astype(jnp.float32) * scale
    kh0 = jnp.swapaxes(k, 1, 2)
    vh0 = jnp.swapaxes(v, 1, 2)

    if positions is None:
        q_pos = idx * s + jnp.arange(s)                  # global q positions
    else:
        q_pos = jnp.asarray(positions, jnp.int32)
    k_pos0 = q_pos

    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(carry, i):
        o, m, l, kh, vh, k_pos = carry
        scores = jnp.einsum("bhqd,bhkd->bhqk", qh,
                            kh.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]       # [sq, sk]
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
        m_blk = jnp.max(scores, axis=-1)                  # [b,h,sq]
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows (all -inf): keep m finite
        m_new = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(scores - m_new[..., None])            # masked → exp(-inf)=0
        corr = jnp.exp(m - m_new)                         # rescale old acc
        l_new = l * corr + jnp.sum(p, axis=-1)
        o_new = o * corr[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p, vh.astype(jnp.float32))
        kh_n = lax.ppermute(kh, axis_name, perm)
        vh_n = lax.ppermute(vh, axis_name, perm)
        kp_n = lax.ppermute(k_pos, axis_name, perm)
        return (o_new, m_new, l_new, kh_n, vh_n, kp_n), None

    o0 = jnp.zeros((b, h, s, d), jnp.float32)
    m0 = jnp.full((b, h, s), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s), jnp.float32)
    (o, m, l, _, _, _), _ = lax.scan(step, (o0, m0, l0, kh0, vh0, k_pos0),
                                     jnp.arange(sp))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def zigzag_permutation(seq_len: int, sp: int):
    """Zigzag sequence layout for causal ring attention load balance.

    Contiguous sharding gives rank 0 almost no unmasked work and rank
    sp-1 nearly all of it. The zigzag order hands rank r chunks r and
    2*sp-1-r (seq split into 2*sp chunks), so every rank sees the same
    causal-mask density. Returns an int32 numpy array `order` of length
    seq_len: token j of the zigzag layout is original position order[j];
    rank r's shard is order[r*seq_len//sp : (r+1)*seq_len//sp].
    """
    import numpy as np
    if seq_len % (2 * sp):
        raise ValueError(f"seq_len {seq_len} must be a multiple of "
                         f"2*sp={2 * sp}")
    chunk = seq_len // (2 * sp)
    order = []
    for r in range(sp):
        order.extend(range(r * chunk, (r + 1) * chunk))
        order.extend(range((2 * sp - 1 - r) * chunk,
                           (2 * sp - r) * chunk))
    return np.asarray(order, np.int32)


def ulysses_attention(q, k, v, axis_name: str = "sequence",
                      causal: bool = False, scale: Optional[float] = None,
                      attn_fn=None):
    """DeepSpeed-Ulysses resharding attention on per-chip shards.

    q, k, v: [b, s_local, h, d]; requires h % sp == 0.
    """
    sp = lax.psum(1, axis_name)   # axis size — static at trace time
    h = q.shape[2]
    if h % sp != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({h}) divisible by the "
            f"'{axis_name}' axis size ({sp}); use ring attention instead")

    def to_seq(x):   # [b, s/sp, h, d] -> [b, s, h/sp, d]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    def to_heads(x):  # [b, s, h/sp, d] -> [b, s/sp, h, d]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    qs, ks, vs = to_seq(q), to_seq(k), to_seq(v)
    if attn_fn is None:
        from ...nn.functional.attention import _xla_attention
        out = _xla_attention(qs, ks, vs, None, 0.0, causal, False, scale)
    else:
        out = attn_fn(qs, ks, vs)
    return to_heads(out)


def make_sp_attention(mesh, mode: str = "ring", causal: bool = False,
                      axis_name: str = "sequence", zigzag: bool = False,
                      jit: bool = True):
    """Wrap ring/ulysses attention as a global-view function on sequence-
    sharded [b, s, h, d] arrays via shard_map (other mesh axes stay auto).

    zigzag (ring+causal only): inputs are expected in the zigzag layout
    (`zigzag_permutation` applied along the sequence dim); positions are
    threaded through the ring so the causal mask is exact. `jit=False`
    returns the raw shard_map for embedding inside an outer jit trace
    (`trainer.build_train_step`)."""
    if mode not in ("ring", "ulysses"):
        raise ValueError(f"mode must be 'ring' or 'ulysses', got {mode!r}")
    if zigzag and mode != "ring":
        raise ValueError("zigzag layout applies to ring attention")
    from jax.sharding import PartitionSpec as P
    spec = P(None, axis_name, None, None)
    sp = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]

    if mode == "ulysses":
        inner = partial(ulysses_attention, axis_name=axis_name,
                        causal=causal)
        wrapped = jax.shard_map(
            lambda q, k, v: inner(q, k, v),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            axis_names={axis_name}, check_vma=False)
        # partial-manual shard_map (axis_names ⊂ mesh axes) only resolves
        # inside a jit trace; eager calls misread the unmentioned axes
        return jax.jit(wrapped) if jit else wrapped

    ring = jax.shard_map(
        lambda q, k, v, pos: ring_attention(q, k, v, axis_name=axis_name,
                                            causal=causal, positions=pos),
        mesh=mesh, in_specs=(spec, spec, spec, P(axis_name)),
        out_specs=spec, axis_names={axis_name}, check_vma=False)

    def call(q, k, v):
        s = q.shape[1]
        if zigzag:
            pos = jnp.asarray(zigzag_permutation(s, sp), jnp.int32)
        else:
            pos = jnp.arange(s, dtype=jnp.int32)
        return ring(q, k, v, pos)

    return jax.jit(call) if jit else call
