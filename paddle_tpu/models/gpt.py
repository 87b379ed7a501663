"""GPT — decoder-only LM, the hybrid-parallel flagship (BASELINE config 3).

Reference model: PaddleNLP GPT (`examples/language_model/gpt`), built on the
reference's meta-parallel layers (`mp_layers.py`, `pp_layers.py`). Here the
same architecture is built TPU-first:

  * uniform pre-LN decoder blocks → stackable: a step builder
    (`paddle_tpu.trainer`) traces one block and scans it over the layer
    dim, or pipelines it where the mesh has a 'pipe' axis;
  * TP via the GSPMD mp_layers (weights carry PartitionSpecs; XLA inserts
    the ICI collectives);
  * tied embedding/output head; vocab-parallel softmax CE;
  * everything bf16-friendly: matmuls hit the MXU, softmax/CE in fp32.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layer_common import Dropout, Embedding, LayerList
from ..nn.layer_conv_norm import LayerNorm
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, _constrain)
from ..profiler import ATTN, MLP, RecordEvent


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_layers: int = 24
    num_heads: int = 16
    ffn_hidden: Optional[int] = None          # default 4*hidden
    max_position_embeddings: int = 1024
    dropout: float = 0.0                       # pretraining bench default
    dtype: Any = jnp.bfloat16                  # activation/weight dtype
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size


def gpt_tiny(**kw) -> GPTConfig:
    # preset values are DEFAULTS: callers may override any of them
    # (e.g. max_position_embeddings for long-context decode exports)
    d = dict(vocab_size=512, hidden_size=64, num_layers=4,
             num_heads=4, max_position_embeddings=128)
    d.update(kw)
    return GPTConfig(**d)


def gpt_345m(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                     num_heads=16, **kw)


def gpt_760m(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=1536, num_layers=24,
                     num_heads=16, **kw)


def gpt_1p3b(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                     num_heads=32, **kw)


def gpt_2p6b(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=2560, num_layers=32,
                     num_heads=32, **kw)


def gpt_6p7b(**kw) -> GPTConfig:
    return GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=32,
                     num_heads=32, **kw)


def ernie_10b(**kw) -> GPTConfig:
    """ERNIE-3.0 10B-class decoder config (BASELINE config 5): train with
    zero_stage=3 + sharding axis so per-chip param residency is
    params/shard_axis (reference bar: static ShardingOptimizer ZeRO-2 +
    offload, `sharding_optimizer.py:87-1385`)."""
    kw.setdefault("max_position_embeddings", 2048)
    return GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=48,
                     num_heads=64, **kw)


class GPTDecoderLayer(Layer):
    """Pre-LN decoder block. TP layout: fused QKV column-parallel, attention
    output row-parallel; MLP column→row (Megatron pattern, reference
    mp_layers usage in PaddleNLP GPTDecoderLayer)."""

    # sequence-parallel ring attention, set by the step builder
    # (`trainer/trunk.py`) for the length of one trace when the mesh has a
    # 'sequence' axis
    _sp_attention = None

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        d = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = d // cfg.num_heads
        init = I.Normal(0.0, cfg.initializer_range)
        dt = cfg.dtype
        self.ln1 = LayerNorm(d)
        self.qkv = ColumnParallelLinear(d, 3 * d, weight_attr=init,
                                        gather_output=False,
                                        compute_dtype=dt)
        self.out_proj = RowParallelLinear(d, d, weight_attr=init,
                                          input_is_parallel=True,
                                          compute_dtype=dt)
        self.ln2 = LayerNorm(d)
        self.fc1 = ColumnParallelLinear(d, cfg.ffn_hidden, weight_attr=init,
                                        gather_output=False,
                                        compute_dtype=dt)
        self.fc2 = RowParallelLinear(cfg.ffn_hidden, d, weight_attr=init,
                                     input_is_parallel=True,
                                     compute_dtype=dt)
        self.dropout = Dropout(cfg.dropout)
        self._dtype_ = dt

    def forward(self, x):
        with jax.named_scope(ATTN):
            x = self._attn(x)
        with jax.named_scope(MLP):
            y = self.fc2(F.gelu(self.fc1(self.ln2(x)), approximate=True))
            return x + self.dropout(y).astype(x.dtype)

    def _attn(self, x):
        b, s, d = x.shape
        # LN in fp32, matmul in compute dtype. The fused weight's columns
        # are [3, heads, head_dim]: a contiguous column shard is NOT a head
        # group, so the product comes sharded by heads from
        # `project_heads`, never reshaped from a column-sharded [b, s, 3d]
        qkv = self.qkv.project_heads(self.ln1(x), 3, self.num_heads)
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        sp_attn = self._sp_attention
        if sp_attn is not None:
            # sequence-parallel ring attention over the 'sequence' mesh
            # axis (set by the step builder when the mesh has one)
            attn = sp_attn(q, k, v)
        else:
            attn = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                                  training=self.training)
        # named so the "dots_attn" remat policy can SAVE it (skips the
        # flash-kernel forward replay in the backward pass)
        from jax.ad_checkpoint import checkpoint_name
        attn = checkpoint_name(attn, "attn_out")
        attn = jnp.reshape(attn, (b, s, d))
        return x + self.dropout(self.out_proj(attn)).astype(x.dtype)


class GPTEmbeddings(Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        # position table is small — plain replicated Embedding
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size, weight_attr=init)
        self.dropout = Dropout(cfg.dropout)
        self._dtype_ = cfg.dtype

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            position_ids = jnp.arange(input_ids.shape[-1], dtype=jnp.int32)
            position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
        x = (F.embedding(input_ids, self.word_embeddings.weight) +
             F.embedding(position_ids, self.position_embeddings.weight))
        return self.dropout(x.astype(self._dtype_))


class GPTModel(Layer):
    """Decoder-only trunk; returns final hidden states [b, s, d]."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = GPTEmbeddings(cfg)
        self.layers = LayerList([GPTDecoderLayer(cfg)
                                 for _ in range(cfg.num_layers)])
        self.ln_f = LayerNorm(cfg.hidden_size)

    def forward(self, input_ids, position_ids=None):
        x = self.embeddings(input_ids, position_ids)
        x = _constrain(x, ("data", "sharding"), None, None)
        for blk in self.layers:
            x = blk(x)
        return self.ln_f(x)


class GPTPretrainingCriterion(Layer):
    """Vocab-parallel softmax CE over tied-logits (reference:
    GPTPretrainingCriterion + `c_softmax_with_cross_entropy`)."""

    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-1)

    def forward(self, logits, labels, loss_mask=None):
        loss = self.ce(logits, labels)[..., 0]
        if loss_mask is not None:
            m = loss_mask.astype(jnp.float32)
            return jnp.sum(loss * m) / jnp.maximum(jnp.sum(m), 1.0)
        return jnp.mean(loss)


class GPTForPretraining(Layer):
    @RecordEvent("model.build")
    def __init__(self, cfg_or_model):
        super().__init__()
        if isinstance(cfg_or_model, GPTModel):
            self.gpt = cfg_or_model
        else:
            self.gpt = GPTModel(cfg_or_model)
        self.criterion = GPTPretrainingCriterion()

    @property
    def config(self):
        return self.gpt.config

    # the pieces a step builder asks for beside `config`, `logits` and
    # `criterion` (`paddle_tpu/trainer/contract.py`): one group of alike
    # blocks to scan over stacked leaves, the embedding and the last norm
    def block_groups(self):
        return [(self.gpt.layers[0], self.config.num_layers)]

    def embed(self, input_ids, position_ids=None):
        return self.gpt.embeddings(input_ids, position_ids)

    def final_norm(self, hidden):
        return self.gpt.ln_f(hidden)

    def logits(self, hidden):
        # tied head: [b,s,d] @ [V,d]^T — vocab dim sharded over 'model'.
        # bf16 operands on the MXU, fp32 accumulation (fp32 operands would
        # run the biggest matmul in the model at 1/4 MXU rate)
        cdt = self.config.dtype
        w = jnp.asarray(self.gpt.embeddings.word_embeddings.weight)
        logits = jnp.einsum("bsd,vd->bsv", hidden.astype(cdt),
                            w.astype(cdt),
                            preferred_element_type=jnp.float32)
        return _constrain(logits, ("data", "sharding"), None, "model")

    def forward(self, input_ids, labels=None, loss_mask=None,
                position_ids=None):
        hidden = self.gpt(input_ids, position_ids)
        logits = self.logits(hidden)
        if labels is None:
            return logits
        return self.criterion(logits, labels, loss_mask)


# --------------------------------------------------------------------------
# KV-cached decode-step export (native serving DECODE workload)
# --------------------------------------------------------------------------

def make_gpt_decode_step(model: GPTForPretraining, context: int,
                         width: int = 1):
    """Build the decode-step function for the native predictor's
    KV-cache convention (csrc/ptpu_predictor.cc kv_plan/kv_attach):

      step(ids[B,W] i32, pos[B] i32, k0, v0, ..., k_{L-1}, v_{L-1})
        -> (logits, nk0, nv0, ..., nk_{L-1}, nv_{L-1})

    ``W = width`` is the number of positions fed per session per step:
    width 1 is the classic autoregressive step (logits ``[B, V]``, the
    shape the r9 engine pinned); width k+1 is the speculative-decoding
    VERIFY artifact — the target model scores a draft's k proposals
    plus the bonus position in ONE pass (logits ``[B, W, V]``, one row
    per fed position). Cache operands are ``[B, context, heads,
    head_dim]`` float32; each ``nk``/``nv`` is the fed window's
    ``[B, W, heads, head_dim]`` projection, which the C runtime
    appends into the session at positions ``pos .. pos+W-1``.
    Attention runs over ``concat(cache, window)``: cache positions
    ``j < pos`` are live, the zero tail ``[pos, P)`` is masked, and
    the window is causal (window key w' attends from window query
    ``w >= w'``) — a fixed-shape graph, so it loads onto the planned
    zero-alloc arena and the attention block fuses into PtpuAttention
    (and onto the block-table PtpuPagedAttention under kv_attach)
    exactly like the width-1 export."""
    cfg = model.config
    if width < 1:
        raise ValueError(f"width must be >= 1 (got {width})")
    if context < 1 or context + width > cfg.max_position_embeddings:
        raise ValueError(
            f"context {context} + width {width} needs "
            f"max_position_embeddings > context + width - 1 "
            f"(got {cfg.max_position_embeddings})")
    W = width

    def block_step(blk, x, k_cache, v_cache, pos):
        b = x.shape[0]
        h, hd = blk.num_heads, blk.head_dim
        res = x
        qkv = blk.qkv(blk.ln1(x))
        qkv = jnp.reshape(qkv, (b, W, 3, h, hd))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        kcat = jnp.concatenate([k_cache, k], axis=1)   # [b, P+W, h, hd]
        vcat = jnp.concatenate([v_cache, v], axis=1)
        P = k_cache.shape[1]
        j = jnp.arange(P + W, dtype=jnp.int32)
        wq = jnp.arange(W, dtype=jnp.int32)
        # [b, W, P+W]: cache keys below the session length, plus the
        # causal lower triangle of the fed window itself
        valid = (j[None, None, :] < pos[:, None, None]) | \
            ((j[None, None, :] >= P) &
             (j[None, None, :] - P <= wq[None, :, None]))
        attn = F.scaled_dot_product_attention(
            q, kcat, vcat, attn_mask=valid[:, None, :, :],
            training=False)
        attn = jnp.reshape(attn, (b, W, h * hd))
        x = res + blk.out_proj(attn)
        res = x
        y = blk.fc2(F.gelu(blk.fc1(blk.ln2(x)), approximate=True))
        return res + y, k, v

    def step(ids, pos, *caches):
        wq = jnp.arange(W, dtype=jnp.int32)
        x = model.gpt.embeddings(ids, pos[:, None] + wq[None, :])
        news = []
        for li, blk in enumerate(model.gpt.layers):
            x, nk, nv = block_step(blk, x, caches[2 * li],
                                   caches[2 * li + 1], pos)
            news.append(nk)
            news.append(nv)
        hidden = model.gpt.ln_f(x)
        logits = model.logits(hidden)   # [B, W, V]
        if W == 1:
            return (logits[:, 0], *news)
        return (logits, *news)

    return step


def export_gpt_decode(model: GPTForPretraining, path: str, batch: int,
                      context: int, width: int = 1) -> str:
    """Export the KV decode-step artifact for ``model`` at a fixed
    decode ``batch``, cache ``context`` (positions per session) and
    step ``width`` (positions fed per step — width 1 is the normal
    autoregressive step; width k+1 is the speculative-decoding verify
    artifact, see ``make_gpt_decode_step``). Returns the written
    path. Serve it with ``inference.create_server(...,
    decode_model=path)`` (width 1) or ``spec_verify_model=path``
    (width k+1), or drive it directly over
    ``ptpu_predictor_kv_plan``/``decode_step``."""
    import numpy as onp
    from ..onnx.converter import trace_to_onnx
    cfg = model.config
    step = make_gpt_decode_step(model, context, width)
    h, hd = cfg.num_heads, cfg.hidden_size // cfg.num_heads
    args = [jnp.zeros((batch, width), jnp.int32),
            jnp.zeros((batch,), jnp.int32)]
    for _ in range(cfg.num_layers):
        args.append(jnp.zeros((batch, context, h, hd), jnp.float32))
        args.append(jnp.zeros((batch, context, h, hd), jnp.float32))
    data = trace_to_onnx(step, tuple(args))
    if not path.endswith(".onnx"):
        path = path + ".onnx"
    with open(path, "wb") as f:
        f.write(onp.frombuffer(data, dtype=onp.uint8).tobytes()
                if not isinstance(data, bytes) else data)
    return path
