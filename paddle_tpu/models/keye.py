"""Keye-VL-2.0-30B-A3B's text decoder (Kwai-Keye; Qwen3-MoE's key names
plus `sa_config`): pre-RMSNorm layers of grouped-query attention under a
learned top-k key selection (DeepSeek-V3.2's lightning indexer) and a
top-8-of-128 SiLU-gated expert layer, rotary positions, an untied head.

The model can be built as one chip's share of an expert- and
vocabulary-parallel deployment: `experts_held` experts from
`expert_offset` (the router stays `num_experts` wide) and the first
`vocab_held` rows of the embedding and the head. What the other chips
would add (their experts' outputs, their vocabulary's logits) is theirs:
no exchange is built here. The vision tower is not part of this file.

Training goes through `trainer.build_train_step`, like GPT: the layers are
uniform, so the builder stacks their leaves and scans one template.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..distributed.meta_parallel.moe import MoEMLP
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, _constrain)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layer_common import Embedding, LayerList
from ..nn.layer_conv_norm import LayerNorm, RMSNorm
from ..ops.index_select import topk_selection
from ..profiler import ATTN, INDEXER, INDEXER_SELECT, MLP, RecordEvent, stats
from .gpt import GPTPretrainingCriterion


@dataclasses.dataclass
class KeyeConfig:
    """The published keys of the language model, and one chip's share."""
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e7
    num_experts: int = 128
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    norm_topk_prob: bool = True
    indexer_num_heads: int = 16         # sa_config
    indexer_head_dim: int = 64
    indexer_topk: int = 2048
    experts_held: Optional[int] = None  # default: all of them
    expert_offset: int = 0
    vocab_held: Optional[int] = None    # default: the whole vocabulary
    dtype: Any = jnp.bfloat16           # activation / matmul operand dtype
    initializer_range: float = 0.02
    dropout: float = 0.0                # the builder asks; there is none

    @property
    def num_layers(self) -> int:
        return self.num_hidden_layers

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held or self.vocab_size


def keye_tiny(**kw) -> KeyeConfig:
    """A CPU-sized config in which everything bites: 8 experts, 4 held,
    top 2; 4 query heads over 2 key/value heads; top 16 keys."""
    d = dict(vocab_size=512, hidden_size=64, num_hidden_layers=2,
             num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
             indexer_num_heads=2, indexer_head_dim=8, indexer_topk=16,
             experts_held=4, vocab_held=256)
    d.update(kw)
    return KeyeConfig(**d)


class KeyeAttention(Layer):
    """Grouped-query attention over the keys the indexer selects."""

    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        d, hd = cfg.hidden_size, cfg.head_dim
        self.cfg = cfg
        init, dt = I.Normal(0.0, cfg.initializer_range), cfg.dtype

        def column(n):
            return ColumnParallelLinear(d, n, weight_attr=init,
                                        has_bias=False, gather_output=False,
                                        compute_dtype=dt)
        self.q_proj = column(cfg.num_attention_heads * hd)
        self.k_proj = column(cfg.num_key_value_heads * hd)
        self.v_proj = column(cfg.num_key_value_heads * hd)
        self.q_norm = RMSNorm(hd, cfg.rms_norm_eps)
        self.k_norm = RMSNorm(hd, cfg.rms_norm_eps)
        self.o_proj = RowParallelLinear(
            cfg.num_attention_heads * hd, d, weight_attr=init,
            has_bias=False, input_is_parallel=True, compute_dtype=dt)
        # the indexer: its own small queries, one key head, head weights
        self.index_q = column(cfg.indexer_num_heads * cfg.indexer_head_dim)
        self.index_k = column(cfg.indexer_head_dim)
        self.index_k_norm = LayerNorm(cfg.indexer_head_dim, epsilon=1e-6)
        self.index_w = column(cfg.indexer_num_heads)
        stats.static("indexer.topk", cfg.indexer_topk)

    def selection(self, u):
        """int8 [b, s, s]: the keys each query reads. No gradient."""
        cfg = self.cfg
        b, s, _ = u.shape
        u = jax.lax.stop_gradient(u)
        with jax.named_scope(INDEXER):
            q = self.index_q(u).reshape(b, s, cfg.indexer_num_heads,
                                        cfg.indexer_head_dim)
            k = self.index_k_norm(self.index_k(u))[:, :, None]
            q = F.rotary_embedding(q, cfg.rope_theta)
            k = F.rotary_embedding(k, cfg.rope_theta)[:, :, 0]
            w = self.index_w(u)
        with jax.named_scope(INDEXER_SELECT):
            return topk_selection(q, k.astype(q.dtype), w, cfg.indexer_topk)

    def forward(self, u):
        cfg = self.cfg
        b, s, _ = u.shape
        hd = cfg.head_dim
        q = self.q_norm(self.q_proj(u).reshape(b, s, -1, hd))
        k = self.k_norm(self.k_proj(u).reshape(b, s, -1, hd))
        v = self.v_proj(u).reshape(b, s, -1, hd)
        q = F.rotary_embedding(q, cfg.rope_theta)
        k = F.rotary_embedding(k, cfg.rope_theta)
        # named so that a remat policy can keep it: the top-k is the part
        # of a replayed layer that no backward kernel needs twice
        sel = checkpoint_name(self.selection(u), "attn_selection")
        o = F.selected_attention(q, k, v, sel)
        return self.o_proj(o.reshape(b, s, -1))


class KeyeDecoderLayer(Layer):
    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        self.ln1 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.attn = KeyeAttention(cfg)
        self.ln2 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.moe = MoEMLP(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts,
            top_k=cfg.num_experts_per_tok, experts_held=cfg.experts_held,
            expert_offset=cfg.expert_offset,
            norm_topk_prob=cfg.norm_topk_prob, compute_dtype=cfg.dtype,
            initializer_range=cfg.initializer_range)

    def forward(self, x):
        with jax.named_scope(ATTN):
            x = x + self.attn(self.ln1(x)).astype(x.dtype)
        with jax.named_scope(MLP):
            return x + self.moe(self.ln2(x)).astype(x.dtype)


class KeyeModel(Layer):
    """The decoder trunk; returns the final hidden states [b, s, d]."""

    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = Embedding(
            cfg.vocab_rows, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([KeyeDecoderLayer(cfg)
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids).astype(self.config.dtype)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class KeyeForCausalLM(Layer):
    """Trunk + untied head + cross-entropy, over the held vocabulary."""

    step_name = "keye_train_step"   # the compiled step's module name

    @RecordEvent("model.build")
    def __init__(self, cfg: KeyeConfig):
        super().__init__()
        self.model = KeyeModel(cfg)
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_rows),
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        self.criterion = GPTPretrainingCriterion()

    @property
    def config(self):
        return self.model.config

    # what a step builder asks of a model (`trainer/contract.py`)
    def block_groups(self):
        return [(self.model.layers[0], self.config.num_layers)]

    def embed(self, input_ids, position_ids=None):
        # positions are rotary, inside the layers; text only: 0 .. s-1
        x = self.model.embed_tokens(input_ids)
        return x.astype(self.config.dtype)

    def final_norm(self, hidden):
        return self.model.norm(hidden)

    def logits(self, hidden):
        cdt = self.config.dtype
        logits = jnp.einsum("bsd,dv->bsv", hidden.astype(cdt),
                            jnp.asarray(self.lm_head).astype(cdt),
                            preferred_element_type=jnp.float32)
        return _constrain(logits, ("data", "sharding"), None, "model")

    def forward(self, input_ids, labels=None, loss_mask=None):
        logits = self.logits(self.model(input_ids))
        if labels is None:
            return logits
        return self.criterion(logits, labels, loss_mask)
