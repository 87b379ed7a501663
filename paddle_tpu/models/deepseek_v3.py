"""DeepSeek-V3-shaped decoders (`model_type: deepseek_v3`; the published
keys are Hugging Face's `DeepseekV3Config`), as
kakaocorp/kanana-2-30b-a3b-instruct-2601 uses them: pre-RMSNorm layers of
latent attention and, after `first_k_dense_replace` dense layers with a
SiLU-gated MLP, expert layers with sigmoid top-k routing, a choice bias,
a scaling factor and shared experts; an untied head.

Latent attention, as it trains (`q_lora_rank` null): queries of
`qk_nope_head_dim + qk_rope_head_dim` a head straight from the hidden
state; keys and values from a `kv_lora_rank`-wide latent under an RMSNorm,
expanded to `qk_nope_head_dim + v_head_dim` a head, beside ONE rotary key
head of `qk_rope_head_dim` that every query head reads; rotary positions
over interleaved pairs on the rotary parts alone; scores over the whole
query width, values `v_head_dim` wide.

The model can be built as one chip's share of an expert- and
vocabulary-parallel deployment, as `models/keye.py` can: `experts_held`
experts from `expert_offset` (the router stays `n_routed_experts` wide,
the shared expert whole) and the first `vocab_held` rows of the embedding
and the head. No exchange is built here.

Training goes through `trainer.build_train_step`: the dense layers and the
expert layers are two groups of alike blocks (`block_groups`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..distributed.meta_parallel.moe import GatedMLP, MoEMLP
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, _constrain)
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..nn.layer_common import Embedding, LayerList
from ..nn.layer_conv_norm import RMSNorm
from ..profiler import ATTN, ATTN_LATENT, MLP, RecordEvent
from .gpt import GPTPretrainingCriterion


@dataclasses.dataclass
class DeepseekV3Config:
    """The published keys, and one chip's share."""
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 6144       # the dense layers' MLP
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    q_lora_rank: Optional[int] = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    rope_interleave: bool = True
    first_k_dense_replace: int = 1
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    scoring_func: str = "sigmoid"
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    n_group: int = 1
    topk_group: int = 1
    experts_held: Optional[int] = None  # default: all of them
    expert_offset: int = 0
    vocab_held: Optional[int] = None    # default: the whole vocabulary
    dtype: Any = jnp.bfloat16           # activation / matmul operand dtype
    initializer_range: float = 0.02
    dropout: float = 0.0                # the builder asks; there is none

    def __post_init__(self):
        if self.q_lora_rank is not None:
            raise NotImplementedError("queries through a latent "
                                      "(q_lora_rank): not built")
        if (self.n_group, self.topk_group) != (1, 1):
            raise NotImplementedError("group-limited routing (n_group > 1):"
                                      " not built")

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held or self.vocab_size


def deepseek_v3_tiny(**kw) -> DeepseekV3Config:
    """A CPU-sized config in which everything bites: one dense layer and
    two expert layers; 8 experts, 4 held, top 2, one shared expert of two
    widths; scores 24 wide (16 + 8 rotary) over values of 16."""
    d = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=8,
             n_shared_experts=2, num_experts_per_tok=2, experts_held=4,
             vocab_held=256)
    d.update(kw)
    return DeepseekV3Config(**d)


class DeepseekV3Attention(Layer):
    """Latent attention (module docstring)."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        d, h = cfg.hidden_size, cfg.num_attention_heads
        self.cfg = cfg
        init, dt = I.Normal(0.0, cfg.initializer_range), cfg.dtype

        def column(n_in, n_out):
            return ColumnParallelLinear(n_in, n_out, weight_attr=init,
                                        has_bias=False, gather_output=False,
                                        compute_dtype=dt)
        self.q_proj = column(d, h * cfg.qk_head_dim)
        self.kv_a_proj_with_mqa = column(
            d, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
        self.kv_a_layernorm = RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps)
        self.kv_b_proj = column(
            cfg.kv_lora_rank, h * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = RowParallelLinear(
            h * cfg.v_head_dim, d, weight_attr=init, has_bias=False,
            input_is_parallel=True, compute_dtype=dt)

    def forward(self, u):
        cfg = self.cfg
        b, s, _ = u.shape
        dn, dr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        with jax.named_scope(ATTN_LATENT):
            q = self.q_proj(u).reshape(b, s, -1, dn + dr)
            kv_a = self.kv_a_proj_with_mqa(u)
            kv = self.kv_b_proj(self.kv_a_layernorm(
                kv_a[..., :cfg.kv_lora_rank]))
            kv = kv.reshape(b, s, -1, dn + cfg.v_head_dim)
        k_rope = kv_a[..., None, cfg.kv_lora_rank:]

        def rotary(x):
            return F.rotary_embedding(x, cfg.rope_theta,
                                      interleaved=cfg.rope_interleave)
        # the attention reads the projections' arrays where they lie: kv
        # whole, the rotary query part head-major as its fusion writes it
        o = F.latent_attention(q[..., :dn],
                               jnp.swapaxes(rotary(q[..., dn:]), 1, 2), kv,
                               rotary(k_rope))
        return self.o_proj(o.reshape(b, s, -1))


class DeepseekV3DecoderLayer(Layer):
    """`dense`: the MLP is one SiLU-gated MLP of `intermediate_size`;
    else the expert layer."""

    def __init__(self, cfg: DeepseekV3Config, dense: bool):
        super().__init__()
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = DeepseekV3Attention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        if dense:
            self.mlp = GatedMLP(cfg.hidden_size, cfg.intermediate_size,
                                cfg.dtype, cfg.initializer_range)
        else:
            self.mlp = MoEMLP(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, top_k=cfg.num_experts_per_tok,
                experts_held=cfg.experts_held,
                expert_offset=cfg.expert_offset,
                norm_topk_prob=cfg.norm_topk_prob, compute_dtype=cfg.dtype,
                initializer_range=cfg.initializer_range,
                scoring=cfg.scoring_func,
                choice_bias=cfg.scoring_func == "sigmoid",
                routed_scaling_factor=cfg.routed_scaling_factor,
                shared_width=cfg.n_shared_experts
                * cfg.moe_intermediate_size)

    def forward(self, x):
        with jax.named_scope(ATTN):
            x = x + self.self_attn(self.input_layernorm(x)).astype(x.dtype)
        with jax.named_scope(MLP):
            return x + self.mlp(
                self.post_attention_layernorm(x)).astype(x.dtype)


class DeepseekV3Model(Layer):
    """The decoder trunk; returns the final hidden states [b, s, d]."""

    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.config = cfg
        self.embed_tokens = Embedding(
            cfg.vocab_rows, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([
            DeepseekV3DecoderLayer(cfg, dense=i < cfg.first_k_dense_replace)
            for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids).astype(self.config.dtype)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class DeepseekV3ForCausalLM(Layer):
    """Trunk + untied head + cross-entropy, over the held vocabulary."""

    step_name = "deepseek_v3_train_step"   # the compiled step's module

    @RecordEvent("model.build")
    def __init__(self, cfg: DeepseekV3Config):
        super().__init__()
        self.model = DeepseekV3Model(cfg)
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_rows),
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        self.criterion = GPTPretrainingCriterion()

    @property
    def config(self):
        return self.model.config

    # what a step builder asks of a model (`trainer/contract.py`)
    def block_groups(self):
        cfg, layers = self.config, self.model.layers
        dense = min(cfg.first_k_dense_replace, cfg.num_hidden_layers)
        groups = [(layers[0], dense),
                  (layers[-1], cfg.num_hidden_layers - dense)]
        return [(template, n) for template, n in groups if n]

    def embed(self, input_ids, position_ids=None):
        # positions are rotary, inside the layers: 0 .. s-1
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
