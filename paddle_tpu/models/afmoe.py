"""AFMoE decoders (`model_type: afmoe`), as arcee-ai/Trinity-Mini publishes
them: layers of grouped-query attention, sliding-window in three of every
four (`layer_types`) and full in the fourth, and after `num_dense_layers`
dense layers with a SiLU-gated MLP, expert layers with sigmoid top-k
routing, a choice bias, a scale and a shared expert; an untied head.

Attention: q, k, v projections to `num_attention_heads` query heads over
`num_key_value_heads` key/value heads of `head_dim`, an RMSNorm over each
head of q and of k, rotary positions (half-split pairs) on the sliding
layers alone, no position on the full ones (NoPE); a sliding layer's
query p reads the keys p - `sliding_window` + 1 .. p. The output is
multiplied by sigmoid(`gate_proj`(u)) before `o_proj`. Each layer norms
both of its branches on the way in and on the way out:

  h = x + RMSNorm(Attn(RMSNorm(x)));  y = h + RMSNorm(F(RMSNorm(h)))

and under µP (`mup_enabled`) the embedding's output is multiplied by
sqrt(`hidden_size`).

The model can be built as one chip's share of an expert- and
vocabulary-parallel deployment, as `models/deepseek_v3.py` can:
`experts_held` experts from `expert_offset` (the router stays
`num_experts` wide, the shared expert whole) and the first `vocab_held`
rows of the embedding and the head. No exchange is built here.

Training goes through `trainer.build_train_step`: the layers are groups
of alike blocks (`block_groups`), a run of one kind each (dense or
expert, sliding or full), in the published order.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

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
from ..ops.flash_attention import window_kblocks
from ..profiler import (ATTN, ATTN_GATE, ATTN_WINDOW, MLP, RecordEvent,
                        stats)
from .gpt import GPTPretrainingCriterion

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass
class AfmoeConfig:
    """The published keys, and one chip's share. `layer_types`: the kind
    of each layer built (default: `global_attn_every_n_layers` - 1
    sliding layers, then a full one, repeating)."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144       # the dense layers' MLP
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e4
    sliding_window: int = 2048
    global_attn_every_n_layers: int = 4
    layer_types: Optional[Tuple[str, ...]] = None
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    mup_enabled: bool = True
    experts_held: Optional[int] = None  # default: all of them
    expert_offset: int = 0
    vocab_held: Optional[int] = None    # default: the whole vocabulary
    dtype: Any = jnp.bfloat16           # activation / matmul operand dtype
    initializer_range: float = 0.02
    dropout: float = 0.0                # the step builder asks; none

    def __post_init__(self):
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                FULL if (i + 1) % n == 0 else SLIDING
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {SLIDING, FULL}:
            raise ValueError(f"layer_types {self.layer_types} for "
                             f"{self.num_hidden_layers} layers")
        if self.score_func != "sigmoid":
            raise NotImplementedError(f"score_func {self.score_func!r}: "
                                      "not built")
        if (self.n_group, self.topk_group) != (1, 1):
            raise NotImplementedError("group-limited routing (n_group > 1):"
                                      " not built")

    @property
    def vocab_rows(self) -> int:
        return self.vocab_held or self.vocab_size

    def kind(self, i: int) -> Tuple[bool, bool]:
        """(dense, sliding) of layer i."""
        return i < self.num_dense_layers, self.layer_types[i] == SLIDING


def afmoe_tiny(**kw) -> AfmoeConfig:
    """A CPU-sized config in which everything bites: a dense sliding
    layer, then expert layers sliding, full, sliding; 8 experts, 4 held,
    top 2, one shared expert; 4 query heads over 2 key/value heads of 16,
    a window of 8."""
    d = dict(vocab_size=512, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=4,
             num_dense_layers=1, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, sliding_window=8,
             layer_types=(SLIDING, SLIDING, FULL, SLIDING), num_experts=8,
             num_experts_per_tok=2, experts_held=4, vocab_held=256)
    d.update(kw)
    return AfmoeConfig(**d)


class AfmoeAttention(Layer):
    """Gated grouped-query attention, sliding or full (module
    docstring)."""

    def __init__(self, cfg: AfmoeConfig, sliding: bool):
        super().__init__()
        d, hd = cfg.hidden_size, cfg.head_dim
        self.cfg, self.sliding = cfg, sliding
        init, dt = I.Normal(0.0, cfg.initializer_range), cfg.dtype

        def column(n):
            return ColumnParallelLinear(d, n, weight_attr=init,
                                        has_bias=False, gather_output=False,
                                        compute_dtype=dt)
        self.q_proj = column(cfg.num_attention_heads * hd)
        self.k_proj = column(cfg.num_key_value_heads * hd)
        self.v_proj = column(cfg.num_key_value_heads * hd)
        self.gate_proj = column(cfg.num_attention_heads * hd)
        self.q_norm = RMSNorm(hd, cfg.rms_norm_eps)
        self.k_norm = RMSNorm(hd, cfg.rms_norm_eps)
        self.o_proj = RowParallelLinear(
            cfg.num_attention_heads * hd, d, weight_attr=init,
            has_bias=False, input_is_parallel=True, compute_dtype=dt)
        if sliding:
            stats.static("attn.window", cfg.sliding_window)

    def forward(self, u):
        cfg = self.cfg
        b, s, _ = u.shape
        hd = cfg.head_dim
        q = self.q_norm(self.q_proj(u).reshape(b, s, -1, hd))
        k = self.k_norm(self.k_proj(u).reshape(b, s, -1, hd))
        v = self.v_proj(u).reshape(b, s, -1, hd)
        if self.sliding:
            q = F.rotary_embedding(q, cfg.rope_theta)
            k = F.rotary_embedding(k, cfg.rope_theta)
            if s % 128 == 0:
                stats.static("attn.window_kblocks", window_kblocks(
                    s, hd, q.dtype, cfg.sliding_window))
            with jax.named_scope(ATTN_WINDOW):
                o = F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, window=cfg.sliding_window)
        else:
            o = F.scaled_dot_product_attention(q, k, v, is_causal=True)
        with jax.named_scope(ATTN_GATE):
            gate = jax.nn.sigmoid(self.gate_proj(u).astype(jnp.float32))
            o = (o.reshape(b, s, -1).astype(jnp.float32) * gate).astype(
                o.dtype)
        return self.o_proj(o)


class AfmoeDecoderLayer(Layer):
    """`dense`: the MLP is one SiLU-gated MLP of `intermediate_size`;
    else the expert layer. `sliding`: the attention's window."""

    def __init__(self, cfg: AfmoeConfig, dense: bool, sliding: bool):
        super().__init__()
        d, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = RMSNorm(d, eps)
        self.self_attn = AfmoeAttention(cfg, sliding)
        self.post_attention_layernorm = RMSNorm(d, eps)
        self.pre_mlp_layernorm = RMSNorm(d, eps)
        self.post_mlp_layernorm = RMSNorm(d, eps)
        if dense:
            self.mlp = GatedMLP(d, cfg.intermediate_size, cfg.dtype,
                                cfg.initializer_range)
        else:
            self.mlp = MoEMLP(
                d, cfg.moe_intermediate_size, cfg.num_experts,
                top_k=cfg.num_experts_per_tok, experts_held=cfg.experts_held,
                expert_offset=cfg.expert_offset,
                norm_topk_prob=cfg.route_norm, compute_dtype=cfg.dtype,
                initializer_range=cfg.initializer_range, scoring="sigmoid",
                choice_bias=True, routed_scaling_factor=cfg.route_scale,
                shared_width=cfg.num_shared_experts
                * cfg.moe_intermediate_size)

    def forward(self, x):
        with jax.named_scope(ATTN):
            a = self.self_attn(self.input_layernorm(x))
            x = x + self.post_attention_layernorm(a).astype(x.dtype)
        with jax.named_scope(MLP):
            y = self.mlp(self.pre_mlp_layernorm(x))
            return x + self.post_mlp_layernorm(y).astype(x.dtype)


class AfmoeModel(Layer):
    """The decoder trunk; returns the final hidden states [b, s, d]."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.config = cfg
        self.embed_tokens = Embedding(
            cfg.vocab_rows, cfg.hidden_size,
            weight_attr=I.Normal(0.0, cfg.initializer_range))
        self.layers = LayerList([AfmoeDecoderLayer(cfg, *cfg.kind(i))
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        sliding = sum(t == SLIDING for t in cfg.layer_types)
        stats.static("attn.window_layers", sliding)
        stats.static("attn.full_layers", cfg.num_hidden_layers - sliding)

    def embed(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.config.mup_enabled:
            x = x * math.sqrt(self.config.hidden_size)
        return x.astype(self.config.dtype)

    def forward(self, input_ids):
        x = self.embed(input_ids)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class AfmoeForCausalLM(Layer):
    """Trunk + untied head + cross-entropy, over the held vocabulary."""

    step_name = "afmoe_train_step"   # the compiled step's module name

    @RecordEvent("model.build")
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.model = AfmoeModel(cfg)
        self.lm_head = self.create_parameter(
            (cfg.hidden_size, cfg.vocab_rows),
            default_initializer=I.Normal(0.0, cfg.initializer_range))
        self.criterion = GPTPretrainingCriterion()

    @property
    def config(self):
        return self.model.config

    # what a step builder asks of a model (`trainer/contract.py`)
    def block_groups(self):
        """Runs of layers of one kind (dense or expert, sliding or full),
        in order: each run's first layer is its template."""
        cfg, layers = self.config, self.model.layers
        groups = []
        for i in range(cfg.num_hidden_layers):
            if groups and cfg.kind(i) == cfg.kind(i - 1):
                groups[-1][1] += 1
            else:
                groups.append([layers[i], 1])
        return [tuple(g) for g in groups]

    def embed(self, input_ids, position_ids=None):
        # positions are rotary, inside the sliding layers: 0 .. s-1
        return self.model.embed(input_ids)

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
