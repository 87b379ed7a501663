"""BERT — encoder LM, the data-parallel north star (BASELINE config 2:
BERT-base pretraining ≥35% MFU).

Reference model: PaddleNLP BERT on the reference's `paddle.nn` layers
(`nn/layer/transformer.py` TransformerEncoder). TPU-first build: post-LN
encoder blocks with the same stackable structure as GPT (lax.scan over
layers), bf16 matmuls, fp32 softmax/LN, MLM+NSP pretraining heads with the
tied decoder.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer, functional_call, trainable_state
from ..nn.layer_common import Dropout, Embedding, LayerList, Linear
from ..nn.layer_conv_norm import LayerNorm
from ..distributed.meta_parallel.mp_layers import (
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    _constrain)
from ..profiler import ATTN, MLM_HEAD, MLP, RecordEvent


@dataclasses.dataclass
class BertConfig:
    vocab_size: int = 30528          # padded to 64 for MXU-friendly head
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    ffn_hidden: Optional[int] = None
    max_position_embeddings: int = 512
    type_vocab_size: int = 2
    dropout: float = 0.0
    dtype: Any = jnp.bfloat16
    initializer_range: float = 0.02

    def __post_init__(self):
        if self.ffn_hidden is None:
            self.ffn_hidden = 4 * self.hidden_size


def bert_tiny(**kw) -> BertConfig:
    for k, v in dict(vocab_size=512, hidden_size=64, num_layers=2,
                     num_heads=4, max_position_embeddings=128).items():
        kw.setdefault(k, v)
    return BertConfig(**kw)


def bert_base(**kw) -> BertConfig:
    return BertConfig(**kw)


class BertEmbeddings(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        init = I.Normal(0.0, cfg.initializer_range)
        self.word_embeddings = VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        # small tables — plain replicated Embeddings
        self.position_embeddings = Embedding(
            cfg.max_position_embeddings, cfg.hidden_size, weight_attr=init)
        self.token_type_embeddings = Embedding(
            cfg.type_vocab_size, cfg.hidden_size, weight_attr=init)
        self.layer_norm = LayerNorm(cfg.hidden_size)
        self.dropout = Dropout(cfg.dropout)
        self._dtype_ = cfg.dtype

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        if position_ids is None:
            position_ids = jnp.arange(input_ids.shape[-1], dtype=jnp.int32)
            position_ids = jnp.broadcast_to(position_ids, input_ids.shape)
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = (F.embedding(input_ids, self.word_embeddings.weight) +
             F.embedding(position_ids, self.position_embeddings.weight) +
             F.embedding(token_type_ids, self.token_type_embeddings.weight))
        return self.dropout(self.layer_norm(x)).astype(self._dtype_)


class BertEncoderLayer(Layer):
    """Post-LN encoder block (original BERT)."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        d = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = d // cfg.num_heads
        init = I.Normal(0.0, cfg.initializer_range)
        self.qkv = ColumnParallelLinear(d, 3 * d, weight_attr=init,
                                        gather_output=False)
        self.out_proj = RowParallelLinear(d, d, weight_attr=init,
                                          input_is_parallel=True)
        self.ln1 = LayerNorm(d)
        self.fc1 = ColumnParallelLinear(d, cfg.ffn_hidden, weight_attr=init,
                                        gather_output=False)
        self.fc2 = RowParallelLinear(cfg.ffn_hidden, d, weight_attr=init,
                                     input_is_parallel=True)
        self.ln2 = LayerNorm(d)
        self.dropout = Dropout(cfg.dropout)
        self._dtype_ = cfg.dtype

    def forward(self, x, attn_mask=None):
        b, s, d = x.shape
        with jax.named_scope(ATTN):
            # sharded by heads from the start (a column shard of the
            # fused [3, heads, head_dim] weight is not a head group)
            qkv = self.qkv.project_heads(x, 3, self.num_heads)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            attn = F.scaled_dot_product_attention(
                q, k, v, attn_mask=attn_mask, training=self.training)
            attn = jnp.reshape(attn, (b, s, d))
            x = self.ln1(x + self.dropout(self.out_proj(attn)))
        with jax.named_scope(MLP):
            y = self.fc2(F.gelu(self.fc1(x.astype(self._dtype_)),
                                approximate=True))
            return self.ln2(x + self.dropout(y)).astype(self._dtype_)


class BertPooler(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.dense = Linear(cfg.hidden_size, cfg.hidden_size)

    def forward(self, hidden):
        return jnp.tanh(self.dense(hidden[:, 0]))


class BertModel(Layer):
    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.config = cfg
        self.embeddings = BertEmbeddings(cfg)
        self.encoder = LayerList([BertEncoderLayer(cfg)
                                  for _ in range(cfg.num_layers)])
        self.pooler = BertPooler(cfg)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                position_ids=None):
        if attention_mask is not None and attention_mask.ndim == 2:
            # [b, s] padding mask -> [b, 1, 1, s] broadcastable boolean
            attention_mask = attention_mask[:, None, None, :].astype(bool)
        x = self.embeddings(input_ids, token_type_ids, position_ids)
        x = _constrain(x, ("data", "sharding"), None, None)
        for blk in self.encoder:
            x = blk(x, attn_mask=attention_mask)
        return x, self.pooler(x)


class BertPretrainingHeads(Layer):
    """MLM transform + tied vocab decoder + NSP classifier."""

    def __init__(self, cfg: BertConfig):
        super().__init__()
        self.transform = Linear(cfg.hidden_size, cfg.hidden_size)
        self.layer_norm = LayerNorm(cfg.hidden_size)
        self.decoder_bias = self.create_parameter((cfg.vocab_size,),
                                                  is_bias=True)
        self.seq_relationship = Linear(cfg.hidden_size, 2)

    @jax.named_scope(MLM_HEAD)
    def forward(self, sequence_output, pooled_output, embedding_weight,
                masked_positions=None):
        # embedding_weight passed (not stored) so the tied table stays a
        # single Parameter slot under bert.embeddings — one grad, one update
        if masked_positions is not None:
            # gather the ~15% masked positions BEFORE the transform and
            # vocab projection (reference: BertPretrainingHeads.forward
            # gathers sequence_output at masked_positions) — the MLM head
            # then costs P/S of the dense version and the [B, S, V]
            # logits tensor never exists
            pos = masked_positions.astype(jnp.int32)
            sequence_output = jnp.take_along_axis(
                sequence_output, pos[..., None], axis=1)
        x = self.layer_norm(F.gelu(self.transform(sequence_output)))
        logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                            jnp.asarray(embedding_weight).astype(jnp.float32))
        logits = logits + self.decoder_bias
        nsp = self.seq_relationship(pooled_output.astype(jnp.float32))
        return logits, nsp


class BertForPretraining(Layer):
    @RecordEvent("model.build")
    def __init__(self, cfg_or_model):
        super().__init__()
        self.bert = (cfg_or_model if isinstance(cfg_or_model, BertModel)
                     else BertModel(cfg_or_model))
        self.cls = BertPretrainingHeads(self.bert.config)

    @property
    def config(self):
        return self.bert.config

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                masked_lm_labels=None, next_sentence_labels=None,
                masked_lm_weights=None, masked_positions=None):
        seq, pooled = self.bert(input_ids, token_type_ids, attention_mask)
        logits, nsp = self.cls(
            seq, pooled, self.bert.embeddings.word_embeddings.weight,
            masked_positions=masked_positions)
        if masked_lm_labels is None:
            return logits, nsp
        # MLM loss: ignore_index = -1 (unmasked / padded prediction slots).
        # With masked_positions, labels are [B, P] aligned to the gathered
        # slots; dense labels [B, S] take a chunked scan so the fp32
        # [B, S, V] CE fusion never materializes (the one-fusion version
        # spilled 208M of vmem registers on TPU at seq 512)
        mask = (masked_lm_labels >= 0).astype(jnp.float32)
        if masked_lm_weights is not None:
            mask = mask * masked_lm_weights.astype(jnp.float32)
        lab = jnp.maximum(masked_lm_labels, 0).astype(jnp.int32)

        def ce_sum(lg, lab_c, mask_c):
            lg = lg.astype(jnp.float32)
            lse = jax.nn.logsumexp(lg, axis=-1)
            picked = jnp.take_along_axis(lg, lab_c[..., None],
                                         axis=-1)[..., 0]
            return jnp.sum((lse - picked) * mask_c)

        s = logits.shape[1]
        cs = 128 if (masked_positions is None and s % 128 == 0
                     and s > 128) else s
        if cs == s:
            tot = ce_sum(logits, lab, mask)
        else:
            n = s // cs
            split = lambda a: jnp.moveaxis(  # noqa: E731
                a.reshape(a.shape[0], n, cs, *a.shape[2:]), 1, 0)

            def chunk(acc, xs):
                lg, lab_c, mask_c = xs
                return acc + ce_sum(lg, lab_c, mask_c), None

            tot, _ = jax.lax.scan(
                jax.checkpoint(chunk), jnp.zeros((), jnp.float32),
                (split(logits), split(lab), split(mask)))
        mlm = tot / jnp.maximum(jnp.sum(mask), 1.0)
        if next_sentence_labels is None:
            return mlm
        nsp32 = nsp.astype(jnp.float32)
        nsp_loss = jnp.mean(
            jax.nn.logsumexp(nsp32, axis=-1) -
            jnp.take_along_axis(
                nsp32, next_sentence_labels.astype(jnp.int32)[:, None],
                axis=-1)[:, 0])
        return mlm + nsp_loss
