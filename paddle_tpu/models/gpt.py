"""GPT — decoder-only LM, the hybrid-parallel flagship (BASELINE config 3).

Reference model: PaddleNLP GPT (`examples/language_model/gpt`), built on the
reference's meta-parallel layers (`mp_layers.py`, `pp_layers.py`). Here the
same architecture is built TPU-first:

  * uniform pre-LN decoder blocks → stackable: one traced block, `lax.scan`
    over the layer dim (fast compile) or the GSPMD pipeline engine
    (`stacked_pipeline.gpipe`) when a 'pipe' mesh axis exists;
  * TP via the GSPMD mp_layers (weights carry PartitionSpecs; XLA inserts
    the ICI collectives);
  * tied embedding/output head; vocab-parallel softmax CE;
  * everything bf16-friendly: matmuls hit the MXU, softmax/CE in fp32.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import (Layer, functional_call, load_state, trainable_state)
from ..nn.layer_common import Dropout, Embedding, LayerList
from ..nn.layer_conv_norm import LayerNorm
from ..distributed.meta_parallel.mp_layers import (
    TP_SUM, ColumnParallelLinear, ParallelCrossEntropy, RowParallelLinear,
    VocabParallelEmbedding, _constrain)
from ..distributed.meta_parallel.stacked_pipeline import (
    one_f_one_b, pipelined_apply, stack_stage_params)
from ..distributed.topology import mesh_scope
from ..profiler import (ATTN, CLIP, DECODER, EMBED, GPT_TRAIN_STEP, LM_LOSS,
                        MLP, OPTIMIZER, RecordEvent, stats)


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

    # sequence-parallel ring attention, set by `build_train_step` for the
    # length of one trace when the mesh has a 'sequence' axis
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
            # axis (set by build_train_step when the mesh has one)
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

    # what `build_train_step` asks of a model, beside `config`
    # (`num_layers`, `dropout`), `logits` and `criterion`: one block to
    # scan over stacked leaves, the embedding and the last norm
    def block_template(self):
        return self.gpt.layers[0]

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
# Distributed train-step builder (bench.py / __graft_entry__ entrypoint)
# --------------------------------------------------------------------------

def _split_params(model: Layer):
    """Partition trainable state into stacked block params + outer params.

    Returns (outer: {name: arr}, blocks: [per-block {relname: arr}],
    relnames keyed to one template block).
    """
    all_params = trainable_state(model)
    nl = model.config.num_layers
    blocks = [dict() for _ in range(nl)]
    outer = {}
    for name, v in all_params.items():
        if ".layers." in name:
            head, rest = name.split(".layers.", 1)
            idx, rel = rest.split(".", 1)
            blocks[int(idx)][rel] = v
        else:
            outer[name] = v
    return outer, blocks


def _block_specs(model: Layer):
    tmpl = model.block_template()
    return {n: (p.sharding_spec or P())
            for n, p in tmpl.named_parameters() if p.trainable}


def _outer_specs(model: Layer):
    out = {}
    for name, p in model.named_parameters():
        if ".layers." in name or not p.trainable:
            continue
        out[name] = p.sharding_spec or P()
    return out


# remat policies that keep one named residual beside the dots:
# "dots_attn" the attention output (+16 MB a layer at GPT-345M buys
# skipping the flash-forward replay in the backward), "dots_sel" a learned
# key selection (int8 [b, s, s] a layer: the indexer and its exact top-k
# are not replayed)
_SAVED_BESIDE_DOTS = {"dots_attn": "attn_out", "dots_sel": "attn_selection"}


@RecordEvent("build_train_step")   # one frame more: warnings below say 3
def build_train_step(model: Layer, optimizer, mesh,
                     num_microbatches: int = 1, remat: bool = True,
                     donate: bool = True, pipeline_schedule: str = "gpipe",
                     remat_policy: str = "dots", loss_chunks: int = 0,
                     zero_stage: int = 2, sequence_zigzag: bool = True,
                     sequence_mode: str = "ring", offload: bool = False,
                     offload_memory_kind: str = "pinned_host",
                     param_dtype=None):
    """Build the one compiled hybrid-parallel training step.

    `model` is any decoder-only LM made of uniform blocks that gives the
    builder its pieces (`GPTForPretraining`, `KeyeForCausalLM`): `config`
    (`num_layers`, `dropout`), `block_template()` (one block, applied to
    stacked leaves under a scan; the blocks are the parameters named
    "...layers.<i>..."), `embed(ids, position_ids)`, `final_norm(hidden)`,
    `logits(hidden)` and `criterion` (with `.ce`). `step_name`, where the
    model has one, names the compiled step's module.

    The eager model's copy of the blocks' weights is given up once they
    are stacked into the state (the arrays are deleted: 1.3 GiB at 345M
    parameters, 2.3 GB at the Keye decoder's 581M in blocks, that no step
    reads); `sync_params_to_model` brings the model back for save / eval.

    Parallelism comes entirely from the mesh axes: 'data' (DP — batch dim),
    'model' (TP — weight PartitionSpecs), 'pipe' (PP — stacked blocks via
    the CollectivePermute schedule), 'sharding' (ZeRO — optimizer-state
    specs), 'sequence' (SP — activations sharded on the seq dim with
    zigzag-balanced causal ring attention in every decoder layer;
    composes with dp×tp×zero AND pp — the schedules split the batch
    dim into microbatches, orthogonal to the sequence shard). This
    replaces the reference's whole meta-optimizer chain
    (`fleet_base.py:1288` → StrategyCompiler → program rewriting).
    Under a 'model' axis (and no 'pipe' axis, whose microbatches already
    are such streams) the layer scan applies each block to the two
    halves of a chip's rows as two streams of one body, so that one
    half's row-parallel sums cross the link while the other half
    computes: one backward, one update, the same batch (`tp_streams`;
    the count is the static counter `tp.streams`).

    Returns (step_fn, state) where state = (outer, stacked_blocks,
    opt_state) and step_fn(state, batch) -> (state, loss);
    batch = (input_ids, labels) int32 [B, S]. When cfg.dropout > 0 the
    signature is step_fn(state, batch, rng_key) — pass a fresh key per
    step.

    offload=True keeps the optimizer slots (Adam m/v, master weights) at
    rest in HOST memory (`memory_kind="pinned_host"`): the step streams
    them to device for the update and back out, trading PCIe bandwidth
    for ~2/3 of optimizer HBM — the reference's sharding offload
    (`fleet/meta_optimizers/sharding/offload_helper.py:1`) re-designed
    as XLA host-offload shardings instead of program rewriting. The
    chunked design keeps all COMPUTE in device memory space (transfers
    happen between the compiled programs), so it runs on the CPU
    backend too — CI proves step parity there.
    """
    cfg = model.config
    axis = dict(zip(mesh.axis_names, mesh.devices.shape))
    pp = axis.get("pipe", 1)
    sp = axis.get("sequence", 1)
    assert cfg.num_layers % pp == 0, "num_layers must divide pipe axis"
    layers_per_stage = cfg.num_layers // pp
    if pp > 1 and num_microbatches < pp:
        warnings.warn(
            f"num_microbatches={num_microbatches} < pipeline stages "
            f"{pp}: the schedule needs at least one microbatch per stage; "
            f"using {pp}", stacklevel=3)
    if sp > 1:
        # sequence parallelism composes with dp x tp x zero AND pp: the
        # pipeline schedules split the BATCH dim into microbatches while
        # SP shards the SEQUENCE dim — orthogonal. Ring attention is a
        # shard_map over only the 'sequence' axis, so it vmaps over the
        # stacked stage dim inside the schedules; the 1F1B path applies
        # the same zigzag layout + position-id threading as loss_fn.
        if loss_chunks > 1:
            warnings.warn("loss_chunks disabled under sequence "
                          "parallelism (the chunk scan would re-slice the "
                          "sequence-sharded dim)", stacklevel=3)
            loss_chunks = 0

    with RecordEvent("build_train_step.stack"):
        outer, block_list = _split_params(model)
        stacked = stack_stage_params(block_list)  # leaves [L, ...]
    master_src = (outer, stacked)  # pre-cast fp32 leaves for master init
    if param_dtype is not None:
        # O2-style residency: params rest in param_dtype (bf16 halves
        # param+grad HBM — the 2.6B offload point exists because of
        # this); pair with optimizer multi_precision=True so fp32
        # master weights live in the (host-offloadable) slots.
        # Reference: pure-fp16 + master weights
        # (`contrib/mixed_precision/decorator.py`, adam multi-precision)
        cast = lambda v: (v.astype(param_dtype)  # noqa: E731
                          if jnp.issubdtype(v.dtype, jnp.floating) else v)
        outer = {n: cast(v) for n, v in outer.items()}
        stacked = {n: cast(v) for n, v in stacked.items()}
        if not getattr(optimizer, "_multi_precision", False):
            warnings.warn(
                "param_dtype set without optimizer multi_precision=True: "
                "no fp32 master weights — low-precision updates will "
                "accumulate rounding error", stacklevel=3)
    template = model.block_template()
    if sp > 1 and not hasattr(type(template), "_sp_attention"):
        raise NotImplementedError(
            f"{type(template).__name__} has no sequence-parallel attention")
    for blk in block_list:
        for v in blk.values():
            v.delete()
    del block_list

    def block_apply(bparams, x):
        # _sp_attention is scoped to THIS trace (set/restore, not a
        # permanent template mutation): the model stays usable eagerly
        # and under other meshes after the step is built
        template._sp_attention = sp_attn_fn
        try:
            out, _ = functional_call(template, bparams, x)
        finally:
            template._sp_attention = None
        return out

    # selective remat: keep the weight-matmul outputs (no batch dims in
    # the dot), recompute elementwise + attention (whose einsums carry
    # batch dims) — the VERDICT r2 lever: full per-block checkpoint
    # alone cost ~25% of achievable MFU. A row-parallel product summed by
    # an explicit exchange (`mp_layers._row_product`) is a weight matmul's
    # output too, but sits where the policy cannot see a dot: saved by its
    # name, else the backward would run product and exchange again
    dots = jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
        jax.checkpoint_policies.save_only_these_names(TP_SUM))
    if remat_policy == "full":
        ckpt_policy = None            # rematerialize everything
    elif remat_policy == "dots":
        ckpt_policy = dots
    elif remat_policy in _SAVED_BESIDE_DOTS:
        ckpt_policy = jax.checkpoint_policies.save_from_both_policies(
            dots, jax.checkpoint_policies.save_only_these_names(
                _SAVED_BESIDE_DOTS[remat_policy]))
    else:
        raise ValueError(f"unknown remat_policy {remat_policy!r}")

    def block_apply_key(bparams, x, key):
        # rng_guard must sit INSIDE the checkpointed function: the guard
        # pushes/pops the scoped key within one trace, so no inner-trace
        # key tracer survives in the thread-local scope (leak otherwise)
        from ..framework.random import rng_guard
        template._sp_attention = sp_attn_fn
        try:
            with rng_guard(key):
                out, _ = functional_call(template, bparams, x)
        finally:
            template._sp_attention = None
        return out

    @jax.named_scope(DECODER)
    def stage_blocks(stage_p, h, key=None):
        """One pipeline stage = scan over its L/pp blocks (shared by the
        gpipe and 1f1b schedules). `key` (when dropout > 0) is split into
        one sub-key per block so masks decorrelate across layers — a
        closure draw would bake a single mask into the scanned body.
        `h` is the batch, or a tuple of streams of it (`tp_streams`):
        the body then applies the block to each, one after the other in
        the program and independent in its data, so that one stream's
        row-parallel sum is on the link while the other computes."""
        if key is None:
            fn = (jax.checkpoint(block_apply, policy=ckpt_policy)
                  if remat else block_apply)

            def body(carry, bp):
                return jax.tree.map(lambda c: fn(bp, c), carry), None
            out, _ = jax.lax.scan(body, h, stage_p)
        else:
            fnk = (jax.checkpoint(block_apply_key, policy=ckpt_policy)
                   if remat else block_apply_key)
            n_local = jax.tree.leaves(stage_p)[0].shape[0]
            keys = jax.random.split(key, n_local)

            def body(carry, xs):
                bp, k = xs
                if isinstance(carry, tuple):   # a sub-key a stream
                    ks = jax.random.split(k, len(carry))
                    return tuple(fnk(bp, c, ki)
                                 for c, ki in zip(carry, ks)), None
                return fnk(bp, carry, k), None
            out, _ = jax.lax.scan(body, h, (stage_p, keys))
        return out

    row_groups = axis.get("data", 1) * axis.get("sharding", 1)

    def tp_streams(x):
        """x [B, ...] as the streams the blocks are applied to: the two
        halves of each chip's rows (split WITHIN a data x sharding group,
        so no row changes chip) where the mesh has a 'model' axis, whose
        sums a stream's partner can hide, and a chip holds an even number
        of rows; else x itself."""
        rows = x.shape[0] // row_groups
        n = 2 if (axis.get("model", 1) > 1
                  and x.shape[0] % row_groups == 0 and rows % 2 == 0) else 1
        stats.static("tp.streams", n)
        if n == 1:
            return x
        parts = x.reshape((row_groups, n, rows // n) + x.shape[1:])
        return tuple(
            _constrain(parts[:, i].reshape((-1,) + x.shape[1:]),
                       ("data", "sharding"), seq_axis, None)
            for i in range(n))

    def tp_join(h):
        """The batch back in its order from `tp_streams`' streams."""
        if not isinstance(h, tuple):
            return h
        parts = jnp.stack([c.reshape((row_groups, -1) + c.shape[1:])
                           for c in h], axis=1)
        return _constrain(parts.reshape((-1,) + h[0].shape[1:]),
                          ("data", "sharding"), seq_axis, None)

    def to_staged(stacked_p):
        """Leaves [L, ...] -> [pp, L/pp, ...]."""
        return jax.tree.map(
            lambda a: a.reshape((pp, layers_per_stage) + a.shape[1:]),
            stacked_p)

    seq_axis = "sequence" if sp > 1 else None

    @jax.named_scope(EMBED)
    def embed_fwd(input_ids, position_ids=None):
        x = model.embed(input_ids, position_ids)
        return _constrain(x, ("data", "sharding"), seq_axis, None)

    if sp > 1:
        from ..distributed.meta_parallel.sequence_parallel import (
            make_sp_attention, zigzag_permutation)
        if sequence_mode == "ulysses":
            # all-to-all resharding: every chip sees the FULL sequence
            # for its head slice, so the contiguous layout is already
            # causal-balanced — no zigzag
            sequence_zigzag = False
        sp_attn_fn = make_sp_attention(
            mesh, mode=sequence_mode, causal=True,
            zigzag=sequence_zigzag, jit=False)

        def sp_layout(input_ids, labels):
            """Zigzag-reorder tokens so each rank gets an equal share of
            causal-mask work; position ids carry the original positions
            (loss is a position-wise mean — invariant to the reorder)."""
            if not sequence_zigzag:
                return input_ids, labels, None
            zperm = jnp.asarray(
                zigzag_permutation(input_ids.shape[1], sp), jnp.int32)
            ids_z = jnp.take(input_ids, zperm, axis=1)
            labels_z = jnp.take(labels, zperm, axis=1)
            pos = jnp.broadcast_to(zperm[None, :], ids_z.shape)
            return ids_z, labels_z, pos
    else:
        sp_attn_fn = None

        def sp_layout(input_ids, labels):
            return input_ids, labels, None

    def trunk(stacked_p, x, key=None):
        """Apply all L blocks: scan over layers (and pipeline over stages
        when pp > 1)."""
        if pp == 1:
            return tp_join(stage_blocks(stacked_p, tp_streams(x), key))
        return pipelined_apply(stage_blocks, to_staged(stacked_p), x,
                               num_stages=pp,
                               num_microbatches=max(num_microbatches, pp),
                               remat=False, rng_key=key)

    @jax.named_scope(LM_LOSS)
    def lm_loss(hidden, labels):
        """ln_f → tied-head logits → CE. With loss_chunks > 1 the [B,S,V]
        fp32 logits tensor never materializes: a checkpointed scan over
        sequence chunks computes logits+CE per chunk and the backward
        rematerializes each chunk's logits (VERDICT r2 lever: the full
        tied-head logit tensor was the largest HBM round-trip in the
        step)."""
        hidden = model.final_norm(hidden)
        if loss_chunks <= 1:
            logits = model.logits(hidden)
            return model.criterion(logits, labels)
        b, s, d = hidden.shape
        c = loss_chunks
        assert s % c == 0, f"seq {s} not divisible by loss_chunks {c}"
        hs = jnp.moveaxis(hidden.reshape(b, c, s // c, d), 1, 0)
        ls = jnp.moveaxis(labels.reshape(b, c, s // c), 1, 0)

        def chunk(tot, xs):
            h, lab = xs
            logits = model.logits(h)
            loss = model.criterion.ce(logits, lab)[..., 0]
            return tot + jnp.sum(loss.astype(jnp.float32)), None

        tot, _ = jax.lax.scan(jax.checkpoint(chunk),
                              jnp.zeros((), jnp.float32), (hs, ls))
        return tot / (b * s)

    def loss_fn(params, batch):
        outer_p, stacked_p = params
        input_ids, labels, pos_ids = sp_layout(*batch)
        # embeddings + ln_f + head run via functional_call on the model with
        # outer params; trunk handled functionally
        def fwd():
            if cfg.dropout > 0.0:
                # derive one base key from the ambient rng_guard scope and
                # key embed/trunk masks explicitly — the SAME derivation
                # value_and_grad_1f1b uses, so gpipe and 1f1b draw
                # identical masks (exact loss parity between schedules)
                from ..framework.random import next_key, rng_guard
                base = next_key()
                with rng_guard(jax.random.fold_in(base, 0)):
                    x = embed_fwd(input_ids, pos_ids)
                x = trunk(stacked_p, x, key=jax.random.fold_in(base, 1))
            else:
                x = embed_fwd(input_ids, pos_ids)
                x = trunk(stacked_p, x)
            return lm_loss(x, labels)
        out, _ = functional_call_outer(model, outer_p, fwd)
        return out

    def functional_call_outer(mdl, outer_p, thunk):
        from ..nn.layer import _slots
        slots = _slots(mdl)
        saved = {n: s.value for n, s in slots.items()}
        try:
            for n, v in outer_p.items():
                if n in slots:
                    slots[n].value = v
            return thunk(), None
        finally:
            for n, s in slots.items():
                s.value = saved[n]

    # optimizer state over combined pytree
    params0 = (outer, stacked)
    flatname_params = dict(outer)
    flatname_params.update({f"blocks.{n}": v for n, v in stacked.items()})

    if offload:
        # structure only: materializing the full [L, ...] slot zeros on
        # device before moving them to host would transiently cost the
        # whole optimizer HBM the offload exists to avoid
        opt_state0 = jax.eval_shape(optimizer.init_state, flatname_params)
    else:
        with RecordEvent("build_train_step.opt_init"):
            opt_state0 = optimizer.init_state(flatname_params)
        if param_dtype is not None:
            # masters must come from the PRE-cast fp32 weights — fp32
            # (bf16(w)) throws away the mantissa bits the masters exist
            # to keep
            m_outer, m_stacked = master_src
            for n, slots in opt_state0["slots"].items():
                if "master" in slots:
                    src = (m_stacked[n[len("blocks."):]]
                           if n.startswith("blocks.") else m_outer[n])
                    slots["master"] = src.astype(jnp.float32)

    def value_and_grad_1f1b(params, batch, rng=None):
        """Loss + grads via the 1F1B schedule (SectionWorker mode 1,
        `section_worker.cc:144-156`): embedding vjp outside the schedule,
        per-microbatch head (ln_f + tied logits + CE) inside it so
        backward starts S-1 ticks after forward. With rng set, dropout
        keys are threaded per (microbatch, stage) through the schedule
        (reference 1F1B runs real configs with dropout)."""
        outer_p, stacked_p = params
        # same sequence-parallel layout as loss_fn: zigzag-reorder tokens
        # and thread the original positions (no-op when sp == 1)
        input_ids, labels, pos_ids = sp_layout(*batch)
        B = input_ids.shape[0]
        M = max(num_microbatches, pp)
        assert B % M == 0, f"batch {B} not divisible by microbatches {M}"

        if rng is not None:
            from ..framework.random import next_key, rng_guard
            with rng_guard(rng):
                base = next_key()   # same derivation as loss_fn's fwd
        else:
            base = None

        def embed_fn(op):
            def thunk():
                if base is None:
                    return embed_fwd(input_ids, pos_ids)
                from ..framework.random import rng_guard
                with rng_guard(jax.random.fold_in(base, 0)):
                    return embed_fwd(input_ids, pos_ids)
            out, _ = functional_call_outer(model, op, thunk)
            return out

        x, embed_vjp = jax.vjp(embed_fn, outer_p)
        mb = x.reshape((M, B // M) + tuple(x.shape[1:]))
        labels_mb = labels.reshape((M, B // M) + tuple(labels.shape[1:]))

        def head_grad(op, y, lab):
            def h(op_, y_):
                def fwd():
                    return lm_loss(y_, lab)
                out, _ = functional_call_outer(model, op_, fwd)
                return out
            loss_v, vjp_fn = jax.vjp(h, op, y)
            # global loss = mean over microbatches → seed cotangent 1/M
            dop, dy = vjp_fn(jnp.asarray(1.0 / M, loss_v.dtype))
            return loss_v, dy, dop

        loss_sum, dx_stream, g_staged, g_outer_head = one_f_one_b(
            stage_blocks, to_staged(stacked_p), mb, head_grad, outer_p,
            labels_mb, num_stages=pp,
            rng_key=(jax.random.fold_in(base, 1) if base is not None
                     else None))
        dx = dx_stream.reshape((B,) + tuple(x.shape[1:]))
        (g_outer_embed,) = embed_vjp(dx)
        g_outer = jax.tree.map(jnp.add, g_outer_head, g_outer_embed)
        g_stacked = jax.tree.map(
            lambda a: a.reshape((pp * layers_per_stage,) + a.shape[2:]),
            g_staged)
        return loss_sum / M, (g_outer, g_stacked)

    use_1f1b = pipeline_schedule == "1f1b" and pp > 1
    if pipeline_schedule not in ("gpipe", "1f1b"):
        raise ValueError(f"unknown pipeline_schedule {pipeline_schedule!r}")

    def _loss_and_grads(params_pair, batch, rng):
        # all model code of the step (plain and offloaded) is traced in
        # here: it shards for THIS step's mesh, not for whatever mesh is
        # the global one by the time of the first call
        with mesh_scope(mesh):
            if use_1f1b:
                return value_and_grad_1f1b(params_pair, batch, rng)
            if rng is None:
                return jax.value_and_grad(loss_fn)(params_pair, batch)
            # scope the traced key so Dropout draws fresh masks per step
            # (an unscoped next_key() inside jit would bake one constant
            # mask into the compiled program)
            from ..framework.random import rng_guard

            def lf(params, batch_):
                with rng_guard(rng):
                    return loss_fn(params, batch_)
            return jax.value_and_grad(lf)(params_pair, batch)

    def gpt_train_step(state, batch, rng=None):
        if cfg.dropout > 0.0 and rng is None:
            # without a key the dropout draws would fall back to the
            # process-global RNG: one constant mask baked into the
            # compiled program + a tracer leaked into eager state
            raise ValueError(
                "cfg.dropout > 0 requires step(state, batch, rng_key) — "
                "pass a fresh jax.random key every step")
        outer_p, stacked_p, opt_state = state
        loss, grads = _loss_and_grads((outer_p, stacked_p), batch, rng)
        g_outer, g_stacked = grads
        flat_p = dict(outer_p)
        flat_p.update({f"blocks.{n}": v for n, v in stacked_p.items()})
        flat_g = dict(g_outer)
        flat_g.update({f"blocks.{n}": v for n, v in g_stacked.items()})
        if shard_axis > 1:
            # ZeRO-2: pin gradients to the optimizer-state layout so XLA
            # reduce-scatters them over 'sharding' (instead of all-reduce)
            # and runs the update sharded; fresh params all-gather on the
            # way out. Reference bar: grad sharding in static
            # ShardingOptimizer (`sharding_optimizer.py:87-1385`).
            flat_g = {n: (jax.lax.with_sharding_constraint(
                              v, ns(opt_spec(n, v)))
                          if jnp.ndim(v) else v)
                      for n, v in flat_g.items()}
        new_flat, new_opt = optimizer.apply(flat_p, flat_g, opt_state)
        new_outer = {n: new_flat[n] for n in outer_p}
        new_stacked = {n: new_flat[f"blocks.{n}"] for n in stacked_p}
        return (new_outer, new_stacked, new_opt), loss

    # ---- shardings ----
    bspecs = _block_specs(model)
    stacked_specs = {n: P("pipe", *s) if pp > 1 else P(None, *s)
                     for n, s in bspecs.items()}
    outer_specs = _outer_specs(model)
    shard_axis = axis.get("sharding", 1)

    def ns(spec):
        return NamedSharding(mesh, spec)

    from ..distributed.meta_parallel.sharding_optimizer import shard_spec_for

    def opt_spec(pname, v):
        if jnp.ndim(v) == 0:
            return P()
        base = (stacked_specs.get(pname[7:]) if pname.startswith("blocks.")
                else outer_specs.get(pname)) or P()
        if shard_axis > 1:
            return shard_spec_for(v.shape, shard_axis, "sharding", base)
        return base

    opt_state_specs = {
        "step": P(),
        "slots": {pname: {sname: opt_spec(pname, v)
                          for sname, v in slots.items()}
                  for pname, slots in opt_state0["slots"].items()}}

    # ZeRO-3: the PARAMETERS themselves rest sharded over 'sharding' (same
    # spec as their optimizer state); XLA all-gathers each layer's weights
    # at its use site inside the layer scan — gather-on-use, param memory
    # at rest = 1/shard_axis. Reference bar: static ShardingOptimizer is
    # only ZeRO-2+offload (`sharding_optimizer.py:87-1385`) — this goes
    # one stage further.
    if zero_stage >= 3 and shard_axis > 1:
        outer_param_specs = {
            n: opt_spec(n, outer[n]) for n in outer_specs}
        stacked_param_specs = {
            n: opt_spec(f"blocks.{n}", stacked[n]) for n in stacked_specs}
    else:
        outer_param_specs = outer_specs
        stacked_param_specs = stacked_specs

    # ZeRO semantics: the 'sharding' axis IS data parallelism with sharded
    # states — the batch splits over data×sharding jointly (reference:
    # sharding_degree multiplies dp for the data split,
    # sharding_optimizer.py:968 _build_groups)
    batch_sharding = (ns(P(("data", "sharding"), seq_axis)),
                      ns(P(("data", "sharding"), seq_axis)))

    if offload:
        # pinned_host is the reference-offload default (DMA-able); some
        # workers cap the pinned pool well below their RAM — 'unpinned_host'
        # rests slots in ordinary host memory instead (staged transfers)
        def ns_host(spec):
            return NamedSharding(mesh, spec,
                                 memory_kind=offload_memory_kind)
        return _build_offload_chunked_step(
            cfg=cfg, optimizer=optimizer, outer=outer, stacked=stacked,
            opt_state0=opt_state0, opt_spec=opt_spec, ns=ns,
            ns_host=ns_host, shard_axis=shard_axis,
            loss_and_grads=_loss_and_grads,
            outer_param_specs=outer_param_specs,
            stacked_param_specs=stacked_param_specs,
            batch_sharding=batch_sharding, donate=donate, pp=pp,
            master_src=master_src)

    is_spec = lambda s: isinstance(s, P)  # noqa: E731
    opt_state_shardings = jax.tree.map(ns, opt_state_specs,
                                       is_leaf=is_spec)

    state_shardings = (
        {n: ns(s) for n, s in outer_param_specs.items()},
        {n: ns(s) for n, s in stacked_param_specs.items()},
        opt_state_shardings)

    # the jitted function's name is the compiled module's ("jit_<name>"),
    # which is how a trace or a compile log tells the step program from
    # every other; without dropout it is called without a key
    gpt_train_step.__name__ = getattr(model, "step_name", GPT_TRAIN_STEP)
    step_jit = jax.jit(
        gpt_train_step,
        in_shardings=(state_shardings, batch_sharding)
        + ((None,) if cfg.dropout > 0.0 else ()),
        out_shardings=(state_shardings, None),
        donate_argnums=(0,) if donate else ())

    # place initial state
    with RecordEvent("build_train_step.place"):
        state0 = jax.device_put(
            (outer, stacked, opt_state0), state_shardings)
    return step_jit, state0


# per-chunk optimizer-slot bytes allowed on device at once in the
# offloaded update (the streaming window, not a model-size limit)
_OFFLOAD_CHUNK_BYTES = 1 << 30


def _build_offload_chunked_step(*, cfg, optimizer, outer, stacked,
                                opt_state0, opt_spec, ns, ns_host,
                                shard_axis, loss_and_grads,
                                outer_param_specs, stacked_param_specs,
                                batch_sharding, donate, pp,
                                master_src=None):
    """Host-offloaded train step with a CHUNKED optimizer update.

    The reference's sharding offload (`fleet/meta_optimizers/sharding/
    offload_helper.py:1`) keeps Adam slots in host memory and streams
    them through device memory parameter-group by parameter-group. A
    single-jit version of that (slots device_put'd in one go) is
    useless: XLA counts the whole optimizer state against peak HBM and
    an ERNIE-1.3B step OOMs exactly as if there were no offload. This
    builds three compiled programs instead:

      1. grad phase — loss + grads (+ global-norm clip, + ZeRO grad
         layout), params resident, slots untouched;
      2. one chunk-update program, reused for every chunk of k decoder
         blocks: dynamic-slice the [L, ...] param/grad stacks at a
         TRACED offset (one compile for all chunks), stream that
         chunk's slots host->device, update, write params back with
         dynamic-update-slice, stream new slots back out;
      3. outer update — embeddings/final-LN slots streamed the same way.

    Peak HBM = params + grads + up to ~TWO chunks of slots: the
    backpressure sync below waits on chunk ci-2, deliberately leaving
    two chunks' transfers in flight to overlap copy with compute, and
    chunk sizing uses the conservative UNSHARDED byte estimate — so
    budget ~2x `_OFFLOAD_CHUNK_BYTES` of slot residency when capacity
    planning at 10B-class sizes. The largest trainable size is still
    bounded by params+grads+activations — the offload promise. Slots
    at rest are tuples of per-chunk arrays in `pinned_host` memory;
    they never exist stacked on device.
    """
    import numpy as onp

    L = cfg.num_layers
    if pp != 1:
        raise ValueError(
            "offload=True requires pipe=1: the chunked update slices the "
            "block stack, which the pipeline axis partitions")
    if not optimizer._elementwise_update:
        raise ValueError(
            f"offload=True cannot stream {type(optimizer).__name__}: its "
            "update is a whole-tensor norm (trust ratio), so per-chunk "
            "streaming would change the numerics. Use an elementwise "
            "rule (Adam/AdamW/Momentum/...) or offload=False")

    slot_struct = opt_state0["slots"]
    # conservative (unsharded) byte estimate: shard_spec_for may leave a
    # leaf replicated, so dividing by shard_axis here could pick a chunk
    # shard_axis x over budget on some device
    per_layer = sum(
        int(onp.prod(v.shape[1:])) * v.dtype.itemsize
        for n, slots in slot_struct.items() if n.startswith("blocks.")
        for v in slots.values())
    k = 1
    for d in range(1, L + 1):
        if L % d == 0 and d * per_layer <= _OFFLOAD_CHUNK_BYTES:
            k = d
    n_chunks = L // k
    starts = [onp.int32(ci * k) for ci in range(n_chunks)]

    # ---- host-resident initial slots, built without an HBM detour ----
    # _init_slot runs on the CPU backend so non-zero initial values
    # (e.g. Adagrad's initial_accumulator_value) are honored exactly as
    # in the resident path, without materializing [L, ...] on the TPU
    try:
        cpu0 = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu0 = None  # no CPU backend: chunk-sized device transient is fine

    def init_slot_values(shape, dtype):
        if cpu0 is not None:
            with jax.default_device(cpu0):
                vals = optimizer._init_slot(jnp.zeros(shape, dtype))
        else:
            vals = optimizer._init_slot(jnp.zeros(shape, dtype))
        return {sn: onp.asarray(v) for sn, v in vals.items()}

    stacked_slot_names = [n for n in slot_struct if n.startswith("blocks.")]
    outer_slot_names = [n for n in slot_struct
                        if not n.startswith("blocks.")]
    # master weights init from the PRE-param_dtype-cast fp32 leaves
    m_outer, m_stacked = master_src if master_src is not None \
        else (outer, stacked)

    chunk_slot_shardings = {}   # pname -> {sname: host sharding (chunk)}
    chunk_slot_dev = {}         # same specs, device memory (stream target)
    slots_host = {}             # pname -> {sname: tuple of n_chunks arrays}
    for pname in stacked_slot_names:
        # slot template from the RESIDENT (possibly cast) params so
        # moment dtypes match slot_struct; masters from the fp32 source
        src_cast = stacked[pname[len("blocks."):]]
        src_master = m_stacked[pname[len("blocks."):]]
        init_vals = init_slot_values((k,) + tuple(src_cast.shape[1:]),
                                     src_cast.dtype)
        per_shard, per_chunks, per_dev = {}, {}, {}
        for sname, sd in slot_struct[pname].items():
            cshape = (k,) + tuple(sd.shape[1:])
            cstruct = jax.ShapeDtypeStruct(cshape, sd.dtype)
            hshard = ns_host(opt_spec(pname, cstruct))
            per_shard[sname] = hshard
            per_dev[sname] = ns(opt_spec(pname, cstruct))
            if sname == "master":
                # master weights initialize FROM the params, not zeros
                per_chunks[sname] = tuple(
                    jax.device_put(
                        onp.asarray(jax.device_get(
                            src_master[ci * k:(ci + 1) * k]),
                            onp.float32),
                        hshard)
                    for ci in range(n_chunks))
            else:
                # one transfer, shared by every chunk slot: jax arrays
                # are immutable and each slot is wholesale-replaced by
                # the first step's update
                v0 = jax.device_put(init_vals[sname], hshard)
                per_chunks[sname] = (v0,) * n_chunks
        chunk_slot_shardings[pname] = per_shard
        chunk_slot_dev[pname] = per_dev
        slots_host[pname] = per_chunks

    outer_slot_shardings = {}
    outer_slot_dev = {}
    for pname in outer_slot_names:
        init_vals = init_slot_values(tuple(outer[pname].shape),
                                     outer[pname].dtype)
        per_shard, per, per_dev = {}, {}, {}
        for sname, sd in slot_struct[pname].items():
            hshard = ns_host(opt_spec(pname, sd))
            per_shard[sname] = hshard
            per_dev[sname] = ns(opt_spec(pname, sd))
            if sname == "master":
                per[sname] = jax.device_put(
                    onp.asarray(jax.device_get(m_outer[pname]),
                                onp.float32), hshard)
            else:
                per[sname] = jax.device_put(init_vals[sname], hshard)
        outer_slot_shardings[pname] = per_shard
        outer_slot_dev[pname] = per_dev
        slots_host[pname] = per

    # ---- compiled programs ----
    outer_shardings = {n: ns(s) for n, s in outer_param_specs.items()}
    stacked_shardings = {n: ns(s) for n, s in stacked_param_specs.items()}
    g_outer_shardings = {n: ns(opt_spec(n, outer[n])) for n in outer}
    g_stacked_shardings = {n: ns(opt_spec(f"blocks.{n}", stacked[n]))
                           for n in stacked}

    def gpt_offload_grad(params_pair, opt_step, batch, rng=None):
        loss, (g_outer, g_stacked) = loss_and_grads(params_pair, batch,
                                                    rng)
        flat_g = dict(g_outer)
        flat_g.update({f"blocks.{n}": v for n, v in g_stacked.items()})
        if shard_axis > 1:
            flat_g = {n: (jax.lax.with_sharding_constraint(
                              v, ns(opt_spec(n, v)))
                          if jnp.ndim(v) else v)
                      for n, v in flat_g.items()}
        if optimizer._grad_clip is not None:
            # global-norm clip sees the FULL grad set here; the per-chunk
            # updates below must not clip again
            with jax.named_scope(OPTIMIZER), jax.named_scope(CLIP):
                flat_g = optimizer._grad_clip(flat_g)
        g_outer = {n: flat_g[n] for n in g_outer}
        g_stacked = {n: flat_g[f"blocks.{n}"] for n in g_stacked}
        return loss, g_outer, g_stacked, opt_step + 1

    grad_kwargs = dict(
        in_shardings=((outer_shardings, stacked_shardings), ns(P()),
                      batch_sharding),
        out_shardings=(None, g_outer_shardings, g_stacked_shardings,
                       ns(P())))
    # named as `build_train_step`'s step is, one name per program
    if cfg.dropout > 0.0:
        grad_kwargs["in_shardings"] = grad_kwargs["in_shardings"] + (None,)
    grad_jit = jax.jit(gpt_offload_grad, **grad_kwargs)

    @jax.named_scope(OPTIMIZER)
    def gpt_offload_chunk(stacked_p, g_stacked, slots_chunk, new_step,
                          start):
        p_c = {f"blocks.{n}": jax.lax.dynamic_slice_in_dim(v, start, k, 0)
               for n, v in stacked_p.items()}
        g_c = {f"blocks.{n}":
               jax.lax.dynamic_slice_in_dim(g_stacked[n], start, k, 0)
               for n in stacked_p}
        new_p_c, new_slots = optimizer.apply_named(p_c, g_c, slots_chunk,
                                                   new_step)
        new_stacked = {
            n: jax.lax.dynamic_update_slice_in_dim(
                stacked_p[n], new_p_c[f"blocks.{n}"].astype(
                    stacked_p[n].dtype), start, 0)
            for n in stacked_p}
        return new_stacked, new_slots

    # slots cross the host<->device boundary OUTSIDE the jits, as plain
    # transfers in the orchestrator below: in-jit memory-space changes
    # (annotate_device_placement) break the SPMD partitioner on
    # multi-device meshes, and outside-jit copies dispatch async anyway,
    # pipelining chunk i+1's upload behind chunk i's compute
    chunk_jit = jax.jit(
        gpt_offload_chunk,
        in_shardings=(stacked_shardings, g_stacked_shardings,
                      chunk_slot_dev, ns(P()), None),
        out_shardings=(stacked_shardings, chunk_slot_dev),
        donate_argnums=(0, 2) if donate else ())

    @jax.named_scope(OPTIMIZER)
    def gpt_offload_outer(outer_p, g_outer, outer_slots, new_step):
        return optimizer.apply_named(outer_p, g_outer, outer_slots,
                                     new_step)

    outer_jit = jax.jit(
        gpt_offload_outer,
        in_shardings=(outer_shardings, g_outer_shardings,
                      outer_slot_dev, ns(P())),
        out_shardings=(outer_shardings, outer_slot_dev),
        donate_argnums=(0, 2) if donate else ())

    def step_fn(state, batch, rng=None):
        if cfg.dropout > 0.0 and rng is None:
            raise ValueError(
                "cfg.dropout > 0 requires step(state, batch, rng_key) — "
                "pass a fresh jax.random key every step")
        # the spans are the host's side of each phase (dispatch, the
        # transfers it starts and the wait for room); the device's side
        # is the three named programs in a trace
        outer_p, stacked_p, opt_state = state
        with RecordEvent("offload.grad"):
            loss, g_outer, g_stacked, new_step = grad_jit(
                (outer_p, stacked_p), opt_state["step"], batch,
                *((rng,) if cfg.dropout > 0.0 else ()))
        slots = opt_state["slots"]
        new_stacked = stacked_p
        chunk_results = []
        for ci in range(n_chunks):
            with RecordEvent("offload.chunk"):
                if ci >= 2:
                    # backpressure: dispatch is async, so without this the
                    # Python loop uploads EVERY chunk's slots before the
                    # first update frees any — the whole optimizer state
                    # lands on device at once and the step OOMs exactly
                    # like the unchunked version. Once chunk ci-2's new
                    # slots are back at rest on the host, its update has
                    # executed and its donated device buffers are free, so
                    # at most ~2 chunks of slots are in flight on device
                    jax.block_until_ready(chunk_results[ci - 2])
                slots_chunk = jax.device_put(
                    {n: {sname: slots[n][sname][ci] for sname in slots[n]}
                     for n in stacked_slot_names}, chunk_slot_dev)
                new_stacked, new_chunk = chunk_jit(
                    new_stacked, g_stacked, slots_chunk, new_step, starts[ci])
                # back to host residence; dropping the device ref frees the
                # chunk's HBM before chunk ci+2 uploads
                chunk_results.append(
                    jax.device_put(new_chunk, chunk_slot_shardings))
        with RecordEvent("offload.outer"):
            outer_slots = jax.device_put(
                {n: slots[n] for n in outer_slot_names}, outer_slot_dev)
            new_outer, new_outer_slots = outer_jit(outer_p, g_outer,
                                                   outer_slots, new_step)
            new_outer_slots = jax.device_put(new_outer_slots,
                                             outer_slot_shardings)
        new_slots = {n: {sname: tuple(cr[n][sname]
                                      for cr in chunk_results)
                         for sname in slots[n]}
                     for n in stacked_slot_names}
        new_slots.update(new_outer_slots)
        return (new_outer, new_stacked,
                {"step": new_step, "slots": new_slots}), loss

    state0 = (jax.device_put(outer, outer_shardings),
              jax.device_put(stacked, stacked_shardings),
              {"step": jax.device_put(jnp.zeros((), jnp.int32), ns(P())),
               "slots": slots_host})
    return step_fn, state0


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


def sync_params_to_model(model: Layer, state):
    """Write (outer, stacked) back into the Layer tree (for save/eval)."""
    outer_p, stacked_p, _ = state
    nl = model.config.num_layers
    # the blocks are the parameters named "<prefix>.layers.<i>.<rel>"
    prefix = next(n for n, _ in model.named_parameters()
                  if ".layers." in n).split(".layers.")[0]
    flat = dict(outer_p)
    for rel, v in stacked_p.items():
        for i in range(nl):
            flat[f"{prefix}.layers.{i}.{rel}"] = v[i]
    load_state(model, flat)
