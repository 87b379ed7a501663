"""Hybrid-parallel tests on the virtual 8-device CPU mesh.

Mirrors the reference's distributed test strategy (SURVEY.md §4): numeric
parity between the parallel implementation and the single-device reference
(`hybrid_parallel_mp_model.py`, `hybrid_parallel_pp_alexnet.py` compare
parallel vs single-card convergence).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import build_mesh
from paddle_tpu.distributed.meta_parallel import (
    ColumnParallelLinear, DygraphShardingOptimizer, ParallelCrossEntropy,
    RowParallelLinear, VocabParallelEmbedding, gpipe, pipelined_apply,
    stack_stage_params)
from paddle_tpu.distributed.meta_parallel.sharding_optimizer import (
    shard_spec_for)
from paddle_tpu.nn.layer import functional_call, trainable_state


class TestMPLayers:
    def test_column_row_pair_matches_dense(self):
        """col(gather=False) → row(input_is_parallel) == two dense linears."""
        pt.seed(0)
        col = ColumnParallelLinear(16, 32, gather_output=False)
        row = RowParallelLinear(32, 16, input_is_parallel=True)
        x = jnp.asarray(np.random.RandomState(0).randn(4, 8, 16),
                        jnp.float32)
        out = row(col(x))
        ref = (x @ np.asarray(col.weight) + np.asarray(col.bias)) \
            @ np.asarray(row.weight) + np.asarray(row.bias)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_vocab_parallel_embedding(self):
        emb = VocabParallelEmbedding(100, 8)
        ids = jnp.asarray([[1, 5, 99], [0, 2, 7]], jnp.int32)
        out = emb(ids)
        np.testing.assert_allclose(
            np.asarray(out[0, 1]), np.asarray(emb.weight)[5], rtol=1e-6)

    def test_parallel_cross_entropy_ignore_index(self):
        ce = ParallelCrossEntropy(ignore_index=-1)
        logits = jnp.asarray(np.random.RandomState(1).randn(2, 4, 7),
                             jnp.float32)
        labels = jnp.asarray([[1, -1, 3, -1], [0, 2, -1, 6]], jnp.int32)
        loss = ce(logits, labels)[..., 0]
        assert float(loss[0, 1]) == 0.0 and float(loss[1, 2]) == 0.0
        assert float(loss[0, 0]) > 0.0

    def test_shared_layer_desc_single_registration(self):
        from paddle_tpu.distributed.meta_parallel import (LayerDesc,
                                                          PipelineLayer,
                                                          SharedLayerDesc)
        import paddle_tpu as pt2
        pipe = PipelineLayer(
            [SharedLayerDesc("emb", pt2.nn.Linear, None, "weight", 8, 8),
             LayerDesc(pt2.nn.Linear, 8, 8),
             SharedLayerDesc("emb", pt2.nn.Linear, None, "weight", 8, 8)],
            num_stages=1)
        names = [n for n, _ in pipe.named_parameters()]
        shared = [n for n in names if "shared_emb" in n]
        assert len(shared) == 2, shared  # one weight + one bias, once

    def test_parallel_cross_entropy_matches_dense(self):
        ce = ParallelCrossEntropy()
        logits = jnp.asarray(np.random.RandomState(1).randn(2, 5, 11),
                             jnp.float32)
        labels = jnp.asarray(np.random.RandomState(2).randint(0, 11, (2, 5)))
        loss = ce(logits, labels)[..., 0]
        # reference: -log_softmax picked at label
        ref = -jax.nn.log_softmax(logits, axis=-1)
        ref = jnp.take_along_axis(ref, labels[..., None], axis=-1)[..., 0]
        np.testing.assert_allclose(np.asarray(loss), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestStackedPipeline:
    def _blocks(self, n, d):
        """n linear+relu blocks as stacked params."""
        rs = np.random.RandomState(0)
        trees = [{"w": jnp.asarray(rs.randn(d, d) * 0.1, jnp.float32),
                  "b": jnp.zeros((d,), jnp.float32)} for _ in range(n)]
        return trees

    @staticmethod
    def _apply(p, x):
        return jax.nn.relu(x @ p["w"] + p["b"])

    def test_gpipe_matches_sequential(self):
        d, S, M = 8, 4, 4
        trees = self._blocks(S, d)
        stacked = stack_stage_params(trees)
        x = jnp.asarray(np.random.RandomState(3).randn(8, d), jnp.float32)
        out = pipelined_apply(self._apply, stacked, x, num_stages=S,
                              num_microbatches=M)
        ref = x
        for t in trees:
            ref = self._apply(t, ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)

    def test_gpipe_grads_match_sequential(self):
        d, S, M = 4, 2, 2
        trees = self._blocks(S, d)
        stacked = stack_stage_params(trees)
        x = jnp.asarray(np.random.RandomState(4).randn(4, d), jnp.float32)

        def loss_pipe(sp):
            return jnp.sum(pipelined_apply(self._apply, sp, x,
                                           num_stages=S, num_microbatches=M))

        def loss_seq(sp):
            h = x
            for i in range(S):
                h = self._apply(jax.tree.map(lambda a, i=i: a[i], sp), h)
            return jnp.sum(h)

        g1 = jax.grad(loss_pipe)(stacked)
        g2 = jax.grad(loss_seq)(stacked)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5), g1, g2)


class TestZeRO:
    """ZeRO over the 'sharding' mesh axis: optimizer state AND grads live
    sharded (ZeRO-2), batch splits over data×sharding, loss matches the
    unsharded run. Reference bar: `sharding_optimizer.py:87-1385`."""

    def _run(self, mesh_dims, steps=3):
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.trainer import build_train_step
        pt.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, max_position_embeddings=64,
                        dtype=jnp.float32)
        model = GPTForPretraining(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-3)
        mesh = build_mesh(**mesh_dims)
        step, state = build_train_step(model, opt, mesh, remat=False)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        losses = []
        for _ in range(steps):
            state, loss = step(state, (ids, labels))
            losses.append(float(loss))
        return losses, state

    def test_zero2_state_sharded_and_loss_parity(self):
        l_ref, _ = self._run(dict(dp=4))
        l_sh, state = self._run(dict(sharding=4))
        np.testing.assert_allclose(l_sh, l_ref, rtol=2e-4)
        # optimizer-state shards must be 1/4 of the full tensor
        slots = state[2]["slots"]
        name = "blocks.qkv.weight"
        m1 = slots[name]["moment1"]
        shard_shape = m1.addressable_shards[0].data.shape
        assert int(np.prod(shard_shape)) == int(np.prod(m1.shape)) // 4, \
            (shard_shape, m1.shape)
        # every per-param moment of rank>=1 with a shardable dim is split
        n_sharded = sum(
            1 for pslots in slots.values() for v in pslots.values()
            if v.ndim and int(np.prod(v.addressable_shards[0].data.shape))
            < int(np.prod(v.shape)))
        assert n_sharded >= 10, n_sharded

    def test_zero2_with_tp_pp(self):
        """sharding composes with mp+pp on one mesh (4-D hybrid)."""
        l_ref, _ = self._run(dict(dp=1, pp=2, mp=2))
        l_sh, _ = self._run(dict(sharding=2, pp=2, mp=2))
        np.testing.assert_allclose(l_sh, l_ref, rtol=2e-4)


class TestOneFOneB:
    """1F1B schedule (reference `section_worker.cc:144-156`): grad parity
    with GPipe/sequential + activation residency bounded by S, not M."""

    def _run(self, schedule, mesh_dims, M=4, steps=2):
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.trainer import build_train_step
        pt.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=4, max_position_embeddings=64,
                        dtype=jnp.float32)
        model = GPTForPretraining(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-3)
        mesh = build_mesh(**mesh_dims)
        step, state = build_train_step(model, opt, mesh,
                                       num_microbatches=M, remat=True,
                                       pipeline_schedule=schedule)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        losses = []
        for _ in range(steps):
            state, loss = step(state, (ids, labels))
            losses.append(float(loss))
        return losses, state

    def test_1f1b_matches_gpipe_and_sequential(self):
        l_g, s_g = self._run("gpipe", dict(dp=2, pp=2, mp=2))
        l_f, s_f = self._run("1f1b", dict(dp=2, pp=2, mp=2))
        l_s, _ = self._run("gpipe", dict(dp=2, mp=2))  # no pipe → scan
        np.testing.assert_allclose(l_f, l_g, rtol=1e-4)
        np.testing.assert_allclose(l_f, l_s, rtol=1e-4)
        # identical params after 2 optimizer steps → identical grads
        for (n, a), (_, b) in zip(sorted(s_g[1].items()),
                                  sorted(s_f[1].items())):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-3, atol=2e-5, err_msg=n)

    def test_1f1b_activation_memory_bounded_by_stages(self):
        """GPipe holds all M microbatch stashes live across the backward;
        1F1B's stash ring is depth 2S-1 — compiled temp memory must grow
        with M for GPipe but stay ~flat for 1F1B."""
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.trainer import build_train_step

        def temp_bytes(schedule, M):
            pt.seed(0)
            cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=2,
                            num_heads=4, max_position_embeddings=64,
                            dtype=jnp.float32)
            model = GPTForPretraining(cfg)
            opt = pt.optimizer.SGD(learning_rate=1e-3)
            mesh = build_mesh(pp=2)
            step, state = build_train_step(model, opt, mesh,
                                           num_microbatches=M, remat=True,
                                           pipeline_schedule=schedule)
            ids = jnp.zeros((2 * M, 32), jnp.int32)
            comp = jax.jit(lambda s, b: step(s, b)).lower(
                state, (ids, ids)).compile()
            ma = comp.memory_analysis()
            if ma is None:
                pytest.skip("backend reports no memory analysis")
            return ma.temp_size_in_bytes

        g4, g32 = temp_bytes("gpipe", 4), temp_bytes("gpipe", 32)
        f4, f32 = temp_bytes("1f1b", 4), temp_bytes("1f1b", 32)
        assert f32 < 0.5 * g32, (f32, g32)   # measured ~0.35 at M=32
        # 1F1B growth M=4→32 far below GPipe growth (O(S) vs O(M) stash)
        assert (f32 - f4) < 0.5 * (g32 - g4), (f4, f32, g4, g32)

    def _run_dropout(self, schedule, steps=3):
        """Train with dropout=0.1 under the given schedule; per-(microbatch,
        stage) dropout keys derive identically in both schedules
        (stacked_pipeline._mb_key) so losses must match exactly."""
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.trainer import build_train_step
        pt.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                        num_heads=4, max_position_embeddings=64,
                        dropout=0.1, dtype=jnp.float32)
        model = GPTForPretraining(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-3)
        step, state = build_train_step(model, opt, build_mesh(pp=2),
                                       num_microbatches=4,
                                       pipeline_schedule=schedule)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        losses = []
        for i in range(steps):
            state, loss = step(state, (ids, labels), jax.random.key(i))
            losses.append(float(loss))
        return losses

    def test_1f1b_trains_with_dropout_matching_gpipe(self):
        """VERDICT r2 item 4: 1F1B must run real configs with dropout
        (reference `section_worker.cc:144-156`)."""
        l_g = self._run_dropout("gpipe")
        l_f = self._run_dropout("1f1b")
        np.testing.assert_allclose(l_f, l_g, rtol=1e-4)

    def test_dropout_masks_differ_across_steps(self):
        """Two different step keys must give different losses (the mask is
        not baked into the compiled program as a constant)."""
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.trainer import build_train_step
        pt.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                        num_heads=4, max_position_embeddings=64,
                        dropout=0.5, dtype=jnp.float32)
        model = GPTForPretraining(cfg)
        opt = pt.optimizer.SGD(learning_rate=0.0)  # frozen params
        step, state = build_train_step(model, opt, build_mesh(pp=2),
                                       num_microbatches=2,
                                       pipeline_schedule="1f1b",
                                       donate=False)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 128, (4, 16)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 128, (4, 16)), jnp.int32)
        _, l1 = step(state, (ids, labels), jax.random.key(1))
        _, l2 = step(state, (ids, labels), jax.random.key(2))
        assert float(l1) != float(l2)


class TestTrainStep:
    def test_hybrid_train_step_decreases_loss(self):
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step
        pt.seed(0)
        mesh = build_mesh(dp=2, pp=2, mp=2)
        model = GPTForPretraining(gpt_tiny())
        opt = pt.optimizer.AdamW(learning_rate=1e-3)
        step, state = build_train_step(model, opt, mesh, num_microbatches=2)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 512, (4, 32)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 512, (4, 32)), jnp.int32)
        state, l0 = step(state, (ids, labels))
        for _ in range(4):
            state, l = step(state, (ids, labels))
        assert float(l) < float(l0)

    def test_parallel_matches_single_device(self):
        """Same model/config trained on the hybrid mesh vs plain jit must
        produce the same loss trajectory (the reference's dist-vs-single
        loss-equivalence assertion, test_dist_base.py:743)."""
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step
        import dataclasses
        cfg = dataclasses.replace(gpt_tiny(), dtype=jnp.float32)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 512, (4, 32)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 512, (4, 32)), jnp.int32)

        losses = {}
        for name, dims in [("single", dict(dp=1)),
                           ("hybrid", dict(dp=2, mp=2, pp=1))]:
            pt.seed(0)
            model = GPTForPretraining(cfg)
            opt = pt.optimizer.AdamW(learning_rate=1e-3)
            mesh = build_mesh(**dims)
            step, state = build_train_step(model, opt, mesh,
                                           num_microbatches=1, remat=False)
            ls = []
            for _ in range(3):
                state, l = step(state, (ids, labels))
                ls.append(float(l))
            losses[name] = ls
        np.testing.assert_allclose(losses["single"], losses["hybrid"],
                                   rtol=2e-4)


class TestShardingOptimizer:
    def test_shard_spec_picks_divisible_dim(self):
        from jax.sharding import PartitionSpec as P
        assert shard_spec_for((33, 64), 8) == P(None, "sharding")
        assert shard_spec_for((64, 33), 8) == P("sharding", None)
        assert shard_spec_for((33,), 8) == P()
        # respects an existing base spec dim
        assert shard_spec_for((64, 64), 8, base_spec=P("model", None)) \
            == P("model", "sharding")

    def test_dygraph_sharding_optimizer_steps(self):
        pt.seed(0)
        build_mesh(dp=2, sharding=4)
        lin = pt.nn.Linear(16, 16)
        inner = pt.optimizer.Adam(learning_rate=1e-2,
                                  parameters=lin.parameters())
        opt = DygraphShardingOptimizer(inner_opt=inner)
        x = jnp.ones((4, 16))

        def loss_fn(params):
            out, _ = functional_call(lin, params, x)
            return jnp.sum(out ** 2)

        params = trainable_state(lin)
        # optimizer params are keyed by p.name — map grads accordingly
        grads_struct = jax.grad(loss_fn)(params)
        name_of = {n: p.name or f"param_{i}"
                   for i, (n, p) in enumerate(lin.named_parameters())}
        grads = {name_of[n]: g for n, g in grads_struct.items()}
        before = np.asarray(lin.weight)
        opt.step(grads)
        after = np.asarray(lin.weight)
        assert not np.allclose(before, after)


class TestBert:
    def test_bert_pretraining_loss(self):
        from paddle_tpu.models import BertForPretraining, bert_tiny
        pt.seed(0)
        model = BertForPretraining(bert_tiny())
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 512, (2, 16)), jnp.int32)
        mlm_labels = jnp.where(jnp.asarray(rs.rand(2, 16) < 0.15),
                               ids, -1)
        nsp = jnp.asarray([0, 1], jnp.int32)
        loss = model(ids, masked_lm_labels=mlm_labels,
                     next_sentence_labels=nsp)
        assert np.isfinite(float(loss))

    def test_masked_positions_gather_matches_dense_loss(self):
        """The reference head gathers masked_positions before the vocab
        projection (BertPretrainingHeads.forward); the gathered loss must
        equal the dense ignore_index(-1) loss over the same mask set."""
        from paddle_tpu.models import BertForPretraining, bert_tiny
        pt.seed(0)
        model = BertForPretraining(bert_tiny())
        rs = np.random.RandomState(1)
        b, s, p = 2, 16, 4
        ids = jnp.asarray(rs.randint(0, 512, (b, s)), jnp.int32)
        positions = np.stack([np.sort(rs.choice(s, p, replace=False))
                              for _ in range(b)])
        labels_p = rs.randint(0, 512, (b, p)).astype(np.int32)
        labels_p[1, -1] = -1  # ragged prediction count pads with -1
        dense = np.full((b, s), -1, np.int32)
        for i in range(b):
            for j in range(p):
                if labels_p[i, j] >= 0:
                    dense[i, positions[i, j]] = labels_p[i, j]
        nsp = jnp.asarray([0, 1], jnp.int32)
        l_gather = model(ids, masked_lm_labels=jnp.asarray(labels_p),
                         next_sentence_labels=nsp,
                         masked_positions=jnp.asarray(positions))
        l_dense = model(ids, masked_lm_labels=jnp.asarray(dense),
                        next_sentence_labels=nsp)
        np.testing.assert_allclose(float(l_gather), float(l_dense),
                                   rtol=1e-5)

    def test_bert_chunked_dense_ce_matches_unchunked(self):
        """Dense [B,S] labels at seq % 128 == 0 take the chunked-scan CE
        (the one-fusion version spilled vmem on TPU); same loss."""
        from paddle_tpu.models import BertForPretraining, bert_tiny
        pt.seed(0)
        # max_position_embeddings must cover the 256-seq chunked path
        # (128-pos default gathers OOB -> NaN, and allclose(nan, nan)
        # passes silently)
        model = BertForPretraining(
            bert_tiny(max_position_embeddings=256))
        rs = np.random.RandomState(2)
        ids = jnp.asarray(rs.randint(0, 512, (2, 256)), jnp.int32)
        labels = jnp.where(jnp.asarray(rs.rand(2, 256) < 0.15), ids, -1)
        nsp = jnp.asarray([0, 1], jnp.int32)
        l_chunked = model(ids, masked_lm_labels=labels,
                          next_sentence_labels=nsp)
        # numpy reference over the returned logits (no-labels call)
        logits, nsp_logits = model(ids)
        lg = np.asarray(logits, np.float32)
        lse = np.log(np.exp(lg - lg.max(-1, keepdims=True)).sum(-1)) \
            + lg.max(-1)
        lab = np.maximum(np.asarray(labels), 0)
        picked = np.take_along_axis(lg, lab[..., None], -1)[..., 0]
        m = (np.asarray(labels) >= 0).astype(np.float32)
        mlm = ((lse - picked) * m).sum() / m.sum()
        ns = np.asarray(nsp_logits, np.float32)
        ns_lse = np.log(np.exp(ns - ns.max(-1, keepdims=True)).sum(-1)) \
            + ns.max(-1)
        ns_picked = np.take_along_axis(
            ns, np.asarray(nsp)[:, None], -1)[:, 0]
        expected = mlm + (ns_lse - ns_picked).mean()
        np.testing.assert_allclose(float(l_chunked), expected, rtol=2e-5)

    def test_bert_padding_mask(self):
        from paddle_tpu.models import BertModel, bert_tiny
        pt.seed(0)
        model = BertModel(bert_tiny())
        ids = jnp.ones((2, 8), jnp.int32)
        mask = jnp.asarray([[1, 1, 1, 1, 0, 0, 0, 0]] * 2, jnp.int32)
        seq, pooled = model(ids, attention_mask=mask)
        assert seq.shape == (2, 8, 64)
        assert pooled.shape == (2, 64)


class TestZero3:
    """ZeRO-3 (zero_stage=3): PARAMETERS rest sharded over 'sharding'
    with gather-on-use (VERDICT r2 item 5). Reference bar: static
    ShardingOptimizer is ZeRO-2+offload only
    (`sharding_optimizer.py:87-1385`)."""

    def _run(self, mesh_dims, zero_stage, steps=3):
        from paddle_tpu.models import GPTConfig, GPTForPretraining
        from paddle_tpu.trainer import build_train_step
        pt.seed(0)
        cfg = GPTConfig(vocab_size=128, hidden_size=64, num_layers=4,
                        num_heads=4, max_position_embeddings=64,
                        dtype=jnp.float32)
        model = GPTForPretraining(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-3)
        mesh = build_mesh(**mesh_dims)
        step, state = build_train_step(model, opt, mesh,
                                       zero_stage=zero_stage)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, 128, (8, 16)), jnp.int32)
        losses = []
        for _ in range(steps):
            state, loss = step(state, (ids, labels))
            losses.append(float(loss))
        return losses, state

    def test_zero3_param_bytes_per_chip_shrink(self):
        """Live param bytes/chip at sharding=4 < half of sharding=1."""
        _, s1 = self._run(dict(dp=4), zero_stage=3)
        _, s4 = self._run(dict(sharding=4), zero_stage=3)

        def chip_param_bytes(state):
            total = 0
            for tree in state[:2]:          # (outer, stacked)
                for v in tree.values():
                    total += v.addressable_shards[0].data.nbytes
            return total

        b1, b4 = chip_param_bytes(s1), chip_param_bytes(s4)
        assert b4 < 0.5 * b1, (b4, b1)
        # and the big block weights are truly sharded 4-way
        qkv = s4[1]["qkv.weight"]
        assert qkv.addressable_shards[0].data.size == qkv.size // 4

    def test_zero3_loss_matches_dp(self):
        l_dp, _ = self._run(dict(dp=4), zero_stage=2)
        l_z3, _ = self._run(dict(sharding=4), zero_stage=3)
        np.testing.assert_allclose(l_z3, l_dp, rtol=2e-4)

    def test_zero3_composes_with_tp(self):
        l_ref, _ = self._run(dict(dp=1, mp=2), zero_stage=2)
        l_z3, s = self._run(dict(sharding=2, mp=2), zero_stage=3)
        np.testing.assert_allclose(l_z3, l_ref, rtol=2e-4)
        # TP dim and ZeRO dim shard DIFFERENT axes of the same weight
        qkv = s[1]["qkv.weight"]
        assert qkv.addressable_shards[0].data.size == qkv.size // 4


def test_ernie_10b_config_shape():
    """BASELINE config 5 model definition exists and is ~10B params."""
    from paddle_tpu.models import ernie_10b
    cfg = ernie_10b()
    d, L, V, ffn = (cfg.hidden_size, cfg.num_layers, cfg.vocab_size,
                    cfg.ffn_hidden)
    params = L * (4 * d * d + 2 * d * ffn) + V * d + \
        cfg.max_position_embeddings * d
    assert 9e9 < params < 13e9, params


class TestShardingOffload:
    def test_dygraph_sharding_offload_roundtrip(self):
        """offload=True (reference: sharding offload_helper.py): slots
        REST in pinned_host memory between steps, stream to device for
        the update, and the update still applies."""
        pt.seed(0)
        build_mesh(dp=2, sharding=4)
        lin = pt.nn.Linear(16, 16)
        inner = pt.optimizer.Adam(learning_rate=1e-2,
                                  parameters=lin.parameters())
        opt = DygraphShardingOptimizer(inner_opt=inner, offload=True)
        x = jnp.ones((4, 16))

        def loss_fn(params):
            out, _ = functional_call(lin, params, x)
            return jnp.sum(out ** 2)

        params = trainable_state(lin)
        grads_struct = jax.grad(loss_fn)(params)
        name_of = {n: p.name or f"param_{i}"
                   for i, (n, p) in enumerate(lin.named_parameters())}
        grads = {name_of[n]: g for n, g in grads_struct.items()}
        before = np.asarray(lin.weight)
        opt.step(grads)
        opt.step(grads)
        assert not np.allclose(before, np.asarray(lin.weight))
        kinds = {v.sharding.memory_kind
                 for v in jax.tree.leaves(inner._accumulators["slots"])}
        assert kinds == {"pinned_host"}
