"""Flash-attention Pallas kernel vs XLA attention.

The kernels are TPU programs; these tests check their arithmetic on the
CPU, so they ask for the Pallas interpreter themselves (the library
never falls into it). tests/test_tpu_compile.py compiles the same
kernels for a described chip."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.ops.flash_attention import flash_attention
from paddle_tpu.nn.functional.attention import _xla_attention


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(fa, "_interpret", lambda: True)


def _qkv(b=2, s=256, h=4, d=64, seed=0):
    rs = np.random.RandomState(seed)
    return [jnp.asarray(rs.randn(b, s, h, d) * 0.5, jnp.float32)
            for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = _qkv()
    out = flash_attention(q, k, v, causal=causal)
    ref = _xla_attention(q, k, v, None, 0.0, causal, False, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=2e-5)


def test_grads_match_xla():
    q, k, v = _qkv(s=128)
    g1 = jax.grad(lambda a, b, c: jnp.sum(
        flash_attention(a, b, c, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda a, b, c: jnp.sum(
        _xla_attention(a, b, c, None, 0.0, True, False, None) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=5e-5)


def test_library_does_not_interpret_unasked(monkeypatch):
    """Off-chip the kernel raises; it does not quietly become a CPU
    emulation of itself."""
    monkeypatch.undo()          # drop this file's request
    assert fa._interpret() is False
    q, k, v = _qkv(s=128)
    with pytest.raises(ValueError, match="interpret mode"):
        flash_attention(q, k, v)


def test_rejects_unaligned_seq():
    q, k, v = _qkv(s=100)
    with pytest.raises(ValueError):
        flash_attention(q, k, v)


class TestMaskedFlash:
    """k-side padding mask (VERDICT r3 item 6): padded-batch BERT keeps
    the flash path."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_masked_forward_matches_xla(self, causal):
        q, k, v = _qkv(s=256)
        lengths = np.array([200, 131])
        mask = np.arange(256)[None, :] < lengths[:, None]   # [b, s]
        out = flash_attention(q, k, v, causal=causal,
                              kv_mask=jnp.asarray(mask))
        # XLA reference: [b, 1, 1, k] boolean mask
        m4 = jnp.asarray(mask)[:, None, None, :]
        ref = _xla_attention(q, k, v, m4, 0.0, causal, False, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)

    def test_masked_grads_match_xla(self):
        q, k, v = _qkv(s=128)
        mask = jnp.asarray(np.arange(128)[None, :] <
                           np.array([100, 77])[:, None])
        # padded loss: only valid q positions contribute (BERT contract)
        wq = mask.astype(jnp.float32)[:, :, None, None]

        def loss_flash(a, b, c):
            return jnp.sum((flash_attention(a, b, c, kv_mask=mask)
                            * wq) ** 2)

        def loss_xla(a, b, c):
            m4 = mask[:, None, None, :]
            return jnp.sum((_xla_attention(a, b, c, m4, 0.0, False,
                                           False, None) * wq) ** 2)

        g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss_xla, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=5e-5)

    def test_fully_masked_rows_are_zero(self):
        q, k, v = _qkv(s=128)
        mask = jnp.zeros((2, 128), bool)
        out = flash_attention(q, k, v, kv_mask=mask)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)

    def test_dispatch_reduces_bert_mask(self):
        """[b, 1, 1, k] bool/int masks reduce to the k-side flash mask in
        the dispatcher; float (additive) and per-query masks do not."""
        from paddle_tpu.nn.functional.attention import _as_kv_mask
        bm = (np.arange(8) < 5)[None, None, None, :]
        m = _as_kv_mask(jnp.asarray(bm), 3, 8)
        assert m is not None and m.shape == (3, 8)
        assert np.asarray(m)[0].tolist() == [True] * 5 + [False] * 3
        # tokenizer-style int 0/1 mask: nonzero = keep
        im = (np.arange(8) < 5).astype(np.int32)[None, None, None, :]
        m = _as_kv_mask(jnp.asarray(im), 3, 8)
        assert m is not None and np.asarray(m)[0].tolist() == \
            [True] * 5 + [False] * 3
        # float masks are ADDITIVE in the XLA path -> never reduced
        add = np.where(np.arange(8) < 5, 0.0, -1e4)[None, None, None, :]
        assert _as_kv_mask(jnp.asarray(add), 3, 8) is None
        # per-query mask cannot reduce
        full = np.ones((3, 1, 8, 8), bool)
        assert _as_kv_mask(jnp.asarray(full), 3, 8) is None
        # [b, k] would mean (q, k) to the XLA path -> no reduction
        assert _as_kv_mask(jnp.ones((3, 8), bool), 3, 8) is None


class TestDispatchUnderMesh:
    """On a multi-device mesh GSPMD cannot partition a Mosaic kernel, so
    the dispatch runs it per shard (batch over data x sharding, heads
    over 'model'). Here: interpreter kernels on the virtual CPU mesh,
    with the dispatch told it is on a TPU."""

    @pytest.fixture
    def mesh(self, monkeypatch):
        from paddle_tpu.distributed import build_mesh, topology
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(topology, "_GLOBAL_MESH", None)
        return build_mesh(dp=2, mp=2)

    def test_masked_kernel_per_shard_matches_xla(self, mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.nn.functional import attention as A
        q, k, v = _qkv(b=4, s=512, h=4)
        mask = jnp.asarray(np.arange(512)[None, :] <
                           np.array([512, 400, 300, 77])[:, None])
        m4 = mask[:, None, None, :]
        sh = NamedSharding(mesh, P("data", None, "model", None))
        q, k, v = (jax.device_put(a, sh) for a in (q, k, v))

        def loss(fn):
            return jax.jit(jax.value_and_grad(
                lambda a, b, c: jnp.sum(fn(a, b, c) ** 2),
                argnums=(0, 1, 2)))

        sdpa = loss(lambda a, b, c: A.scaled_dot_product_attention(
            a, b, c, attn_mask=m4))
        assert "shard_map" in str(jax.make_jaxpr(sdpa)(q, k, v))
        got, got_g = sdpa(q, k, v)
        ref, ref_g = loss(lambda a, b, c: A._xla_attention(
            a, b, c, m4, 0.0, False, False, None))(q, k, v)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
        for a, b in zip(got_g, ref_g):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=5e-5)

    @staticmethod
    def _ok_traced(q, k, causal=False):
        """_pallas_ok as a step sees it: on traced operands, which do not
        show where they will live."""
        from paddle_tpu.nn.functional import attention as A
        seen = []
        jax.eval_shape(
            lambda a, b: seen.append(A._pallas_ok(a, b, causal)) or a, q, k)
        return seen[0]

    def test_uneven_split_takes_the_xla_path(self, mesh):
        q, k, _ = _qkv(b=3, s=512, h=4)          # 3 rows over data=2
        assert not self._ok_traced(q, k)
        q, k, _ = _qkv(b=4, s=512, h=3)          # 3 heads over model=2
        assert not self._ok_traced(q, k)
        q, k, _ = _qkv(b=4, s=512, h=4)
        assert self._ok_traced(q, k)

    @pytest.mark.parametrize("axes", [dict(dp=2, pp=2, mp=2),
                                      dict(dp=2, mp=2, sp=2)],
                             ids=["pipe", "sequence"])
    def test_unverified_mesh_axes_take_the_xla_path(self, mesh, axes):
        """The per-shard kernel was compiled and run for data x sharding
        x model only; a mesh with any other axis > 1 stays off it."""
        from paddle_tpu.distributed import build_mesh
        build_mesh(**axes)
        q, k, _ = _qkv(b=4, s=512, h=4)
        assert not self._ok_traced(q, k)

    def test_stale_mesh_leaves_single_device_operands_alone(self, mesh):
        """A mesh left over from an earlier step must not spread an eager
        call whose operands rest on one device."""
        from paddle_tpu.nn.functional import attention as A
        q, k, v = _qkv(b=3, s=512, h=3)          # would not even divide
        assert A._mesh_shards(q) is None and A._pallas_ok(q, k, False)
        out = A.scaled_dot_product_attention(q, k, v)
        assert out.sharding.device_set == q.sharding.device_set
        ref = _xla_attention(q, k, v, None, 0.0, False, False, None)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=2e-5)

    def test_step_scope_beats_the_global_mesh(self, mesh):
        """Inside `mesh_scope` the dispatch follows the mesh the step is
        traced for — in both directions."""
        from paddle_tpu.distributed import build_mesh
        from paddle_tpu.distributed.topology import mesh_scope
        from paddle_tpu.nn.functional import attention as A
        q, k, v = _qkv(b=4, s=512, h=4)

        def traced(scope):
            def f(a, b, c):
                with mesh_scope(scope):
                    return A.scaled_dot_product_attention(a, b, c)
            return str(jax.make_jaxpr(f)(q, k, v))

        one = build_mesh(devices=jax.devices()[:1])   # now the global one
        assert "shard_map" in traced(mesh)
        build_mesh(dp=2, mp=2)                        # stale for `one`
        text = traced(one)
        assert "shard_map" not in text and "pallas_call" in text


def test_train_step_traces_for_its_own_mesh():
    """build_train_step traces at the first call; a mesh built in between
    must not leak into a step that was built for one device."""
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models.gpt import (GPTForPretraining, build_train_step,
                                       gpt_tiny)
    pt.seed(0)
    cfg = gpt_tiny(dropout=0.0)
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-3,
                             parameters=model.parameters())
    one = build_mesh(devices=jax.devices()[:1])
    step, state = build_train_step(model, opt, one, remat=False)
    build_mesh(dp=2, mp=2)                            # the stale mesh
    ids = jnp.asarray(np.random.RandomState(0).randint(
        0, cfg.vocab_size, (4, 32)), jnp.int32)
    state, loss = step(state, (ids, ids))
    assert np.isfinite(float(loss))
    devs = {d for leaf in jax.tree.leaves(state)
            for d in leaf.sharding.device_set}
    assert devs == {jax.devices()[0]}
