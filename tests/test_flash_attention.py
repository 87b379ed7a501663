"""Flash-attention Pallas kernel vs XLA attention.

The kernels are TPU programs; these tests check their arithmetic on the
CPU, so they ask for the Pallas interpreter themselves (the library
never falls into it). tests/test_tpu_compile.py compiles the same
kernels for a described chip."""
import functools

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


def _against_xla(s, causal, masked, d, dtype, fwd_tol, grad_tol):
    """Forward and all three gradients against `_xla_attention`, through a
    loss that weighs every output element differently."""
    rs = np.random.RandomState(s + d)
    q, k, v = [jnp.asarray(rs.randn(2, s, 1, d) * 0.5, dtype)
               for _ in range(3)]
    w = jnp.asarray(rs.randn(2, s, 1, d), jnp.float32)
    mask = m4 = None
    if masked:
        mask = jnp.asarray(np.arange(s)[None, :] <
                           np.array([s - s // 3, s // 2 + 3])[:, None])
        m4 = mask[:, None, None, :]

    def run(attend):
        def loss(a, b, c):
            out = attend(a, b, c)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    got = run(lambda a, b, c: flash_attention(a, b, c, causal=causal,
                                              kv_mask=mask))
    ref = run(lambda a, b, c: _xla_attention(a, b, c, m4, 0.0, causal,
                                             False, None))
    for a, b, tol in zip(got, ref, [fwd_tol] + [grad_tol] * 3):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), **tol)


@pytest.mark.parametrize("d", [64, 80])
@pytest.mark.parametrize("masked", [False, True], ids=["dense", "kvmask"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("s", [128, 384, 1024, 2048])
def test_forward_and_grads_match_xla(s, causal, masked, d):
    """The walks `_plan` chooses for float32 operands: one chunk (128),
    three groups of one chunk (384), the whole-sequence block with a group
    that stops at its diagonal (1024), and several resident blocks with
    carried state (2048: float32 operands leave the whole-sequence path
    there)."""
    _against_xla(s, causal, masked, d, jnp.float32,
                 dict(rtol=1e-5, atol=2e-5), dict(rtol=1e-4, atol=5e-5))


@pytest.mark.parametrize("causal,masked", [
    (True, False), (False, True), (True, True)],
    ids=["causal", "kvmask", "causal-kvmask"])
@pytest.mark.parametrize("s", [1024, 1152, 2048])
def test_bf16_operands_match_xla(s, causal, masked):
    """bf16 operands, where both sides round p and ds to bf16 at different
    places of the sum: the GPT shape's sequence (one chunk), three blocks
    of one 384-row chunk with carried state (1152), and the one walk of
    two chunks a side, which only bf16 operands reach (2048: a resident
    block of two 1024-row chunks)."""
    assert fa._plan(s, 64, jnp.bfloat16, causal)[:2] == {
        1024: (1024, 1024), 1152: (384, 384), 2048: (2048, 1024)}[s]
    _against_xla(s, causal, masked, 64, jnp.bfloat16,
                 dict(rtol=2e-2, atol=2e-2), dict(rtol=5e-2, atol=5e-2))


def _covered(s, causal, d, dtype, transposed, window=None):
    """The spans and groups the kernels are built from, laid over the (q,
    k) plane: every needed score is covered exactly once, a group that
    applies no mask holds none outside the causal band, and the area and
    the bodies are what `score_work` counts."""
    _, c, sub = fa._plan(s, d, dtype, causal)
    seen = np.zeros((s, s), np.int32)                     # [q, k]
    bodies = masked_bodies = 0
    q_pos, k_pos = np.arange(s)[:, None], np.arange(s)[None, :]
    keep = np.ones((s, s), bool) if not causal else q_pos >= k_pos
    if window is not None:
        keep &= q_pos - k_pos < window
    for o0 in range(0, s, c):
        for j, delta in fa._chunk_spans(o0, c, s // c, causal,
                                        not transposed, window):
            groups = fa._groups(c, sub, not transposed, delta, window)
            bodies += 1
            masked_bodies += any(off is not None for *_, off in groups)
            for g, r_lo, r_hi, off in groups:
                out = slice(o0 + g, o0 + g + sub)
                red = slice(j * c + r_lo, j * c + r_hi)
                q, k = (red, out) if transposed else (out, red)
                if off is None:
                    assert keep[q, k].all()             # needs no mask
                seen[q, k] += 1
    assert seen.max() == 1 and (seen >= keep).all()
    work = fa.score_work(s, causal, d, dtype, transposed, window)
    assert (work.chunks, work.masked_chunks) == (bodies, masked_bodies)
    assert work.needed == keep.sum()
    assert work.ratio == pytest.approx(seen.sum() / keep.sum())


@pytest.mark.parametrize("transposed", [False, True], ids=["fwd-dq", "dkv"])
@pytest.mark.parametrize("s,causal,d,dtype", [
    (1024, True, 64, jnp.bfloat16), (1024, False, 64, jnp.bfloat16),
    (384, True, 64, jnp.bfloat16), (2048, True, 64, jnp.bfloat16),
    (2048, True, 64, jnp.float32), (1152, True, 80, jnp.bfloat16),
    (512, False, 64, jnp.bfloat16)])
def test_score_work_is_what_the_walk_covers(s, causal, d, dtype, transposed):
    """`score_work` against the geometry: laid over the (q, k) plane, the
    spans and groups the kernels are built from cover every needed score
    exactly once, a group that applies no causal mask holds none above
    the diagonal, and the area is what the counter says."""
    _covered(s, causal, d, dtype, transposed)


@pytest.mark.parametrize("transposed", [False, True], ids=["fwd-dq", "dkv"])
@pytest.mark.parametrize("s,d,dtype,window", [
    (2048, 64, jnp.float32, 200), (2048, 64, jnp.float32, 512),
    (2048, 64, jnp.float32, 700), (2048, 64, jnp.float32, 2048),
    (2048, 64, jnp.bfloat16, 1), (2048, 64, jnp.bfloat16, 300),
    (1152, 80, jnp.bfloat16, 500), (4096, 128, jnp.bfloat16, 1024)])
def test_window_score_work_is_what_the_walk_covers(s, d, dtype, window,
                                                   transposed):
    """The same geometry under a sliding window: the band's lower edge is
    a second diagonal, and the chunks below it are not walked."""
    _covered(s, True, d, dtype, transposed, window)


def test_window_score_work_at_the_trinity_cell():
    """8192 tokens of head dim 128 in bf16 under a window of 2048: 1024-row
    blocks of one chunk, so a query block meets 3 of the 8 key blocks
    (its own, masked causally, the one before in full, and the one the
    band's lower edge crosses, masked by the band) where the causal
    kernels meet 1 to 8: 21 chunk bodies against 36. The band needs
    0.4375 of the causal triangle's scores."""
    causal = fa.score_work(8192, True, 128)
    band = fa.score_work(8192, True, 128, window=2048)
    assert fa._plan(8192, 128, jnp.bfloat16, True) == (1024, 1024, 256)
    assert fa.window_kblocks(8192, 128, jnp.bfloat16, 2048) == 3
    assert (causal.chunks, causal.masked_chunks) == (36, 8)
    assert (band.chunks, band.masked_chunks) == (21, 8 + 6)
    assert band.needed / causal.needed == pytest.approx(0.4375, rel=1e-3)
    assert band.ratio == pytest.approx(1.125, rel=1e-3)
    assert fa.score_work(8192, True, 128, transposed=True, window=2048) \
        == band
    # a window as long as the row is the causal mask
    assert fa.score_work(8192, True, 128, window=8192) == causal


def test_score_work_bounds():
    """Causal attention at the GPT cell's shape computed 1.50 times the
    scores it needs on 512 x 512 tiles; the groups bring it under 1.25,
    and without a causal mask nothing is computed twice or in vain."""
    assert fa.score_work(1024, True).ratio <= 1.25
    assert fa.score_work(1024, True, transposed=True).ratio <= 1.25
    for s in (128, 512, 1024, 4096):
        assert fa.score_work(s, False).ratio == 1.0


def test_kernels_trace_the_groups_score_work_counts(monkeypatch):
    """The forward kernel's body at the GPT shape, traced: two matmuls a
    group, and as many groups as the counter's chunks hold."""
    dots = []
    real = fa._dot
    monkeypatch.setattr(fa, "_dot", lambda a, b, dims: dots.append(
        (a.shape, b.shape)) or real(a, b, dims))
    x = jax.ShapeDtypeStruct((2, 1024, 64), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: fa._fwd(q, k, v, True, 0.125), x, x, x)
    _, c, sub = fa._plan(1024, 64, jnp.bfloat16, True)
    assert len(dots) == 2 * fa.score_work(1024, True).chunks * (c // sub)
    scores = sum(a[0] * b[0] for a, b in dots[0::2])      # q k^T shapes
    assert scores == pytest.approx(
        fa.score_work(1024, True).ratio * 1024 * 1025 / 2)


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

    @pytest.mark.parametrize("s,widths,resident", [
        (1024, (16, 8, 16), None), (1024, (16, 8, 16), 128 * 1024),
        (1024, (128, 64, 128), None)],
        ids=["one-block", "eight-blocks", "projections-layout"])
    def test_latent_kernels_per_shard_match_xla(self, mesh, monkeypatch,
                                                s, widths, resident):
        """`F.latent_attention` under dp2 x mp2: the `flash_mla_*` kernels
        per shard, the one rotary head whole on every chip and its
        gradient summed over a chip's heads and then over 'model'; with
        eight blocks a head, dq crosses the k blocks in the fused
        backward's accumulator; at 128-lane widths each chip's kernels
        read its local `[b, s, h w]` arrays where they lie."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        from paddle_tpu.nn.functional import attention as A
        if resident:
            monkeypatch.setattr(fa, "_RESIDENT_BYTES", resident)
            assert fa._values_plan(1024, 16, jnp.float32).block * 8 == 1024
        q, kn, kr, v, do = _latent_operands(s, 4, *widths, b=4)
        args = _projected(q, kn, kr, v)
        specs = [P("data", None, "model", None), P("data", "model"),
                 P("data", None, "model", None), P("data")]
        args = [jax.device_put(a, NamedSharding(mesh, p))
                for a, p in zip(args, specs)]
        do = jax.device_put(do, NamedSharding(mesh, specs[0]))

        def grads():
            return jax.jit(jax.value_and_grad(
                lambda *a: jnp.sum(A.latent_attention(*a) * do),
                argnums=(0, 1, 2, 3)))
        sharded = grads()
        jaxpr = jax.make_jaxpr(sharded)(*args)
        text = str(jaxpr)
        assert "shard_map" in text and "flash_mla_bwd_dkv" in text
        calls = [e for e in _eqns(jaxpr.jaxpr)
                 if e.primitive.name == "pallas_call"]
        assert len(calls) == 2
        # a chip's two rows of two heads: q without position as it lies,
        # [2, s, 2 x 128], or laid out [4, s, 16]
        local_q = (2, s, 2 * widths[0]) if widths[0] % 128 == 0 else \
            (4, s, widths[0])
        assert {c.invars[0].aval.shape for c in calls} == {local_q}
        got, got_g = sharded(*args)
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        ref, ref_g = grads()(*args)
        np.testing.assert_allclose(float(got), float(ref), rtol=1e-5)
        for name, a, b in zip(("dq_nope", "dq_rope", "dkv", "dk_rope"),
                              got_g, ref_g):
            assert a.shape == b.shape, name
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=5e-5, err_msg=name)

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
    from paddle_tpu.models.gpt import GPTForPretraining, gpt_tiny
    from paddle_tpu.trainer import build_train_step
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


# ------------------------------------------------------------ PR 29
# grouped key/value heads, head dim 128 and a per-(q, k) selection,
# against masked XLA attention: forward and the three gradients

def _masked_xla(q, k, v, keep):
    """Plain attention over the pairs `keep` [b, s, s] allows, grouped
    heads repeated; rows that keep nothing give 0, like the kernels."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, 2), jnp.repeat(v, g, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(keep[:, None], sc, -1e30), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v)
    return jnp.where(keep.any(-1)[:, :, None, None], out, 0.0)


@pytest.mark.parametrize("selected", [False, True], ids=["causal", "sel"])
@pytest.mark.parametrize("s,h,h_kv,d", [
    (256, 4, 2, 128), (256, 8, 1, 64), (128, 2, 2, 128),
    (2304, 2, 1, 128),      # six blocks of 384 rows: carried state
], ids=["s256-4over2-d128", "s256-8over1-d64", "s128-2over2-d128",
        "s2304-2over1-d128"])
def test_grouped_heads_and_selection_match_masked_xla(s, h, h_kv, d,
                                                      selected):
    rs = np.random.RandomState(s + h + d)
    b = 2 if s <= 256 else 1
    q = jnp.asarray(rs.randn(b, s, h, d) * 0.5, jnp.float32)
    k, v = [jnp.asarray(rs.randn(b, s, h_kv, d) * 0.5, jnp.float32)
            for _ in range(2)]
    w = jnp.asarray(rs.randn(b, s, h, d), jnp.float32)
    keep = jnp.broadcast_to(jnp.tril(jnp.ones((s, s), bool)), (b, s, s))
    sel = None
    if selected:
        # a third of the causal pairs, every query its own position, but
        # for one row that selects nothing at all
        keep = (keep & jnp.asarray(rs.rand(b, s, s) < 0.3)) \
            | jnp.eye(s, dtype=bool)
        keep = keep.at[:, 5, :].set(False)
        sel = keep.astype(jnp.int8)

    def kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       selection=sel) * w)

    def plain(q, k, v):
        return jnp.sum(_masked_xla(q, k, v, keep) * w)

    np.testing.assert_allclose(
        np.asarray(flash_attention(q, k, v, causal=True, selection=sel)),
        np.asarray(_masked_xla(q, k, v, keep)), rtol=1e-5, atol=2e-5)
    g1 = jax.grad(kernel, (0, 1, 2))(q, k, v)
    g2 = jax.grad(plain, (0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=5e-5)


def test_selected_kernels_carry_their_own_names():
    from paddle_tpu import profiler as prof
    x = jnp.zeros((1, 128, 2, 64), jnp.float32)
    sel = jnp.ones((1, 128, 128), jnp.int8)
    text = str(jax.make_jaxpr(jax.grad(lambda q: jnp.sum(
        flash_attention(q, x[:, :, :1], x[:, :, :1], causal=True,
                        selection=sel))))(x))
    for name in prof.SEL_KERNELS:
        assert f"name={name}" in text or name in text, name
    assert "name=flash_fwd" not in text
    # the forward and ONE backward: no dq kernel of its own (ISSUE 35)
    assert text.count("pallas_call[") == len(prof.SEL_KERNELS) == 2
    assert "flash_sel_bwd_dq" not in text and "flash_bwd" not in text


@pytest.mark.parametrize("s,h,h_kv,d,sizes", [
    # four blocks a head
    (512, 2, 2, 128, {"_RESIDENT_BYTES": 64 * 1024}),
    # six blocks, four query heads on one key/value head, a 64-wide dq
    (768, 4, 1, 64, {"_RESIDENT_BYTES": 64 * 1024}),
    # three blocks of two chunks of two groups: every offset of the walk
    # (block, chunk, group) moves the rows of dq a pair adds to, and the
    # rows and columns of the selection it reads
    (1536, 2, 1, 128, {"_RESIDENT_BYTES": 512 * 1024, "_CHUNK": 256,
                       "_CAUSAL_SUB": 128}),
], ids=["s512-2over2-d128-four-blocks", "s768-4over1-d64-six-blocks",
        "s1536-2over1-d128-blocks-chunks-groups"])
def test_selected_backward_sums_dq_across_k_blocks(monkeypatch, s, h, h_kv,
                                                   d, sizes):
    """The selected backward is the dk/dv walk alone (ISSUE 35): a q
    block's dq is added to by every k block up to its own, grid steps that
    do not follow each other, zeroed at the head's first k block and
    written when its diagonal k block has passed. All three gradients
    against masked XLA attention, one query head a key/value head and
    several; one row selects nothing but its own position, one nothing at
    all."""
    for name, size in sizes.items():
        monkeypatch.setattr(fa, name, size)
    plan = fa._plan(s, d, jnp.float32, True)
    assert plan.block * 3 <= s, plan
    if "_CHUNK" in sizes:
        assert plan == (512, 256, 128)
    rs = np.random.RandomState(s + h + d)
    q = jnp.asarray(rs.randn(1, s, h, d) * 0.5, jnp.float32)
    k, v = [jnp.asarray(rs.randn(1, s, h_kv, d) * 0.5, jnp.float32)
            for _ in range(2)]
    w = jnp.asarray(rs.randn(1, s, h, d), jnp.float32)
    eye = jnp.eye(s, dtype=bool)
    keep = (jnp.tril(jnp.ones((s, s), bool))
            & jnp.asarray(rs.rand(1, s, s) < 0.3)) | eye
    lone = plan.block + 7       # a row of the second q block
    keep = keep.at[:, lone, :].set(eye[lone]).at[:, 5, :].set(False)
    sel = keep.astype(jnp.int8)

    def kernel(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True,
                                       selection=sel) * w)

    def plain(q, k, v):
        return jnp.sum(_masked_xla(q, k, v, keep) * w)

    got = jax.grad(kernel, (0, 1, 2))(q, k, v)
    want = jax.grad(plain, (0, 1, 2))(q, k, v)
    for name, a, b_ in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=1e-4,
                                   atol=5e-5, err_msg=name)
    # the lone row reads its own key with weight 1: dq = (dO v - delta) k
    # = 0 there, and nothing of the row that selects nothing
    assert float(jnp.abs(got[0][:, lone]).max()) < 1e-5
    assert float(jnp.abs(got[0][:, 5]).max()) == 0.0


def test_selected_backward_counts_itself_and_sizes_its_vmem(monkeypatch):
    """One backward kernel under a selection, said by the `pallas_call[`
    count of its trace; the head's dq accumulator and the kernel's VMEM
    limit follow the shapes (the Keye cell's: 4 MiB of dq, the selection's
    1 MiB block twice), and a sequence whose dq does not fit is refused by
    name, as the latent backward refuses it."""
    acc, limit = fa._fused_bwd_vmem(8192, 1024, [128], 128, selected=True)
    assert acc == 8192 * 128 * 4
    assert limit == fa._fused_bwd_vmem(8192, 1024, [128], 128)[1] \
        + 2 * 1024 * 1024
    assert acc + (16 << 20) < limit < (32 << 20)
    # dq is held transposed: a narrow one pads to 8 sublanes, not 128 lanes
    assert fa._fused_bwd_vmem(1024, 1024, [64], 64)[0] == 1024 * 64 * 4

    x = jnp.zeros((1, 128, 2, 64), jnp.float32)
    sel = jnp.ones((1, 128, 128), jnp.int8)

    def trace_backward():       # a new function each time: no cached trace
        return str(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
            q, x[:, :, :1], x[:, :, :1], causal=True,
            selection=sel).sum()))(x))
    assert trace_backward().count("pallas_call[") == 2
    monkeypatch.setattr(fa, "_DQ_BYTES", 128 * 64 * 4 - 1)
    with pytest.raises(ValueError,
                       match="flash_sel_bwd_dkv keeps a head's whole dq in "
                             "VMEM: 128 rows"):
        trace_backward()


def test_selection_with_a_kv_mask_is_refused():
    x = jnp.zeros((1, 128, 2, 64), jnp.float32)
    with pytest.raises(NotImplementedError):
        flash_attention(x, x, x, kv_mask=jnp.ones((1, 128), bool),
                        selection=jnp.ones((1, 128, 128), jnp.int8))
    # the selected backward ends a q block's dq at its diagonal k block
    with pytest.raises(NotImplementedError, match="causal=True"):
        flash_attention(x, x, x, selection=jnp.ones((1, 128, 128), jnp.int8))
    with pytest.raises(ValueError):
        flash_attention(jnp.zeros((1, 128, 3, 64)), x, x)


def test_selected_backward_refuses_a_walk_that_is_not_causal():
    """The refusal stands where the assumption is made too: the wrapper
    under the public op, handed a selection without the causal mask,
    would pair a forward over every block with a backward that ends a q
    block's dq at its diagonal."""
    x = jnp.ones((2, 128, 64), jnp.float32)
    sel = jnp.ones((1, 128, 128), jnp.int8)

    def loss(q, causal):
        return jnp.sum(fa._flash3(q, x, x, None, sel, causal, 0.125, 2, 1))
    jax.grad(loss)(x, True)
    with pytest.raises(NotImplementedError, match="causal=True"):
        jax.grad(loss)(x, False)


# ------------------------------------------------------ latent attention

def _latent_operands(s, h, dn, dr, dv, dtype=jnp.float32, b=2):
    """q [b, s, h, dn + dr], k_nope [b, s, h, dn], k_rope [b, s, 1, dr],
    v and dO [b, s, h, dv]."""
    ks = jax.random.split(jax.random.key(1), 5)
    shapes = [(b, s, h, dn + dr), (b, s, h, dn), (b, s, 1, dr),
              (b, s, h, dv), (b, s, h, dv)]
    return [jax.random.normal(k, sh, dtype) for k, sh in zip(ks, shapes)]


def _projected(q, kn, kr, v):
    """The operands of `F.latent_attention` as the projections make them:
    q without position, the rotary query part head-major [b, h, s, dr],
    k_nope and v side by side a head [b, s, h, dn + dv], the rotary key."""
    dn = kn.shape[-1]
    return (q[..., :dn], jnp.swapaxes(q[..., dn:], 1, 2),
            jnp.concatenate([kn, v], -1), kr)


def _latent(q, kn, kr, v, concat=False):
    """`flash_attention_latent`, or the same kernels given the key as ONE
    part of the full width, concatenated beforehand with the rotary head
    broadcast (the other way of handing them the rotary key, ranked by
    tools/flash_mla_step0.py)."""
    if not concat:
        return fa.flash_attention_latent(*_projected(q, kn, kr, v))
    b, s, h, d = q.shape
    dv = v.shape[-1]

    def to3(x):
        return jnp.swapaxes(x, 1, 2).reshape(-1, s, x.shape[-1])
    k = jnp.concatenate(
        [kn, jnp.broadcast_to(kr, kn.shape[:-1] + kr.shape[-1:])], -1)
    one = fa.Lay()
    parts = fa.Parts(q_lays=(one,), k_lays=(one, one), q=((0, 0, d),),
                     k=((0, 0, d),), v=(1, 0, dv))
    o3 = fa._mla3((to3(q),), (to3(k), to3(v)), parts, d ** -0.5)
    return jnp.swapaxes(o3.reshape(b, h, s, -1), 1, 2)


@pytest.mark.parametrize("concat", [False, True],
                         ids=["rotary-key-by-index-map", "key-concatenated"])
@pytest.mark.parametrize("s,h,dn,dr,dv,sizes", [
    (256, 2, 128, 64, 128, {}),         # the published widths, one block
    (1024, 3, 16, 8, 16, {}),           # a whole-sequence block of one chunk
    # one block of two chunks of two groups, values narrower than the
    # 128 lanes the forward's statistics are replicated over
    (512, 2, 16, 8, 16, {"_CHUNK": 256, "_CAUSAL_SUB": 128}),
    # four blocks on the grid
    (512, 2, 128, 64, 128, {"_RESIDENT_BYTES": 64 * 1024}),
    # six blocks: a q block's dq is added to by up to six k blocks, grid
    # steps that do not follow each other
    (768, 2, 16, 8, 16, {"_RESIDENT_BYTES": 64 * 1024}),
    # three blocks of two chunks of two groups: every offset of the walk
    # (block, chunk, group) moves the rows of dq a pair adds to
    (1536, 2, 16, 8, 16, {"_RESIDENT_BYTES": 512 * 1024, "_CHUNK": 256,
                          "_CAUSAL_SUB": 128}),
    # three heads, an odd count, four blocks: the projections' own arrays
    # (a row's heads side by side, found by division, not by shift) and
    # `[b h, s, w]` operands
    (512, 3, 128, 64, 128, {"_RESIDENT_BYTES": 64 * 1024}),
    (512, 3, 16, 8, 16, {"_RESIDENT_BYTES": 64 * 1024}),
], ids=["192-128-s256", "24-16-s1024", "24-16-s512-chunks-groups",
        "192-128-s512-four-blocks", "24-16-s768-six-blocks",
        "24-16-s1536-blocks-chunks-groups", "192-128-s512-three-heads",
        "24-16-s512-three-heads"])
def test_latent_kernels_match_the_xla_path(monkeypatch, s, h, dn, dr, dv,
                                           sizes, concat):
    """Scores over dn + dr lanes with ONE rotary key head shared by every
    query head, values dv wide: forward and all four gradients, `dk_rope`
    (summed over the heads) among them, against `F.latent_attention`'s
    XLA path; dq, which the backward sums over a head's k blocks, with
    one block and with three and more."""
    from paddle_tpu.nn import functional as F
    for name, size in sizes.items():
        monkeypatch.setattr(fa, name, size)
    plan = fa._values_plan(s, dv, jnp.float32)
    if "_RESIDENT_BYTES" in sizes:
        assert plan.block * 3 <= s, plan
    if "_CHUNK" in sizes:
        assert plan == (512, 256, 128)
    q, kn, kr, v, do = _latent_operands(s, h, dn, dr, dv)

    def xla(*a):
        return jnp.sum(F.latent_attention(*_projected(*a)) * do)

    def kernels(*a):
        return jnp.sum(_latent(*a, concat=concat) * do)
    np.testing.assert_allclose(
        np.asarray(_latent(q, kn, kr, v, concat=concat)),
        np.asarray(F.latent_attention(*_projected(q, kn, kr, v))),
        atol=5e-6)
    want = jax.grad(xla, argnums=(0, 1, 2, 3))(q, kn, kr, v)
    got = jax.grad(kernels, argnums=(0, 1, 2, 3))(q, kn, kr, v)
    for name, a, b in zip(("dq", "dk_nope", "dk_rope", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5,
            atol=1e-5 * float(jnp.abs(b).max()), err_msg=name)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bf16"])
@pytest.mark.parametrize("s,sizes", [
    (256, {}),                                      # one block, one chunk
    (512, {"_CHUNK": 256, "_CAUSAL_SUB": 128}),     # one block, two chunks
    (512, {"_RESIDENT_BYTES": 64 * 1024}),          # four k blocks a head
], ids=["one-block", "two-chunks", "four-k-blocks"])
def test_latent_forward_o_and_lse_wherever_a_rows_maximum_lies(
        monkeypatch, s, sizes, dtype, tol):
    """The forward's o and lse, as `_mla_fwd` hands them to the backward
    (rows along lanes), against the formula written out in float32. Head
    0's scores rise with the key's position, so every row's running
    maximum is replaced in each chunk and block and arrives in its LAST;
    head 1's fall, so the maximum is the first key's and every later chunk
    is added under it; head 2 is noise."""
    for name, size in sizes.items():
        monkeypatch.setattr(fa, name, size)
    h, dn, dr, dv = 3, 128, 64, 128
    block, _, sub = fa._values_plan(s, dv, dtype)
    assert (s // block == 4) == ("_RESIDENT_BYTES" in sizes)
    q, kn, kr, v, _ = _latent_operands(s, h, dn, dr, dv, b=1)
    ramp = jnp.linspace(0.0, 100.0, s)
    q = q.at[..., 0].set(8.0)
    kn = kn.at[:, :, 0, 0].set(ramp).at[:, :, 1, 0].set(-ramp)
    q, kn, kr, v = (x.astype(dtype) for x in (q, kn, kr, v))
    q3, kn3, kr3, v3 = (jnp.swapaxes(x, 1, 2).reshape(-1, s, x.shape[-1])
                        for x in (q, kn, kr, v))
    scale = (dn + dr) ** -0.5
    o, lse = fa._mla_fwd(*fa._laid(*_projected(q, kn, kr, v)), scale)
    # o as `o_proj` takes it, [b, s, h dv]
    assert o.dtype == dtype and o.shape == (1, s, h * dv)
    o = jnp.swapaxes(o.reshape(s, h, dv), 0, 1)
    assert lse.dtype == jnp.float32
    assert lse.shape == (h, s // block, block // sub, sub)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    hi = functools.partial(jnp.einsum, precision="highest")
    k = jnp.concatenate([f32(kn3), jnp.broadcast_to(f32(kr3), (h, s, dr))],
                        -1)
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                       hi("hqd,hkd->hqk", f32(q3), k) * scale, -jnp.inf)
    want_lse = jax.nn.logsumexp(scores, axis=-1)
    # where the maximum lies: head 0 among a row's last keys (its last
    # group of every plan here), head 1 among its first
    assert (jnp.arange(s) - jnp.argmax(scores[0], -1)).max() < 64
    assert jnp.argmax(scores[1], -1).max() < 64
    np.testing.assert_allclose(np.asarray(lse.reshape(h, s)),
                               np.asarray(want_lse), rtol=1e-6, atol=1e-5)
    want = hi("hqk,hkd->hqd", jnp.exp(scores - want_lse[..., None]), f32(v3))
    np.testing.assert_allclose(np.asarray(o, np.float32), np.asarray(want),
                               atol=tol)


def _eqns(jaxpr, kernels=True):
    """Every equation of a jaxpr and of the jaxprs its equations hold (a
    `pallas_call`'s kernel unless `kernels` is False, a `cond`'s
    branches)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if not kernels and eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub, kernels)


@pytest.mark.parametrize("s,sizes", [
    (1024, {}), (512, {"_RESIDENT_BYTES": 64 * 1024})],
    ids=["one-block", "four-blocks"])
def test_latent_forward_counts_itself_and_carries_no_columns(
        monkeypatch, s, sizes):
    """The statistics' layout, seen in the traced kernel: `m`, `l` and
    `corr` are [rows, 128], alike in every lane. A reduction's result, a
    column [rows, 1], meets them at once (one broadcast a row group); no
    column is broadcast over the scores or the accumulator, none goes
    through `exp`, and none is carried between grid steps."""
    for name, size in sizes.items():
        monkeypatch.setattr(fa, name, size)
    x = [jax.ShapeDtypeStruct(shape, jnp.bfloat16)
         for shape in ((1, s, 4, 128), (1, 4, s, 64), (1, s, 4, 256),
                       (1, s, 1, 64))]
    jaxpr = jax.make_jaxpr(lambda *a: fa._mla_fwd(
        *fa._laid(*a), 192 ** -0.5))(*x)
    assert fa._STAT_LANES == 128
    block, _, sub = fa._values_plan(s, 128, jnp.bfloat16)
    eqns = list(_eqns(jaxpr.jaxpr))
    reduces = [e for e in eqns if e.primitive.name.startswith("reduce_")]
    assert {e.primitive.name for e in reduces} == {"reduce_max",
                                                   "reduce_sum"}
    # every use of a column: the reduction's result against the statistic
    met = {(e.primitive.name, *(v.aval.shape for v in (*e.invars,
                                                       *e.outvars)))
           for e in eqns
           if any(getattr(v.aval, "shape", ())[-1:] == (1,)
                  for v in e.invars)}
    assert met == {("max", (sub, 128), (sub, 1), (sub, 128)),
                   ("add", (sub, 128), (sub, 1), (sub, 128))}, met
    exps = {e.outvars[0].aval.shape for e in eqns
            if e.primitive.name == "exp"}
    assert (sub, 128) in exps and all(sh[1] % 128 == 0 for sh in exps), exps
    call, = (e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call")
    scratch = [v.aval.shape for v in call.params["jaxpr"].invars[-3:]]
    if s > block:
        assert scratch == [(block, 128), (block, 128), (block, 128)], scratch


def test_latent_dq_is_summed_in_float32_and_rounded_once(monkeypatch):
    """bf16 operands, four k blocks a head: dq is the float32 sum over
    ALL the head's keys of ds (bf16, as the MXU takes it) x k, rounded to
    bf16 once. A backward that rounds each k block's share to bf16 and
    adds the shares up is told apart: the reference does that too, and
    must fail the same comparison."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 64 * 1024)
    s, h, dn, dr, dv = 512, 2, 128, 64, 128
    block = fa._values_plan(s, dv, jnp.bfloat16).block
    assert block * 4 == s
    operands = _latent_operands(s, h, dn, dr, dv, jnp.bfloat16, b=1)
    qs, ks, parts = fa._laid(*_projected(*operands[:4]))
    scale = (dn + dr) ** -0.5
    o, lse = fa._mla_fwd(qs, ks, parts, scale)
    (dq_nope, dq_rope), _ = fa._mla_bwd(parts, scale, (qs, ks, o, lse),
                                        operands[4].reshape(1, s, -1))
    assert dq_nope.dtype == dq_rope.dtype == jnp.bfloat16
    # dq's two parts as they leave, [b, s, h dn] and [b h, s, dr]
    got = jnp.concatenate(
        [jnp.swapaxes(dq_nope.reshape(s, h, dn), 0, 1), dq_rope], -1)
    q, kn, kr, v, do = (jnp.swapaxes(x, 1, 2).reshape(-1, s, x.shape[-1])
                        for x in operands)
    o = jnp.swapaxes(o.reshape(s, h, dv), 0, 1)

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    hi = functools.partial(jnp.einsum, precision="highest")
    k = jnp.concatenate([f32(kn), jnp.broadcast_to(f32(kr), (h, s, dr))], -1)
    scores = hi("hqd,hkd->hqk", f32(q), k) * scale
    p = jnp.where(jnp.tril(jnp.ones((s, s), bool)),
                  jnp.exp(scores - lse.reshape(h, s, 1)), 0.0)
    delta = jnp.sum(f32(do) * f32(o), -1, keepdims=True)
    ds = f32((p * (hi("hqd,hkd->hqk", f32(do), f32(v)) - delta)
              * scale).astype(jnp.bfloat16))
    once = hi("hqk,hkd->hqd", ds, k).astype(jnp.bfloat16)
    shares = [hi("hqk,hkd->hqd", ds[:, :, i:i + block], k[:, i:i + block])
              for i in range(0, s, block)]
    by_block = sum(f32(x.astype(jnp.bfloat16)) for x in shares).astype(
        jnp.bfloat16)

    def differ(a):
        # the share of dq's elements that are not `once`'s bf16 value
        return float(jnp.mean(f32(a) != f32(once)))
    # the kernel sums the same products in another order and takes lse's
    # exp and ds's rounding from its own float32 scores: an element whose
    # sum lies next to a rounding boundary may fall to the other side
    # (read here: 0.03 % of the kernel's elements, 28 % of the shares')
    assert differ(got) < 0.005, differ(got)
    assert differ(by_block) > 0.1, differ(by_block)


def test_latent_backward_counts_itself_and_sizes_its_vmem(monkeypatch):
    """One backward kernel, said by the `pallas_call[` count of its
    trace; the head's dq accumulator and the kernel's VMEM limit follow the
    shapes, and a head whose dq does not fit is refused by name."""
    acc, limit = fa._fused_bwd_vmem(8192, 1024, [128, 64], 128)
    assert acc == 8192 * 192 * 4            # dq transposed: no lane padding
    assert acc + (16 << 20) < limit < (40 << 20)
    assert fa._fused_bwd_vmem(8192, 1024, [192], 128)[0] == acc
    assert fa._fused_bwd_vmem(32768, 1024, [128, 64], 128)[0] \
        <= fa._DQ_BYTES

    a = _projected(*_latent_operands(128, 2, 16, 8, 16)[:4])

    def trace_backward():       # a new function each time: no cached trace
        jax.make_jaxpr(jax.grad(
            lambda *a: fa.flash_attention_latent(*a).sum()))(*a)
    assert str(jax.make_jaxpr(jax.grad(
        lambda *a: fa.flash_attention_latent(*a).sum()))(*a)
    ).count("pallas_call[") == 2
    monkeypatch.setattr(fa, "_DQ_BYTES", 128 * 24 * 4 - 1)
    with pytest.raises(ValueError, match="whole dq in VMEM"):
        trace_backward()


def test_latent_kernels_in_bf16_and_by_their_own_names():
    from paddle_tpu.nn import functional as F
    a = _projected(*_latent_operands(256, 2, 128, 64, 128, jnp.bfloat16)[:4])
    got = fa.flash_attention_latent(*a)
    want = F.latent_attention(*(x.astype(jnp.float32) for x in a))
    assert got.dtype == jnp.bfloat16 and got.shape == (2, 256, 2, 128)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=3e-2)
    text = jax.jit(jax.grad(lambda *a: fa.flash_attention_latent(
        *a).astype(jnp.float32).sum(), argnums=(0, 1, 2, 3))).lower(
        *a).as_text(debug_info=True)
    for name in ("flash_mla_fwd", "flash_mla_bwd_dkv"):
        assert name in text, name
    assert "flash_mla_bwd_dq" not in text
    assert "flash_fwd" not in text and "flash_sel" not in text
    # 192 lanes pad to 256, so `_plan` by the scores' width would keep
    # 512 rows resident at 8192 tokens; the latent kernels plan by the
    # values' 128 and keep 1024 (PERF.md, PR 33: 58.4 ms a layer for 68.2)
    assert fa._plan(8192, 192, jnp.bfloat16, True) == (512, 512, 256)
    assert fa._values_plan(8192, 128, jnp.bfloat16) == (1024, 1024, 256)


def test_latent_attention_off_the_kernels_and_what_it_refuses():
    """Off the TPU, and at a length no kernel takes, `F.latent_attention`
    is the XLA path: the same numbers as the formula written out."""
    from paddle_tpu.nn import functional as F
    q, kn, kr, v, _ = _latent_operands(72, 2, 16, 8, 16)
    got = F.latent_attention(*_projected(q, kn, kr, v))
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (2, 72, 2, 8))], -1)
    want = _xla_attention(q, k, jnp.pad(v, ((0, 0),) * 3 + ((0, 8),)), None,
                          0.0, True, False, None)[..., :16]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)
    with pytest.raises(ValueError, match="% 128"):
        fa.flash_attention_latent(*_projected(q, kn, kr, v))
    q, kn, kr, v, _ = _latent_operands(128, 2, 16, 8, 16)
    with pytest.raises(ValueError, match="key parts"):
        fa.flash_attention_latent(
            *_projected(q, kn, jnp.concatenate([kr, kr], 2), v))


@pytest.mark.parametrize("dn,dr,dv", [(128, 64, 128), (16, 8, 16)],
                         ids=["projections-layout", "head-rows"])
def test_latent_operands_stay_where_the_projections_leave_them(dn, dr, dv):
    """The gradient of `flash_attention_latent` as a step takes it: at
    128 + 64 / 128 lanes no operand and no gradient of the two kernels is
    transposed, sliced or concatenated between the projections' arrays
    and the `pallas_call`s (q without position, kv, o and dO go as `[b,
    s, h w]`, dq in its two parts and kv's gradient as ONE `[b, s, h
    (dn + dv)]`); at widths that are not whole 128-lane tiles the same
    kernels take `[b h, s, w]` operands, laid out by transposes, which is
    what the check can see. Both kernels, two calls, either way."""
    b, s, h = 2, 256, 3
    q, kn, kr, v, do = _latent_operands(s, h, dn, dr, dv, b=b)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(fa.flash_attention_latent(*a) * do),
        argnums=(0, 1, 2, 3)))(*_projected(q, kn, kr, v))
    eqns = list(_eqns(jaxpr.jaxpr, kernels=False))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert [c.params["name"] for c in calls] == ["flash_mla_fwd",
                                                  "flash_mla_bwd_dkv"]
    wide = [e for e in eqns if e.primitive.name in
            ("transpose", "slice", "concatenate")
            and len(e.invars[0].aval.shape) == 4]
    fwd, bwd = calls
    if dn % 128:
        assert any(e.primitive.name == "transpose" for e in wide)
        assert fwd.invars[0].aval.shape == (b * h, s, dn)
        return
    assert wide == [], [(e.primitive.name, e.invars[0].aval.shape)
                        for e in wide]
    ins = [x.aval.shape for x in fwd.invars]
    assert ins == [(b, s, h * dn), (b * h, s, dr), (b, s, h * (dn + dv)),
                   (b, s, dr)], ins
    assert fwd.outvars[0].aval.shape == (b, s, h * dv)
    outs = [x.aval.shape for x in bwd.outvars]
    assert outs == [(b, s, h * dn), (b * h, s, dr), (b, s, h * (dn + dv)),
                    (b * h, s, dr)], outs
    assert bwd.invars[4].aval.shape == (b, s, h * dv)     # dO
