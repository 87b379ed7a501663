"""The causal flash kernels under a sliding window (`flash_attention(...,
window=)`) against a plain masked softmax, forward and every gradient.

The kernels are TPU programs; these tests check their arithmetic on the
CPU in the Pallas interpreter, as tests/test_flash_attention.py does. The
geometry (which blocks, chunks and groups are walked) is checked there,
beside the causal walk's; tests/test_tpu_compile.py compiles the windowed
kernels for a described chip."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from paddle_tpu import profiler as prof
from paddle_tpu.ops import flash_attention as fa
from paddle_tpu.nn.functional.attention import _xla_attention


@pytest.fixture(autouse=True)
def interpret_mode(monkeypatch):
    monkeypatch.setattr(fa, "_interpret", lambda: True)


def _plain(q, k, v, window):
    """Softmax over the keys p - window + 1 .. p of query p, in float32 at
    the highest precision; query head i reads key/value head i // (h /
    h_kv)."""
    b, s, h, d = q.shape
    k, v = (jnp.repeat(x, h // x.shape[2], axis=2) for x in (k, v))
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    scores = jnp.einsum("bqhd,bkhd->bhqk", f32[0], f32[1],
                        precision="highest") / np.sqrt(d)
    back = np.arange(s)[:, None] - np.arange(s)[None, :]
    scores = jnp.where((back >= 0) & (back < window), scores, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), f32[2],
                      precision="highest")


def _against_plain(s, h, h_kv, d, dtype, window, tol):
    rs = np.random.RandomState(s + window)
    q = jnp.asarray(rs.randn(1, s, h, d) * 0.5, dtype)
    k, v = (jnp.asarray(rs.randn(1, s, h_kv, d) * 0.5, dtype)
            for _ in range(2))
    w = jnp.asarray(rs.randn(1, s, h, d), jnp.float32)

    def run(attend):
        def loss(a, b, c):
            out = attend(a, b, c)
            return jnp.sum(out.astype(jnp.float32) * w), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (out,) + grads

    got = run(lambda a, b, c: fa.flash_attention(a, b, c, causal=True,
                                                 window=window))
    ref = run(lambda a, b, c: _plain(a, b, c, window))
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), err_msg=name,
                                   **tol)


F32 = dict(rtol=1e-4, atol=5e-5)


@pytest.mark.parametrize("window", [200, 512, 700, 2048, 5000],
                         ids=["below-block", "block", "above-block",
                              "row", "beyond-row"])
def test_window_against_a_masked_softmax(window):
    """Four 512-row blocks of one chunk (float32 operands at 2048 tokens)
    under GQA 4/2: a window below the block and no multiple of 128 (the
    band's edge crosses the diagonal block and the one before), one
    block, above a block and no multiple of 128 (three blocks, the edge
    inside the farthest), the row, and beyond it (the causal mask)."""
    assert fa._plan(2048, 64, jnp.float32, True) == (512, 512, 256)
    _against_plain(2048, 4, 2, 64, jnp.float32, window, F32)


def test_window_under_32_query_heads_over_4():
    """The Trinity cell's grouping, 32 query heads over 4 key/value heads,
    at one resident block of two groups a chunk: the band's edge inside
    the block."""
    _against_plain(512, 32, 4, 32, jnp.float32, 200, F32)


def test_window_in_bf16_across_two_chunks_a_side():
    """bf16 operands at 2048 tokens: one resident block of two 1024-row
    chunks a side, so the band skips a whole chunk of the block."""
    assert fa._plan(2048, 64, jnp.bfloat16, True)[:2] == (2048, 1024)
    _against_plain(2048, 2, 1, 64, jnp.bfloat16, 300,
                   dict(rtol=5e-2, atol=5e-2))


def test_window_beyond_the_row_is_the_causal_kernels_result():
    rs = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rs.randn(1, 1024, 2, 64), jnp.float32)
               for _ in range(3))
    np.testing.assert_allclose(
        np.asarray(fa.flash_attention(q, k, v, causal=True, window=4096)),
        np.asarray(fa.flash_attention(q, k, v, causal=True)),
        rtol=1e-6, atol=1e-6)


def test_window_kernels_are_named_apart():
    """Forward and backward of a windowed call are the three windowed
    kernels and none of the causal ones, so a trace tells a sliding
    layer's attention from a full layer's."""
    x = jnp.zeros((1, 1024, 4, 64), jnp.bfloat16)
    kv = x[:, :, :2]
    text = str(jax.make_jaxpr(jax.grad(lambda a, b, c: jnp.sum(
        fa.flash_attention(a, b, c, causal=True, window=256).astype(
            jnp.float32)), argnums=(0, 1, 2)))(x, kv, kv))
    for name in prof.WIN_KERNELS:
        assert f"name={name}" in text, name
    for name in prof.KERNELS:
        assert f"name={name}\n" not in text and f"name={name} " not in text
    assert text.count("pallas_call[") == len(prof.WIN_KERNELS) == 3


@pytest.mark.parametrize("kw", [dict(causal=False), dict(window=0),
                                dict(kv_mask=np.ones((1, 256), bool))])
def test_window_needs_the_causal_mask_alone(kw):
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    args = dict(causal=True, window=64)
    args.update(kw)
    with pytest.raises(NotImplementedError):
        fa.flash_attention(q, q, q, **args)


@pytest.mark.parametrize("h_kv", [4, 2])
def test_xla_path_applies_the_same_band(h_kv):
    """The entry's XLA path (what runs where the kernels do not) masks the
    same band, under grouped heads too."""
    from paddle_tpu.nn.functional.attention import (
        scaled_dot_product_attention)
    rs = np.random.RandomState(5)
    q = jnp.asarray(rs.randn(2, 64, 4, 16), jnp.float32)
    k, v = (jnp.asarray(rs.randn(2, 64, h_kv, 16), jnp.float32)
            for _ in range(2))
    got = _xla_attention(q, k, v, None, 0.0, True, False, None, window=10)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_plain(q, k, v, 10)),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(scaled_dot_product_attention(q, k, v, is_causal=True,
                                                window=10)),
        np.asarray(got), rtol=1e-6, atol=1e-6)
    with pytest.raises(NotImplementedError):
        scaled_dot_product_attention(q, k, v, window=10)
