"""Tensor functional API tests (reference analogue: per-op OpTest files in
unittests/, e.g. test_elementwise_add_op.py, test_reduce_op.py)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import tensor as T

from op_test import check_eager_vs_jit, check_grad


class TestCreation:
    def test_to_tensor(self):
        x = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]])
        assert x.shape == (2, 2)
        assert x.dtype == paddle.float32

    def test_full_zeros_ones(self):
        assert paddle.full([2, 3], 7).shape == (2, 3)
        assert float(paddle.zeros([2]).sum()) == 0.0
        assert float(paddle.ones([4]).sum()) == 4.0

    def test_arange_linspace(self):
        np.testing.assert_array_equal(np.asarray(paddle.arange(5)),
                                      np.arange(5))
        assert paddle.linspace(0, 1, 11).shape == (11,)

    def test_eye_tril_triu(self):
        e = paddle.eye(3)
        assert float(e.trace()) == 3.0
        x = paddle.ones([3, 3])
        assert float(paddle.tril(x).sum()) == 6.0
        assert float(paddle.triu(x, 1).sum()) == 3.0


class TestMath:
    def test_elementwise_binary(self):
        a = paddle.to_tensor([1.0, 2.0, 3.0])
        b = paddle.to_tensor([4.0, 5.0, 6.0])
        np.testing.assert_allclose(np.asarray(paddle.add(a, b)),
                                   [5, 7, 9])
        np.testing.assert_allclose(np.asarray(paddle.multiply(a, b)),
                                   [4, 10, 18])
        np.testing.assert_allclose(np.asarray(paddle.divide(b, a)),
                                   [4, 2.5, 2])

    def test_broadcast(self):
        a = paddle.ones([2, 1, 3])
        b = paddle.ones([4, 1])
        assert paddle.add(a, b).shape == (2, 4, 3)

    def test_reductions(self):
        x = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]])
        assert float(paddle.sum(x)) == 10.0
        assert float(paddle.mean(x)) == 2.5
        assert float(paddle.max(x)) == 4.0
        np.testing.assert_allclose(
            np.asarray(paddle.sum(x, axis=0)), [4, 6])
        assert paddle.sum(x, axis=1, keepdim=True).shape == (2, 1)

    def test_matmul_grad(self, rng_seed):
        a = np.random.randn(3, 4).astype(np.float32)
        b = np.random.randn(4, 5).astype(np.float32)
        check_eager_vs_jit(paddle.matmul, (a, b))
        check_grad(lambda x, y: paddle.matmul(x, y), (a, b), idx=0)
        check_grad(lambda x, y: paddle.matmul(x, y), (a, b), idx=1)

    def test_activation_grads(self, rng_seed):
        x = np.random.randn(4, 4).astype(np.float32) + 2.5  # avoid kinks
        for fn in [paddle.exp, paddle.tanh, paddle.sqrt, paddle.log]:
            check_grad(fn, (np.abs(x) + 0.5,))

    def test_cumsum(self):
        x = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0]])
        np.testing.assert_allclose(np.asarray(paddle.cumsum(x, axis=1)),
                                   [[1, 3], [3, 7]])

    def test_clip(self):
        x = paddle.to_tensor([-2.0, 0.5, 9.0])
        np.testing.assert_allclose(np.asarray(paddle.clip(x, 0.0, 1.0)),
                                   [0, 0.5, 1])


class TestManipulation:
    def test_reshape_transpose(self):
        x = paddle.arange(24).reshape((2, 3, 4))
        assert paddle.reshape(x, [4, 6]).shape == (4, 6)
        assert paddle.transpose(x, [2, 0, 1]).shape == (4, 2, 3)

    def test_concat_split_stack(self):
        a = paddle.ones([2, 3])
        b = paddle.zeros([2, 3])
        c = paddle.concat([a, b], axis=0)
        assert c.shape == (4, 3)
        parts = paddle.split(c, 2, axis=0)
        assert len(parts) == 2
        parts = paddle.split(c, [1, -1], axis=0)
        assert parts[1].shape == (3, 3)
        assert paddle.stack([a, b]).shape == (2, 2, 3)

    def test_gather_scatter(self):
        x = paddle.to_tensor([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])
        out = paddle.gather(x, paddle.to_tensor([0, 2]))
        np.testing.assert_allclose(np.asarray(out), [[1, 2], [5, 6]])
        updated = paddle.scatter(x, paddle.to_tensor([0]),
                                 paddle.to_tensor([[9.0, 9.0]]))
        assert float(updated[0, 0]) == 9.0

    def test_squeeze_unsqueeze_flatten(self):
        x = paddle.ones([1, 3, 1, 4])
        assert paddle.squeeze(x).shape == (3, 4)
        assert paddle.unsqueeze(paddle.ones([3]), [0, 2]).shape == (1, 3, 1)
        assert paddle.flatten(x, 1, 2).shape == (1, 3, 4)

    def test_pad(self):
        x = paddle.ones([1, 1, 2, 2])
        out = paddle.nn.functional.pad(x, [1, 1, 1, 1])
        assert out.shape == (1, 1, 4, 4)

    def test_where_masked_fill(self):
        x = paddle.to_tensor([1.0, -1.0, 2.0])
        out = paddle.where(x > 0, x, paddle.zeros_like(x))
        np.testing.assert_allclose(np.asarray(out), [1, 0, 2])


class TestSearchSort:
    def test_argmax_topk(self):
        x = paddle.to_tensor([[1.0, 5.0, 3.0], [9.0, 2.0, 4.0]])
        np.testing.assert_array_equal(np.asarray(paddle.argmax(x, axis=1)),
                                      [1, 0])
        vals, idx = paddle.topk(x, 2, axis=1)
        np.testing.assert_allclose(np.asarray(vals), [[5, 3], [9, 4]])

    def test_sort_argsort(self):
        x = paddle.to_tensor([3.0, 1.0, 2.0])
        np.testing.assert_allclose(np.asarray(paddle.sort(x)), [1, 2, 3])
        np.testing.assert_array_equal(np.asarray(paddle.argsort(x)),
                                      [1, 2, 0])


class TestLinalg:
    def test_norm_det_inv(self, rng_seed):
        x = np.asarray([[2.0, 0.0], [0.0, 4.0]], dtype=np.float32)
        assert abs(float(paddle.linalg.det(x)) - 8.0) < 1e-5
        inv = paddle.linalg.inverse(x)
        np.testing.assert_allclose(np.asarray(inv), [[0.5, 0], [0, 0.25]],
                                   atol=1e-6)
        assert abs(float(T.linalg.norm(paddle.ones([4]), p=2)) - 2.0) < 1e-6

    def test_cholesky_solve_svd(self, rng_seed):
        a = np.random.randn(4, 4).astype(np.float32)
        spd = a @ a.T + 4 * np.eye(4, dtype=np.float32)
        L = paddle.linalg.cholesky(spd)
        np.testing.assert_allclose(np.asarray(L @ L.T), spd, rtol=1e-4,
                                   atol=1e-4)
        u, s, vt = paddle.linalg.svd(spd)
        np.testing.assert_allclose(np.asarray(u * s @ vt), spd, rtol=1e-3,
                                   atol=1e-3)


class TestLogic:
    def test_compare(self):
        a = paddle.to_tensor([1, 2, 3])
        b = paddle.to_tensor([3, 2, 1])
        np.testing.assert_array_equal(np.asarray(paddle.equal(a, b)),
                                      [False, True, False])
        assert bool(paddle.allclose(a.astype("float32"),
                                    a.astype("float32")))

    def test_logical(self):
        t = paddle.to_tensor([True, False])
        f = paddle.to_tensor([False, False])
        np.testing.assert_array_equal(
            np.asarray(paddle.logical_or(t, f)), [True, False])


class TestRandom:
    def test_seed_reproducible(self):
        paddle.seed(42)
        a = paddle.randn([4])
        paddle.seed(42)
        b = paddle.randn([4])
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_shapes_ranges(self):
        u = paddle.uniform([100], min=0.0, max=1.0)
        assert float(u.min()) >= 0.0 and float(u.max()) <= 1.0
        r = paddle.randint(0, 10, [50])
        assert int(r.min()) >= 0 and int(r.max()) < 10
        p = paddle.randperm(10)
        assert sorted(np.asarray(p).tolist()) == list(range(10))

    def test_unscoped_draw_under_jit_leaves_no_tracer_behind(self):
        """A trace that draws from the global key (no rng_guard — e.g.
        jit.save of a layer with hard-wired training dropout) must not
        store a tracer there: every later eager draw of the process
        would fail with UnexpectedTracerError."""
        import jax
        from paddle_tpu.framework import random as R
        paddle.seed(3)
        jax.jit(lambda x: paddle.nn.functional.dropout(
            x, p=0.5, training=True))(paddle.ones([8]))
        assert not isinstance(R.get_rng_state(), jax.core.Tracer)
        paddle.randn([2])       # the eager state still works


class TestTensorArray:
    """TensorArray ops (reference tensor/array.py): eager list mode and
    the stacked-buffer mode for lax loops."""

    def test_eager_list_mode(self):
        import numpy as np
        import paddle_tpu as pt
        arr = pt.create_array("float32")
        arr = pt.array_write(pt.to_tensor([1.0, 2.0]), 0, arr)
        arr = pt.array_write(pt.to_tensor([3.0, 4.0]), 1, arr)
        assert pt.array_length(arr) == 2
        np.testing.assert_array_equal(np.asarray(pt.array_read(arr, 1)),
                                      [3.0, 4.0])
        arr = pt.array_write(pt.to_tensor([9.0, 9.0]), 0, arr)  # overwrite
        np.testing.assert_array_equal(np.asarray(pt.array_read(arr, 0)),
                                      [9.0, 9.0])

    def test_stacked_mode_in_lax_loop(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as pt

        def body(i, buf):
            return i + 1, pt.array_write(jnp.full((2,), i, jnp.float32),
                                         i, buf)

        def run():
            buf = jnp.zeros((4, 2))
            i = 0
            i, buf = jax.lax.while_loop(
                lambda c: c[0] < 4, lambda c: body(*c), (i, buf))
            return buf

        out = np.asarray(jax.jit(run)())
        np.testing.assert_array_equal(out[:, 0], [0, 1, 2, 3])

    def test_traced_read_of_list(self):
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as pt
        arr = [jnp.asarray([1.0]), jnp.asarray([2.0]), jnp.asarray([3.0])]

        @jax.jit
        def pick(i):
            return pt.array_read(arr, i)

        np.testing.assert_array_equal(np.asarray(pick(2)), [3.0])
