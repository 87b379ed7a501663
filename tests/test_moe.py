"""Mixture-of-Experts: top-k routing over all experts, the share of them
one chip holds, and the dropless grouped product (beyond-reference; the
reference snapshot only ships the alltoall building block,
`operators/collective/alltoall_op.cc`). The cases of the old GShard top-2
layer's tests live on here, against the layer that took its place."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed.meta_parallel import MoEMLP, topk_gating
from paddle_tpu.distributed.meta_parallel.moe import (
    balance_loss, dispatch_plan, grouped_experts, sigmoid_gating)
from paddle_tpu.nn.layer import buffer_state, functional_call, \
    trainable_state


def dense_moe(x, experts, weights, w_gate, w_up, w_down, offset=0):
    """Every token through every held expert, masked: the plain sum."""
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(w_gate.shape[0]):
        g = jnp.sum(jnp.where(experts == e + offset, weights, 0.0), -1)
        y = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        out = out + g[:, None] * y
    return out


class TestGating:
    @pytest.mark.parametrize("top_k", [1, 2, 3])
    def test_topk_weights_normalized_and_nothing_dropped(self, top_k):
        rs = np.random.RandomState(0)
        logits = jnp.asarray(rs.randn(32, 4), jnp.float32)
        experts, weights, probs = topk_gating(logits, top_k)
        assert experts.shape == weights.shape == (32, top_k)
        # every token keeps all its k assignments and their weights sum to 1
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), 1.0,
                                   rtol=1e-6)
        # they are the k largest of the softmax, largest first
        order = np.argsort(-np.asarray(probs), axis=-1)[:, :top_k]
        np.testing.assert_array_equal(np.asarray(experts), order)
        plan = dispatch_plan(experts, 0, 4, 32 * top_k)
        assert int(plan.sizes.sum()) == 32 * top_k
        assert bool(plan.filled.all())
        assert float(balance_loss(experts, probs)) > 0

    def test_unnormalised_weights_are_the_router_probabilities(self):
        logits = jnp.asarray(np.random.RandomState(1).randn(8, 6),
                             jnp.float32)
        experts, weights, probs = topk_gating(logits, 2,
                                              norm_topk_prob=False)
        np.testing.assert_allclose(
            np.asarray(weights),
            np.take_along_axis(np.asarray(probs), np.asarray(experts), -1))

    def test_overflow_tokens_are_kept(self):
        # all tokens prefer expert 0: the old layer kept `capacity` of
        # them, this one keeps every one
        logits = jnp.zeros((10, 3)).at[:, 0].set(10.0)
        experts, weights, _ = topk_gating(logits, 2)
        plan = dispatch_plan(experts, 0, 3, 20)
        assert int(plan.sizes[0]) == 10
        assert int(plan.sizes.sum()) == 20 and bool(plan.filled.all())
        # rows of one expert lie together, in token order
        np.testing.assert_array_equal(np.asarray(plan.token[:10]),
                                      np.arange(10))

    def test_ties_go_to_the_smaller_expert(self):
        experts, _, _ = topk_gating(jnp.zeros((4, 5)), 2)
        np.testing.assert_array_equal(np.asarray(experts),
                                      [[0, 1]] * 4)


class TestGroupedExperts:
    def _weights(self, held=4, d=16, f=8, seed=0):
        rs = np.random.RandomState(seed)
        return [jnp.asarray(rs.randn(*s) * 0.3, jnp.float32)
                for s in ((held, d, f), (held, d, f), (held, f, d))]

    @staticmethod
    def _poison(monkeypatch):
        """NaN in the rows of no group, in every grouped product forward
        and backward: what the TPU may leave there."""
        from paddle_tpu.distributed.meta_parallel import moe as mod
        real = jax.lax.ragged_dot

        @jax.custom_vjp
        def poisoned(a, w, sizes):
            out = real(a, w, sizes, preferred_element_type=jnp.float32)
            beyond = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
            return jnp.where(beyond, jnp.nan, out)

        def fwd(a, w, sizes):
            return poisoned(a, w, sizes), (a, w, sizes)

        def bwd(res, g):
            a, w, sizes = res
            _, vjp = jax.vjp(lambda a, w: real(
                a, w, sizes, preferred_element_type=jnp.float32), a, w)
            da, dw = vjp(g)
            beyond = jnp.arange(a.shape[0])[:, None] >= jnp.sum(sizes)
            return jnp.where(beyond, jnp.nan, da), dw, None
        poisoned.defvjp(fwd, bwd)
        monkeypatch.setattr(
            mod.jax.lax, "ragged_dot",
            lambda a, w, sizes, preferred_element_type=None:
            poisoned(a, w, sizes))

    @pytest.mark.parametrize("held,offset", [(8, 0), (4, 0), (4, 2), (2, 6)])
    def test_held_share_is_the_dense_masked_sum(self, held, offset):
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(24, 16), jnp.float32)
        experts, weights, _ = topk_gating(
            jnp.asarray(rs.randn(24, 8), jnp.float32), 3)
        ws = self._weights(held)
        plan = dispatch_plan(experts, offset, held, 24 * min(3, held))
        got = grouped_experts(x, plan, weights, *ws)
        want = dense_moe(x, experts, weights, *ws, offset=offset)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_all_rows_to_one_held_expert(self):
        """Nothing is dropped: every token's first choice is held expert
        1 and the buffer still gives the dense answer, gradients too."""
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(40, 16), jnp.float32)
        logits = jnp.asarray(rs.randn(40, 8), jnp.float32).at[:, 3].set(9.0)
        experts, weights, _ = topk_gating(logits, 2)
        assert bool((experts[:, 0] == 3).all())
        ws = self._weights(4)

        def grouped(x, weights, *ws):
            plan = dispatch_plan(experts, 2, 4, 80)
            return jnp.sum(grouped_experts(x, plan, weights, *ws) ** 2)

        def dense(x, weights, *ws):
            return jnp.sum(dense_moe(x, experts, weights, *ws,
                                     offset=2) ** 2)
        plan = dispatch_plan(experts, 2, 4, 80)
        assert int(plan.sizes[1]) == 40
        got = jax.value_and_grad(grouped, (0, 1, 2, 3, 4))(x, weights, *ws)
        want = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(x, weights, *ws)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_rows_of_no_group_may_hold_anything(self, monkeypatch):
        """The TPU's grouped product skips the tiles behind the last
        group and leaves there what it finds: with NaN in those rows, in
        every product forward and backward, output and gradients are the
        dense answer still."""
        self._poison(monkeypatch)
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(24, 16), jnp.float32)
        experts, weights, _ = topk_gating(
            jnp.asarray(rs.randn(24, 8), jnp.float32), 3)
        ws = self._weights(4)

        def grouped(x, weights, *ws):
            plan = dispatch_plan(experts, 2, 4, 72)
            return jnp.sum(grouped_experts(x, plan, weights, *ws) ** 2)

        def dense(x, weights, *ws):
            return jnp.sum(dense_moe(x, experts, weights, *ws,
                                     offset=2) ** 2)
        assert int(dispatch_plan(experts, 2, 4, 72).sizes.sum()) < 72
        got = jax.value_and_grad(grouped, (0, 1, 2, 3, 4))(x, weights, *ws)
        want = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(x, weights, *ws)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("rows", [8, 16, 40, 80])
    def test_rounds_of_a_small_buffer_drop_nothing(self, rows):
        """The static buffer holds `rows`; the loop takes as many rounds
        as the assignments need, here up to ten, with every first choice
        on one expert: value and every gradient as the dense sum."""
        rs = np.random.RandomState(5)
        x = jnp.asarray(rs.randn(40, 16), jnp.float32)
        logits = jnp.asarray(rs.randn(40, 8), jnp.float32).at[:, 3].set(9.0)
        experts, weights, _ = topk_gating(logits, 2)
        ws = self._weights(4)

        def grouped(x, weights, *ws):
            plan = dispatch_plan(experts, 2, 4, 80)
            return jnp.sum(grouped_experts(x, plan, weights, *ws,
                                           rows=rows) ** 2)

        def dense(x, weights, *ws):
            return jnp.sum(dense_moe(x, experts, weights, *ws,
                                     offset=2) ** 2)
        got = jax.jit(jax.value_and_grad(grouped, (0, 1, 2, 3, 4)))(
            x, weights, *ws)
        want = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(x, weights, *ws)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("rows,tail", [(16, 4), (24, 8), (40, 8),
                                           (16, 16)])
    @pytest.mark.parametrize("first", [0, 3, 17, 29, 40])
    def test_short_rounds_behind_the_buffer_drop_nothing(
            self, rows, tail, first, monkeypatch):
        """`first` of 40 tokens send their first choice to held expert 1:
        the load ends inside the buffer, just behind it (a short round or
        two), and far behind it (whole buffers, then short rounds), with
        NaN wherever the grouped product would leave its rows alone."""
        from paddle_tpu.distributed.meta_parallel.moe import plan_rows
        self._poison(monkeypatch)
        rs = np.random.RandomState(7)
        x = jnp.asarray(rs.randn(40, 16), jnp.float32)
        logits = jnp.asarray(rs.randn(40, 8), jnp.float32)
        logits = logits.at[:first, 3].set(9.0).at[:, 2].set(-9.0)
        experts, weights, _ = topk_gating(logits, 2)
        ws = self._weights(4)
        total = plan_rows(80, rows, tail)

        def grouped(x, weights, *ws):
            plan = dispatch_plan(experts, 2, 4, total)
            return jnp.sum(grouped_experts(x, plan, weights, *ws, rows=rows,
                                           tail=tail) ** 2)

        def dense(x, weights, *ws):
            return jnp.sum(dense_moe(x, experts, weights, *ws,
                                     offset=2) ** 2)
        got = jax.jit(jax.value_and_grad(grouped, (0, 1, 2, 3, 4)))(
            x, weights, *ws)
        want = jax.value_and_grad(dense, (0, 1, 2, 3, 4))(x, weights, *ws)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("rows,tail", [(24576, 3072), (18432, 2560),
                                           (16, 4), (16, 16), (40, 8)])
    def test_the_schedule_covers_every_load_inside_the_plan(self, rows,
                                                            tail):
        """Whatever the load: the rounds tile [0, load) without a gap,
        end inside `plan_rows` (a slice that did not would be moved back
        without a word), and what lies behind the whole buffers takes at
        most `TAIL_ROUNDS` short rounds."""
        from paddle_tpu.distributed.meta_parallel import moe as mod
        worst = 8 * rows + 5
        total = mod.plan_rows(worst, rows, tail)
        loads = sorted({0, 1, rows - 1, rows, rows + 1, rows + tail,
                        rows + 2 * tail, rows + 2 * tail + 1, 2 * rows,
                        2 * rows + 1, 3 * rows + tail - 1, worst - 1,
                        worst} | set(range(0, worst, max(1, worst // 97))))
        sizes = jnp.asarray(loads, jnp.int32)[:, None]
        whole, short = jax.vmap(
            lambda s: mod._schedule(mod.Dispatch(None, None, None, s),
                                    rows, tail))(sizes)
        for load, w, s in zip(loads, np.asarray(whole), np.asarray(short)):
            s = max(int(s), 0)
            end = (1 + w) * rows + s * tail
            assert w >= 0 and load <= end <= total, (load, w, s)
            assert end - load < (tail if s else rows) or load < rows
            assert s <= max(mod.TAIL_ROUNDS, 2 * (tail == rows)), (load, s)

    def test_a_buffer_that_is_too_small_is_seen(self):
        experts = jnp.zeros((6, 1), jnp.int32)
        plan = dispatch_plan(experts, 0, 2, 4)
        assert int(plan.filled.sum()) == 4 and int(plan.sizes[0]) == 6


class TestMoEMLP:
    def _x(self, b=2, s=16, d=32):
        return jnp.asarray(np.random.RandomState(0).randn(b, s, d),
                           jnp.float32)

    def test_forward_shape_and_grad(self):
        pt.seed(0)
        moe = MoEMLP(32, 64, num_experts=4)
        x = self._x()
        y = moe(x)
        assert y.shape == x.shape
        params = trainable_state(moe)

        def loss(p):
            out, _ = functional_call(moe, p, x)
            return jnp.sum(out ** 2)

        g = jax.grad(loss)(params)
        for name in ("w_gate", "w_up", "w_down", "gate_weight"):
            assert float(jnp.abs(g[name]).max()) > 0, name

    @pytest.mark.parametrize("shares", [1, 2, 4])
    def test_shares_add_up_to_the_whole_layer(self, shares):
        """Expert parallelism as the deployment cuts it: the partial
        results of all the chips that share a layer (each holding
        experts/shares of them, the router whole on each) add up to the
        uncut layer. The old layer's sharded-vs-single-device bar."""
        pt.seed(0)
        whole = MoEMLP(32, 64, num_experts=4, top_k=2)
        x = self._x()
        want = whole(x)
        p = trainable_state(whole)
        held = 4 // shares
        total = jnp.zeros_like(want)
        for i in range(shares):
            part = MoEMLP(32, 64, num_experts=4, top_k=2, experts_held=held,
                          expert_offset=i * held)
            cut = {"gate_weight": p["gate_weight"],
                   **{n: p[n][i * held:(i + 1) * held]
                      for n in ("w_gate", "w_up", "w_down")}}
            out, _ = functional_call(part, cut, x)
            total = total + out
        np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_aux_loss_encourages_balance(self):
        pt.seed(0)
        moe = MoEMLP(16, 32, num_experts=4, aux_loss=True)
        x = self._x(d=16)
        moe(x)
        # eager path: buffer holds the value
        assert float(moe.aux_loss.value) > 0.5  # ~1 at balance

    def test_aux_loss_usable_from_jitted_step(self):
        """The aux loss must flow OUT of a jitted functional step (via
        new_buffers) — a plain attribute would leak a tracer."""
        pt.seed(0)
        moe = MoEMLP(16, 32, num_experts=4, aux_loss=True)
        x = self._x(d=16)
        params = trainable_state(moe)
        buffers = buffer_state(moe)

        @jax.jit
        def loss(p, b, x):
            out, new_b = functional_call(moe, p, x, buffers=b)
            return jnp.sum(out ** 2) + 0.01 * new_b["aux_loss"]

        v = float(loss(params, buffers, x))
        assert np.isfinite(v)
        # and the module attribute did not trap a tracer
        float(moe.aux_loss.value)

    def test_static_counters_say_what_is_held(self):
        from paddle_tpu.profiler import stats
        moe = MoEMLP(16, 32, num_experts=8, top_k=2, experts_held=2,
                     expert_offset=4)
        moe(self._x(d=16))
        snap = stats.REGISTRY.snapshot()
        assert snap["moe.experts_held"] == 2
        assert snap["moe.rows_buffer"] == 32 * 2
        # at the cell's size: 16384 tokens, top 8 of 128, 16 held
        big = MoEMLP(16, 32, num_experts=128, top_k=8, experts_held=16)
        assert big.rows_buffer(16384) == (24576, 3072, 6 * 24576 + 3072)
        with pytest.raises(ValueError):
            MoEMLP(16, 32, num_experts=8, experts_held=4, expert_offset=6)


class TestSigmoidRouting:
    """DeepSeek-V3's routing: the choice by score + bias, the weight by
    the score alone."""

    def logits(self, t=64, e=8, seed=0):
        return jnp.asarray(np.random.RandomState(seed).randn(t, e),
                           jnp.float32)

    def test_choice_by_the_biased_score_weight_by_the_unbiased_one(self):
        logits = self.logits()
        bias = jnp.asarray([0.4, -0.4, 0.0, 0.3, -0.2, 0.0, 0.1, -0.1])
        experts, weights, scores = sigmoid_gating(logits, 3, bias,
                                                  scaling=2.5)
        s = 1.0 / (1.0 + np.exp(-np.asarray(logits, np.float64)))
        np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-6)
        chosen = np.argsort(-(s + np.asarray(bias)), axis=-1)[:, :3]
        np.testing.assert_array_equal(np.sort(np.asarray(experts), -1),
                                      np.sort(chosen, -1))
        w = np.take_along_axis(s, np.asarray(experts), -1)
        np.testing.assert_allclose(
            np.asarray(weights), 2.5 * w / (w.sum(-1, keepdims=True) + 1e-20),
            rtol=1e-6)
        # a case where the two differ: the bias changes some token's
        # experts, and weighting by the biased score would change weights
        plain, plain_w, _ = sigmoid_gating(logits, 3, None, scaling=2.5)
        assert (np.sort(np.asarray(plain), -1) != np.sort(chosen, -1)).any()
        biased = np.take_along_axis(s + np.asarray(bias),
                                    np.asarray(experts), -1)
        biased = 2.5 * biased / biased.sum(-1, keepdims=True)
        assert np.abs(biased - np.asarray(weights)).max() > 0.05

    @pytest.mark.parametrize("norm, scaling", [(True, 1.0), (False, 1.0),
                                               (True, 2.448)])
    def test_renormalised_over_all_the_chosen_and_scaled(self, norm, scaling):
        _, weights, scores = sigmoid_gating(self.logits(), 2, None, norm,
                                            scaling)
        top = np.sort(np.asarray(scores), -1)[:, -2:].sum(-1)
        want = scaling if norm else scaling * top
        np.testing.assert_allclose(np.asarray(weights.sum(-1)), want,
                                   rtol=1e-6)

    def test_the_bias_takes_no_gradient_and_the_router_does(self):
        logits = self.logits()

        def f(logits, bias):
            _, weights, _ = sigmoid_gating(logits, 2, bias, False)
            return jnp.sum(weights ** 2)
        g_logits, g_bias = jax.grad(f, argnums=(0, 1))(
            logits, 0.1 * jnp.arange(8.0))
        assert float(jnp.abs(g_bias).max()) == 0.0
        assert float(jnp.abs(g_logits).max()) > 0.0

    def layer(self, **kw):
        pt.seed(0)
        return MoEMLP(16, 8, 8, top_k=2, scoring="sigmoid", choice_bias=True,
                      routed_scaling_factor=2.0, shared_width=12, **kw)

    def test_layer_is_routed_experts_plus_the_shared_expert(self):
        layer = self.layer()
        layer.choice_bias = 0.3 * jnp.asarray(
            np.random.RandomState(1).randn(8), jnp.float32)
        for p in (layer.gate_weight, layer.w_gate, layer.w_up, layer.w_down):
            p.set_value(p.value * 20)
        x = jnp.asarray(np.random.RandomState(2).randn(2, 8, 16),
                        jnp.float32)
        xt = x.reshape(16, 16)
        experts, weights, _ = sigmoid_gating(
            xt @ layer.gate_weight.value, 2, layer.choice_bias.value,
            scaling=2.0)
        routed = dense_moe(xt, experts, weights, layer.w_gate.value,
                           layer.w_up.value, layer.w_down.value)
        sh = layer.shared
        shared = (jax.nn.silu(xt @ sh.gate_proj.weight.value)
                  * (xt @ sh.up_proj.weight.value)) @ sh.down_proj.weight.value
        np.testing.assert_allclose(np.asarray(layer(x)).reshape(16, 16),
                                   np.asarray(routed + shared), rtol=2e-5,
                                   atol=2e-6)
        names = {n for n, _ in layer.named_parameters()}
        assert {"choice_bias", "shared.gate_proj.weight",
                "shared.up_proj.weight", "shared.down_proj.weight"} <= names
        grads = jax.grad(lambda p: jnp.sum(
            functional_call(layer, p, x)[0] ** 2))(trainable_state(layer))
        assert float(jnp.abs(grads["choice_bias"]).max()) == 0.0
        assert float(jnp.abs(grads["shared.down_proj.weight"]).max()) > 0.0

    def test_the_eight_shares_and_the_shared_expert_once_make_the_layer(self):
        whole = self.layer()
        x = jnp.asarray(np.random.RandomState(3).randn(2, 8, 16),
                        jnp.float32)
        params = trainable_state(whole)
        want, _ = functional_call(whole, params, x)
        shared = whole.shared(x.reshape(16, 16)).reshape(x.shape)
        total = shared
        for off in range(8):
            share = self.layer(experts_held=1, expert_offset=off)
            cut = {n: (v[off:off + 1] if n.startswith("w_") else v)
                   for n, v in params.items()}
            total = total + functional_call(share, cut, x)[0] - shared
        np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                                   rtol=2e-5, atol=2e-6)

    def test_what_does_not_go_together_is_refused(self):
        with pytest.raises(ValueError, match="scoring"):
            MoEMLP(16, 8, 8, scoring="tanh")
        with pytest.raises(ValueError, match="choice bias"):
            MoEMLP(16, 8, 8, choice_bias=True)
        plain = MoEMLP(16, 8, 8)
        assert plain.shared is None and plain.choice_bias is None
        assert {n for n, _ in plain.named_parameters()} == {
            "gate_weight", "w_gate", "w_up", "w_down"}
