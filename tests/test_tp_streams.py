"""What crosses the 'model' link in a decoder block, and when (PR 30).

On the 8 virtual CPU devices: the fused QKV product stays sharded by heads
from the column-parallel matmul to the row-parallel one (no activation is
gathered, forward, replay or backward), a block exchanges its four
row-parallel sums and nothing else of activation size (with two chips on
'model' each sum is one collective-permute, else an all-reduce), and under
a 'model' axis the blocks are applied to the two halves of a chip's rows
as two streams of one scan body.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
from paddle_tpu.distributed import build_mesh
from paddle_tpu.models import GPTConfig, GPTForPretraining
from paddle_tpu.trainer import build_train_step
from paddle_tpu.profiler import stats

SEQ, WIDTH, HEADS, LAYERS, VOCAB = 48, 64, 4, 3, 128
COLLECTIVE = re.compile(
    r"^\s+(?:ROOT )?%[\w.\-]+ = (.*?) (all-reduce|all-gather|all-to-all|"
    r"reduce-scatter|collective-permute)(?:-start)?\((.*?)\), ", re.M)


def _model(dtype=jnp.float32, seed=0, dropout=0.0):
    pt.seed(seed)
    return GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=WIDTH, num_layers=LAYERS,
        num_heads=HEADS, max_position_embeddings=SEQ, dtype=dtype,
        dropout=dropout))


def _batch(rows):
    rs = np.random.RandomState(0)
    return tuple(jnp.asarray(rs.randint(0, VOCAB, (rows, SEQ)), jnp.int32)
                 for _ in range(2))


def _build(mesh_dims, model=None, **kw):
    model = model or _model()
    opt = pt.optimizer.AdamW(learning_rate=1e-3)
    return build_train_step(model, opt, build_mesh(**mesh_dims),
                            donate=False, **kw)


def _streams():
    return stats.REGISTRY.counter("tp.streams").value


def _run(mesh_dims, rows, steps=3, **kw):
    """Losses of `steps` steps, the first gradient leaf by leaf (Adam's
    first moment after one step is (1 - beta1) x gradient), and the
    streams the step was traced with."""
    step, state = _build(mesh_dims, **kw)
    batch, losses, grad = _batch(rows), [], None
    for i in range(steps):
        state, loss = step(state, batch)
        losses.append(float(loss))
        if i == 0:
            grad = {n: np.asarray(s["moment1"]) / 0.1
                    for n, s in state[2]["slots"].items()}
    return losses, grad, _streams()


def _loop_collectives(hlo: str):
    """(opcode, result shapes, op_name) of every collective of a loop
    body that belongs to the decoder's scans."""
    out = []
    for comp in re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \(.*\{\n)", hlo):
        for m in COLLECTIVE.finditer(comp):
            line = comp[m.start():comp.find("\n", m.end())]
            name = re.search(r'op_name="([^"]*)"', line)
            if name and "decoder" in name.group(1) \
                    and "while/body" in name.group(1):
                shapes = re.findall(r"\w+\[([\d,]*)\]", m.group(1))
                out.append((m.group(2),
                            [tuple(int(d) for d in s.split(",") if d)
                             for s in shapes], name.group(1)))
    return out


def _activation(shape, rows) -> bool:
    """Does a shape lead with a chip's rows (or a stream's) x sequence?"""
    return len(shape) >= 3 and shape[0] in (rows, rows // 2) \
        and shape[1] == SEQ


class TestBlockExchangesItsSumsAlone:
    """(a) The optimised HLO of the ZeRO-3 x TP step."""

    @pytest.fixture(scope="class")
    def collectives(self):
        step, state = _build(dict(sharding=2, mp=2), zero_stage=3,
                             remat_policy="dots")
        hlo = step.lower(state, _batch(8)).compile().as_text()
        found = _loop_collectives(hlo)
        assert found, "no collective of the decoder's loops was recognised"
        return found

    def test_no_activation_is_gathered(self, collectives):
        rows = 8 // 2
        moved = [(op, shapes, name) for op, shapes, name in collectives
                 if op in ("all-gather", "all-to-all")
                 and any(_activation(s, rows) for s in shapes)]
        assert not moved, moved

    def test_four_activation_sums_a_layer(self, collectives):
        """Forward and backward loop body are traced once each, so the
        rows summed over both are 4 sums x a chip's rows, however they
        are split into streams or combined into one instruction."""
        rows = 8 // 2
        summed = sum(s[0] for op, shapes, _ in collectives
                     if op in ("all-reduce", "collective-permute")
                     for s in shapes
                     if _activation(s, rows) and s[2:] == (WIDTH,))
        assert summed == 4 * rows, [c for c in collectives
                                    if c[0] == "all-reduce"]


class TestTwoStreamsSameNumbers:
    """(b) Two streams against one stream against one device, float32,
    at `test_parallel_matches_single_device`'s tolerance."""

    @pytest.fixture(scope="class")
    def runs(self):
        return {
            "one device": _run(dict(dp=1), 6),
            "one stream": _run(dict(sharding=2, mp=2), 6, zero_stage=3),
            "two streams": _run(dict(mp=2), 6),
            # four chips on 'model': GSPMD's all-reduce, no exchange
            "two streams, mp=4": _run(dict(mp=4), 6),
            "two streams, ZeRO-3, dots": _run(
                dict(sharding=2, mp=2), 12, zero_stage=3,
                remat_policy="dots"),
            "one device, 12 rows": _run(dict(dp=1), 12),
        }

    def test_streams_engaged_as_meant(self, runs):
        assert {k: v[2] for k, v in runs.items()} == {
            "one device": 1, "one stream": 1, "two streams": 2,
            "two streams, mp=4": 2,
            "two streams, ZeRO-3, dots": 2, "one device, 12 rows": 1}

    PAIRS = [("two streams", "one stream"), ("two streams", "one device"),
             ("two streams, mp=4", "one device"),
             ("two streams, ZeRO-3, dots", "one device, 12 rows")]

    @pytest.mark.parametrize("ours,ref", PAIRS)
    def test_losses_of_three_steps(self, runs, ours, ref):
        np.testing.assert_allclose(runs[ours][0], runs[ref][0], rtol=2e-4)

    @pytest.mark.parametrize("ours,ref", PAIRS)
    def test_every_leaf_of_the_first_gradient(self, runs, ours, ref):
        got, want = runs[ours][1], runs[ref][1]
        assert got.keys() == want.keys()
        for name in want:
            scale = np.abs(want[name]).max() + 1e-12
            np.testing.assert_allclose(got[name] / scale, want[name] / scale,
                                       atol=2e-4, err_msg=name)


class TestStreamCount:
    """(c) `tp.streams` follows the mesh and the rows, nothing else."""

    @pytest.mark.parametrize("mesh_dims,rows,want", [
        (dict(sharding=2, mp=2), 8, 2),     # 4 rows a chip
        (dict(dp=2, mp=2), 4, 2),           # 2 rows a chip
        (dict(sharding=2, mp=2), 6, 1),     # 3 rows a chip: odd
        (dict(mp=2), 1, 1),                 # one row
        (dict(sharding=2), 8, 1),           # no 'model' axis to hide
        (dict(dp=1), 8, 1),
    ])
    def test_counter(self, mesh_dims, rows, want):
        stats.static("tp.streams", 0)
        step, state = _build(mesh_dims)
        step.lower(state, _batch(rows))
        assert _streams() == want

    def test_two_streams_under_dropout(self):
        """Under dropout the two halves take sub-keys of the block's key
        inside the scan body: the step still is a function of its key
        alone."""
        step, state = _build(dict(mp=2), model=_model(dropout=0.5))
        batch = _batch(4)
        _, a = step(state, batch, jax.random.key(1))
        _, again = step(state, batch, jax.random.key(1))
        _, b = step(state, batch, jax.random.key(2))
        assert _streams() == 2
        assert np.isfinite(float(a)) and float(a) == float(again)
        assert float(a) != float(b)

    @pytest.mark.parametrize("mesh_dims,calls", [
        (dict(dp=2), 1), (dict(sharding=2, mp=2), 2)])
    def test_attention_calls_a_block_body(self, mesh_dims, calls):
        """Without a 'model' axis the scan body is the one block it was;
        with one it holds the block once a stream. Counted by the
        softmax's `exp`: once a call in the forward body, once in the
        backward body's replay, and once in the loss."""
        step, state = _build(mesh_dims, remat_policy="dots")
        text = step.lower(state, _batch(8)).as_text()
        assert text.count("stablehlo.exponential") == 2 * calls + 1
        # the layers are still one loop forward and one backward
        assert text.count("stablehlo.while") == 2


class TestFusedWeightKeepsItsLayout:
    """(d) Name, shape, column order and the quarter-size shard."""

    def test_parent_layout_state_dict_loads_and_agrees(self):
        donor = _model(seed=3)
        saved = {k: np.asarray(v) for k, v in donor.state_dict().items()}
        w = saved["gpt.layers.0.qkv.weight"]
        assert w.shape == (WIDTH, 3 * WIDTH)
        # the columns are [3, heads, head_dim]: q first, then k, then v
        x = np.random.RandomState(1).randn(2, 5, WIDTH).astype(np.float32)
        blk = donor.gpt.layers[0]
        got = np.asarray(blk.qkv.project_heads(jnp.asarray(x), 3, HEADS))
        want = (x @ w + saved["gpt.layers.0.qkv.bias"]).reshape(
            2, 5, 3, HEADS, WIDTH // HEADS)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

        losses = {}
        for name, dims, kw in [
                ("one device", dict(dp=1), {}),
                ("sharded", dict(sharding=2, mp=2), dict(zero_stage=3))]:
            model = _model(seed=0)
            model.set_state_dict(saved)
            step, state = _build(dims, model=model, **kw)
            qkv = state[1]["qkv.weight"]
            assert qkv.shape == (LAYERS, WIDTH, 3 * WIDTH)
            if name == "sharded":
                # TP and ZeRO split DIFFERENT dims of the weight
                shard = qkv.addressable_shards[0].data
                assert shard.shape == (LAYERS, WIDTH // 2, 3 * WIDTH // 2)
            _, loss = step(state, _batch(8))
            losses[name] = float(loss)
        np.testing.assert_allclose(losses["sharded"], losses["one device"],
                                   rtol=2e-4)

    def test_heads_of_a_model_shard_are_whole(self):
        """Under a 'model' axis the product is sharded on the heads dim,
        and equals the dense product."""
        from paddle_tpu.distributed.meta_parallel import ColumnParallelLinear
        pt.seed(0)
        mesh = build_mesh(mp=2)
        col = ColumnParallelLinear(WIDTH, 3 * WIDTH, gather_output=False)
        x = jnp.asarray(np.random.RandomState(0).randn(4, SEQ, WIDTH),
                        jnp.float32)
        out = jax.jit(lambda a: col.project_heads(a, 3, HEADS))(x)
        assert out.shape == (4, SEQ, 3, HEADS, WIDTH // HEADS)
        assert out.sharding.shard_shape(out.shape)[3] == HEADS // 2
        want = (np.asarray(x) @ np.asarray(col.weight)
                + np.asarray(col.bias)).reshape(out.shape)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5,
                                   atol=1e-5)
        del mesh
