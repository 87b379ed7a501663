"""What the step hands out (ISSUE 38): the expert layers' counters (rows
routed, rows computed, whole and short rounds) come out of the jitted
step beside the loss and are kept, a call a record, in
`profiler.step_records()`; the step's callable still returns `(state,
loss)`, and a model without an expert layer compiles the program it
compiled before."""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn, profiler
from paddle_tpu.distributed import build_mesh
from paddle_tpu.distributed.meta_parallel import moe
from paddle_tpu.distributed.meta_parallel.mp_layers import \
    ParallelCrossEntropy
from paddle_tpu.models import GPTForPretraining
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.nn.layer import Layer
from paddle_tpu.trainer import build_train_step

VOCAB, WIDTH, FF, SEQ, ROWS = 64, 16, 8, 16, 2
EXPERTS, TOP, HELD, OFFSET = 8, 2, 4, 2
TOKENS = ROWS * SEQ


class MoEBlock(Layer):
    def __init__(self, scoring, buffer):
        super().__init__()
        self.norm = nn.LayerNorm(WIDTH)
        self.moe = moe.MoEMLP(
            WIDTH, FF, EXPERTS, top_k=TOP, experts_held=HELD,
            expert_offset=OFFSET, scoring=scoring,
            choice_bias=scoring == "sigmoid", compute_dtype=jnp.float32,
            initializer_range=0.5)
        if buffer:       # (rows, tail) at toy size: whole and short rounds
            rows, tail = buffer
            worst = TOKENS * min(TOP, HELD)
            self.moe.rows_buffer = lambda tokens: (
                rows, tail, moe.plan_rows(worst, rows, tail))

    def forward(self, x):
        return x + self.moe(self.norm(x))


class DenseBlock(Layer):
    def __init__(self):
        super().__init__()
        self.norm = nn.LayerNorm(WIDTH)
        self.mlp = moe.GatedMLP(WIDTH, 2 * WIDTH)

    def forward(self, x):
        return x + self.mlp(self.norm(x))


class Criterion(Layer):
    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-1)

    def forward(self, logits, labels):
        return jnp.mean(self.ce(logits, labels)[..., 0])


class Config:
    dropout = 0.0


class MoELM(Layer):
    """`blocks` expert blocks, behind one dense block where `dense`."""

    step_name = "moe_toy_train_step"

    def __init__(self, blocks=2, scoring="softmax", buffer=None,
                 dense=False):
        super().__init__()
        self.config = Config()
        self.table = nn.Embedding(VOCAB, WIDTH)
        self.layers = nn.LayerList(
            ([DenseBlock()] if dense else [])
            + [MoEBlock(scoring, buffer) for _ in range(blocks)])
        self._groups = ([(self.layers[0], 1)] if dense else []) \
            + [(self.layers[int(dense)], blocks)]
        self.norm = nn.LayerNorm(WIDTH)
        self.head = nn.Linear(WIDTH, VOCAB)
        self.criterion = Criterion()

    def block_groups(self):
        return self._groups

    def embed(self, input_ids, position_ids=None):
        return self.table(input_ids)

    def final_norm(self, hidden):
        return self.norm(hidden)

    def logits(self, hidden):
        return self.head(hidden)


def batch(seed=0):
    ids = jax.random.randint(jax.random.key(seed), (ROWS, SEQ), 0, VOCAB)
    return ids.astype(jnp.int32), jnp.roll(ids, -1, axis=1).astype(jnp.int32)


@pytest.fixture
def choices(monkeypatch):
    """The routers' own choices, handed out beside the counters: every
    call of `dispatch_plan` also counts its `[tokens, k]` experts."""
    plan = moe.dispatch_plan

    def counted(experts, *args):
        profiler.count("test.experts", experts)
        return plan(experts, *args)
    monkeypatch.setattr(moe, "dispatch_plan", counted)
    profiler.reset()
    yield
    profiler.reset()


def numpy_counts(experts, rows, tail, total):
    """The four counters from the choices `[tokens, k]` alone: the held
    assignments, then the first buffer, whole buffers while more than
    `TAIL_ROUNDS` short rounds' worth is left, short rounds for the rest."""
    routed = int(((experts >= OFFSET) & (experts < OFFSET + HELD)).sum())
    if rows == total:
        return {"routed": routed, "computed": rows, "whole": 0, "short": 0}
    left, whole = routed - rows, 0
    while left > moe.TAIL_ROUNDS * tail:
        left -= rows
        whole += 1
    short = -(-left // tail) if left > 0 else 0
    return {"routed": routed, "computed": (1 + whole) * rows + short * tail,
            "whole": whole, "short": short}


def run(model, steps=2, **build):
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    step, state = build_train_step(
        model, pt.optimizer.SGD(learning_rate=0.1), mesh, **build)
    out = []
    for i in range(steps):
        got = step(state, batch(i))
        assert isinstance(got, tuple) and len(got) == 2
        state, loss = got
        out.append(float(loss))
    return out


@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
@pytest.mark.parametrize("buffer", [None, (6, 6), (24, 4), (8, 2)],
                         ids=["one_buffer", "whole", "short", "both"])
@pytest.mark.parametrize("remat_policy", ["full", "dots"])
def test_counters_are_the_routers_choices(choices, scoring, buffer,
                                          remat_policy):
    model = MoELM(scoring=scoring, buffer=buffer)
    rows, tail, total = model.layers[0].moe.rows_buffer(TOKENS)
    losses = run(model, remat_policy=remat_policy)
    assert all(np.isfinite(losses))
    records = profiler.step_records()
    assert [r.step for r in records] == [0, 1]
    seen = {"whole": 0, "short": 0}
    for r in records:
        assert set(r.counters) == {"moe.routed", "moe.computed",
                                   "moe.whole", "moe.short", "test.experts"}
        experts = r.counters["test.experts"]
        assert experts.shape == (2, TOKENS, TOP)
        for layer in range(2):
            want = numpy_counts(experts[layer], rows, tail, total)
            got = {k: int(r.counters["moe." + k][layer]) for k in want}
            assert got == want, (layer, got, want)
            seen = {k: v + want[k] for k, v in seen.items()}
        assert all(v.dtype == np.int32 for v in r.counters.values())
        assert r.begin_ns <= r.end_ns
    # each buffer takes the rounds it was chosen for
    if buffer is None:
        assert total == rows and seen == {"whole": 0, "short": 0}
    if buffer in [(6, 6), (8, 2)]:
        assert seen["whole"]
    if buffer == (24, 4):
        assert seen["short"]


def test_a_whole_buffer_that_holds_the_rest_leaves_no_short_round():
    """129 rows behind the first buffer of 512, over two short rounds of
    64: one whole buffer takes them, and `_schedule`'s negative count of
    short rounds (which its loop runs no times) reads 0."""
    rows, tail = 512, 64
    total = moe.plan_rows(1024, rows, tail)
    sizes = jnp.array([512 + 129, 0, 0], jnp.int32)
    plan = moe.Dispatch(jnp.zeros(total, jnp.int32),
                        jnp.zeros(total, jnp.int32),
                        jnp.zeros(total, bool), sizes)
    assert int(moe._schedule(plan, rows, tail)[1]) < 0
    got = {k: int(v) for k, v in moe.round_counts(plan, rows, tail).items()}
    assert got == {"routed": 641, "computed": 1024, "whole": 1, "short": 0}


def test_two_groups_count_under_the_expert_groups_key(choices):
    """A dense block first, as in the kanana decoder: the counters are
    the second group's, `g1.`, stacked over its blocks."""
    model = MoELM(blocks=3, dense=True, buffer=(8, 2))
    rows, tail, total = model.layers[1].moe.rows_buffer(TOKENS)
    run(model, steps=1, remat_policy="full")
    (r,) = profiler.step_records()
    assert set(r.counters) == {"g1.moe.routed", "g1.moe.computed",
                               "g1.moe.whole", "g1.moe.short",
                               "g1.test.experts"}
    for layer in range(3):
        want = numpy_counts(r.counters["g1.test.experts"][layer], rows,
                            tail, total)
        got = {k: int(r.counters["g1.moe." + k][layer]) for k in want}
        assert got == want


def test_counting_outside_a_step_is_nothing():
    """Eager, or traced outside the trunk, a block counts nowhere."""
    profiler.reset()
    layer = moe.MoEMLP(WIDTH, FF, EXPERTS, top_k=TOP)
    x = jnp.ones((1, SEQ, WIDTH))
    assert layer(x).shape == x.shape
    assert jax.jit(layer)(x).shape == x.shape
    assert profiler.step_records() == []
    with profiler.counting() as counted:
        profiler.count("a", jnp.int32(2))
        profiler.count("a", jnp.int32(3))
        with profiler.counting() as inner:
            profiler.count("b", 1)
        assert inner == {"b": 1}
    assert int(counted["a"]) == 5 and "b" not in counted


def test_the_record_is_bounded_and_reset_clears_it(monkeypatch):
    profiler.reset()
    monkeypatch.setattr(profiler, "_steps", __import__(
        "collections").deque(maxlen=3))
    model = GPTForPretraining(gpt_tiny(dtype=jnp.float32,
                                       max_position_embeddings=SEQ))
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    step, state = build_train_step(model, pt.optimizer.SGD(learning_rate=0.1),
                                   mesh)
    ids = jnp.zeros((2, SEQ), jnp.int32)
    for _ in range(5):
        state, loss = step(state, (ids, ids))
    records = profiler.step_records()
    assert [r.step for r in records] == [2, 3, 4]
    assert all(r.counters == {} for r in records)
    assert records[0].end_ns <= records[1].begin_ns
    assert profiler.MAX_STEP_RECORDS >= 200
    profiler.reset()
    assert profiler.step_records() == []


def _main_results(text):
    """The result types of the lowered program's `@main`."""
    head = re.search(r"func\.func public @main\((.*?)\) -> \((.*?)\) \{",
                     text, re.S)
    return re.findall(r"tensor<[^>]*>", head.group(2))


def test_a_model_without_experts_lowers_as_before():
    """GPT's step hands out no counters: the program's results are the
    state's leaves and the loss, as the parent's builder made them
    (`tools/step_hlo.py forks` checks the whole text against a parent's
    checkout); an expert model's step has one result more, its four
    counters of [blocks] int32 packed into one array (one copy to the
    host a step). Called inside another trace, the step records
    nothing."""
    model = GPTForPretraining(gpt_tiny(dtype=jnp.float32,
                                       max_position_embeddings=SEQ))
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    step, state = build_train_step(model, pt.optimizer.SGD(learning_rate=0.1),
                                   mesh)
    ids = jnp.zeros((2, SEQ), jnp.int32)
    text = step.lower(state, (ids, ids)).as_text()
    results = _main_results(text)
    assert len(results) == len(jax.tree.leaves(state)) + 1
    assert results[-1] == "tensor<f32>"     # the loss, last
    profiler.reset()
    jax.jit(lambda s, b: step(s, b)[1]).lower(state, (ids, ids))
    assert profiler.step_records() == []

    model = MoELM()
    step, state = build_train_step(model, pt.optimizer.SGD(learning_rate=0.1),
                                   mesh)
    results = _main_results(step.lower(state, batch()).as_text())
    assert len(results) == len(jax.tree.leaves(state)) + 1 + 1
    assert results[-2:] == ["tensor<f32>", "tensor<8xi32>"]
