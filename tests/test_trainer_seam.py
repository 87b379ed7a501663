"""The seam between the models and what trains them (ISSUE 31):
`paddle_tpu/trainer/` knows no model, `paddle_tpu/models/` knows no
trainer, and a model written HERE, outside `models/`, goes through
`build_train_step` on the strength of the contract alone."""
from __future__ import annotations

import ast
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn
from paddle_tpu.distributed import build_mesh
from paddle_tpu.distributed.meta_parallel.mp_layers import \
    ParallelCrossEntropy
from paddle_tpu.models import GPTForPretraining, KeyeForCausalLM, keye_tiny
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.nn.layer import (Layer, functional_call, swap_state,
                                 trainable_state)
from paddle_tpu.trainer import (build_train_step, check_model, flatten,
                                sync_params_to_model, unflatten)
from paddle_tpu.trainer.state import stack_params

PKG = os.path.dirname(os.path.abspath(pt.__file__))
VOCAB, WIDTH, SEQ = 64, 32, 16


# -- (a) the arrows point one way ------------------------------------------

def imports_of(path: str):
    """(absolute module, names) of every import in a source file of the
    package, relative ones resolved."""
    here = os.path.relpath(path, os.path.dirname(PKG))[:-3].split(os.sep)
    here.pop()       # `.` is the package a module, or an __init__, lies in
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name, ()
        elif isinstance(node, ast.ImportFrom):
            base = here[:len(here) - node.level + 1] if node.level else []
            module = ".".join(base + ([node.module] if node.module else []))
            yield module, tuple(a.name for a in node.names)


def package_imports(sub: str):
    """(file, what it imports): a `from m import n` both as `m` and as
    `m.n`, since `n` may be a module."""
    for path in sorted(glob.glob(os.path.join(PKG, sub, "**", "*.py"),
                                 recursive=True)):
        for module, names in imports_of(path):
            for full in [module] + [f"{module}.{n}" for n in names]:
                yield os.path.relpath(path, PKG), full


@pytest.mark.parametrize("sub, forbidden, allowed", [
    ("trainer", "paddle_tpu.models", set()),
    # the one re-export, for `benchmarks/families/*.py` (ROADMAP D1)
    ("models", "paddle_tpu.trainer",
     {("models/__init__.py", "paddle_tpu.trainer"),
      ("models/__init__.py", "paddle_tpu.trainer.build_train_step")}),
])
def test_no_import_crosses_the_seam(sub, forbidden, allowed):
    crossing = {(path, full) for path, full in package_imports(sub)
                if full == forbidden or full.startswith(forbidden + ".")}
    assert crossing == allowed


def test_model_files_know_no_mesh_and_no_schedule():
    for name in ("gpt.py", "keye.py", "bert.py"):
        seen = {part for module, names
                in imports_of(os.path.join(PKG, "models", name))
                for part in module.split(".") + list(names)}
        assert not seen & {"trainer", "stacked_pipeline", "mesh_scope",
                           "NamedSharding"}, (name, seen)
    with open(os.path.join(PKG, "models", "gpt.py")) as f:
        source = f.read()
    assert source.count("\n") < 450
    for word in ("jax.jit", "lax.scan", "NamedSharding"):
        assert word not in source, word


def trainer_functions():
    for path in sorted(glob.glob(os.path.join(PKG, "trainer", "*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read())

        def walk(node, depth):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef, ast.Lambda)):
                    named = isinstance(child, ast.FunctionDef)
                    if named:
                        yield os.path.basename(path), child, depth
                    yield from walk(child, depth + named)
                else:
                    yield from walk(child, depth)
        yield from walk(tree, 0)


def test_trainer_functions_stay_short_and_shallow():
    """No function of the package is longer than about 150 lines or
    defines functions more than two levels deep, and the rule that names
    the blocks (`.layers.`) is in one helper."""
    found = list(trainer_functions())
    assert len(found) > 30
    for path, fn, depth in found:
        assert fn.end_lineno - fn.lineno < 160, (path, fn.name)
        assert depth <= 2, (path, fn.name)
    naming = [(path, fn.name) for path, fn, _ in found
              if any(".layers." in text for text in code_strings(fn))]
    assert naming == [("contract.py", "_block_of")]


def code_strings(fn):
    """The string constants of a function's own code, its docstring
    left out."""
    doc = ast.get_docstring(fn, clean=False)
    for node in ast.walk(fn):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and node.value != doc:
            yield node.value


# -- (b) a model that is not under models/ ---------------------------------

class ToyBlock(Layer):
    def __init__(self):
        super().__init__()
        self.norm = nn.LayerNorm(WIDTH)
        self.up = nn.Linear(WIDTH, 2 * WIDTH)
        self.down = nn.Linear(2 * WIDTH, WIDTH)

    def forward(self, x):
        return x + self.down(jnp.tanh(self.up(self.norm(x))))


class ToyCriterion(Layer):
    def __init__(self):
        super().__init__()
        self.ce = ParallelCrossEntropy(ignore_index=-1)

    def forward(self, logits, labels):
        return jnp.mean(self.ce(logits, labels)[..., 0])


class ToyConfig:
    num_layers = 2
    dropout = 0.0


class ToyLM(Layer):
    """Two uniform blocks, an embedding, a norm, a head, a criterion."""

    step_name = "toy_train_step"

    def __init__(self):
        super().__init__()
        self.config = ToyConfig()
        self.table = nn.Embedding(VOCAB, WIDTH)
        self.layers = nn.LayerList([ToyBlock() for _ in range(2)])
        self.norm = nn.LayerNorm(WIDTH)
        self.head = nn.Linear(WIDTH, VOCAB)
        self.criterion = ToyCriterion()

    def block_groups(self):
        return [(self.layers[0], len(self.layers))]

    def embed(self, input_ids, position_ids=None):
        return self.table(input_ids)

    def final_norm(self, hidden):
        return self.norm(hidden)

    def logits(self, hidden):
        return self.head(hidden)

    def forward(self, input_ids, labels):
        x = self.embed(input_ids)
        for block in self.layers:
            x = block(x)
        return self.criterion(self.logits(self.final_norm(x)), labels)


def toy_batch(rows=8):
    ids = jax.random.randint(jax.random.key(0), (rows, SEQ), 0, VOCAB)
    return ids.astype(jnp.int32), jnp.roll(ids, -1, axis=1).astype(jnp.int32)


@pytest.mark.parametrize("mesh_axes, devices, build", [
    ({"dp": 1}, 1, {}),
    ({"sharding": 2, "mp": 2}, 4, {"zero_stage": 3}),
], ids=["one_device", "sharding2_mp2_zero3"])
def test_a_model_outside_models_trains(mesh_axes, devices, build):
    pt.seed(0)
    model, batch, lr = ToyLM(), toy_batch(), 0.1
    params = trainable_state(model)
    want_loss, want = jax.value_and_grad(
        lambda p: functional_call(model, p, *batch)[0])(params)
    before = {n: np.asarray(v) for n, v in params.items()}
    mesh = build_mesh(devices=jax.devices()[:devices], **mesh_axes)
    step, state = build_train_step(model, pt.optimizer.SGD(learning_rate=lr),
                                   mesh, loss_chunks=2, **build)
    assert "toy_train_step" in step.lower(state, batch).as_text()[:200]
    losses = []
    for i in range(4):
        state, loss = step(state, batch)
        losses.append(float(loss))
        if i == 0:     # plain SGD: the first gradient is the first change
            sync_params_to_model(model, state)
            got = {n: (before[n] - np.asarray(v)) / lr
                   for n, v in trainable_state(model).items()}
    assert losses[0] == pytest.approx(float(want_loss), rel=1e-5)
    assert losses[-1] < losses[0]
    assert set(got) == set(want)
    for n, g in want.items():
        np.testing.assert_allclose(got[n], np.asarray(g), rtol=2e-3,
                                   atol=2e-6, err_msg=n)


def lacking(piece: str) -> Layer:
    """A ToyLM without one piece of the contract."""
    if piece.endswith("()") and "." not in piece:
        model = type("Lacking", (ToyLM,), {piece[:-2]: None})()
        if piece == "criterion()":
            del model.criterion
        return model
    model = ToyLM()
    if piece == "criterion.ce()":
        del model.criterion.ce
    elif piece == "config.dropout":
        model.config = type("Config", (), {"num_layers": 2})()
    else:                      # the same blocks, under a name the rule
        model.blocks = model.layers      # does not know
        del model.layers
    return model


@pytest.mark.parametrize("piece", ["block_groups()", "embed()",
                                   "final_norm()", "logits()",
                                   "criterion()", "criterion.ce()",
                                   "config.dropout", "layers"])
def test_a_model_missing_a_piece_is_refused_by_its_name(piece):
    model = lacking(piece)
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    with pytest.raises(TypeError) as refused:
        build_train_step(model, pt.optimizer.SGD(learning_rate=0.1), mesh)
    assert piece in str(refused.value)
    # refused before anything of the model was given up
    assert not any(p.value.is_deleted() for _, p in model.named_parameters())
    check_model(ToyLM())


# -- (c), (d) the state's layout -------------------------------------------

@pytest.mark.parametrize("make", [
    lambda: GPTForPretraining(gpt_tiny(dtype=jnp.float32)),
    lambda: KeyeForCausalLM(keye_tiny(dtype=jnp.float32)),
    ToyLM,
], ids=["gpt_tiny", "keye_tiny", "toy"])
def test_split_then_write_back_is_the_identity(make):
    model = make()
    before = {n: np.asarray(p.value) for n, p in model.named_parameters()}
    outer, stacked, masters = stack_params(model, None)
    assert masters is None
    blocks = model.config.num_layers
    assert all(v.shape[0] == blocks for v in stacked.values())
    assert len(outer) + blocks * len(stacked) == len(before)
    # the eager copy of the blocks is given up once they are stacked
    gone = [n for n, p in model.named_parameters() if p.value.is_deleted()]
    assert len(gone) == blocks * len(stacked)
    sync_params_to_model(model, (outer, stacked, None))
    after = {n: np.asarray(p.value) for n, p in model.named_parameters()}
    assert list(after) == list(before)
    for n in before:
        np.testing.assert_array_equal(after[n], before[n], err_msg=n)


def test_flatten_round_trips_parameters_and_a_slot_tree():
    outer = {"table.weight": jnp.ones((4, 2)), "norm.bias": jnp.zeros(2)}
    stacked = {"up.weight": jnp.ones((3, 2, 2)), "up.bias": jnp.zeros((3, 2))}
    flat = flatten(outer, stacked)
    assert sorted(flat) == ["blocks.up.bias", "blocks.up.weight",
                            "norm.bias", "table.weight"]
    assert flat["blocks.up.weight"] is stacked["up.weight"]
    back = unflatten(flat)
    assert back == (outer, stacked)
    assert flatten(*back) == flat
    # the optimizer's slots are keyed as the flat parameters are
    slots = pt.optimizer.AdamW(learning_rate=1e-3).init_state(flat)["slots"]
    outer_slots, stacked_slots = unflatten(slots)
    assert set(outer_slots) == set(outer)
    assert set(stacked_slots) == set(stacked)
    assert stacked_slots["up.weight"]["moment1"].shape == (3, 2, 2)
    again = flatten(outer_slots, stacked_slots)
    assert again.keys() == slots.keys()
    assert all(again[n] is slots[n] for n in slots)
    assert unflatten({}) == ({}, {})


# -- (c') groups of alike blocks ---------------------------------------------

class WideBlock(Layer):
    """A block unlike `ToyBlock`: another width, other names."""

    def __init__(self):
        super().__init__()
        self.norm = nn.LayerNorm(WIDTH)
        self.a = nn.Linear(WIDTH, 3 * WIDTH)
        self.b = nn.Linear(3 * WIDTH, WIDTH)

    def forward(self, x):
        return x + self.b(jax.nn.silu(self.a(self.norm(x))))


class TwoGroupLM(ToyLM):
    """One wide block, then three `ToyBlock`s: two groups."""

    def __init__(self):
        super().__init__()
        self.layers = nn.LayerList([WideBlock()]
                                   + [ToyBlock() for _ in range(3)])

    def block_groups(self):
        return [(self.layers[0], 1), (self.layers[1], 3)]


def test_a_two_group_model_trains_through_the_trunk():
    """Both groups run inside the trunk (the `decoder` scope, the remat
    policy), each a scan over its own leaves `g<i>.<rel>`; the first
    gradient is the plain model's, leaf by leaf, and the state goes back
    into the model."""
    from paddle_tpu.profiler import stats
    pt.seed(0)
    model, batch, lr = TwoGroupLM(), toy_batch(), 0.1
    params = trainable_state(model)
    want_loss, want = jax.value_and_grad(
        lambda p: functional_call(model, p, *batch)[0])(params)
    before = {n: np.asarray(v) for n, v in params.items()}
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    step, state = build_train_step(
        model, pt.optimizer.SGD(learning_rate=lr), mesh, loss_chunks=2,
        donate=False)
    stacked = state[1]
    assert sorted(stacked) == sorted(
        [f"g0.{n}" for n, _ in model.layers[0].named_parameters()]
        + [f"g1.{n}" for n, _ in model.layers[1].named_parameters()])
    assert stacked["g0.a.weight"].shape == (1, WIDTH, 3 * WIDTH)
    assert stacked["g1.up.weight"].shape[0] == 3
    text = step.lower(state, batch).as_text(debug_info=True)
    assert text.count("stablehlo.while") >= 2      # a scan a group
    new_state, loss = step(state, batch)
    assert stats.REGISTRY.snapshot()["trunk.groups"] == 2
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    sync_params_to_model(model, new_state)
    got = {n: (before[n] - np.asarray(v)) / lr
           for n, v in trainable_state(model).items()}
    assert set(got) == set(want)
    for n, g in want.items():
        np.testing.assert_allclose(got[n], np.asarray(g), rtol=2e-3,
                                   atol=2e-6, err_msg=n)
    # the optimizer's slots are keyed as the flat parameters are
    _, (outer, stacked, opt) = build_train_step(
        model, pt.optimizer.AdamW(learning_rate=lr), mesh)
    assert set(opt["slots"]) == set(outer) | {"blocks." + n for n in stacked}
    assert opt["slots"]["blocks.g1.up.weight"]["moment1"].shape[0] == 3


@pytest.mark.parametrize("make, rels", [
    (lambda: GPTForPretraining(gpt_tiny(dtype=jnp.float32)), None),
    (lambda: KeyeForCausalLM(keye_tiny(dtype=jnp.float32)), None),
    (ToyLM, {"norm.weight", "norm.bias", "up.weight", "up.bias",
             "down.weight", "down.bias"}),
], ids=["gpt_tiny", "keye_tiny", "toy"])
def test_a_one_group_state_keeps_its_keys(make, rels):
    """`(outer, {rel: [L, ...]}, opt)` with the optimizer's slots under
    `"blocks." + rel`: what `benchmarks/families/gpt.py` and `keye.py`
    read, whatever the contract has grown."""
    model = make()
    (template, _), = model.block_groups()
    rels = rels or {n for n, p in template.named_parameters() if p.trainable}
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    _, (outer, stacked, opt) = build_train_step(
        model, pt.optimizer.AdamW(learning_rate=1e-3), mesh)
    assert set(stacked) == rels
    assert not any(n.startswith("g0.") for n in stacked)
    assert set(opt["slots"]) == set(outer) | {"blocks." + n for n in rels}
    assert all(v.shape[0] == model.config.num_layers
               for v in stacked.values())


@pytest.mark.parametrize("mesh_axes, build, said", [
    ({"pp": 2}, {"num_microbatches": 2}, "'pipe' axis of 2 over 2 groups"),
    ({"dp": 1}, {"offload": True}, "offload=True over 2 groups"),
], ids=["pipe", "offload"])
def test_what_groups_cannot_do_yet_is_refused_by_name(mesh_axes, build, said):
    model = TwoGroupLM()
    mesh = build_mesh(devices=jax.devices()[:2 if "pp" in mesh_axes else 1],
                      **mesh_axes)
    with pytest.raises(NotImplementedError, match=said):
        build_train_step(model, pt.optimizer.SGD(learning_rate=0.1), mesh,
                         **build)


def test_groups_that_do_not_cover_the_layers_are_refused():
    model = TwoGroupLM()
    model.block_groups = lambda: [(model.layers[0], 1), (model.layers[1], 2)]
    with pytest.raises(TypeError, match="3 blocks and its `layers` 4"):
        check_model(model)


# -- (e) values bound to a Layer for the length of a trace ------------------

def test_swap_state_restores_every_slot_after_an_exception_in_a_trace():
    model = ToyLM()
    model.register_buffer("seen", jnp.zeros(()))
    held = {n: p.value for n, p in model.named_parameters()}
    held.update({n: b.value for n, b in model.named_buffers()})
    assert "seen" in held

    @jax.jit
    def traced(params, seen, ids):
        with swap_state(model, params, {"seen": seen}):
            assert isinstance(model.table.weight.value, jax.core.Tracer)
            assert isinstance(dict(model.named_buffers())["seen"].value,
                              jax.core.Tracer)
            model.embed(ids)
            raise RuntimeError("inside the trace")

    with pytest.raises(RuntimeError, match="inside the trace"):
        traced(trainable_state(model), jnp.ones(()), toy_batch()[0])
    now = {n: p.value for n, p in model.named_parameters()}
    now.update({n: b.value for n, b in model.named_buffers()})
    assert now.keys() == held.keys()
    assert all(now[n] is held[n] for n in held)


def test_functional_call_is_swap_state_around_the_forward():
    model = ToyLM()
    batch = toy_batch()
    params = {n: v * 0.5 for n, v in trainable_state(model).items()}
    params["no.such.parameter"] = jnp.zeros(())      # passed over
    out, buffers = functional_call(model, params, *batch)
    with swap_state(model, params):
        inside = model(*batch)
        assert model.table.weight.value is params["table.weight"]
    assert float(out) == float(inside) != float(model(*batch))
    assert buffers == {}


# -- the tool a later refactor of the trainer is checked with --------------

def test_step_hlo_tool_hashes_a_fork_the_same_twice():
    import json
    import subprocess
    import sys
    repo = os.path.dirname(PKG)
    runs = [subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "step_hlo.py"), "forks",
         repo, "--only", "one_noremat,offload_chunks"],
        capture_output=True, text=True, timeout=600) for _ in range(2)]
    lines = [[json.loads(ln) for ln in r.stdout.splitlines()] for r in runs]
    assert all(r.returncode == 0 for r in runs), runs[0].stderr[-2000:]
    assert [(ln["fork"], ln["program"]) for ln in lines[0]] == [
        ("one_noremat_whole_loss", "gpt_train_step"),
        ("offload_chunks", "gpt_offload_grad"),
        ("offload_chunks", "gpt_offload_chunk"),
        ("offload_chunks", "gpt_offload_outer")]
    assert [ln["sha"] for ln in lines[0]] == [ln["sha"] for ln in lines[1]]
    assert len({ln["sha"] for ln in lines[0]}) == 4
