"""Trinity-Mini (`model_type: afmoe`): the program's model against the
benchmark's plain reference on seeded weights at a tiny size (hidden 64,
the cell's five layers: a dense sliding layer, then expert layers sliding,
full, sliding, sliding; 8 experts / 4 held / top 2 with a choice bias,
one shared expert; 4 query heads over 2 key/value heads of 16 and a
window of 16 over rows of 64), through `build_train_step`; the groups of
alike blocks; the shares of one expert layer."""
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.families import afmoe as adapter  # noqa: E402
from benchmarks.families import afmoe_reference as ref  # noqa: E402
from benchmarks.harness import cells, check, reference_train  # noqa: E402
from benchmarks.harness import weights as wt  # noqa: E402
from paddle_tpu.models import (AfmoeConfig, AfmoeForCausalLM,  # noqa: E402
                               afmoe_tiny)
from paddle_tpu.nn.layer import functional_call, trainable_state  # noqa
from paddle_tpu.profiler import stats  # noqa: E402

SEQ = 64
CONFIG = "trinity-mini.json"


def tiny_config(**over) -> dict:
    config = cells.load_json("configs", CONFIG)
    config.update(hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, sliding_window=16,
                  num_experts=4, num_experts_per_tok=2, vocab_size=256,
                  expert_offset=2, choice_bias_range=0.05)
    config["published"] = dict(config["published"], num_experts=8,
                               vocab_size=512)
    config["step"].update(config["layouts"]["1"], compute_dtype="float32",
                          loss_chunks=2)
    config.update(over)
    return config


def seeded(config, seed=3):
    """The reference's draw, made harder: norm scales off 1 and matrices
    five times as large, so that routing is far from uniform."""
    key = jax.random.key(seed)
    out = {}
    for i, (k, v) in enumerate(ref.init_weights(config, key).items()):
        if k.endswith(".w") and v.ndim == (2 if k.startswith("blocks.")
                                           else 1):
            v = v + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            v.shape)
        elif v.ndim >= 2 and k != "embed":
            v = v * 5
        out[k] = v
    return out


def program(config, w):
    model = AfmoeForCausalLM(adapter.program_config(config))
    names = adapter.names(config["num_hidden_layers"])
    wt.load(model, w, names)
    return model, names


def batch(config, b=4, seed=0):
    rs = np.random.RandomState(seed)
    return {k: rs.randint(1, config["vocab_size"], (b, SEQ)).astype(np.int32)
            for k in ("ids", "labels")}


def reference_leaf(tree, leaf):
    parts = leaf.split(".")
    if parts[0] == "blocks":
        return tree["blocks." + ".".join(parts[2:])][int(parts[1])]
    return tree[leaf]


def test_the_cell_holds_a_sliding_and_a_full_layer():
    config = tiny_config()
    assert ref.layer_types(config) == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    model, _ = program(config, seeded(config))
    kinds = [(type(t.mlp).__name__, t.self_attn.sliding, n)
             for t, n in model.block_groups()]
    assert kinds == [("GatedMLP", True, 1), ("MoEMLP", True, 1),
                     ("MoEMLP", False, 1), ("MoEMLP", True, 2)]


def test_model_agrees_with_the_reference_on_logits_loss_and_every_leaf():
    config = tiny_config()
    w = seeded(config)
    model, names = program(config, w)
    bt = batch(config, b=2)
    mm = reference_train.matmul_f32
    with jax.default_matmul_precision("highest"):
        got = model(bt["ids"])
    want = ref.logits(config, w, bt["ids"], mm)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=2e-4)

    def loss(p):
        out, _ = functional_call(model, p, bt["ids"], bt["labels"])
        return out
    with jax.default_matmul_precision("highest"):
        l1, g1 = jax.value_and_grad(loss)(trainable_state(model))
    l2, g2 = jax.value_and_grad(lambda w: ref.loss(config, w, bt, mm))(w)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    assert set(names) == set(reference_train.flatten_norms(
        jax.device_get(reference_train.leaf_norms(w))))
    for leaf, name in names.items():
        r = reference_leaf(g2, leaf)
        scale = max(float(jnp.abs(r).max()), 1e-3)
        assert float(jnp.abs(g1[name] - r).max()) <= 2e-4 * scale, leaf
        if leaf.endswith("router.bias"):
            # the choice bias takes no gradient on either side
            assert float(jnp.abs(r).max()) == 0.0 == \
                float(jnp.abs(g1[name]).max()), leaf


@pytest.mark.parametrize("layer", [1, 2], ids=["sliding", "full"])
def test_the_window_and_the_positions_are_the_layers_own(layer):
    """A sliding layer's output at a position does not see a key further
    back than the window, and moves when the whole row is shifted in
    position (rotary); a full layer's sees the first key (no window)."""
    config = tiny_config()
    model, _ = program(config, seeded(config))
    attn = model.model.layers[layer].self_attn
    u = jax.random.normal(jax.random.key(2), (1, SEQ, 64))
    with jax.default_matmul_precision("highest"):
        base = attn(u)
        bumped = attn(u.at[:, 0].add(1.0))
    moved = np.abs(np.asarray(bumped - base)[0]).max(-1) > 1e-6
    window = config["sliding_window"]
    if attn.sliding:
        assert moved[:window].all() and not moved[window:].any()
    else:
        assert moved.all()


def test_three_steps_through_build_train_step_follow_the_reference():
    """The comparison the cell makes, at tiny size in float32: losses,
    the first gradient and the change after three steps, leaf by leaf,
    over the trunk's four groups."""
    config = tiny_config()
    mix = {"batch": 4, "seq": SEQ}
    key = jax.random.key(11)
    init = jax.jit(functools.partial(ref.init_weights, config))
    pool = [batch(config, seed=i) for i in range(3)]
    prog = adapter.build(config, mix, init(key), jax.devices()[:1])
    state, got = prog.state, {"losses": []}
    assert sorted({n.split(".")[0] for n in state[1]}) == [
        "g0", "g1", "g2", "g3"]
    assert state[1]["g0.mlp.gate_proj.weight"].shape == (1, 64, 96)
    assert state[1]["g3.mlp.w_gate"].shape == (2, 4, 64, 32)
    beta1 = config["optimizer"]["beta1"]
    for i in range(3):
        state, loss = prog.step(state, prog.put(pool[i]))
        got["losses"].append(float(loss))
        if i == 0:
            first = reference_train.flatten_norms(jax.device_get(
                reference_train.leaf_norms(prog.moment1(state))))
            got["grad"] = {k: v / (1 - beta1) for k, v in first.items()}
    got["change"] = reference_train.flatten_norms(jax.device_get(
        reference_train.delta_norms(prog.params(state), init(key))))
    want = reference_train.run(ref, config, pool, 11, blocks=2)
    gaps = check.gaps(got, want)
    assert gaps["loss_gap"][0] < 1e-6, gaps
    assert gaps["grad_gap"][0] < 1e-4, gaps
    assert gaps["change_gap"][0] < 2e-3, gaps
    assert "dense.gate.w" in got["grad"] and \
        "blocks.3.shared.down" in got["grad"]
    assert got["grad"]["blocks.0.router.bias"] == 0.0
    # what the model and the trace of the step left in the static counters
    counted = stats.REGISTRY.snapshot()
    assert (counted["trunk.groups"], counted["moe.top_k"],
            counted["attn.window"], counted["attn.window_layers"],
            counted["attn.full_layers"]) == (4, 2, 16, 4, 1)


def test_shares_of_one_expert_layer_add_up_to_the_uncut_layer():
    """The guide's shares test, at eight shares: the routed parts that
    the eight chips of one expert layer give (two experts each of the
    sixteen), plus the shared expert ONCE, add up to the uncut
    reference's expert layer; the router and the shared expert are whole
    on every chip."""
    whole = tiny_config(num_experts=16, expert_offset=0)
    whole["published"]["num_experts"] = 16
    w = seeded(whole)
    z = ref.sizes(whole)
    p = {k[7:]: v[0] for k, v in w.items() if k.startswith("blocks.")}
    u = jax.random.normal(jax.random.key(1), (2, SEQ, 64))
    mm = reference_train.matmul_f32
    with jax.default_matmul_precision("highest"):
        want = ref.moe(z, p, u, mm)
        shared = ref.gated_mlp(u, p["shared.gate"], p["shared.up"],
                               p["shared.down"], mm)
        total = shared
        for off in range(0, 16, 2):
            share = tiny_config(num_experts=2, expert_offset=off)
            share["published"]["num_experts"] = 16
            mlp = AfmoeForCausalLM(adapter.program_config(share)) \
                .model.layers[1].mlp
            cut = {k: (v[off:off + 2] if k.startswith("experts.") else v)
                   for k, v in p.items()}
            y, _ = functional_call(
                mlp, {n[len("mlp."):]: cut[c] for c, n in
                      adapter.BLOCK.items() if n.startswith("mlp.")}, u)
            np.testing.assert_allclose(
                np.asarray(y),
                np.asarray(ref.moe(dict(z, held=2, off=off), cut, u, mm)),
                rtol=2e-4, atol=2e-4)
            total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_default_layer_types_are_three_sliding_then_one_full():
    cfg = AfmoeConfig()
    assert cfg.layer_types[:8] == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + ("full_attention",)
    assert cfg.kind(1) == (True, True) and cfg.kind(3) == (False, False)


@pytest.mark.parametrize("kw,match", [
    (dict(score_func="softmax"), "score_func"),
    (dict(n_group=2), "group-limited"),
    (dict(layer_types=("sliding_attention",) * 3), "layer_types"),
])
def test_what_is_not_built_is_refused_by_name(kw, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        afmoe_tiny(**kw)
