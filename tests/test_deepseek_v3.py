"""kanana-2-30b-a3b-instruct-2601 (`model_type: deepseek_v3`): the
program's model against the benchmark's plain reference on seeded weights
at a tiny size (hidden 64, one dense layer and two expert layers, 8
experts / 4 held / top 2 with a choice bias, one shared expert, scores 24
wide over values of 16), through `build_train_step`, the builder GPT and
Keye go through; the shares of one layer; the rotary convention."""
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

import paddle_tpu as pt  # noqa: E402
from benchmarks.families import deepseek_v3 as adapter  # noqa: E402
from benchmarks.families import deepseek_v3_reference as ref  # noqa: E402
from benchmarks.harness import cells, check, reference_train  # noqa: E402
from benchmarks.harness import weights as wt  # noqa: E402
from paddle_tpu.distributed import build_mesh  # noqa: E402
from paddle_tpu.models import (DeepseekV3Config,  # noqa: E402
                               DeepseekV3ForCausalLM, deepseek_v3_tiny)
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.nn.layer import functional_call, trainable_state  # noqa
from paddle_tpu.profiler import stats  # noqa: E402
from paddle_tpu.trainer import (build_train_step,  # noqa: E402
                                sync_params_to_model)

SEQ = 64
CONFIG = "kanana-2-30b-a3b-instruct-2601.json"


def tiny_config(**over) -> dict:
    config = cells.load_json("configs", CONFIG)
    config.update(hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_hidden_layers=3,
                  num_attention_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  n_routed_experts=4, num_experts_per_tok=2, vocab_size=256,
                  expert_offset=2, choice_bias_range=0.05)
    config["published"] = dict(config["published"], n_routed_experts=8,
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
        if "norm" in k or "ln" in k:
            v = v + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            v.shape)
        elif v.ndim >= 2 and k != "embed":
            v = v * 5
        out[k] = v
    return out


def program(config, w):
    model = DeepseekV3ForCausalLM(adapter.program_config(config))
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


def test_three_steps_through_build_train_step_follow_the_reference():
    """The comparison the cell makes, at tiny size in float32: losses,
    the first gradient and the change after three steps, leaf by leaf,
    the dense layer's among them (group 0 of the trunk)."""
    config = tiny_config()
    mix = {"batch": 4, "seq": SEQ}
    key = jax.random.key(11)
    init = jax.jit(functools.partial(ref.init_weights, config))
    pool = [batch(config, seed=i) for i in range(3)]
    prog = adapter.build(config, mix, init(key), jax.devices()[:1])
    state, got = prog.state, {"losses": []}
    assert sorted({n.split(".")[0] for n in state[1]}) == ["g0", "g1"]
    assert state[1]["g0.mlp.gate_proj.weight"].shape == (1, 64, 96)
    assert state[1]["g1.mlp.w_gate"].shape == (2, 4, 64, 32)
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
    assert "dense.mlp.gate" in got["grad"] and \
        "blocks.1.shared.down" in got["grad"]
    assert got["grad"]["blocks.0.router.bias"] == 0.0
    # what the trace of the step left in the static counters
    counted = stats.REGISTRY.snapshot()
    assert (counted["trunk.groups"], counted["moe.top_k"],
            counted["moe.shared_width"]) == (2, 2, 64)


def test_the_state_goes_back_into_the_model():
    config = tiny_config()
    model, _ = program(config, seeded(config))
    before = {n: np.asarray(p.value) for n, p in model.named_parameters()}
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    _, state = build_train_step(
        model, pt.optimizer.SGD(learning_rate=0.1), mesh, loss_chunks=2)
    sync_params_to_model(model, state)
    after = {n: np.asarray(p.value) for n, p in model.named_parameters()}
    assert list(after) == list(before)
    for n in before:
        np.testing.assert_array_equal(after[n], before[n], err_msg=n)


def test_shares_of_one_layer_add_up_to_the_uncut_layer():
    """The guide's shares test, at eight shares: the routed parts that
    the eight chips of one layer give (one expert each of the eight),
    plus the shared expert ONCE, add up to the uncut reference layer;
    attention and the shared expert are whole on every chip."""
    whole = tiny_config(n_routed_experts=8, expert_offset=0,
                        num_hidden_layers=2)
    w = seeded(whole)
    z = ref.sizes(whole)
    p = {k[7:]: v[0] for k, v in w.items() if k.startswith("blocks.")}
    x = jax.random.normal(jax.random.key(1), (2, SEQ, 64))
    mm = reference_train.matmul_f32
    with jax.default_matmul_precision("highest"):
        want = ref.layer(z, p, x, mm, dense=False)
        h = x + ref.attention(z, p, ref.rms_norm(x, p["ln1.w"], z["eps"]),
                              mm)
        u = ref.rms_norm(h, p["ln2.w"], z["eps"])
        shared = ref.gated_mlp(u, p["shared.gate"], p["shared.up"],
                               p["shared.down"], mm)
        total = h + shared
        for off in range(8):
            cfg = adapter.program_config(
                tiny_config(n_routed_experts=1, expert_offset=off,
                            num_hidden_layers=2))
            block = DeepseekV3ForCausalLM(cfg).model.layers[1]
            cut = {k: (v[off:off + 1] if k.startswith("experts.") else v)
                   for k, v in p.items()}
            y, _ = functional_call(
                block, {n: cut[c] for c, n in adapter.BLOCK.items()}, x)
            # a share's output is h + its expert's part + the shared
            # expert: take h and the shared expert out
            total = total + (y - h - shared)
            zc = dict(z, held=1, off=off)
            np.testing.assert_allclose(
                np.asarray(y),
                np.asarray(ref.layer(zc, cut, x, mm, dense=False)),
                rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_interleaved_rotary_is_the_sources_to_the_last_bit_in_scores():
    """`F.rotary_embedding(interleaved=True)` against the reference's
    `apply_rotary_pos_emb_interleave` written out: equal bit for bit, and
    so are the scores made of them."""
    ks = jax.random.split(jax.random.key(5), 2)
    q = jax.random.normal(ks[0], (2, SEQ, 4, 8))
    k = jax.random.normal(ks[1], (2, SEQ, 1, 8))
    got_q, got_k = (F.rotary_embedding(x, 1e6, interleaved=True)
                    for x in (q, k))
    want_q, want_k = (jnp.stack([ref.rotary_interleave(row, 1e6)
                                 for row in x]) for x in (q, k))
    np.testing.assert_array_equal(np.asarray(got_q), np.asarray(want_q))
    np.testing.assert_array_equal(np.asarray(got_k), np.asarray(want_k))
    score = functools.partial(jnp.einsum, "bqhd,bkd->bhqk",
                              precision="highest")
    np.testing.assert_array_equal(
        np.asarray(score(got_q, got_k[:, :, 0])),
        np.asarray(score(want_q, want_k[:, :, 0])))
    # and it is NOT the half-split convention on the same lanes
    assert float(jnp.abs(F.rotary_embedding(q, 1e6) - got_q).max()) > 0.1


def test_what_is_not_built_is_refused_by_name():
    with pytest.raises(NotImplementedError, match="q_lora_rank"):
        DeepseekV3Config(q_lora_rank=768)
    with pytest.raises(NotImplementedError, match="n_group"):
        DeepseekV3Config(n_group=8, topk_group=4)
    model = DeepseekV3ForCausalLM(deepseek_v3_tiny(dtype=jnp.float32))
    assert [n for _, n in model.block_groups()] == [1, 2]
    assert model.step_name == "deepseek_v3_train_step"
    # no dense layer, or dense layers only: one group
    for dense, layers in ((0, [3]), (3, [3])):
        m = DeepseekV3ForCausalLM(deepseek_v3_tiny(
            dtype=jnp.float32, first_k_dense_replace=dense))
        assert [n for _, n in m.block_groups()] == layers
