"""Keye-VL-2.0-30B-A3B's text decoder: the program's model against the
benchmark's plain reference on seeded weights at a tiny size (hidden 64,
2 layers, 8 experts / 4 held / top 2, top 16 keys at 64 positions, so
the selection bites), the indexer's exact top-k against a sort, and the
model through `build_train_step`, the builder GPT goes through."""
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
from benchmarks.families import keye as adapter  # noqa: E402
from benchmarks.families import keye_reference as ref  # noqa: E402
from benchmarks.harness import cells, reference_train  # noqa: E402
from benchmarks.harness import weights as wt  # noqa: E402
from paddle_tpu.distributed import build_mesh  # noqa: E402
from paddle_tpu.models import GPTForPretraining, KeyeForCausalLM  # noqa
from paddle_tpu.models.gpt import gpt_tiny  # noqa: E402
from paddle_tpu.trainer import (build_train_step,  # noqa: E402
                                sync_params_to_model)
from paddle_tpu.nn import functional as F  # noqa: E402
from paddle_tpu.nn.layer import functional_call, trainable_state  # noqa
from paddle_tpu.ops import flash_attention as fa  # noqa: E402
from paddle_tpu.ops import index_select as ix  # noqa: E402

SEQ = 64


def tiny_config(**over) -> dict:
    config = cells.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    config.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, num_experts=8,
                  num_local_experts=4, num_experts_per_tok=2,
                  moe_intermediate_size=32, vocab_size=256, expert_offset=2)
    config["sa_config"].update(indexer_num_heads=2, indexer_head_dim=8,
                               topk=16)
    config["published"]["vocab_size"] = 512
    config["step"]["compute_dtype"] = "float32"
    config.update(over)
    return config


def seeded(config, seed=3):
    """The reference's draw, made harder: norm scales off 1 and matrices
    five times as large, so that routing and selection are far from
    uniform."""
    key = jax.random.key(seed)
    w = ref.init_weights(config, key)
    out = {}
    for i, (k, v) in enumerate(w.items()):
        if "norm" in k or "ln" in k:
            v = v + 0.1 * jax.random.normal(jax.random.fold_in(key, i),
                                            v.shape)
        elif v.ndim >= 2 and k != "embed":
            v = v * 5
        out[k] = v
    return out


def program(config, w):
    model = KeyeForCausalLM(adapter.program_config(config))
    names = wt.layer_names(adapter.OUTER, adapter.BLOCK,
                           config["num_hidden_layers"], "model.layers")
    wt.load(model, w, names)
    return model, names


def batch(config, b=2, seed=0):
    rs = np.random.RandomState(seed)
    return {k: jnp.asarray(rs.randint(1, config["vocab_size"], (b, SEQ)),
                           jnp.int32) for k in ("ids", "labels")}


def test_model_agrees_with_the_reference_on_logits_loss_and_every_leaf():
    config = tiny_config()
    w = seeded(config)
    model, names = program(config, w)
    bt = batch(config)
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
    l2, g2 = jax.value_and_grad(
        lambda w: ref.loss(config, w, bt, mm))(w)
    assert float(l1) == pytest.approx(float(l2), rel=1e-5)
    assert set(names) == set(reference_train.flatten_norms(
        jax.device_get(reference_train.leaf_norms(w))))
    for leaf, name in names.items():
        parts = leaf.split(".")
        r = (g2["blocks." + ".".join(parts[2:])][int(parts[1])]
             if parts[0] == "blocks" else g2[leaf])
        scale = max(float(jnp.abs(r).max()), 1e-3)
        assert float(jnp.abs(g1[name] - r).max()) <= 2e-4 * scale, leaf
        if ".idx_" in leaf:
            # the selection carries no gradient: the indexer's leaves
            # move by weight decay alone
            assert float(jnp.abs(r).max()) == 0.0 == \
                float(jnp.abs(g1[name]).max()), leaf


def test_shares_of_one_layer_add_up_to_the_uncut_layer():
    """The guide's shares test: two chips that each hold four of the
    eight experts give partial expert sums that add up to the layer
    with all eight held; attention is whole on every chip."""
    whole = tiny_config(num_local_experts=8, expert_offset=0,
                        num_hidden_layers=1)
    w = seeded(whole)
    z = ref.sizes(whole)
    p = {k[7:]: v[0] for k, v in w.items() if k.startswith("blocks.")}
    x = jax.random.normal(jax.random.key(1), (2, SEQ, 64))
    mm = reference_train.matmul_f32
    with jax.default_matmul_precision("highest"):
        want = ref.layer(z, p, x, mm)
        h = x + ref.attention(z, p, ref.rms_norm(x, p["ln1.w"], z["eps"]),
                              mm)
        total = h
        for off in (0, 4):
            cfg = adapter.program_config(
                tiny_config(num_local_experts=4, expert_offset=off,
                            num_hidden_layers=1))
            model = KeyeForCausalLM(cfg)
            cut = {k: (v[off:off + 4] if k.startswith("experts.") else v)
                   for k, v in p.items()}
            (block, _), = model.block_groups()
            params = {n: cut[c] for c, n in adapter.BLOCK.items()}
            y, _ = functional_call(block, params, x)
            # a share's output is h + its experts' sum: take h out
            total = total + (y - h)
            zc = dict(z, held=4, off=off)
            np.testing.assert_allclose(
                np.asarray(y), np.asarray(ref.layer(zc, cut, x, mm)),
                rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


TOPK_CASES = [
    (64, 64, 16, 0, "random"), (64, 64, 16, 0, "ties"),
    (64, 128, 16, 64, "ties"), (128, 256, 100, 128, "random"),
    (64, 64, 64, 0, "random"), (64, 64, 70, 0, "random"),
    (8, 8192, 2048, 8184, "ties"),
    # at shapes the kernel takes: t < topk in a block that also searches,
    # topk at and above the keys, an offset, three row blocks of which the
    # first keeps every key, the 8192-wide ties, and ties that crowd the
    # threshold in the second row block alone
    (128, 128, 16, 0, "random"), (128, 128, 16, 0, "ties"),
    (128, 128, 128, 0, "random"), (64, 128, 130, 64, "random"),
    (384, 384, 128, 0, "random"), (32, 8192, 2048, 8160, "ties"),
    (512, 512, 100, 0, "ties below"),
]


def kernel_takes(rows, s, t0):
    return rows % 32 == 0 and s % 128 == 0 and t0 + rows <= s


@pytest.mark.parametrize("rows,s,topk,t0,kind,how", [
    case + (how,) for case in TOPK_CASES for how in ("xla", "kernel")
    if how == "xla" or kernel_takes(case[0], case[1], case[3])])
def test_exact_topk_against_a_sort(monkeypatch, rows, s, topk, t0, kind,
                                   how):
    """Ties to the smaller key, every key where t < topk, signed zeros
    one value: XLA's digit search and the kernel (interpreted), each
    against the reference's sort."""
    rs = np.random.RandomState(rows + topk)
    x = rs.randn(2, rows, s).astype(np.float32)
    if kind == "ties":
        x = np.round(x * 1.5)
    elif kind == "ties below":      # the kernel's second block of 256 rows
        x[:, 256:] = np.round(x[:, 256:] * 1.5)
    x[0, 3, ::2] = -0.0
    x[0, 3, 1::2] = 0.0
    if how == "kernel":
        monkeypatch.setattr(fa, "_interpret", lambda: True)
        got = np.asarray(jax.jit(
            lambda x: ix.index_topk(x, topk, t0))(jnp.asarray(x)))
    else:
        got = np.asarray(ix.select_topk(jnp.asarray(x), topk, t0))
    want = np.stack([np.asarray(ref.select(jnp.asarray(r), t0, topk))
                     for r in x])
    assert got.dtype == np.int8
    np.testing.assert_array_equal(got.astype(bool), want)
    t = t0 + np.arange(rows)
    np.testing.assert_array_equal(got.sum(-1)[0], np.minimum(t + 1, topk))


def test_index_scores_kernel_and_the_whole_selection(monkeypatch):
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(2, 512, 4, 64), jnp.float32)
    k = jnp.asarray(rs.randn(2, 512, 64), jnp.float32)
    w = jnp.asarray(rs.randn(2, 512, 4), jnp.float32)
    got, want = ix.index_scores(q, k, w), ix.index_scores_xla(q, k, w)
    tri = np.tril(np.ones((512, 512), bool))
    np.testing.assert_allclose(np.where(tri, got, 0), np.where(tri, want, 0),
                               rtol=1e-5, atol=1e-4)
    # tiles (256 x 256 at float32) above the diagonal are not computed
    assert float(jnp.abs(got[:, :256, 256:]).max()) == 0.0
    # the whole selection is one call of the top-k kernel: two row blocks
    # of 256, `topk` inside the first
    select = functools.partial(ix.topk_selection, topk=100)
    text = str(jax.make_jaxpr(select)(q, k, w))
    assert text.count("name=index_topk") == 1
    sel = select(q, k, w)
    ref_sel = np.stack([np.asarray(ref.select(want[i], 0, 100))
                        for i in range(2)])
    np.testing.assert_array_equal(np.asarray(sel).astype(bool), ref_sel)
    # and off the TPU XLA's blocks of rows give the same
    np.testing.assert_array_equal(
        np.asarray(sel), np.asarray(ix._select_blocks(got, 100, block=256)))


def test_rotary_and_selected_attention_against_the_reference():
    rs = np.random.RandomState(4)
    x = jnp.asarray(rs.randn(2, SEQ, 3, 16), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(F.rotary_embedding(x, 1e7)),
        np.stack([np.asarray(ref.rotary(r, 1e7)) for r in x]),
        rtol=1e-5, atol=1e-6)
    q = jnp.asarray(rs.randn(1, SEQ, 4, 16), jnp.float32)
    k, v = [jnp.asarray(rs.randn(1, SEQ, 2, 16), jnp.float32)
            for _ in range(2)]
    sel = ix.select_topk(jnp.asarray(rs.randn(1, SEQ, SEQ), jnp.float32), 8)
    got = F.selected_attention(q, k, v, sel)
    kk, vv = jnp.repeat(k, 2, 2), jnp.repeat(v, 2, 2)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / 4.0
    p = jax.nn.softmax(jnp.where(sel[:, None] > 0, sc, -jnp.inf), -1)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(jnp.einsum("bhqk,bkhd->bqhd", p, vv)),
        rtol=1e-5, atol=1e-5)


def test_flops_by_hand():
    config = cells.load_json("configs", "keye-vl-2.0-30b-a3b.json")
    mix = cells.load_json("traffic", "pretrain-s8192.json")
    from benchmarks.harness import data
    stats = data.batch_stats(mix)
    assert stats["tokens"] == 16384
    got = ref.counts(config, stats)
    # a query reads min(t + 1, 2048) keys: per row of 8192
    pairs = 2 * (2048 * 2049 // 2 + 6144 * 2048)
    assert pairs == 29362176
    sel = 6 * 3.5 * 4 * 4096 * pairs
    assert got["selected_attention"]["flops"] == pytest.approx(sel)
    assert sel / 1e13 == pytest.approx(1.01, abs=0.005)
    # weights with a gradient: q, o 2 x 2048 x 4096; k, v 2 x 2048 x 512;
    # router 2048 x 128; the held experts on 16384 x 8 x 16/128 rows
    dense = 2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128
    experts = 6 * 6 * (3 * 2048 * 768) * 16384
    assert got["experts"]["flops"] == pytest.approx(experts)
    assert got["rows_held"] == 16384
    weights = 6 * 6 * dense * 16384 + experts + 6 * 2048 * 18992 * 16384
    assert weights / 1e13 == pytest.approx(1.79, abs=0.005)
    # the indexer has no backward: projections and scores forward only
    index_proj = 6 * 2 * (2048 * 1024 + 2048 * 64 + 2048 * 16) * 16384
    index_scores = 6 * 2 * 16 * 64 * 2 * (8192 * 8193 // 2)
    assert index_proj / 1e11 == pytest.approx(4.4, abs=0.1)
    assert index_scores / 1e11 == pytest.approx(8.2, abs=0.1)
    assert got["step_flops"] == pytest.approx(
        weights + sel + index_proj + index_scores, rel=1e-12)
    assert got["step_flops"] / 1e13 == pytest.approx(2.93, abs=0.01)
    # the selection's bytes: once forward, twice backward
    assert got["selected_attention"]["bytes"] == 6 * (
        (6 * 32 + 6 * 4) * 16384 * 128 * 2 + 3 * 2 * 8192 * 8192)


def adamw():
    return pt.optimizer.AdamW(learning_rate=1e-3, weight_decay=0.01,
                              grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))


@pytest.mark.parametrize("policy", ["dots", "dots_sel", "full"])
def test_trains_through_build_train_step(policy):
    """The same builder and the same (state, batch) -> (state, loss) step
    as GPT: the loss is the eager model's and goes down."""
    config = tiny_config()
    model, _ = program(config, seeded(config))
    bt = batch(config, b=4)
    eager = float(model(bt["ids"], bt["labels"]))
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    step, state = build_train_step(model, adamw(), mesh,
                                   remat_policy=policy, loss_chunks=4)
    losses = []
    for _ in range(4):
        state, loss = step(state, (bt["ids"], bt["labels"]))
        losses.append(float(loss))
    assert losses[0] == pytest.approx(eager, rel=1e-4)
    assert losses[-1] < losses[0]
    assert "keye_train_step" in step.lower(
        state, (bt["ids"], bt["labels"])).as_text()[:200]


def test_the_builder_gives_up_the_eager_copy_and_sync_brings_it_back():
    config = tiny_config()
    model, _ = program(config, seeded(config))
    bt = batch(config, b=4)
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    step, state = build_train_step(model, adamw(), mesh, donate=False)
    blocks = [p for n, p in model.named_parameters() if ".layers." in n]
    assert blocks and all(p.value.is_deleted() for p in blocks)
    state, loss = step(state, (bt["ids"], bt["labels"]))
    sync_params_to_model(model, state)
    assert float(model(bt["ids"], bt["labels"])) < float(loss)


def test_builder_names_no_member_of_one_model():
    """One builder for every model that gives it its pieces."""
    import glob
    import paddle_tpu.trainer as trainer
    sources = glob.glob(os.path.join(os.path.dirname(trainer.__file__),
                                     "*.py"))
    assert len(sources) >= 5
    for path in sources:
        with open(path) as f:
            src = f.read()
        code = src[src.index('"""', src.index('"""') + 3):]   # past its docstring
        for member in ("model.gpt", "model.model", "lm_head",
                       "GPTForPretraining(", "KeyeForCausalLM("):
            assert member not in code, (path, member)
    for cls in (GPTForPretraining, KeyeForCausalLM):
        for piece in ("block_groups", "embed", "final_norm", "logits"):
            assert callable(getattr(cls, piece)), (cls, piece)
    model = GPTForPretraining(gpt_tiny(dtype=jnp.float32))
    assert model.block_groups() == [(model.gpt.layers[0], 4)]
