"""The Trinity-Mini cell's comparison at a size a test run can hold: the
program at tiny size agrees with its float32 reference through the
harness's own `run_cell`; the control (the reference in fp8 in the
program's place) and the half-batch fault come out as not correct; the
cell's files resolve by name with no edit to the harness, and its
per-layer metrics include the window's; `counts` is a hand count at tiny
size. Nothing here needs a chip."""
import copy
import os
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cells, check, data, peaks  # noqa: E402

CELL = "trinity-mini.pretrain-s8192-fresh"
MIX = {"task": "causal_lm", "batch": 4, "seq": 64,
       "lengths": {"lo": 1.0, "hi": 1.0}, "pool_batches": 6,
       "reference_blocks": 2, "trace_steps": 3}
# limits for the tiny CPU size, set as the cell's are: between readings at
# this size on this CPU, seeds 1 to 8 of the program and 1 to 4 of the
# control and of the half-batch fault (least .. most):
#                   program            control_fp8        fault_half_batch
#   loss_gap        1.4e-5 .. 4.1e-5   9.2e-5 .. 2.0e-4   1.2e-3 .. 2.3e-3
#   grad_gap        0.0015 .. 0.0086   0.0122 .. 0.0184   0.459 .. 0.527
#   grad_scale_gap  0 .. 1.8e-4        1.1e-4 .. 3.2e-3   0.394 .. 0.426
#   grad_gap_p75    2.3e-4 .. 6.3e-4   2.9e-3 .. 3.6e-3   0.021 .. 0.026
#   change_gap      0.0035 .. 0.066    0.0108 .. 0.0145   0.149 .. 0.160
# At this size `grad_gap_p75` tells bf16 from fp8 by 4.6x between the
# readings and `loss_gap` by 2.3x; `grad_scale_gap` and `change_gap` are
# held between the program and the fault (one program seed reads a
# change_gap of 0.066, over every control's).
TINY_LIMITS = {"loss_gap": {"limit": 6.5e-5}, "grad_gap": {"limit": 0.0105},
               "grad_scale_gap": {"limit": 0.02},
               "grad_gap_p75": {"limit": 1.3e-3},
               "change_gap": {"limit": 0.1}}


def tiny() -> dict:
    """The cell's spec with the configuration cut to a CPU test's size:
    hidden 64, the cell's five layers (dense sliding, then expert layers
    sliding, full, sliding, sliding), 8 experts / 4 held from the third /
    top 2 under a choice bias ten times the cell's, 4 query heads over 2
    key/value heads of 16, a window of 16 over rows of 64."""
    spec = cells.resolve(CELL)
    config = spec["config"]
    config.update(hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_attention_heads=4,
                  num_key_value_heads=2, head_dim=16, sliding_window=16,
                  num_experts=4, num_experts_per_tok=2, vocab_size=256,
                  expert_offset=2, choice_bias_range=0.05)
    config["published"].update(num_experts=8, vocab_size=512)
    config["step"]["loss_chunks"] = 4
    return dict(spec, mix=dict(MIX), limits=copy.deepcopy(TINY_LIMITS))


def run_tiny(seed=7, trace=False, wrap_step=None, seconds=0.2):
    import jax
    from benchmarks.harness import loop
    return loop.run_cell(tiny(), seed, seconds, trace, jax.devices()[:1],
                         peaks.peaks("TPU v5 lite"), time.perf_counter(),
                         wrap_step=wrap_step)


def test_the_cell_resolves_by_name_and_reports_its_metrics():
    spec = cells.resolve(CELL)
    adapter, reference = cells.family(spec["config"])
    assert adapter.__name__.endswith("families.afmoe")
    assert reference.__name__.endswith("families.afmoe_reference")
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    # a subset: metrics that later cells add may be listed here too
    assert names >= {"win_attn_ms", "win_attn_roofline", "full_attn_ms",
                     "moe_experts_ms", "moe_rows_routed",
                     "moe_tail_rows_ratio", "step_mfu", "setup_model_s"}
    assert not names & {"mla_attn_ms", "sel_attn_ms", "flash_fwd_ms"}
    assert {m["name"] for m in spec["end_to_end"]} == {
        "setup_s", "tokens_per_s_per_chip", "step_ms_p90"}
    counts = reference.counts(spec["config"], data.batch_stats(spec["mix"]))
    for m in spec["per_layer"]:
        read, params = cells.reader(m["name"])
        if "cost" in params:
            assert set(counts[params["cost"]]) == {"flops", "bytes"}
    # every width as published; the cut is depth, dense layers, experts
    # held, vocabulary
    config = spec["config"]
    published = {
        "hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048,
        "global_attn_every_n_layers": 4, "num_experts_per_tok": 8,
        "num_shared_experts": 1, "route_scale": 2.826, "route_norm": True,
        "score_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-05,
        "mup_enabled": True, "max_position_embeddings": 131072,
        "model_type": "afmoe", "tie_word_embeddings": False}
    assert {k: config[k] for k in published} == published
    assert len(config["layer_types"]) == 32
    assert sorted(config["reduced"]) == sorted(config["published"]) == [
        "num_dense_layers", "num_experts", "num_hidden_layers",
        "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 32,
                                   "num_dense_layers": 2,
                                   "num_experts": 128,
                                   "vocab_size": 200192}
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 16, 25024)
    assert reference.layer_types(config) == [
        "sliding_attention", "sliding_attention", "full_attention",
        "sliding_attention", "sliding_attention"]
    assert config["deployment"]["chips_sharing_a_layer"] == 8
    mix = spec["mix"]
    assert (mix["batch"], mix["seq"], mix["pool_batches"],
            mix["trace_steps"]) == (2, 8192, 128, 4)
    # every limit lies between its two readings
    for name, lim in spec["limits"].items():
        if isinstance(lim, dict) and lim.get("limit") is not None:
            assert lim["lower"] < lim["limit"] < lim["upper"], name


def test_counts_agree_with_a_hand_count_at_tiny_size():
    spec = tiny()
    _, reference = cells.family(spec["config"])
    stats = data.batch_stats(spec["mix"])
    assert (stats["tokens"], stats["rows"]) == (256, 256)
    got = reference.counts(spec["config"], stats)
    # by hand: attention's five products q 64x64, k 64x32, v 64x32, gate
    # 64x64, o 64x64 = 16384 parameters a layer, five layers; layer 0's
    # MLP 3 x 64 x 96; four expert layers of router 64 x 8 + shared
    # 3 x 64 x 32; the head 64 x 256; all at 6 x parameters x 256 tokens
    dense = 5 * 16384 + 18432 + 4 * (512 + 6144)
    # an expert is 3 x 64 x 32 = 6144; balanced rows 256 x 2 x 4 / 8
    experts = 4 * 6.0 * 6144 * 256
    # 4 rows of 64 under a window of 16: 16 x 17 / 2 + 48 x 16 = 904 pairs
    # a row; the causal triangle 64 x 65 / 2 = 2080; 3.5 x 4 x 4 heads x
    # 16 a pair; four sliding layers and one full
    per_pair = 3.5 * 4 * 4 * 16
    window = 4 * per_pair * 4 * 904
    full = 1 * per_pair * 4 * 2080
    assert got["rows_held"] == 256
    assert got["experts"]["flops"] == experts
    assert got["window_attention"]["flops"] == window
    assert got["full_attention"]["flops"] == full
    assert got["step_flops"] == pytest.approx(
        6.0 * (dense + 64 * 256) * 256 + experts + window + full,
        rel=1e-12)
    # q, o, dO, dq at 4 x 16 and k, v, dk, dv at 2 x 16, each twice
    # (forward and backward), bf16, 256 rows
    per_row = 2 * (4 * 64 + 4 * 32)
    assert got["window_attention"]["bytes"] == 4 * 256 * per_row
    assert got["full_attention"]["bytes"] == 1 * 256 * per_row


def test_the_band_needs_0_4375_of_the_causal_pairs_at_the_cell():
    spec = cells.resolve(CELL)
    _, reference = cells.family(spec["config"])
    counts = reference.counts(spec["config"], data.batch_stats(spec["mix"]))
    per_layer = (counts["window_attention"]["flops"] / 4) / \
        counts["full_attention"]["flops"]
    assert per_layer == pytest.approx(0.4375, rel=1e-3)


def test_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(REPO, "benchmarks", "families", "afmoe_reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert mods <= {"__future__", "math", "jax", "jax.numpy"}, mods


def test_adapter_imports_the_program_at_once():
    """A checkout without the model fails on this cell as it resolves its
    family, before any chip is asked for: the adapter imports
    `paddle_tpu.models.afmoe` at its top."""
    import ast
    path = os.path.join(REPO, "benchmarks", "families", "afmoe.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    top = {n.module for n in tree.body if isinstance(n, ast.ImportFrom)}
    assert "paddle_tpu.models.afmoe" in top


def test_program_agrees_with_its_reference():
    out = run_tiny(trace=True)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["compared"]["compiles_in_window"]["value"] == 0
    # off the chip no device plane exists: trace readers stay silent
    for name in ("win_attn_ms", "win_attn_roofline", "full_attn_ms"):
        assert name not in out["metrics"]
    assert out["metrics"]["host_feed_ms"]["value"] > 0
    assert out["metrics"]["moe_rows_routed"]["value"] > 0
    assert list(out)[-1] == "compared"


def test_control_in_fp8_is_not_correct():
    from benchmarks.harness import reference_train
    spec = tiny()
    _, reference = cells.family(spec["config"])
    pool = data.make_pool(spec["mix"], spec["config"]["vocab_size"], 5)[:3]
    ref = reference_train.run(reference, spec["config"], pool, 5, blocks=2)
    control = reference_train.run(reference, spec["config"], pool, 5,
                                  precision="fp8", blocks=2)
    ok, compared = check.compare(control, ref, spec["limits"])
    assert not ok, compared
    same, compared = check.compare(ref, ref, spec["limits"])
    assert same and all(c["value"] == 0 for c in compared.values())
    # the choice bias has no gradient and moves by weight decay alone;
    # layer 0's leaves are outer leaves of the record
    assert ref["grad"]["blocks.0.router.bias"] == 0.0
    assert ref["change"]["blocks.0.router.bias"] > 0.0
    assert ref["grad"]["dense.mlp.down"] > 0.0


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    import jax

    def broken(state, batch):
        return step(state, jax.tree.map(lambda a: a[:a.shape[0] // 2],
                                        batch))
    return broken


@pytest.mark.parametrize("fault", [half_batch])
def test_a_broken_timed_path_is_not_correct(fault):
    out = run_tiny(wrap_step=fault)
    assert not out["correct"], out["compared"]
    failed = [k for k, c in out["compared"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert failed, out["compared"]
