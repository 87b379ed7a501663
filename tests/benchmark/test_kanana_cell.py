"""The kanana cell's comparison at a size a test run can hold (ISSUE 33):
the program at tiny size agrees with its float32 reference through the
harness's own `run_cell`; the control (the reference in fp8 in the
program's place) and the half-batch fault come out as not correct; the
cell's files resolve by name with no edit to the harness; `counts` is a
hand count at tiny size. Nothing here needs a chip."""
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

CELL = "kanana-2-30b-a3b-instruct-2601.pretrain-s8192-fresh"
MIX = {"task": "causal_lm", "batch": 4, "seq": 64,
       "lengths": {"lo": 1.0, "hi": 1.0}, "pool_batches": 6,
       "reference_blocks": 2, "trace_steps": 3}
# limits for the tiny CPU size, set as the cell's are: between readings at
# this size on this CPU, seeds 1 to 8 of the program and 1 to 4 of the
# control and of the half-batch fault (my CPU run, PR 33; least .. most):
#                   program            control_fp8        fault_half_batch
#   loss_gap        3.3e-6 .. 1.5e-5   4.4e-5 .. 6.0e-5   1.4e-3 .. 2.9e-3
#   grad_gap        0.0007 .. 0.0055   0.0103 .. 0.0265   0.45 .. 0.71
#   grad_scale_gap  0 .. 6.6e-5        0.0029 .. 0.0038   0.38 .. 0.46
#   grad_gap_p75    0.00013 .. 0.00029 0.0025 .. 0.0040   0.039 .. 0.056
#   change_gap      0.0005 .. 0.0032   0.0058 .. 0.0125   0.150 .. 0.160
# At this size `grad_scale_gap` tells bf16 from fp8 by 44x between the
# readings and `grad_gap_p75` by 8.6x; `change_gap`'s room is 1.8x.
TINY_LIMITS = {"loss_gap": {"limit": 2.6e-5}, "grad_gap": {"limit": 7.5e-3},
               "grad_scale_gap": {"limit": 4e-4},
               "grad_gap_p75": {"limit": 8.5e-4},
               "change_gap": {"limit": 4.3e-3}}


def tiny() -> dict:
    """The cell's spec with the configuration cut to a CPU test's size:
    hidden 64, one dense layer and two expert layers, 8 experts / 4 held
    from the third / top 2 under a choice bias ten times the cell's,
    scores 24 wide over values of 16."""
    spec = cells.resolve(CELL)
    config = spec["config"]
    config.update(hidden_size=64, intermediate_size=96,
                  moe_intermediate_size=32, num_hidden_layers=3,
                  num_attention_heads=4, kv_lora_rank=32,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                  n_routed_experts=4, num_experts_per_tok=2, vocab_size=256,
                  expert_offset=2, choice_bias_range=0.05)
    config["published"].update(n_routed_experts=8, vocab_size=512)
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
    assert adapter.__name__.endswith("families.deepseek_v3")
    assert reference.__name__.endswith("families.deepseek_v3_reference")
    assert spec["cell"]["chips"] == 1
    names = {m["name"] for m in spec["per_layer"]}
    assert names == {"mla_attn_ms", "mla_attn_roofline", "moe_experts_ms",
                     "moe_product_runs", "step_mfu", "host_feed_ms",
                     "step_gap_ms_max", "device_idle_share",
                     "setup_import_s", "setup_model_s", "setup_build_s",
                     "setup_compile_s"}
    assert {m["name"] for m in spec["end_to_end"]} == {
        "setup_s", "tokens_per_s_per_chip", "step_ms_p90"}
    counts = reference.counts(spec["config"], data.batch_stats(spec["mix"]))
    for m in spec["per_layer"]:
        read, params = cells.reader(m["name"])
        if "cost" in params:
            assert set(counts[params["cost"]]) == {"flops", "bytes"}
    # every width as published; the cut is depth, experts held, vocabulary
    config = spec["config"]
    published = {
        "hidden_size": 2048, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_attention_heads": 32,
        "num_key_value_heads": 32, "head_dim": 64, "kv_lora_rank": 512,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "v_head_dim": 128, "n_shared_experts": 2,
        "num_experts_per_tok": 6, "first_k_dense_replace": 1,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
        "rope_theta": 1000000, "rope_interleave": True,
        "rope_scaling": None, "rms_norm_eps": 1e-06,
        "max_position_embeddings": 32768, "model_type": "deepseek_v3"}
    assert {k: config[k] for k in published} == published
    assert sorted(config["reduced"]) == sorted(config["published"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 128,
                                   "vocab_size": 128256}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (6, 16, 16032)
    mix = spec["mix"]
    assert (mix["batch"], mix["seq"], mix["pool_batches"],
            mix["trace_steps"], mix["reference_blocks"]) == (2, 8192, 128,
                                                             4, 2)
    # every limit lies between its two readings
    for name, lim in spec["limits"].items():
        if isinstance(lim, dict):
            assert lim["lower"] < lim["limit"] < lim["upper"], name


def test_counts_agree_with_a_hand_count_at_tiny_size():
    spec = tiny()
    _, reference = cells.family(spec["config"])
    stats = data.batch_stats(spec["mix"])
    assert (stats["tokens"], stats["rows"]) == (256, 256)
    got = reference.counts(spec["config"], stats)
    # by hand: attention's four products 64x96 + 64x40 + 32x128 + 64x64 =
    # 16896 parameters a layer, three layers; layer 0's MLP 3 x 64 x 96;
    # two expert layers of router 64 x 8 + shared 3 x 64 x 64; the head
    # 64 x 256; all at 6 x parameters x 256 tokens
    dense = 3 * 16896 + 18432 + 2 * (512 + 12288)
    # an expert is 3 x 64 x 32 = 6144; balanced rows 256 x 2 x 4 / 8
    experts = 2 * 6.0 * 6144 * 256
    # 4 rows of 64: 4 x 64 x 65 / 2 causal pairs; 3.5 x 2 x (24 + 16) a
    # pair a head a layer, 4 heads, 3 layers
    attention = 3 * 3.5 * 2 * 40 * 4 * (4 * 64 * 65 / 2)
    assert got["rows_held"] == 256
    assert got["experts"]["flops"] == experts
    assert got["latent_attention"]["flops"] == attention
    assert got["step_flops"] == pytest.approx(
        6.0 * (dense + 64 * 256) * 256 + experts + attention, rel=1e-12)
    # q and dq three times at 4 x 24; k_nope three times at 4 x 16; the
    # rotary key three times at ONE head of 8; v, o, do, dv six times at
    # 4 x 16; bf16, 256 rows, three layers
    assert got["latent_attention"]["bytes"] == \
        3 * 256 * (3 * 96 + 3 * 64 + 3 * 8 + 6 * 64) * 2


def test_reference_imports_nothing_of_the_program():
    import ast
    path = os.path.join(REPO, "benchmarks", "families",
                        "deepseek_v3_reference.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    mods = {n.module for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)}
    mods |= {a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names}
    assert mods <= {"__future__", "math", "jax", "jax.numpy"}, mods


def test_program_agrees_with_its_reference():
    out = run_tiny(trace=True)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["compared"]["compiles_in_window"]["value"] == 0
    # off the chip no device plane exists: trace readers stay silent
    assert "mla_attn_roofline" not in out["metrics"]
    assert "mla_attn_ms" not in out["metrics"]
    assert out["metrics"]["host_feed_ms"]["value"] > 0
    assert out["metrics"]["setup_model_s"]["value"] > 0
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
