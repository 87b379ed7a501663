"""The benchmark's own checks (ISSUE 26): nothing here needs a chip or
describes a topology, and nothing happens at import time.

The arithmetic against hand-worked numbers, the trace reduction on a
small synthetic trace, every name in BENCHMARK.json resolving to files,
the command refusing to run without a TPU — and the comparison that
decides `correct`, at a size a test run can hold: the float32 references
against the program at tiny size, the control (the reference in fp8 in
the program's place) and each planted fault coming out as not correct
through the harness's own `run_cell`.
"""
import copy
import json
import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cells, check, data, flops, peaks  # noqa: E402
from benchmarks.harness import trace as tr  # noqa: E402

GPT_MIX = {"task": "causal_lm", "batch": 4, "seq": 64,
           "lengths": {"lo": 1.0, "hi": 1.0}, "pool_batches": 6}
BERT_MIX = {"task": "mlm_nsp", "batch": 4, "seq": 64,
            "lengths": {"lo": 0.7, "hi": 1.0}, "max_predictions": 10,
            "mask_fraction": 0.15, "pool_batches": 6}
# limits for the tiny CPU sizes, set as the cells' are, from readings at
# these sizes on this CPU (a dozen seeds of the program, four of the
# control and of the half-batch fault). gpt: sound runs read loss 2.2e-5,
# grad 6.8e-3, change 1.43e-2 at worst; the fp8 control 5.1e-5, 1.2e-2,
# 2.15e-2 at least; half a batch 1.0e-3, 8.4e-2, 8.0e-2. bert: sound runs
# 2.9e-5, 1.23e-3, 1.36e-2; the control 2.0e-4, 1.37e-2, 1.87e-2; half a
# batch 3.6e-3, 0.62, 7.5e-2. At two layers of width 64 the control
# stands nearer to the program than at the cells' sizes.
TINY_LIMITS = {
    "gpt": {"loss_gap": {"limit": 2e-4}, "grad_gap": {"limit": 0.0095},
            "change_gap": {"limit": 0.018}},
    "bert": {"loss_gap": {"limit": 8e-5}, "grad_gap": {"limit": 0.004},
             "change_gap": {"limit": 0.03}},
}


_ONE = {"losses": [1.0], "grad": {"a": 1.0}, "change": {"a": 1.0}}
NUMBERS = set(check.gaps(_ONE, _ONE))      # what `check` compares


def tiny(name: str) -> dict:
    """A cell's spec with the configuration cut to a CPU test's size;
    "<config>-4chip" is the four-chip cell's layout on four of the
    virtual CPU devices that tests/conftest.py makes."""
    chips = 4 if name.endswith("-4chip") else 1
    config = cells.load_json("configs", name.removesuffix("-4chip") + ".json")
    if config["family"] == "gpt":
        config.update(n_layer=2, n_embd=64, n_head=4, n_positions=64,
                      n_ctx=64, vocab_size=500, padded_vocab_size=512)
        config["step"]["loss_chunks"] = 4
        mix = GPT_MIX
        if chips == 4:
            mix = dict(GPT_MIX, batch=8, reference_blocks=4)
    else:
        config.update(hidden_size=64, num_hidden_layers=2,
                      num_attention_heads=4, intermediate_size=256,
                      max_position_embeddings=64, vocab_size=500,
                      padded_vocab_size=512)
        mix = BERT_MIX
    config["step"].update(config["layouts"][str(chips)])
    bench = cells.benchmark()
    return {"cell": {"chips": chips}, "config": config,
            "mix": dict(mix, trace_steps=3),
            "limits": copy.deepcopy(TINY_LIMITS[config["family"]]),
            "end_to_end": bench["end_to_end"],
            "per_layer": bench["per_layer"]}


def run_tiny(name, seed=7, trace=False, wrap_step=None, seconds=0.2):
    import jax
    from benchmarks.harness import loop
    spec = tiny(name)
    return loop.run_cell(spec, seed, seconds, trace,
                         jax.devices()[:spec["cell"]["chips"]],
                         peaks.peaks("TPU v5 lite"),
                         time.perf_counter(), wrap_step=wrap_step)


# ------------------------------------------------------------ arithmetic

def test_gpt2_medium_flops_by_hand():
    from benchmarks.families import gpt_reference
    config = cells.load_json("configs", "gpt2-medium.json")
    mix = cells.load_json("traffic", "pretrain-s1024.json")
    stats = data.batch_stats(mix)
    assert stats["tokens"] == 8192
    # 6 x (24 x (4 x 1024^2 + 2 x 1024 x 4096) + 50304 x 1024) per token
    matmul = 6 * (24 * (4 * 1024**2 + 2 * 1024 * 4096) + 50304 * 1024)
    full = (matmul + 12 * 24 * 1024 * 1024) * 8192      # PR 23's count
    half = (matmul + 6 * 24 * 1024 * 1024) * 8192       # causal at half
    assert abs(full / 1e12 - 19.85) < 0.01
    got = gpt_reference.counts(config, stats)
    assert got["step_flops"] == pytest.approx(half, rel=1e-12)
    assert abs(got["step_flops"] / 1e12 - 18.61) < 0.01
    # kernels: forward 4 d pairs a layer, x 3.5 with the backward
    pairs = 8 * 1024 * 1024 / 2
    assert got["attention"]["flops"] == pytest.approx(
        24 * 3.5 * 4 * 1024 * pairs)
    assert got["attention"]["bytes"] == 24 * 12 * 8192 * 1024 * 2
    least, bound = flops.least_seconds(got["attention"],
                                       peaks.peaks("TPU v5 lite"))
    assert bound == "flops" and least == pytest.approx(
        got["attention"]["flops"] / 197e12)


def test_bert_large_counts_real_tokens_only():
    from benchmarks.families import bert_reference
    config = cells.load_json("configs", "bert-large.json")
    mix = cells.load_json("traffic", "pretrain-s512.json")
    stats = data.batch_stats(mix)
    lengths = stats["lengths"]
    assert len(lengths) == 16 and lengths.min() == 358 \
        and lengths.max() == 512
    assert stats["tokens"] == lengths.sum() < 16 * 512
    block = 24 * (4 * 1024**2 + 2 * 1024 * 4096)
    want = (6 * block * stats["tokens"]
            + 12 * 24 * 1024 * float((lengths**2).sum())
            + 6 * (30528 * 1024 + 1024**2) * stats["predictions"]
            + 6 * (1024**2 + 2048) * 16)
    assert bert_reference.counts(config, stats)["step_flops"] == \
        pytest.approx(want, rel=1e-12)
    # every seed sees the same sizes: a rate compares across seeds
    for seed in (1, 2**31 + 11):
        pool = data.make_pool(dict(mix, pool_batches=2), 30522, seed)
        for b in pool:
            assert sorted(b["valid"].sum(1)) == sorted(lengths)
            assert (b["mlm_labels"] >= 0).sum() == stats["predictions"]
            assert b["ids"].max() < 30522


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no peaks on record"):
        peaks.peaks("TPU v9 imaginary")


# ------------------------------------------------------- trace reduction

WHILE = "%while.3 = (s32[]{:T(128)}, bf16[8,64]{1,0:T(8,128)(2,1)}) while(%t)"
FUSION = "%fusion.12 = bf16[8,64]{1,0:T(8,128)(2,1)S(1)} fusion(%p), kind=kLoop"
KERNEL = ("%checkpoint.4 = (bf16[8,64]{1,0:T(8,128)(2,1)}) custom-call(%q), "
          "custom_call_target=\"tpu_custom_call\"")
ALLOC = "%custom-call.9 = bf16[8]{0} custom-call(), custom_call_target=\"AllocateBuffer\""
TAIL = "%fusion.7 = f32[8]{0:T(128)} fusion(%g), kind=kOutput"
GATHER = ("%all-gather-start.3 = (bf16[8]{0}, bf16[16]{0}) "
          "all-gather-start(%w), dimensions={0}")


def synthetic_trace():
    # two runs of the step program, 0.0-1.0 and 1.5-2.5, named as this
    # runtime names them (an op by its instruction's whole text); in each
    # a while loop that holds a fusion, a Pallas kernel and a collective,
    # a buffer allocation that is a custom call and no kernel, a fusion
    # outside
    ops = []
    for t in (0.0, 1.5):
        ops += [(t, t + 0.8, WHILE), (t + 0.1, t + 0.3, FUSION),
                (t + 0.3, t + 0.7, KERNEL), (t + 0.7, t + 0.75, GATHER),
                (t + 0.8, t + 0.8, ALLOC),
                (t + 0.9, t + 1.0, TAIL)]
    modules = [(0.0, 1.0, "jit__unknown(123)"),
               (1.5, 2.5, "jit__unknown(123)"), (3.0, 3.1, "jit_norms(9)")]
    spans = [(0.95, 1.2, "feed"), (1.2, 1.45, "dispatch"),
             (1.45, 2.5, "wait")]
    return {"devices": {0: {"ops": ops, "modules": modules}},
            "spans": spans}


def test_trace_reduction_on_a_synthetic_trace():
    t = synthetic_trace()
    s = tr.summary(t)        # the heaviest module is the step program
    assert s["steps"] == 2 and s["window_s"] == pytest.approx(2.5)
    # busy: 0-0.8, 0.9-1.0, 1.5-2.3, 2.4-2.5
    assert s["busy_s"] == pytest.approx(1.8)
    gaps = tr.idle_gaps(t["devices"][0]["ops"], 0.0, 2.5)
    assert gaps[0] == pytest.approx((1.0, 1.5))
    assert s["idle_gaps"][0][0] == "dispatch"      # 0.25 s of the 0.5
    assert s["idle_gaps"][0][1] == pytest.approx(0.5)
    kinds = dict(s["device_ops"])
    # the while keeps only what its body does not cover
    assert kinds["while while (s32[], bf16[8,64])"] == \
        pytest.approx(2 * (0.8 - 0.65))
    assert kinds["checkpoint custom-call (bf16[8,64])"] == pytest.approx(0.8)
    assert kinds["fusion fusion bf16[8,64]"] == pytest.approx(0.4)
    assert kinds["fusion fusion f32[8]"] == pytest.approx(0.2)
    assert sum(kinds.values()) == pytest.approx(s["busy_s"])

    from benchmarks.readers import device_idle_share, kernel_roofline, \
        module_gap_max, op_share
    ctx = {"trace": t, "summary": s, "chips": 1,
           "peak": peaks.peaks("TPU v5 lite"),
           "counts": {"attention": {"flops": 0.2 * 197e12, "bytes": 1.0}}}
    assert device_idle_share.read(ctx, {}) == pytest.approx(28.0)
    assert module_gap_max.read(ctx, {}) == pytest.approx(500.0)
    _, params = cells.reader("flash_attn_roofline")
    # 0.4 s of kernel a step against 0.2 s at the peak; the buffer
    # allocation is a custom call too and is not counted
    assert kernel_roofline.read(ctx, params) == pytest.approx(50.0)
    _, params = cells.reader("collective_share")
    assert op_share.read(ctx, params) == pytest.approx(100 * 0.1 / 2.5)
    assert op_share.read(ctx, {"pattern": "all-to-all"}) is None
    # a cell that takes another path has nothing to read: no zero
    assert kernel_roofline.read(
        ctx, {"pattern": "no-such-kernel", "cost": "attention"}) is None
    assert device_idle_share.read({"summary": {}}, {}) is None


# ------------------------------------------------------------ the files

def test_every_name_resolves_to_files():
    bench = cells.benchmark()
    assert bench["command"] == ["python3", "benchmarks/run.py"]
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, path))
    names = {c["name"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert cell["config"] in names
        spec = cells.resolve(cell["name"], bench)
        adapter, reference = cells.family(spec["config"])
        assert callable(adapter.build) and callable(reference.loss)
        assert {m["name"] for m in spec["end_to_end"]} >= {
            "setup_s", "tokens_per_s_per_chip"}
        assert spec["per_layer"]
        for m in spec["per_layer"]:
            read, params = cells.reader(m["name"])
            assert callable(read)
        held = {k for k, v in spec["limits"].items()
                if isinstance(v, dict) and v.get("limit") is not None}
        assert held and held <= NUMBERS
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as f:
            held = json.load(f)
        assert held["source"] == c["source"]
        assert held["reduced"] == c["reduced"]


def test_the_command_refuses_to_run_without_a_tpu():
    bench = cells.benchmark()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        bench["command"] + ["--workload", bench["workloads"][0]["name"],
                            "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


# ------------------------------------------- what decides `correct`

@pytest.mark.parametrize("name", ["gpt2-medium", "bert-large",
                                  "gpt2-medium-4chip"])
def test_program_agrees_with_its_reference(name):
    out = run_tiny(name, trace=True)
    assert out["correct"], out["compared"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["compared"]["compiles_in_window"]["value"] == 0
    # off the chip no device plane exists: trace readers stay silent,
    # and nothing reads 0
    assert "flash_attn_roofline" not in out["metrics"]
    assert out["metrics"]["host_feed_ms"]["value"] > 0
    assert list(out)[-1] == "compared"


@pytest.mark.parametrize("name", ["gpt2-medium", "bert-large"])
def test_control_in_fp8_is_not_correct(name):
    from benchmarks.harness import reference_train
    spec = tiny(name)
    _, reference = cells.family(spec["config"])
    pool = data.make_pool(spec["mix"], spec["config"]["vocab_size"], 5)[:3]
    ref = reference_train.run(reference, spec["config"], pool, 5)
    control = reference_train.run(reference, spec["config"], pool, 5,
                                  precision="fp8")
    ok, compared = check.compare(control, ref, spec["limits"])
    assert not ok, compared
    same, compared = check.compare(ref, ref, spec["limits"])
    assert same and all(c["value"] == 0 for c in compared.values())


def test_reference_in_blocks_of_rows_is_the_reference():
    """One chip's reference takes the four-chip cell's batch in blocks of
    rows, one after the other; over several devices each takes a share.
    Full rows, so every split gives the batch's own mean."""
    import jax
    from benchmarks.harness import reference_train
    spec = tiny("gpt2-medium-4chip")
    _, reference = cells.family(spec["config"])
    pool = data.make_pool(spec["mix"], spec["config"]["vocab_size"], 3)[:2]
    whole = reference_train.run(reference, spec["config"], pool, 3)
    for blocks, devices in ((4, None), (4, jax.devices()[:2])):
        split = reference_train.run(reference, spec["config"], pool, 3,
                                    blocks=blocks, devices=devices)
        got = {k: v for k, (v, _) in check.gaps(split, whole).items()}
        assert got["loss_gap"] < 1e-6 and got["grad_gap"] < 1e-5, got


def frozen_state(step):
    """A step that returns its state unchanged."""
    import jax

    def broken(state, batch):
        kept = jax.tree.map(lambda a: a.copy(), state)
        _, loss = step(state, batch)
        return kept, loss
    return broken


def half_batch(step):
    """Half of the batch left out, the mean taken over the rest."""
    import jax

    def broken(state, batch):
        return step(state, jax.tree.map(lambda a: a[:a.shape[0] // 2],
                                        batch))
    return broken


def no_exchange(step):
    """The exchange between chips left out, as the first data shard sees
    it: its own rows stand for every shard's, so the gradient it applies
    is that of its rows alone."""
    import jax

    def broken(state, batch):
        return step(state, jax.tree.map(
            lambda a: jax.device_put(
                jax.numpy.concatenate([a[:a.shape[0] // 2]] * 2),
                a.sharding), batch))
    return broken


@pytest.mark.parametrize("name,fault", [
    ("gpt2-medium", frozen_state), ("gpt2-medium", half_batch),
    ("bert-large", frozen_state), ("bert-large", half_batch),
    ("gpt2-medium-4chip", no_exchange)])
def test_a_broken_timed_path_is_not_correct(name, fault):
    out = run_tiny(name, wrap_step=fault)
    assert not out["correct"], out["compared"]
    failed = [k for k, c in out["compared"].items()
              if c["limit"] is not None and not c["value"] <= c["limit"]]
    assert set(failed) & NUMBERS, out["compared"]
