"""The five expert-layer metrics of PR 38 on synthetic step records and
windows: `step_counters` (rows routed, buffer fill, rounds),
`counted_roofline` and `tail_counter_ratio`, each from the program's
record (`paddle_tpu.profiler.step_records()`), the traced steps and the
window's steps told apart by the shared clock."""
from __future__ import annotations

import collections
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cells, data, flops  # noqa: E402
from benchmarks.harness import trace as tr  # noqa: E402
from benchmarks.readers import (counted_roofline, step_counters,  # noqa: E402
                                tail_counter_ratio)

KEYE = "keye-vl-2.0-30b-a3b.pretrain-s8192"
RAGGED = "%ragged-dot-none.3 = f32[24576,768]{1,0} custom-call(%a, %w)"


@pytest.fixture
def record(monkeypatch):
    """`add(begin_s, routed=[...], computed=[...], rounds=(whole, short),
    prefix="")` keeps one step in the program's record, its dispatch at
    `begin_s` on the window's clock."""
    from paddle_tpu import profiler
    monkeypatch.setattr(profiler, "_steps", collections.deque(maxlen=512))

    def add(begin_s, routed=None, computed=None, rounds=(0, 0), prefix=""):
        counters = {}
        if routed is not None:
            n = len(routed)
            counters = {
                prefix + "moe.routed": np.array(routed, np.int32),
                prefix + "moe.computed": np.array(
                    computed or [100] * n, np.int32),
                prefix + "moe.whole": np.full(n, rounds[0], np.int32),
                prefix + "moe.short": np.full(n, rounds[1], np.int32)}
        profiler.record_step(
            len(profiler._steps), int(begin_s * 1e9),
            int((begin_s + 1e-4) * 1e9),
            profiler.Counters.pack(counters) if counters else {})
    return add


def params(metric, reader):
    read, p = cells.reader(metric)
    assert read is reader.read
    return p


WINDOW = {"t0": 10.0, "t1": 20.0, "done": [11.0, 12.0, 13.0]}


def test_the_traced_steps_are_those_after_the_window(record):
    record(5.0, [999, 999])                  # a checked step, before it
    for t in (10.1, 11.1, 12.1):             # the window's
        record(t, [500, 500], rounds=(3, 3))
    record(21.0, [60, 40], computed=[100, 100], rounds=(1, 0))
    record(22.0, [80, 20], computed=[100, 100], rounds=(0, 2),
           prefix="g1.")                      # a second group's name
    ctx = {"window": WINDOW}
    assert step_counters.read(ctx, params(
        "moe_rows_routed", step_counters)) == pytest.approx(100.0)
    assert step_counters.read(ctx, params(
        "moe_buffer_fill", step_counters)) == pytest.approx(50.0)
    assert step_counters.read(ctx, params(
        "moe_rounds", step_counters)) == pytest.approx((2 + 4) / 2)
    assert "over 2 traced steps, by step [100, 100]" in ctx["notes"][0]


def test_nothing_to_read_returns_nothing(record, monkeypatch):
    for metric in ("moe_rows_routed", "moe_buffer_fill", "moe_rounds"):
        p = params(metric, step_counters)
        assert step_counters.read({"window": WINDOW}, p) is None  # no step
        record(21.0)                      # a step of a model that counts
        assert step_counters.read({"window": WINDOW}, p) is None  # nothing
        assert step_counters.read({}, p) is None
    # the parent of PR 38: a profiler with no record
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "step_records")
    record(22.0, [1, 2])
    assert step_counters.records() is None
    assert step_counters.read({"window": WINDOW}, params(
        "moe_rows_routed", step_counters)) is None
    assert tail_counter_ratio.read(
        {"window": WINDOW, "mix": {}},
        params("moe_tail_rows_ratio", tail_counter_ratio)) is None


def keye_ctx(ms_a_step, steps=2):
    """The Keye cell's context with a trace of `steps` step programs in
    which the grouped products take `ms_a_step` in three runs."""
    spec = cells.resolve(KEYE)
    config, mix = spec["config"], spec["mix"]
    _, reference = cells.family(config)
    stats = data.batch_stats(mix)
    ops, modules = [], []
    for i in range(steps):
        t = 30.0 + i
        modules.append((t, t + 0.9, "jit_keye_train_step(1)"))
        for k in range(3):
            ops.append((t + 0.1 * k, t + 0.1 * k + ms_a_step / 3e3, RAGGED))
    trace = {"devices": {0: {"ops": ops, "modules": modules}}, "spans": []}
    return {"trace": trace, "summary": tr.summary(trace), "window": WINDOW,
            "stats": stats, "config": config, "mix": mix, "chips": 1,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "counts": reference.counts(config, stats)}


def test_the_experts_roofline_takes_the_rows_really_routed(record):
    ctx = keye_ctx(120.0)
    balanced = ctx["counts"]["rows_held"]         # a layer, a step
    layers = 6
    record(30.0, [int(2 * balanced)] * layers)
    record(31.0, [int(2 * balanced)] * layers)
    got = counted_roofline.read(ctx, params("moe_experts_roofline",
                                            counted_roofline))
    # flops twice the balanced count's; of the bytes only the rows' double
    z = cells.family(ctx["config"])[1].sizes(ctx["config"])
    cost = dict(ctx["counts"]["experts"])
    cost["flops"] *= 2
    cost["bytes"] += layers * balanced * (4 * z["d"] + 6 * z["ff"]) * 2
    least, _ = flops.least_seconds(cost, ctx["peak"])
    assert got == pytest.approx(100.0 * least / 0.120, rel=1e-6)
    assert 0 < got < 100
    assert "2.000 x balanced" in ctx["notes"][0]
    # no product in the trace, or no record: nothing
    assert counted_roofline.read(keye_ctx(0.0), params(
        "moe_experts_roofline", counted_roofline)) is None
    from paddle_tpu import profiler
    profiler._steps.clear()
    assert counted_roofline.read(ctx, params(
        "moe_experts_roofline", counted_roofline)) is None


@pytest.mark.parametrize("k", [1, 2])
def test_the_tail_ratio_puts_the_slow_steps_down_to_their_rows(record, k):
    """Ten window steps, a second each but the two that routed twice the
    rows, which took two: the slow intervals carry 2x the median."""
    routed = [100] * 10
    routed[4] = routed[8] = 200
    done, t = [], 0.0
    for i, r in enumerate(routed):
        record(t + 0.01, [r // 2, r // 2])
        t += 2.0 if r == 200 else 1.0
        done.append(t)
    record(t + 5.0, [10_000, 10_000])              # traced: not the window's
    ctx = {"window": {"t0": 0.0, "t1": t + 0.5, "done": done},
           "mix": {"interval_steps": k}}
    p = params("moe_tail_rows_ratio", tail_counter_ratio)
    got = tail_counter_ratio.read(ctx, p)
    # k = 1: the two slow steps alone; k = 2: the intervals that hold one
    want = 2.0 if k == 1 else 1.5
    assert got == pytest.approx(want)
    # a window whose steps the record does not all hold: nothing
    ctx["window"]["done"] = done + [t + 1.0]
    assert tail_counter_ratio.read(ctx, p) is None
    assert tail_counter_ratio.read({}, p) is None


def test_an_even_tail_reads_one(record):
    done = []
    for i in range(20):
        record(i + 0.01, [50, 50])
        done.append(i + (1.1 if i % 7 == 3 else 1.0))
    ctx = {"window": {"t0": 0.0, "t1": 20.5, "done": done},
           "mix": {"interval_steps": 1}}
    assert tail_counter_ratio.read(ctx, params(
        "moe_tail_rows_ratio", tail_counter_ratio)) == pytest.approx(1.0)


def test_the_tiny_keye_cell_reports_the_counters_off_the_chip():
    """The whole run of the Keye cell at a CPU test's size
    (`test_keye_cell.run_tiny`: 2 layers, 4 of 8 experts held, top 2 of
    256 tokens, 256 rows a step when balanced): the record's metrics
    read on any device; the roofline, which needs the device's trace,
    stays silent."""
    import test_keye_cell
    from paddle_tpu import profiler
    profiler.reset()
    metrics = test_keye_cell.run_tiny(trace=True)["metrics"]
    assert 0 < metrics["moe_rows_routed"]["value"] <= 2 * 256 * 2
    assert 0 < metrics["moe_buffer_fill"]["value"] <= 100
    assert metrics["moe_rounds"]["value"] >= 0
    assert metrics["moe_tail_rows_ratio"]["value"] > 0
    assert "moe_experts_roofline" not in metrics
    assert metrics["moe_rows_routed"]["unit"] == "rows"
