"""The two readers of PR 27 on a small synthetic trace and span list:
`op_ms` (kernels by the names the program gives them, apart by where
they lie) and `program_span_s` (the program's own host spans before the
window), and every per-layer entry of BENCHMARK.json through
`cells.reader`."""
from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmarks.harness import cells  # noqa: E402
from benchmarks.harness import trace as tr  # noqa: E402
from benchmarks.readers import op_ms, program_span_s  # noqa: E402

CALL = ', custom_call_target="tpu_custom_call"'
FWD = "%flash_fwd.16 = (bf16[8,64]{1,0}, f32[8,8]{1,0}) custom-call(%q)" + CALL
REPLAY = "%flash_fwd.17 = (bf16[8,64]{1,0}, f32[8,8]{1,0}) custom-call(%r)" \
    + CALL
DQ = "%flash_bwd_dq.9 = bf16[8,64]{1,0} custom-call(%q, %do)" + CALL
DKV = "%flash_bwd_dkv.9 = (bf16[8,64]{1,0}, bf16[8,64]{1,0}) " \
    "custom-call(%q, %do)" + CALL
LOOP_F = "%while.13 = (s32[], bf16[8,64]{1,0}) while(%t.1)"
LOOP_B = "%while.14 = (s32[], bf16[8,64]{1,0}) while(%t.2)"
FUSION = "%fusion.12 = bf16[8,64]{1,0} fusion(%p), kind=kLoop"
# a kernel of another name that only starts alike, and the parent's names
OTHER = "%flash_fwd_x.3 = bf16[8,64]{1,0} custom-call(%q)" + CALL
UNNAMED = "%checkpoint.4 = (bf16[8,64]{1,0}) custom-call(%q)" + CALL


def synthetic_trace(steps=2):
    """`steps` runs of the step program, 10 s apart. In each: a forward
    loop of two layers (a fusion and the forward kernel each), a backward
    loop of two layers (the replayed forward kernel, dq, dk/dv, a
    fusion), and after the loops a fusion of the optimizer."""
    ops, modules = [], []
    for i in range(steps):
        t = 10.0 * i
        modules.append((t, t + 9.0, "jit_gpt_train_step(77)"))
        ops.append((t, t + 2.0, LOOP_F))
        for k in (0.0, 1.0):
            ops += [(t + k, t + k + 0.4, FUSION),
                    (t + k + 0.4, t + k + 0.9, FWD)]
        ops.append((t + 2.0, t + 8.0, LOOP_B))
        for k in (2.0, 5.0):
            ops += [(t + k, t + k + 0.6, REPLAY),
                    (t + k + 0.6, t + k + 1.3, DQ),
                    (t + k + 1.3, t + k + 2.3, DKV),
                    (t + k + 2.3, t + k + 2.9, FUSION)]
        ops += [(t + 8.0, t + 8.5, FUSION), (t + 8.5, t + 8.6, OTHER)]
    modules.append((100.0, 100.1, "jit_norms(9)"))
    return {"devices": {0: {"ops": ops, "modules": modules}}, "spans": []}


def context(trace):
    return {"trace": trace, "summary": tr.summary(trace)}


def params(metric):
    read, p = cells.reader(metric)
    assert read is op_ms.read
    return p


def test_op_ms_by_the_kernels_names():
    ctx = context(synthetic_trace())
    assert ctx["summary"]["steps"] == 2
    # per traced step: two layers of each kernel
    assert op_ms.read(ctx, params("flash_dq_ms")) == pytest.approx(1400.0)
    assert op_ms.read(ctx, params("flash_dkv_ms")) == pytest.approx(2000.0)
    # one name, two places: the forward pass's loop holds no backward
    # kernel, the backward pass's loop does
    assert op_ms.read(ctx, params("flash_fwd_ms")) == pytest.approx(1000.0)
    assert op_ms.read(ctx, params("flash_fwd_replay_ms")) == \
        pytest.approx(1200.0)
    assert op_ms.read(ctx, {"pattern": r"^%flash_fwd(\.[.\w]*)? = "}) == \
        pytest.approx(2200.0)
    # the runs a step are logged: the counter
    assert sum("2 runs" in n for n in ctx["notes"]) == 4
    assert sum("4 runs" in n for n in ctx["notes"]) == 1


def test_op_ms_self_time_and_division():
    trace = synthetic_trace(steps=4)
    ctx = context(trace)
    # a `while` keeps what its body does not cover: 2.0 - 2 x 0.9 and
    # 6.0 - 2 x 2.9 seconds a step
    assert op_ms.read(ctx, {"pattern": r"^%while\.13 "}) == \
        pytest.approx(200.0)
    assert op_ms.read(ctx, {"pattern": r" while\("}) == pytest.approx(400.0)
    # five fusions a step, in and out of the loops
    assert op_ms.read(ctx, {"pattern": r" fusion\("}) == \
        pytest.approx(1e3 * (2 * 0.4 + 2 * 0.6 + 0.5))
    # the reader's nesting is `trace.self_seconds`'s
    ops = trace["devices"][0]["ops"]
    mine = {}
    for _, _, name, _, sec in op_ms.nested(ops):
        mine[name] = mine.get(name, 0.0) + sec
    assert mine == pytest.approx(tr.self_seconds(ops))
    holders = {name: held for _, _, name, held, _ in op_ms.nested(ops)}
    assert holders[LOOP_F] is None and holders[FWD] is not None


def test_op_ms_returns_nothing_where_nothing_is_named():
    ctx = context(synthetic_trace())
    assert op_ms.read(ctx, {"pattern": "%no_such_kernel"}) is None
    assert op_ms.read({"trace": None, "summary": None}, {"pattern": "x"}) \
        is None
    # the parent's program: kernels named by the computation around them
    trace = synthetic_trace()
    ops = trace["devices"][0]["ops"]
    trace["devices"][0]["ops"] = [
        (s, e, UNNAMED if "flash_" in n else n) for s, e, n in ops]
    ctx = context(trace)
    for metric in ("flash_fwd_ms", "flash_fwd_replay_ms", "flash_dq_ms",
                   "flash_dkv_ms"):
        assert op_ms.read(ctx, params(metric)) is None
    assert "notes" not in ctx
    # no layer loop (an unrolled program): the two forward metrics
    # cannot tell their runs apart and read nothing; the others read
    flat = synthetic_trace()
    flat["devices"][0]["ops"] = [o for o in flat["devices"][0]["ops"]
                                 if " while(" not in o[2]]
    ctx = context(flat)
    assert op_ms.read(ctx, params("flash_fwd_ms")) is None
    assert op_ms.read(ctx, params("flash_fwd_replay_ms")) is None
    assert op_ms.read(ctx, params("flash_dq_ms")) == pytest.approx(1400.0)


# -------------------------------------------------------- program_span_s

@pytest.fixture
def spans():
    """Spans recorded through the program's recorder at chosen times:
    seconds on the window's clock."""
    from paddle_tpu import profiler
    profiler.reset()

    def record(name, start, end):
        profiler.record_span(name, int(start * 1e9), int(end * 1e9))
    yield record
    profiler.reset()


def span_params(metric):
    read, p = cells.reader(metric)
    assert read is program_span_s.read
    return p


def test_program_span_s_before_the_window(spans):
    spans("import.paddle_tpu", 10.0, 12.5)
    spans("model.build", 13.0, 17.0)
    spans("set_state_dict", 17.5, 18.0)
    spans("set_state_dict", 40.0, 41.0)       # a checkpoint load, later
    spans("build_train_step.stack", 18.0, 20.0)   # a child of the next,
    spans("build_train_step", 18.0, 21.0)         # not counted again
    ctx = {"window": {"t0": 30.0}}
    assert program_span_s.read(ctx, span_params("setup_import_s")) == \
        pytest.approx(2.5)
    assert program_span_s.read(ctx, span_params("setup_model_s")) == \
        pytest.approx(4.5)
    assert program_span_s.read(ctx, span_params("setup_build_s")) == \
        pytest.approx(3.0)
    assert program_span_s.read(
        ctx, {"spans": ["build_train_step", "build_train_step.stack"]}) == \
        pytest.approx(3.0)
    assert program_span_s.read(ctx, {"spans": ["no.such.span"]}) is None
    assert program_span_s.read({}, span_params("setup_import_s")) is None
    # a span that is still running when the window starts is not set-up
    spans("model.build", 29.0, 31.0)
    assert program_span_s.read(ctx, span_params("setup_model_s")) == \
        pytest.approx(4.5)


def test_setup_metrics_do_not_count_a_compile_twice(spans):
    spans("model.build", 13.0, 17.0)
    spans("compile.backend", 14.0, 15.0)      # an initializer, inside
    spans("build_train_step", 18.0, 21.0)
    spans("compile.trace", 20.5, 21.5)        # half inside, half after
    spans("compile.trace", 22.0, 24.0)        # the step: traced, with a
    spans("compile.trace", 22.5, 23.0)        # program traced inside it,
    spans("compile.lower", 24.0, 25.0)        # lowered and compiled
    spans("compile.backend", 25.0, 28.0)
    spans("compile.backend", 50.0, 70.0)      # the reference, afterwards
    ctx = {"window": {"t0": 30.0}}
    compile_s = program_span_s.read(ctx, span_params("setup_compile_s"))
    assert compile_s == pytest.approx(0.5 + 2.0 + 1.0 + 3.0)
    assert "8.000 s before the window, of which 1.500 s inside" \
        in ctx["notes"][0]
    model_s = program_span_s.read(ctx, span_params("setup_model_s"))
    build_s = program_span_s.read(ctx, span_params("setup_build_s"))
    # the three cover 13-17, 18-21 and 21-21.5, 22-28: nothing twice
    assert model_s + build_s + compile_s == pytest.approx(4.0 + 3.0 + 6.5)


def test_program_span_s_without_the_recorder(monkeypatch):
    """The parent of PR 27 has `paddle_tpu.profiler` and no `spans`."""
    from paddle_tpu import profiler
    monkeypatch.delattr(profiler, "spans")
    assert program_span_s.read({"window": {"t0": 30.0}},
                               span_params("setup_import_s")) is None


def test_overlap_of_interval_lists():
    a = [(0.0, 2.0), (3.0, 5.0)]
    assert program_span_s.overlap(a, [(1.0, 4.0)]) == pytest.approx(2.0)
    assert program_span_s.overlap(a, []) == 0.0
    assert program_span_s.overlap(a, [(2.0, 3.0), (5.0, 9.0)]) == 0.0
    assert program_span_s.overlap(a, a) == pytest.approx(4.0)


# ------------------------------------------------------------- the files

BENCH = cells.benchmark()


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_entry_resolves(metric):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    read, p = cells.reader(metric)
    assert callable(read) and isinstance(p, dict)
    reports = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", reports)) <= reports
    assert entry["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    # with nothing to read, a reader returns nothing and does not raise
    assert read({}, p) is None
