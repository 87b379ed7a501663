"""The names the program gives its own work (ISSUE 27): the step
program's module name, the Pallas kernels' names, the `jax.named_scope`s
of the training path in every instruction's `op_name`, forward and
backward, and the host spans of `paddle_tpu.profiler.RecordEvent`. All
on the CPU: names are metadata, so the lowered text shows them without
a chip. The metric files of the benchmark that match these names are
held to the program's constants here."""
from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as pt
import paddle_tpu.profiler as prof
from paddle_tpu.core import compile_cache, native
from paddle_tpu.distributed import build_mesh
from paddle_tpu.models import GPTForPretraining
from paddle_tpu.trainer import build_train_step
from paddle_tpu.models.bert import BertForPretraining, bert_tiny
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.nn.functional import attention
from paddle_tpu.nn.layer import functional_call, trainable_state
from paddle_tpu.ops import flash_attention as fa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEQ = 128


@pytest.fixture
def kernels_on_cpu(monkeypatch):
    """The flash path off-chip: the dispatch takes it and the kernels
    run in the Pallas interpreter."""
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    monkeypatch.setattr(attention, "_pallas_ok", lambda q, k, causal: True)


def op_names(lowered) -> set:
    """Every `op_name` of the compiled program (the lowered text names a
    called function's instructions without their caller's scopes)."""
    return set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))


def adamw():
    return pt.optimizer.AdamW(learning_rate=1e-4,
                              grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))


def tiny_gpt_step(mesh_axes=None, devices: int = 1, **build):
    """(step, state, a batch) of `build_train_step` on a tiny GPT."""
    model = GPTForPretraining(gpt_tiny(dtype=jnp.float32,
                                       max_position_embeddings=SEQ))
    mesh = build_mesh(devices=jax.devices()[:devices],
                      **(mesh_axes or {"dp": 1}))
    step, state = build_train_step(model, adamw(), mesh, **build)
    ids = jnp.zeros((4, SEQ), jnp.int32)
    return step, state, (ids, ids)


# forward / backward of every scope of the GPT step, as JAX wraps the
# outermost scope of a differentiated function
GPT_FORWARD = [f"jvp({prof.EMBED})/", f"jvp({prof.DECODER})/",
               f"jvp({prof.LM_LOSS})/"]
GPT_BACKWARD = [f"transpose(jvp({s}))/"
                for s in (prof.EMBED, prof.DECODER, prof.LM_LOSS)]
GPT_INSIDE = [prof.ATTN, prof.MLP, f"{prof.ATTN}/{prof.ATTENTION}"]


@pytest.mark.parametrize("mesh_axes, devices, build", [
    ({"dp": 1}, 1, {}),
    ({"sharding": 2, "mp": 2}, 4, {"zero_stage": 3}),
], ids=["one_device", "sharding2_mp2"])
def test_gpt_step_is_named_inside_and_out(kernels_on_cpu, mesh_axes,
                                          devices, build):
    step, state, batch = tiny_gpt_step(mesh_axes, devices, loss_chunks=2,
                                       **build)
    lowered = step.lower(state, batch)
    assert f"module @jit_{prof.GPT_TRAIN_STEP} " in lowered.as_text()
    names = op_names(lowered)
    step = f"jit({prof.GPT_TRAIN_STEP})/"
    for wrapped in GPT_FORWARD + GPT_BACKWARD:
        assert any(n.startswith(step + wrapped) for n in names), wrapped
    for half in (f"jvp({prof.DECODER})/", f"transpose(jvp({prof.DECODER}))/"):
        under = [n for n in names if n.startswith(step + half)]
        for scope in GPT_INSIDE:
            assert any(f"/{scope}/" in n for n in under), (half, scope)
    assert any(n.startswith(f"{step}{prof.OPTIMIZER}/{prof.CLIP}/")
               for n in names)
    assert any(re.match(re.escape(f"{step}{prof.OPTIMIZER}/") + r"(?!clip/)",
                        n) for n in names)
    # the kernels lie under the attention dispatch; the forward kernel
    # runs in the forward pass and again, replayed, in the backward pass
    kernel = {k: [n for n in names
                  if f"/{prof.ATTENTION}/" in n and f"/{k}/" in n]
              for k in prof.KERNELS}
    assert all(kernel.values())
    fwd = kernel[prof.FLASH_FWD]
    assert any(f"/jvp({prof.DECODER})/" in n for n in fwd)
    assert any(f"/transpose(jvp({prof.DECODER}))/" in n for n in fwd)
    for k in (prof.FLASH_BWD_DQ, prof.FLASH_BWD_DKV):
        assert all(f"/transpose(jvp({prof.DECODER}))/" in n
                   for n in kernel[k])


def test_offloaded_step_names_its_three_programs():
    step, state, batch = tiny_gpt_step(offload=True,
                                       offload_memory_kind="unpinned_host")
    compile_cache.enable()
    prof.reset()
    state, loss = step(state, batch)
    assert jnp.isfinite(loss)
    got = prof.spans()
    names = [s.name for s in got]
    assert names.count("offload.grad") == 1
    assert names.count("offload.outer") == 1
    assert names.count("offload.chunk") >= 1
    compiled = {s.detail for s in got if s.name == "compile.backend"}
    for program in (prof.GPT_OFFLOAD_GRAD, prof.GPT_OFFLOAD_CHUNK,
                    prof.GPT_OFFLOAD_OUTER):
        assert f"jit({program})" in compiled
    sources = glob.glob(os.path.join(REPO, "paddle_tpu", "trainer", "*.py"))
    assert len(sources) >= 5
    for path in sources:
        with open(path) as f:
            source = f.read()
        assert "PTPU_OFFLOAD_SYNC" not in source and "_trace(" not in source


def test_pallas_calls_carry_their_names(kernels_on_cpu):
    q = jnp.ones((1, SEQ, 2, 64), jnp.float32)

    def loss(q, k, v):
        return fa.flash_attention(q, k, v, causal=True).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q))
    called = re.findall(r"\bname=(\w+)", text)
    assert [n for n in called if n in prof.KERNELS] == list(prof.KERNELS)
    assert text.count("pallas_call[") == len(prof.KERNELS)


def test_bert_step_is_named_inside(kernels_on_cpu):
    model = BertForPretraining(bert_tiny(dtype=jnp.float32,
                                         max_position_embeddings=SEQ))
    opt = adamw()
    params = trainable_state(model)
    state = (params, opt.init_state(params))

    def loss_fn(params, ids, labels, nsp):
        out, _ = functional_call(model, params, ids, None, None, labels, nsp)
        return out

    @jax.jit
    def bert_step(state, ids, labels, nsp):
        params, opt_state = state
        loss, g = jax.value_and_grad(loss_fn)(params, ids, labels, nsp)
        return opt.apply(params, g, opt_state), loss

    ids = jnp.zeros((2, SEQ), jnp.int32)
    names = op_names(bert_step.lower(state, ids, ids,
                                     jnp.zeros((2,), jnp.int32)))
    # no layer scan here: each block's scopes are the outermost, which
    # is where JAX puts its forward / backward wrappers
    for scope in (prof.ATTN, prof.MLP, prof.MLM_HEAD):
        assert any(f"/jvp({scope})/" in n for n in names), scope
        assert any(f"/transpose(jvp({scope}))/" in n for n in names), scope
    assert any(f"/jvp({prof.ATTN})/{prof.ATTENTION}/{prof.FLASH_FWD}/" in n
               for n in names)
    for k in (prof.FLASH_BWD_DQ, prof.FLASH_BWD_DKV):
        assert any(f"/transpose(jvp({prof.ATTN}))/{prof.ATTENTION}/{k}/" in n
                   for n in names)
    assert any(f"/{prof.OPTIMIZER}/{prof.CLIP}/" in n for n in names)


# ------------------------------------------- the benchmark's metric files

def metric_files() -> dict:
    out = {}
    for path in glob.glob(os.path.join(REPO, "benchmarks", "metrics",
                                       "*.json")):
        with open(path) as f:
            out[os.path.basename(path)[:-5]] = json.load(f)
    return out


# what an `op_ms` pattern may name: the program's kernels, and the one
# instruction the compiler names for it (`jax.lax.ragged_dot`)
NAMED = prof.KERNELS + prof.SEL_KERNELS + prof.MLA_KERNELS + \
    prof.WIN_KERNELS + (
    prof.INDEX_SCORES, prof.INDEX_TOPK, prof.RAGGED_DOT)
# names the accepted patterns still carry and no kernel bears: the latent
# dq kernel went into the dk/dv walk (ISSUE 34) and the selected one after
# it (ISSUE 35); `mla_attn_ms` / `mla_attn_roofline` and `sel_attn_ms` /
# `sel_attn_roofline` keep the alternative until a `benchmark` PR renames
RETIRED = ("flash_mla_bwd_dq", "flash_sel_bwd_dq")


def test_metric_patterns_name_the_programs_kernels():
    """A pattern of an `op_ms` metric matches an instruction by the name
    the program gave it, "%<kernel>.<n> = ...": every such name is one
    of the program's constants, and the text of a real instruction of
    that kernel matches."""
    seen = set()
    for name, spec in metric_files().items():
        if spec["reader"] != "op_ms":
            continue
        for key in ("pattern", "beside", "not_beside"):
            if key not in spec["params"]:
                continue
            pattern = spec["params"][key]
            named = re.findall(r"%([\w-]+)", pattern)
            assert named and set(named) <= set(NAMED + RETIRED), (name, key)
            seen.update(named)
            for kernel in named:
                assert re.search(pattern, f"%{kernel}.16 = (bf16[128,1024,64]"
                                 "{2,1,0}) custom-call(%bitcast.517), "
                                 'custom_call_target="tpu_custom_call"')
                assert not re.search(pattern, f"%{kernel}_x.16 = bf16[8]{{0}} "
                                     "custom-call(%p)")
            for stem in ("checkpoint", "closed_call", "rematted_computation",
                         "bf16[", "f32["):
                assert stem not in pattern, (name, stem)
    assert seen == set(NAMED + RETIRED)
    assert not set(RETIRED) & set(NAMED)


def test_index_topk_ms_is_the_keye_cells_alone():
    """The top-k kernel's metric (ISSUE 32) is reported where an indexer
    runs and nowhere else."""
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmarks.harness import cells
    for cell in cells.benchmark()["workloads"]:
        names = {m["name"] for m in cells.resolve(cell["name"])["per_layer"]}
        assert ("index_topk_ms" in names) == cell["name"].startswith("keye")
        assert ("index_topk_ms" in names) == ("index_select_ms" in names)
    read, params = cells.reader("index_topk_ms")
    assert read.__module__.endswith("readers.op_ms")
    assert re.search(params["pattern"], f"%{prof.INDEX_TOPK}.3 = (s8[2,8192,"
                     "8192]{2,1,0}) custom-call(%fusion.1)")
    # nothing to read (the parent's program, or no trace): nothing returned
    assert read({}, params) is None


def program_span_names() -> set:
    return {s for spec in metric_files().values()
            if spec["reader"] == "program_span_s"
            for key in ("spans", "minus") for s in spec["params"].get(key, ())}


def test_metric_spans_are_spans_the_program_makes():
    """Every span name a `program_span_s` metric reads is recorded by
    building and compiling a tiny step (the import's own span: below)."""
    compile_cache.enable()
    prof.reset()
    model = GPTForPretraining(gpt_tiny(dtype=jnp.float32))
    model.set_state_dict(model.state_dict())
    step, state, batch = tiny_gpt_step()
    step(state, batch)
    got = prof.spans()
    made = {s.name for s in got}
    assert program_span_names() - {"import.paddle_tpu"} <= made
    by_id = {s.id: s for s in got}
    build = next(s for s in got if s.name == "build_train_step")
    for child in ("stack", "opt_init", "place"):
        span = next(s for s in got if s.name == f"build_train_step.{child}")
        assert by_id[span.parent] is build
        assert build.start_ns <= span.start_ns <= span.end_ns <= build.end_ns
    assert any(s.name == "compile.backend"
               and s.detail == f"jit({prof.GPT_TRAIN_STEP})" for s in got)
    assert "import.paddle_tpu" in program_span_names()


def test_import_is_a_span():
    out = subprocess.run(
        [sys.executable, "-c",
         "import time; t0 = time.perf_counter_ns(); import paddle_tpu;"
         "t1 = time.perf_counter_ns();"
         "s, = [s for s in paddle_tpu.profiler.spans()"
         "      if s.name == 'import.paddle_tpu'];"
         "assert t0 <= s.start_ns < s.end_ns <= t1 and s.parent == 0;"
         "print('span', (s.end_ns - s.start_ns) / (t1 - t0))"],
        cwd=REPO, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    # first line to last: nearly all of what the import statement took
    assert float(out.stdout.split()[-1]) > 0.9


# ------------------------------------------------------------- RecordEvent

@pytest.fixture
def clean_spans():
    prof.reset()
    yield
    prof.reset()


def test_record_event_keeps_parent_and_child(clean_spans):
    with prof.RecordEvent("outer") as outer:
        with prof.RecordEvent("inner"):
            pass
        with prof.RecordEvent("inner"):
            pass
    assert isinstance(outer, prof.RecordEvent)
    inner1, inner2, out = prof.spans()       # in the order they ended
    assert (inner1.name, inner2.name, out.name) == ("inner", "inner", "outer")
    assert inner1.parent == inner2.parent == out.id and out.parent == 0
    assert len({inner1.id, inner2.id, out.id}) == 3
    assert out.start_ns <= inner1.start_ns <= inner1.end_ns \
        <= inner2.start_ns <= inner2.end_ns <= out.end_ns
    # the clock is time.perf_counter_ns, a caller's own
    import time
    assert 0 <= time.perf_counter_ns() - out.end_ns < 5e9


def test_record_event_begin_end_and_decorator(clean_spans):
    @prof.RecordEvent("decorated")
    def step(x, k=1):
        with prof.RecordEvent("body"):
            return x + k

    assert step.__name__ == "step" and step(1, k=2) == 3 and step(2) == 3
    ev = prof.RecordEvent("by_hand")
    ev.begin()
    ev.end()
    names = [s.name for s in prof.spans()]
    assert names == ["body", "decorated", "body", "decorated", "by_hand"]
    spans = prof.spans()
    assert spans[0].parent == spans[1].id and spans[2].parent == spans[3].id
    assert spans[1].id != spans[3].id        # a fresh scope per call


def test_record_event_unwinds_after_an_exception(clean_spans):
    with pytest.raises(ValueError):
        with prof.RecordEvent("fails"):
            prof.RecordEvent("left_open").begin()
            raise ValueError
    with prof.RecordEvent("after"):
        pass
    assert [(s.name, s.parent) for s in prof.spans()] == \
        [("fails", 0), ("after", 0)]


def test_spans_without_the_native_library(clean_spans, monkeypatch):
    def gone():
        raise AssertionError("the native library was called")
    monkeypatch.setattr(native, "available", lambda: False)
    monkeypatch.setattr(native, "lib", gone)
    prof.start_profiler()                    # nothing to turn on
    with prof.RecordEvent("no_native"):
        pass
    prof.stop_profiler()
    assert [s.name for s in prof.spans()] == ["no_native"]
    assert prof.event_count() == 0


def test_no_native_call_while_profiling_is_off(clean_spans, monkeypatch):
    calls = []
    real = native.lib

    def counted():
        calls.append(1)
        return real()
    monkeypatch.setattr(native, "lib", counted)
    for _ in range(3):
        with prof.RecordEvent("off"):
            pass
    assert not calls and len(prof.spans()) == 3
    if not native.available():
        return
    calls.clear()
    prof.start_profiler()
    try:
        with prof.RecordEvent("on"):
            pass
        assert calls and prof.event_count() == 1
    finally:
        prof.stop_profiler(profile_path=os.devnull)
    calls.clear()
    with prof.RecordEvent("off_again"):
        pass
    assert not calls


def test_record_event_enters_a_trace_annotation(clean_spans, monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    monkeypatch.setattr(prof, "TraceAnnotation", Annotation)
    with prof.RecordEvent("annotated"):
        seen.append("body")
    assert seen == [("enter", "annotated"), "body", ("exit", "annotated")]
    from jax.profiler import TraceAnnotation
    monkeypatch.undo()
    assert prof.TraceAnnotation is TraceAnnotation


def test_span_list_is_bounded(clean_spans, monkeypatch):
    monkeypatch.setattr(prof, "MAX_SPANS", 3)
    dropped = prof.stats.REGISTRY.counter("profiler.spans_dropped")
    before = dropped.value
    for i in range(5):
        prof.record_span(f"s{i}", i, i + 1)
    assert [s.name for s in prof.spans()] == ["s0", "s1", "s2"]
    assert dropped.value == before + 2
    prof.reset()
    assert prof.spans() == []


def test_compiles_become_spans_and_counters(clean_spans):
    compile_cache.enable()
    compile_cache.enable()                   # listens once, not twice
    x = jnp.ones((3, 5))                     # compiled before the count
    requests = prof.stats.REGISTRY.counter("compile.requests")
    before = requests.value

    def a_function_of_this_test(x):
        return jnp.tanh(x) * 3

    import time
    t0 = time.perf_counter_ns()
    with prof.RecordEvent("caller"):
        jitted = jax.jit(a_function_of_this_test)
        jitted(x)
    t1 = time.perf_counter_ns()
    mine = [s for s in prof.spans() if "a_function_of_this_test" in s.detail]
    assert sorted(s.name for s in mine) == \
        ["compile.backend", "compile.lower", "compile.trace"]
    caller = next(s for s in prof.spans() if s.name == "caller")
    for s in mine:
        assert s.parent == caller.id
        assert t0 <= s.start_ns <= s.end_ns <= t1
    trace, lower, backend = (next(s for s in mine if s.name == n) for n in
                             ("compile.trace", "compile.lower",
                              "compile.backend"))
    assert trace.end_ns <= lower.end_ns <= backend.end_ns
    assert requests.value == before + 1
    n = len(prof.spans())
    jitted(x)                                # compiles nothing more
    assert requests.value == before + 1 and len(prof.spans()) == n
