"""Native C-ABI predictor (csrc/ptpu_predictor.cc) round-trips.

The reference serves models from C++ with no Python
(capi_exp/pd_inference_api.h:1 over analysis_predictor.cc:381). Here the
deployment artifact is the self-contained ONNX wire file from
paddle_tpu.onnx.export; `_native_predictor.so` interprets it natively.
These tests exercise the FULL chain: jax model -> exported bytes ->
C ABI (ctypes) -> numerics vs the jax forward; plus the pure-C demo
binary as the no-Python-serving proof.
"""
import ctypes
import os
import subprocess

import numpy as np
import pytest

from _csrc import build_all

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "paddle_tpu", "_native_predictor.so")
DEMO = os.path.join(REPO, "csrc", "ptpu_predictor_demo")


def _build():
    build_all()


@pytest.fixture(scope="module")
def lib():
    try:
        _build()  # incremental: no-op when current, rebuilds stale
    except FileNotFoundError:
        # no make/compiler on PATH: fall back to a prebuilt .so if any
        if not os.path.exists(LIB):
            raise
    except subprocess.CalledProcessError as e:
        # a real COMPILE error must never be masked by a stale binary
        raise RuntimeError(
            f"native predictor build failed:\n{e.stderr}") from e
    lib = ctypes.CDLL(LIB)
    lib.ptpu_predictor_create.restype = ctypes.c_void_p
    lib.ptpu_predictor_create.argtypes = [ctypes.c_char_p, ctypes.c_char_p,
                                          ctypes.c_int]
    lib.ptpu_predictor_input_name.restype = ctypes.c_char_p
    lib.ptpu_predictor_input_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptpu_predictor_set_input.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int]
    lib.ptpu_predictor_run.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                       ctypes.c_int]
    lib.ptpu_predictor_output_ndim.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptpu_predictor_output_dims.restype = \
        ctypes.POINTER(ctypes.c_int64)
    lib.ptpu_predictor_output_dims.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptpu_predictor_output_data.restype = \
        ctypes.POINTER(ctypes.c_float)
    lib.ptpu_predictor_output_data.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.ptpu_predictor_destroy.argtypes = [ctypes.c_void_p]
    lib.ptpu_predictor_set_input_i32.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int32),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int]
    lib.ptpu_predictor_set_input_i64.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64), ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int]
    return lib


def _run_native(lib, model, x, tmp_path):
    """`model` is ONNX bytes (written to tmp) or an existing file path."""
    if isinstance(model, (bytes, bytearray)):
        path = os.path.join(str(tmp_path), "model.onnx")
        with open(path, "wb") as f:
            f.write(model)
    else:
        path = model
    err = ctypes.create_string_buffer(512)
    h = lib.ptpu_predictor_create(path.encode(), err, 512)
    assert h, err.value.decode()
    name = lib.ptpu_predictor_input_name(h, 0)
    xc = np.ascontiguousarray(x, np.float32)
    dims = (ctypes.c_int64 * x.ndim)(*x.shape)
    rc = lib.ptpu_predictor_set_input(
        h, name, xc.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), dims,
        x.ndim, err, 512)
    assert rc == 0, err.value.decode()
    rc = lib.ptpu_predictor_run(h, err, 512)
    assert rc == 0, err.value.decode()
    nd = lib.ptpu_predictor_output_ndim(h, 0)
    odims = lib.ptpu_predictor_output_dims(h, 0)
    shape = tuple(odims[k] for k in range(nd))
    data = lib.ptpu_predictor_output_data(h, 0)
    n = int(np.prod(shape)) if shape else 1
    out = np.ctypeslib.as_array(data, shape=(n,)).reshape(shape).copy()
    lib.ptpu_predictor_destroy(h)
    return out


class TestNativePredictor:
    def test_lenet_matches_jax(self, lib, tmp_path):
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.vision.models import LeNet

        pt.seed(0)
        m = LeNet()
        m.eval()
        x = np.random.RandomState(0).randn(2, 1, 28, 28).astype(np.float32)
        model_bytes = trace_to_onnx(lambda a: m(a), (jnp.asarray(x),))
        want = np.asarray(m(jnp.asarray(x)))
        got = _run_native(lib, model_bytes, x, tmp_path)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)

    def test_db_ocr_detector_matches_jax(self, lib, tmp_path):
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.vision.models import db_detector

        pt.seed(0)
        m = db_detector()
        m.eval()
        x = np.random.RandomState(1).randn(1, 3, 64, 64).astype(np.float32)
        model_bytes = trace_to_onnx(lambda a: m(a)["maps"],
                                    (jnp.asarray(x),))
        want = np.asarray(m(jnp.asarray(x))["maps"])
        got = _run_native(lib, model_bytes, x, tmp_path)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-4)

    def test_resnet18_matches_jax(self, lib, tmp_path):
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.vision.models import resnet18

        pt.seed(0)
        m = resnet18(num_classes=10)
        m.eval()
        x = np.random.RandomState(2).randn(1, 3, 64, 64).astype(np.float32)
        model_bytes = trace_to_onnx(lambda a: m(a), (jnp.asarray(x),))
        want = np.asarray(m(jnp.asarray(x)))
        got = _run_native(lib, model_bytes, x, tmp_path)
        np.testing.assert_allclose(got, want, rtol=2e-3, atol=1e-4)

    def test_pure_c_demo_no_python(self, lib, tmp_path):
        """The C binary serves the artifact in a process with NO Python —
        the reference's capi_exp deployment story."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.vision.models import LeNet

        if not os.path.exists(DEMO):
            _build()
        pt.seed(0)
        m = LeNet()
        m.eval()
        x = np.zeros((1, 1, 28, 28), np.float32)
        model_bytes = trace_to_onnx(lambda a: m(a), (jnp.asarray(x),))
        path = os.path.join(str(tmp_path), "lenet.onnx")
        with open(path, "wb") as f:
            f.write(model_bytes)
        r = subprocess.run([DEMO, path, "1", "1", "28", "28"],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr
        assert "output dims: 1 10" in r.stdout, r.stdout
        want = np.asarray(m(jnp.asarray(x)))[0]
        got = np.asarray([float(v) for v in
                          r.stdout.split("values:")[1].split()])
        np.testing.assert_allclose(got, want[:8], rtol=1e-4, atol=1e-5)

    def test_int8_artifact_serves_natively(self, lib, tmp_path):
        """The int8-EXECUTING export (convert_to_int8) round-trips
        through the C predictor — native int8 serving."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.quantization import QAT, convert_to_int8

        pt.seed(0)
        net = pt.nn.Sequential(pt.nn.Linear(8, 16), pt.nn.ReLU(),
                               pt.nn.Linear(16, 4))
        QAT().quantize(net)
        x = np.random.RandomState(0).randn(4, 8).astype(np.float32)
        net.train()
        net(jnp.asarray(x))          # one observer pass
        net.eval()
        convert_to_int8(net)
        want = np.asarray(net(jnp.asarray(x)))
        model_bytes = trace_to_onnx(lambda a: net(a), (jnp.asarray(x),))
        got = _run_native(lib, model_bytes, x, tmp_path)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


class TestTransformerServing:
    def test_bert_encoder_serves_natively_int32_ids(self, lib, tmp_path):
        """A BERT encoder artifact serves from C with int32 token ids:
        the exporter lowers every dot_general (attention included) to
        Transpose/Reshape/batched-MatMul, and the C API's
        set_input_i32 binds integer inputs (reference capi_exp
        PD_DataType parity). Zero Python in the serving path."""
        import paddle_tpu as pt
        from paddle_tpu.models import BertModel, bert_tiny
        from paddle_tpu.static import InputSpec

        pt.seed(0)
        m = BertModel(bert_tiny())
        m.eval()
        path = pt.onnx.export(m, os.path.join(str(tmp_path), "bert"),
                              input_spec=[InputSpec([2, 16], "int32")])
        err = ctypes.create_string_buffer(512)
        h = lib.ptpu_predictor_create(path.encode(), err, 512)
        assert h, err.value.decode()
        name = lib.ptpu_predictor_input_name(h, 0)
        ids = np.random.RandomState(0).randint(
            0, 512, (2, 16)).astype(np.int32)
        dims = (ctypes.c_int64 * 2)(*ids.shape)

        def run_with(setter, arr, ctype):
            rc = setter(h, name,
                        arr.ctypes.data_as(ctypes.POINTER(ctype)),
                        dims, arr.ndim, err, 512)
            assert rc == 0, err.value.decode()
            rc = lib.ptpu_predictor_run(h, err, 512)
            assert rc == 0, err.value.decode()
            nd = lib.ptpu_predictor_output_ndim(h, 0)
            odims = lib.ptpu_predictor_output_dims(h, 0)
            shape = tuple(odims[k] for k in range(nd))
            data = lib.ptpu_predictor_output_data(h, 0)
            return np.ctypeslib.as_array(data, shape=shape).copy()

        got = run_with(lib.ptpu_predictor_set_input_i32, ids,
                       ctypes.c_int32)
        got64 = run_with(lib.ptpu_predictor_set_input_i64,
                         ids.astype(np.int64), ctypes.c_int64)
        lib.ptpu_predictor_destroy(h)
        np.testing.assert_array_equal(got, got64)
        import jax.numpy as jnp
        seq, _ = m(jnp.asarray(ids))
        # the jax model computes in bf16; the C interpreter in fp64/fp32
        np.testing.assert_allclose(got, np.asarray(seq, np.float32),
                                   rtol=0.05, atol=0.05)

    def test_crnn_ocr_serves_natively(self, lib, tmp_path):
        """The CRNN recognizer (conv trunk + bidirectional LSTM head,
        exported via scan unrolling) serves from C — the OCR deployment
        story end to end, no Python."""
        import paddle_tpu as pt
        from paddle_tpu.static import InputSpec
        from paddle_tpu.vision.models import crnn_ocr

        pt.seed(0)
        m = crnn_ocr(num_classes=50)
        m.eval()
        path = pt.onnx.export(
            m, os.path.join(str(tmp_path), "crnn"),
            input_spec=[InputSpec([1, 3, 32, 60], "float32")])
        x = np.random.RandomState(0).randn(1, 3, 32, 60).astype(
            np.float32)
        got = _run_native(lib, path, x, tmp_path)
        import jax.numpy as jnp
        ref = m(jnp.asarray(x))
        ref = ref[0] if isinstance(ref, (tuple, list)) else ref
        np.testing.assert_allclose(got, np.asarray(ref, np.float32),
                                   rtol=2e-3, atol=2e-3)


class TestServingOptimizations:
    """r6 execution-core overhaul: load-time op fusion (conv+bn+relu,
    gemm+bias+act), static memory planning (one arena, lifetimes
    computed at load), packed cache-blocked GEMM. PTPU_PREDICTOR_OPT=0
    keeps the unoptimized interpreter — the parity baseline."""

    def _outputs(self, lib, path, x, opt):
        import os
        old = os.environ.get("PTPU_PREDICTOR_OPT")
        os.environ["PTPU_PREDICTOR_OPT"] = opt
        try:
            err = ctypes.create_string_buffer(512)
            h = lib.ptpu_predictor_create(path.encode(), err, 512)
            assert h, err.value.decode()
            name = lib.ptpu_predictor_input_name(h, 0)
            xc = np.ascontiguousarray(x, np.float32)
            dims = (ctypes.c_int64 * x.ndim)(*x.shape)
            rc = lib.ptpu_predictor_set_input(
                h, name, xc.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
                dims, x.ndim, err, 512)
            assert rc == 0, err.value.decode()
            outs = []
            for _ in range(2):   # second run reuses the planned arena
                rc = lib.ptpu_predictor_run(h, err, 512)
                assert rc == 0, err.value.decode()
                nd = lib.ptpu_predictor_output_ndim(h, 0)
                odims = lib.ptpu_predictor_output_dims(h, 0)
                shape = tuple(odims[k] for k in range(nd))
                data = lib.ptpu_predictor_output_data(h, 0)
                n = int(np.prod(shape)) if shape else 1
                outs.append(np.ctypeslib.as_array(
                    data, shape=(n,)).reshape(shape).copy())
            stats = (lib.ptpu_predictor_num_nodes(h),
                     lib.ptpu_predictor_fused_nodes(h),
                     lib.ptpu_predictor_arena_bytes(h))
            lib.ptpu_predictor_destroy(h)
            return outs, stats
        finally:
            if old is None:
                os.environ.pop("PTPU_PREDICTOR_OPT", None)
            else:
                os.environ["PTPU_PREDICTOR_OPT"] = old

    def _bind_stats(self, lib):
        lib.ptpu_predictor_num_nodes.restype = ctypes.c_int
        lib.ptpu_predictor_num_nodes.argtypes = [ctypes.c_void_p]
        lib.ptpu_predictor_fused_nodes.restype = ctypes.c_int
        lib.ptpu_predictor_fused_nodes.argtypes = [ctypes.c_void_p]
        lib.ptpu_predictor_arena_bytes.restype = ctypes.c_int64
        lib.ptpu_predictor_arena_bytes.argtypes = [ctypes.c_void_p]

    def test_fused_planned_parity_fp32_convnet(self, lib, tmp_path):
        """conv+bn+relu fusion and the planned arena against the
        unfused per-tensor interpreter on a BN convnet (the exporter
        emits the eval-BN Sub/Mul/Mul/Add chain the fuser folds)."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.vision.models import resnet18

        self._bind_stats(lib)
        pt.seed(0)
        m = resnet18(num_classes=10)
        m.eval()
        x = np.random.RandomState(3).randn(2, 3, 32, 32).astype(np.float32)
        model_bytes = trace_to_onnx(lambda a: m(a), (jnp.asarray(x),))
        path = os.path.join(str(tmp_path), "m.onnx")
        with open(path, "wb") as f:
            f.write(model_bytes)
        base, stats0 = self._outputs(lib, path, x, "0")
        opt, stats1 = self._outputs(lib, path, x, "1")
        # optimized vs unoptimized numerics (BN scale folded into
        # weights reorders fp32 rounding, nothing more)
        np.testing.assert_allclose(opt[0], base[0], rtol=2e-4, atol=2e-5)
        # planned arena is deterministic: run 2 == run 1 bitwise
        np.testing.assert_array_equal(opt[0], opt[1])
        np.testing.assert_array_equal(base[0], base[1])
        # fusion shrank the graph; planning produced a real arena
        assert stats1[0] < stats0[0]
        assert stats1[1] > 0 and stats0[1] == 0
        assert stats1[2] > 0 and stats0[2] == 0

    def test_fused_planned_parity_int8(self, lib, tmp_path):
        """int8-executing artifact: the integer GEMM is exact, so the
        planned/prepacked engine must match the unoptimized one
        BITWISE."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.quantization import QAT, convert_to_int8

        self._bind_stats(lib)
        pt.seed(0)
        net = pt.nn.Sequential(
            pt.nn.Conv2D(3, 8, 3, padding=1), pt.nn.ReLU(),
            pt.nn.Conv2D(8, 4, 3, stride=2, padding=1))
        QAT().quantize(net)
        x = np.random.RandomState(5).randn(2, 3, 16, 16).astype(np.float32)
        net.train()
        net(jnp.asarray(x))
        net.eval()
        convert_to_int8(net)
        model_bytes = trace_to_onnx(lambda a: net(a), (jnp.asarray(x),))
        path = os.path.join(str(tmp_path), "q.onnx")
        with open(path, "wb") as f:
            f.write(model_bytes)
        base, _ = self._outputs(lib, path, x, "0")
        opt, _ = self._outputs(lib, path, x, "1")
        np.testing.assert_array_equal(opt[0], base[0])
        np.testing.assert_array_equal(opt[0], opt[1])

    def test_two_predictors_two_threads(self, lib, tmp_path):
        """The r5 WorkPool was a process-global singleton with no
        dispatch serialization: two predictors on two threads (ctypes
        releases the GIL) corrupted each other's GEMM chunks. Serve two
        DIFFERENT models concurrently and check every result against
        the serial answers."""
        import threading
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx

        pt.seed(0)
        nets, paths, xs, wants = [], [], [], []
        for i, width in enumerate((64, 96)):
            net = pt.nn.Sequential(pt.nn.Linear(32, width), pt.nn.ReLU(),
                                   pt.nn.Linear(width, 8))
            net.eval()
            x = np.random.RandomState(10 + i).randn(16, 32).astype(
                np.float32)
            model_bytes = trace_to_onnx(lambda a, n=net: n(a),
                                        (jnp.asarray(x),))
            p = os.path.join(str(tmp_path), f"m{i}.onnx")
            with open(p, "wb") as f:
                f.write(model_bytes)
            want = _run_native(lib, p, x, tmp_path)
            nets.append(net)
            paths.append(p)
            xs.append(x)
            wants.append(want)

        failures = []

        def serve(i):
            try:
                err = ctypes.create_string_buffer(512)
                h = lib.ptpu_predictor_create(paths[i].encode(), err, 512)
                assert h, err.value.decode()
                name = lib.ptpu_predictor_input_name(h, 0)
                x = xs[i]
                dims = (ctypes.c_int64 * 2)(*x.shape)
                dp = x.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
                for _ in range(50):
                    assert lib.ptpu_predictor_set_input(
                        h, name, dp, dims, 2, err, 512) == 0
                    assert lib.ptpu_predictor_run(h, err, 512) == 0, \
                        err.value.decode()
                    nd = lib.ptpu_predictor_output_ndim(h, 0)
                    odims = lib.ptpu_predictor_output_dims(h, 0)
                    shape = tuple(odims[k] for k in range(nd))
                    data = lib.ptpu_predictor_output_data(h, 0)
                    got = np.ctypeslib.as_array(
                        data, shape=shape).copy()
                    np.testing.assert_array_equal(got, wants[i])
                lib.ptpu_predictor_destroy(h)
            except Exception as e:  # noqa: BLE001
                failures.append((i, e))

        threads = [threading.Thread(target=serve, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not failures, failures

    def test_gather_rejects_out_of_range_index(self, lib, tmp_path):
        """An out-of-vocab token id from the C ABI must fail the run
        with a clear error, not read a full row out of bounds (the r5
        row-copy fast path had no check)."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx

        pt.seed(0)
        emb = pt.nn.Embedding(16, 8)
        ids_ok = np.array([[0, 3, 15]], np.int32)
        model_bytes = trace_to_onnx(lambda a: emb(a),
                                    (jnp.asarray(ids_ok),))
        path = os.path.join(str(tmp_path), "emb.onnx")
        with open(path, "wb") as f:
            f.write(model_bytes)
        err = ctypes.create_string_buffer(512)
        h = lib.ptpu_predictor_create(path.encode(), err, 512)
        assert h, err.value.decode()
        name = lib.ptpu_predictor_input_name(h, 0)
        dims = (ctypes.c_int64 * 2)(1, 3)

        def run_ids(ids):
            arr = np.ascontiguousarray(ids, np.int32)
            rc = lib.ptpu_predictor_set_input_i32(
                h, name, arr.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                dims, 2, err, 512)
            assert rc == 0, err.value.decode()
            return lib.ptpu_predictor_run(h, err, 512)

        assert run_ids(np.array([[0, 3, 15]], np.int32)) == 0
        assert run_ids(np.array([[0, 16, 1]], np.int32)) != 0
        assert b"out of range" in err.value
        assert run_ids(np.array([[0, 1000000, 1]], np.int32)) != 0
        assert b"out of range" in err.value
        # negative indices within range still work (the exporter wraps
        # them model-side; ONNX Gather also allows one negative level)
        assert run_ids(np.array([[0, -1, 1]], np.int32)) == 0
        lib.ptpu_predictor_destroy(h)

    def test_run_without_set_input_still_errors(self, lib, tmp_path):
        """The memory planner's load-time dry run must not leak its
        dummy zero inputs into serving state: run() before set_input
        fails with 'missing input tensor', exactly like pre-r6."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx

        pt.seed(0)
        net = pt.nn.Sequential(pt.nn.Linear(4, 4))
        net.eval()
        x = np.zeros((2, 4), np.float32)
        model_bytes = trace_to_onnx(lambda a: net(a), (jnp.asarray(x),))
        path = os.path.join(str(tmp_path), "nosi.onnx")
        with open(path, "wb") as f:
            f.write(model_bytes)
        err = ctypes.create_string_buffer(512)
        h = lib.ptpu_predictor_create(path.encode(), err, 512)
        assert h, err.value.decode()
        assert lib.ptpu_predictor_run(h, err, 512) != 0
        assert b"missing input" in err.value
        lib.ptpu_predictor_destroy(h)

    def test_large_batched_matmul_no_nested_dispatch_deadlock(
            self, lib, tmp_path):
        """Batched MatMul parallelizes over the batch axis with the
        CALLER thread taking chunks; a per-element GEMM big enough to
        want its own pool dispatch must run serially inside, not
        re-enter the dispatcher (self-deadlock on the dispatch mutex)."""
        from paddle_tpu.onnx import proto

        B, M = 2, 160   # M^3 > 2^21: the inner GEMM's parallel threshold
        rs = np.random.RandomState(7)
        b = rs.randn(B, M, M).astype(np.float32)
        nodes = [proto.node_proto("MatMul", ["a", "b"], ["y"])]
        inits = [proto.tensor_proto("b", b)]
        vin = [proto.value_info("a", np.dtype(np.float32), (B, M, M))]
        vout = [proto.value_info("y", np.dtype(np.float32), (B, M, M))]
        g = proto.graph_proto("g", nodes, inits, vin, vout)
        path = os.path.join(str(tmp_path), "bmm.onnx")
        with open(path, "wb") as f:
            f.write(proto.model_proto(g))
        a = rs.randn(B, M, M).astype(np.float32)
        got = _run_native(lib, path, a, tmp_path)
        np.testing.assert_allclose(got, a @ b, rtol=1e-4, atol=1e-4)

    def test_set_input_overrides_initializer_default(self, lib, tmp_path):
        """ONNX allows an initializer to be the DEFAULT for a graph
        input; fold_constants must not bake it in, so set_input on that
        name is honored (r5 silently ignored it)."""
        import numpy as np
        from paddle_tpu.onnx import proto

        x_def = np.array([2.0, 3.0], np.float32)
        two = np.array([10.0], np.float32)
        nodes = [proto.node_proto("Mul", ["x", "c"], ["y"])]
        inits = [proto.tensor_proto("x", x_def),
                 proto.tensor_proto("c", two)]
        vin = [proto.value_info("x", np.dtype(np.float32), (2,))]
        vout = [proto.value_info("y", np.dtype(np.float32), (2,))]
        g = proto.graph_proto("g", nodes, inits, vin, vout)
        path = os.path.join(str(tmp_path), "shadow.onnx")
        with open(path, "wb") as f:
            f.write(proto.model_proto(g))

        err = ctypes.create_string_buffer(512)
        h = lib.ptpu_predictor_create(path.encode(), err, 512)
        assert h, err.value.decode()
        name = lib.ptpu_predictor_input_name(h, 0)

        def fetch():
            assert lib.ptpu_predictor_run(h, err, 512) == 0, \
                err.value.decode()
            data = lib.ptpu_predictor_output_data(h, 0)
            return np.ctypeslib.as_array(data, shape=(2,)).copy()

        # no set_input: the initializer default flows through
        np.testing.assert_allclose(fetch(), [20.0, 30.0])
        xs = np.array([5.0, 7.0], np.float32)
        dims = (ctypes.c_int64 * 1)(2)
        assert lib.ptpu_predictor_set_input(
            h, name, xs.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            dims, 1, err, 512) == 0
        np.testing.assert_allclose(fetch(), [50.0, 70.0])
        lib.ptpu_predictor_destroy(h)


class TestInt8ConvServing:
    def test_int8_conv_artifact_serves_natively(self, lib, tmp_path):
        """A QAT conv net converted to int8 EXECUTION serves through
        the C predictor's integer im2col+GEMM path (r5) with parity
        against the jax int8 forward."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        from paddle_tpu.quantization import QAT, convert_to_int8

        pt.seed(0)
        net = pt.nn.Sequential(
            pt.nn.Conv2D(3, 8, 3, padding=1), pt.nn.ReLU(),
            pt.nn.Conv2D(8, 4, 3, stride=2, padding=1))
        QAT().quantize(net)
        x = np.random.RandomState(0).randn(2, 3, 16, 16).astype(np.float32)
        net.train()
        net(jnp.asarray(x))          # observer pass
        net.eval()
        convert_to_int8(net)
        want = np.asarray(net(jnp.asarray(x)))
        model_bytes = trace_to_onnx(lambda a: net(a), (jnp.asarray(x),))
        got = _run_native(lib, model_bytes, x, tmp_path)
        np.testing.assert_allclose(got.reshape(want.shape), want,
                                   rtol=1e-4, atol=1e-4)
