"""Weight-only int4 decode path (ISSUE 16 tentpole a) — Python level.

The C kernels' edge cases (nibble layout, all-equal groups, K % G != 0,
zero extents, per-ISA parity of the raw GEMV/GEMM) live in
csrc/ptpu_selftest.cc; these tests exercise the USER-visible contract
through the full chain: jax model -> ONNX artifact -> PTPU_INT4=1 load
-> quantized panels -> outputs.

  * int4 must ENGAGE (outputs differ bitwise from fp32 — a silently
    disabled path would pass any tolerance check) yet stay inside the
    quality bound,
  * the quantize-at-load step is deterministic (two loads, identical
    bytes out),
  * PTPU_INT4_GROUP reaches the packer (different group -> different
    rounding) and every legal group stays in-bound,
  * per-ISA parity holds end to end (PTPU_ISA is latched per process,
    so each leg is a subprocess),
  * PTPU_TUNE=1 probes on first load, persists, and a second process
    warm-starts with zero probes; a corrupt cache silently re-probes
    (the untrusted-input contract of csrc/ptpu_tune.h).

PTPU_INT4 / PTPU_INT4_GROUP are read at predictor load, so the
in-process tests just flip os.environ around NativePredictor();
PTPU_TUNE and PTPU_ISA are latched once per process (the repo's ISA
idiom) and get subprocesses. The subprocess runner is ctypes-only — no
jax import — so each leg costs milliseconds, not a jax warmup.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from _csrc import build_all

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIB = os.path.join(REPO, "paddle_tpu", "_native_predictor.so")

# Relative L2 bound for the quantized forward on GAUSSIAN random
# weights — the worst case for 4-bit: uniform rounding error is
# ~(range/15)/(sigma*sqrt(12)) of the signal regardless of K, about
# 0.10 for a +-3-sigma group range. 0.15 catches a broken kernel
# (sign flip, wrong scale plane, nibble swap all blow past 1.0)
# without flaking on the statistics; the DECODE-QUALITY gate (argmax
# agreement on a trained GPT) is tools/decode_bench.py --int4's job.
REL_L2_BOUND = 0.15


@pytest.fixture(scope="module")
def built():
    try:
        build_all()
    except FileNotFoundError:
        if not os.path.exists(LIB):
            raise
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    from paddle_tpu.core import native
    if not native.serving_available():
        pytest.skip("native predictor runtime unavailable")
    return True


@pytest.fixture(scope="module")
def mlp_artifact(built, tmp_path_factory):
    """An MLP whose projections all clear Q4_MIN_ELEMS (K*N >= 1024),
    so PTPU_INT4=1 quantizes every MatMul weight."""
    import paddle_tpu as pt
    from paddle_tpu.onnx.converter import trace_to_onnx

    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(48, 96), pt.nn.ReLU(),
                           pt.nn.Linear(96, 64))
    net.eval()
    x = np.zeros((4, 48), np.float32)
    d = tmp_path_factory.mktemp("int4")
    path = str(d / "mlp.onnx")
    with open(path, "wb") as f:
        f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
    xin = np.random.RandomState(7).randn(4, 48).astype(np.float32)
    np.save(str(d / "x.npy"), xin)
    return path, str(d / "x.npy")


def _run(model_path, x, env=None):
    """One fresh predictor load + run under temporary env overrides
    (None value = unset). The knobs are read at load time, so this is
    the whole A/B harness."""
    from paddle_tpu.core.native import NativePredictor
    saved = {}
    env = env or {}
    for k, v in env.items():
        saved[k] = os.environ.get(k)
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v
    try:
        with NativePredictor(model_path) as p:
            p.set_input(p.input_name(0), x)
            p.run()
            return p.output(0)
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _rel_l2(a, b):
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12))


# ctypes-only runner for the per-process knobs (PTPU_ISA, PTPU_TUNE):
# loads the .so raw so the subprocess never pays a jax import.
_RUNNER = textwrap.dedent("""\
    import ctypes, json, os, sys
    import numpy as np

    so, model, xpath, outpath = sys.argv[1:5]
    lib = ctypes.CDLL(so)
    c = ctypes
    lib.ptpu_predictor_create.restype = c.c_void_p
    lib.ptpu_predictor_create.argtypes = [c.c_char_p, c.c_char_p, c.c_int]
    lib.ptpu_predictor_input_name.restype = c.c_char_p
    lib.ptpu_predictor_input_name.argtypes = [c.c_void_p, c.c_int]
    lib.ptpu_predictor_set_input.argtypes = [
        c.c_void_p, c.c_char_p, c.POINTER(c.c_float),
        c.POINTER(c.c_int64), c.c_int, c.c_char_p, c.c_int]
    lib.ptpu_predictor_run.argtypes = [c.c_void_p, c.c_char_p, c.c_int]
    lib.ptpu_predictor_output_ndim.argtypes = [c.c_void_p, c.c_int]
    lib.ptpu_predictor_output_dims.restype = c.POINTER(c.c_int64)
    lib.ptpu_predictor_output_dims.argtypes = [c.c_void_p, c.c_int]
    lib.ptpu_predictor_output_data.restype = c.POINTER(c.c_float)
    lib.ptpu_predictor_output_data.argtypes = [c.c_void_p, c.c_int]
    lib.ptpu_predictor_destroy.argtypes = [c.c_void_p]
    lib.ptpu_tune_stats_json.restype = c.c_char_p

    err = ctypes.create_string_buffer(512)
    h = lib.ptpu_predictor_create(model.encode(), err, 512)
    assert h, err.value.decode()
    x = np.load(xpath)
    dims = (c.c_int64 * x.ndim)(*x.shape)
    rc = lib.ptpu_predictor_set_input(
        h, lib.ptpu_predictor_input_name(h, 0),
        x.ctypes.data_as(c.POINTER(c.c_float)), dims, x.ndim, err, 512)
    assert rc == 0, err.value.decode()
    rc = lib.ptpu_predictor_run(h, err, 512)
    assert rc == 0, err.value.decode()
    nd = lib.ptpu_predictor_output_ndim(h, 0)
    od = lib.ptpu_predictor_output_dims(h, 0)
    shape = tuple(od[k] for k in range(nd))
    data = lib.ptpu_predictor_output_data(h, 0)
    n = int(np.prod(shape)) if shape else 1
    out = np.ctypeslib.as_array(data, shape=(n,)).reshape(shape).copy()
    np.save(outpath, out)
    stats = json.loads(lib.ptpu_tune_stats_json().decode())
    lib.ptpu_predictor_destroy(h)
    print(json.dumps(stats))
""")


def _run_subprocess(runner, model_path, x_path, out_path, env_extra):
    env = dict(os.environ)
    env.update(env_extra)
    r = subprocess.run(
        [sys.executable, runner, LIB, model_path, x_path, out_path],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def runner_py(tmp_path_factory):
    p = tmp_path_factory.mktemp("int4run") / "runner.py"
    p.write_text(_RUNNER)
    return str(p)


class TestInt4Predictor:
    def test_engages_and_stays_in_bound(self, mlp_artifact):
        model, xp = mlp_artifact
        x = np.load(xp)
        ref = _run(model, x, {"PTPU_INT4": None})
        q = _run(model, x, {"PTPU_INT4": "1"})
        assert q.shape == ref.shape
        # bitwise inequality proves the quantized panels actually ran
        assert not np.array_equal(q, ref), \
            "PTPU_INT4=1 produced bitwise-fp32 outputs: path not engaged"
        assert _rel_l2(q, ref) < REL_L2_BOUND

    def test_int4_ignored_on_tiny_weights(self, built, tmp_path):
        """Below Q4_MIN_ELEMS the packer must keep exact fp32 panels:
        int4 on == int4 off, bitwise."""
        import paddle_tpu as pt
        from paddle_tpu.onnx.converter import trace_to_onnx
        pt.seed(1)
        net = pt.nn.Linear(8, 8)   # 64 elements < 1024
        net.eval()
        x = np.random.RandomState(1).randn(2, 8).astype(np.float32)
        model = str(tmp_path / "tiny.onnx")
        with open(model, "wb") as f:
            f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
        ref = _run(model, x, {"PTPU_INT4": None})
        q = _run(model, x, {"PTPU_INT4": "1"})
        np.testing.assert_array_equal(q, ref)

    def test_quantize_deterministic_across_loads(self, mlp_artifact):
        model, xp = mlp_artifact
        x = np.load(xp)
        a = _run(model, x, {"PTPU_INT4": "1"})
        b = _run(model, x, {"PTPU_INT4": "1"})
        np.testing.assert_array_equal(a, b)

    def test_group_knob_reaches_packer(self, mlp_artifact):
        model, xp = mlp_artifact
        x = np.load(xp)
        ref = _run(model, x, {"PTPU_INT4": None})
        outs = {}
        for g in ("16", "48", "1024"):
            outs[g] = _run(model, x,
                           {"PTPU_INT4": "1", "PTPU_INT4_GROUP": g})
            assert _rel_l2(outs[g], ref) < REL_L2_BOUND, f"group {g}"
        # different group -> different rounding: if these match bitwise
        # the knob never reached pack_b_q4
        assert not np.array_equal(outs["16"], outs["1024"])
        # finer groups track the fp32 weights at least as closely
        assert _rel_l2(outs["16"], ref) <= _rel_l2(outs["1024"], ref) * 1.5

    def test_isa_parity_end_to_end(self, mlp_artifact, runner_py,
                                   tmp_path):
        """PTPU_ISA=generic|avx2|avx512 under PTPU_INT4=1: same
        quantized panels, tolerance-bounded outputs (FMA contraction
        differs per ISA; the C selftest bounds the raw kernels, this
        bounds the full artifact path)."""
        model, xp = mlp_artifact
        outs = {}
        for isa in ("generic", "avx2", "avx512"):
            op = str(tmp_path / f"out_{isa}.npy")
            _run_subprocess(runner_py, model, xp, op,
                            {"PTPU_INT4": "1", "PTPU_ISA": isa})
            outs[isa] = np.load(op)
        base = outs["generic"]
        for isa in ("avx2", "avx512"):
            np.testing.assert_allclose(outs[isa], base, rtol=1e-3,
                                       atol=1e-3, err_msg=isa)


class TestTunePersistence:
    def test_tune_abi_bound(self, built):
        from paddle_tpu.core import native
        if not native.tune_available():
            pytest.skip("stale _native_predictor.so predates tune ABI")
        s = native.tune_stats()
        for k in ("enabled", "entries", "hits", "misses", "probes",
                  "probe_us", "file_loads", "file_rejects",
                  "wrong_cpu", "saves"):
            assert k in s, k

    def test_cold_probe_warm_skip_corrupt_reprobe(self, mlp_artifact,
                                                  runner_py, tmp_path):
        """The persisted-autotuning contract across three processes
        sharing one cache file: cold load probes and saves; warm load
        adopts the file and probes NOTHING; a corrupt cache is
        rejected silently and the load re-probes (never crashes)."""
        model, xp = mlp_artifact
        cache = str(tmp_path / "tune.cache")
        env = {"PTPU_TUNE": "1", "PTPU_TUNE_CACHE": cache,
               "PTPU_INT4": "1"}

        s1 = _run_subprocess(runner_py, model, xp,
                             str(tmp_path / "o1.npy"), env)
        assert s1["enabled"] == 1
        assert s1["probes"] > 0
        assert s1["entries"] > 0
        assert s1["saves"] >= 1
        assert os.path.exists(cache)

        s2 = _run_subprocess(runner_py, model, xp,
                             str(tmp_path / "o2.npy"), env)
        assert s2["file_loads"] == 1
        assert s2["file_entries"] == s1["entries"]
        assert s2["probes"] == 0, \
            f"warm cache still probed: {s2}"
        assert s2["hits"] > 0
        # identical winners -> identical numerics across the processes
        np.testing.assert_array_equal(np.load(str(tmp_path / "o1.npy")),
                                      np.load(str(tmp_path / "o2.npy")))

        # corrupt one payload byte past the header: reject + re-probe
        with open(cache, "r+b") as f:
            f.seek(25)
            b = f.read(1)
            f.seek(25)
            f.write(bytes([b[0] ^ 0xFF]))
        s3 = _run_subprocess(runner_py, model, xp,
                             str(tmp_path / "o3.npy"), env)
        assert s3["file_rejects"] >= 1
        assert s3["file_entries"] == 0
        assert s3["probes"] > 0
        # the re-probe may time a DIFFERENT winner (group included),
        # so only the quality bound holds vs the first process — never
        # bitwise
        o1 = np.load(str(tmp_path / "o1.npy"))
        o3 = np.load(str(tmp_path / "o3.npy"))
        assert _rel_l2(o3, o1) < REL_L2_BOUND
