"""Concurrent serving runtime (csrc/ptpu_serving.cc) + parallel
predictor instances — ISSUE r8 tentpole tests.

The C internals (batcher flush semantics, FIFO de-mux, HMAC socket
round trips) are covered by csrc/ptpu_serving_selftest.cc via
tests/test_native_selftest.py; this module exercises the FULL Python
chain: exported artifact -> create_server -> InferenceClient over TCP
-> numeric parity vs a local predictor, plus the two-instance
concurrency contract (output parity under contention AND the >= 1.3x
aggregate-throughput guard) and the dynamic_shape_fallback stats
counter.
"""
import os
import subprocess
import threading
import time

import numpy as np
import pytest

from _csrc import build_all

import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build():
    build_all()


@pytest.fixture(scope="module")
def built():
    try:
        _build()
    except FileNotFoundError:
        if not os.path.exists(os.path.join(REPO, "paddle_tpu",
                                           "_native_predictor.so")):
            raise
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    from paddle_tpu.core import native
    if not native.serving_available():
        pytest.skip("native serving runtime unavailable")
    return True


@pytest.fixture(scope="module")
def mlp_artifact(built, tmp_path_factory):
    import paddle_tpu as pt
    from paddle_tpu.onnx.converter import trace_to_onnx

    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(32, 64), pt.nn.ReLU(),
                           pt.nn.Linear(64, 8))
    net.eval()
    x = np.zeros((4, 32), np.float32)
    path = str(tmp_path_factory.mktemp("sv") / "mlp.onnx")
    with open(path, "wb") as f:
        f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
    return path


class TestServingServer:
    def test_round_trip_parity_and_counters(self, mlp_artifact):
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.inference import create_server

        ref = NativePredictor(mlp_artifact)
        with create_server(mlp_artifact, max_batch=4, deadline_us=1500,
                           instances=2) as srv:
            cli = srv.client()
            meta = cli.meta()
            assert meta["buckets"] == [1, 2, 4]
            assert meta["inputs"][0]["tail_dims"] == [32]
            rs = np.random.RandomState(0)
            for rows in (1, 2, 3, 4):
                x = rs.randn(rows, 32).astype(np.float32)
                out = cli.infer(x)
                ref.set_input(ref.input_name(0), x)
                ref.run()
                np.testing.assert_allclose(out[0], ref.output(0),
                                           rtol=1e-5, atol=1e-6)
            st = srv.stats()
            assert st["server"]["requests"] == 4
            assert st["server"]["replies"] == 4
            assert st["server"]["req_errors"] == 0
            assert st["batcher"]["batched_requests"] == 4
            # rows=3 had no exact bucket -> padded run counted
            assert st["batcher"]["bucket_miss"] == 1
            # every batched run stayed on a pre-planned arena
            assert st["batcher"]["dynamic_shape_fallback"] == 0
            # e2e latency histogram observed every reply. The server
            # bumps `replies` BEFORE the send and observes e2e_us AFTER
            # it (the latency includes the send), so the last
            # observation may trail the client's read of the reply
            deadline = time.monotonic() + 5.0
            while st["batcher"]["e2e_us"]["count"] < 4 and \
                    time.monotonic() < deadline:
                time.sleep(0.01)
                st = srv.stats()
            assert st["batcher"]["e2e_us"]["count"] == 4
            cli.close()
        # a stopped server raises instead of handing NULL to the C ABI
        with pytest.raises(RuntimeError, match="stopped"):
            srv.stats()
        ref.close()

    def test_pipelined_requests_batch_and_demux(self, mlp_artifact):
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.inference import create_server

        ref = NativePredictor(mlp_artifact)
        with create_server(mlp_artifact, max_batch=4, deadline_us=4000,
                           instances=1) as srv:
            cli = srv.client()
            rs = np.random.RandomState(1)
            reqs = [[rs.randn(1, 32).astype(np.float32)]
                    for _ in range(12)]
            res = cli.infer_many(reqs, depth=6)
            for req, out in zip(reqs, res):
                ref.set_input(ref.input_name(0), req[0])
                ref.run()
                np.testing.assert_allclose(out[0], ref.output(0),
                                           rtol=1e-5, atol=1e-6)
            st = srv.stats()
            assert st["server"]["replies"] == 12
            # pipelining + batching: far fewer runs than requests
            assert st["batcher"]["batches"] < 12
            cli.close()
        ref.close()

    def test_validation_errors_and_bad_authkey(self, mlp_artifact):
        from paddle_tpu.inference import create_server
        from paddle_tpu.inference.serving import (InferenceClient,
                                                  ServingError)

        with create_server(mlp_artifact, max_batch=4,
                           instances=1) as srv:
            cli = srv.client()
            with pytest.raises(ServingError, match="non-batch dims"):
                cli.infer(np.zeros((1, 33), np.float32))
            with pytest.raises(ServingError, match="dtype"):
                cli.infer(np.zeros((1, 32), np.int64))
            with pytest.raises(ServingError, match="max_batch"):
                cli.infer(np.zeros((9, 32), np.float32))
            # the connection survives request-level errors
            out = cli.infer(np.zeros((1, 32), np.float32))
            assert out[0].shape == (1, 8)
            # a pipelined batch with one bad request must not desync:
            # every good reply still lands in its slot, the error
            # surfaces per-entry (or re-raises after draining)
            reqs = [[np.ones((1, 32), np.float32)],
                    [np.ones((1, 33), np.float32)],   # bad dims
                    [np.ones((1, 32), np.float32)]]
            res = cli.infer_many(reqs, depth=3, return_exceptions=True)
            assert res[0][0].shape == (1, 8)
            assert isinstance(res[1], ServingError)
            assert res[2][0].shape == (1, 8)
            with pytest.raises(ServingError, match="non-batch dims"):
                cli.infer_many(reqs, depth=3)
            # ...and the stream is STILL in sync afterwards
            out = cli.infer(np.zeros((1, 32), np.float32))
            assert out[0].shape == (1, 8)
            st = srv.stats()
            assert st["server"]["req_errors"] == 5
            assert st["server"]["replies"] == 6
            cli.close()
            with pytest.raises(ConnectionError):
                InferenceClient(srv.port, b"wrong-key")


class TestParallelInstances:
    """Tentpole contract: N concurrent predictor instances actually
    scale (private sub-pools) with outputs identical under
    contention."""

    def test_two_instances_parity_under_contention(self, built,
                                                   tmp_path):
        import paddle_tpu as pt
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.onnx.converter import trace_to_onnx

        pt.seed(0)
        paths, xs, wants = [], [], []
        for i, width in enumerate((48, 80)):
            net = pt.nn.Sequential(pt.nn.Linear(32, width), pt.nn.ReLU(),
                                   pt.nn.Linear(width, 8))
            net.eval()
            x = np.random.RandomState(20 + i).randn(16, 32).astype(
                np.float32)
            path = str(tmp_path / f"m{i}.onnx")
            with open(path, "wb") as f:
                f.write(trace_to_onnx(lambda a, n=net: n(a),
                                      (jnp.asarray(x),)))
            p = NativePredictor(path)
            p.set_input(p.input_name(0), x)
            p.run()
            wants.append(p.output(0))
            p.close()
            paths.append(path)
            xs.append(x)

        failures = []

        def serve(i):
            try:
                with NativePredictor(paths[i], threads=2) as p:
                    name = p.input_name(0)
                    for _ in range(50):
                        p.set_input(name, xs[i])
                        p.run()
                        np.testing.assert_array_equal(p.output(0),
                                                      wants[i])
            except Exception as e:  # noqa: BLE001
                failures.append((i, e))

        ts = [threading.Thread(target=serve, args=(i,))
              for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert not failures, failures

    def test_two_instance_aggregate_speedup(self, built, tmp_path):
        """>= 1.3x aggregate throughput: two instances on two threads
        with single-thread private pools vs the same work serialized.
        (The C selftest asserts the same bound on the raw ABI; this is
        the ctypes/NativePredictor face.) On a 1–2-core box two host
        threads time-slice each other and 1.3x is physically out of
        reach (r14/r15 ran on 1-core machines — ROADMAP caveat), so
        the throughput gate softens to a gross-serialization floor
        while the concurrent-correctness exercise still runs."""
        import paddle_tpu as pt
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.onnx.converter import trace_to_onnx

        pt.seed(0)
        # sized so that a leg is long enough to judge: at 64 x 256 x 20
        # runs a leg lasted ~25 ms, and here two threads need a few
        # hundred ms before they run at full speed side by side (~1.0x
        # measured until then, ~1.9x after)
        net = pt.nn.Sequential(pt.nn.Linear(512, 512), pt.nn.ReLU(),
                               pt.nn.Linear(512, 512))
        net.eval()
        x = np.random.RandomState(0).randn(256, 512).astype(np.float32)
        path = str(tmp_path / "wide.onnx")
        with open(path, "wb") as f:
            f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))

        ps = [NativePredictor(path, threads=1) for _ in range(2)]
        name = ps[0].input_name(0)

        def loop(p, iters=60):
            for _ in range(iters):
                p.set_input(name, x)
                p.run()

        def concurrent():
            ts = [threading.Thread(target=loop, args=(p,)) for p in ps]
            for t in ts:
                t.start()
            for t in ts:
                t.join()

        for p in ps:
            loop(p, 3)  # warm
        concurrent()    # warm the side-by-side leg too
        best = 0.0
        for _ in range(3):
            t0 = time.perf_counter()
            for p in ps:
                loop(p)
            serial = time.perf_counter() - t0
            t0 = time.perf_counter()
            concurrent()
            conc = time.perf_counter() - t0
            best = max(best, serial / conc)
        for p in ps:
            p.close()
        cores = len(os.sched_getaffinity(0)) if hasattr(
            os, "sched_getaffinity") else (os.cpu_count() or 1)
        if cores >= 3:
            assert best >= 1.3, f"aggregate speedup {best:.2f}x < 1.3x"
        else:
            assert best >= 0.5, (
                f"{cores}-core box: concurrent leg {best:.2f}x of "
                "serial — gross serialization even without spare cores")


class TestDynamicShapeFallback:
    def test_counter_in_stats_json(self, built, tmp_path):
        """Satellite: runs that miss the planned-arena path are
        observable from ptpu_predictor_stats_json."""
        import paddle_tpu as pt
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.onnx.converter import trace_to_onnx

        pt.seed(0)
        net = pt.nn.Sequential(pt.nn.Linear(8, 4))
        net.eval()
        x4 = np.zeros((4, 8), np.float32)
        path = str(tmp_path / "m.onnx")
        with open(path, "wb") as f:
            f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x4),)))
        with NativePredictor(path) as p:
            name = p.input_name(0)
            p.set_input(name, x4)
            p.run()                       # planned shape: no fallback
            assert p.stats()["dynamic_shape_fallback"] == 0
            assert p.dynamic_fallbacks == 0
            p.set_input(name, np.zeros((2, 8), np.float32))
            p.run()                       # off-plan batch: fallback
            p.set_input(name, x4)
            p.run()
            st = p.stats()
            assert st["dynamic_shape_fallback"] == 1
            assert p.dynamic_fallbacks == 1
            p.stats_reset()
            assert p.stats()["dynamic_shape_fallback"] == 0


@pytest.fixture(scope="module")
def wide_artifact(built, tmp_path_factory):
    """32 -> 16384 linear: one 4-row reply is ~256KB, big enough to
    jam the 32KB sockbufs the reply-pinning tests run under."""
    import paddle_tpu as pt
    from paddle_tpu.onnx.converter import trace_to_onnx

    pt.seed(3)
    net = pt.nn.Linear(32, 16384)
    net.eval()
    x = np.zeros((4, 32), np.float32)
    path = str(tmp_path_factory.mktemp("svpin") / "wide.onnx")
    with open(path, "wb") as f:
        f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
    return path


class TestReplyPinning:
    """ISSUE 17 zero-copy replies over the full Python chain — twins
    of the native pinning selftests. Replies ship pinned predictor
    output segments (no staging copy), so the output holder must stay
    alive until the net core flushes the last byte: a stalled reader,
    a deferred request's pinned inbuf, and a connection dying with a
    pinned reply queued must all keep exact parity."""

    def test_slow_reader_reply_survives_pool_recycle(
            self, wide_artifact, monkeypatch):
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.inference import create_server

        monkeypatch.setenv("PTPU_NET_SOCKBUF", "32768")
        ref = NativePredictor(wide_artifact)
        with create_server(wide_artifact, max_batch=4, deadline_us=500,
                           instances=1) as srv:
            slow = srv.client()
            fast = srv.client()
            rs = np.random.RandomState(7)
            x = rs.randn(4, 32).astype(np.float32)
            # fire the big request and do NOT read: the scatter reply
            # jams the tiny sockbufs with its tail still pinned
            slow._send_frame(slow._encode_request(1, [x]))
            time.sleep(0.05)
            # meanwhile other batches recycle output holders through
            # the bounded pin pool on the same instance
            for _ in range(6):
                xf = rs.randn(1, 32).astype(np.float32)
                out = fast.infer(xf)
                ref.set_input(ref.input_name(0), xf)
                ref.run()
                np.testing.assert_allclose(out[0], ref.output(0),
                                           rtol=1e-5, atol=1e-6)
            # now drain the stalled reply: still the ORIGINAL rows
            rid, outs = slow._decode_reply(slow._read_frame())
            assert rid == 1
            ref.set_input(ref.input_name(0), x)
            ref.run()
            np.testing.assert_allclose(outs[0], ref.output(0),
                                       rtol=1e-5, atol=1e-6)
            st = srv.stats()
            assert st["server"]["replies"] == 7
            assert st["batcher"]["dynamic_shape_fallback"] == 0
            slow.close()
            fast.close()
        ref.close()

    def test_defer_retry_keeps_order_and_parity(self, mlp_artifact):
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.inference import create_server

        ref = NativePredictor(mlp_artifact)
        # max_batch=1 -> 64-row request queue; 300 pipelined rows
        # overflow it, so overflow frames ride the kDefer retry path
        # with their input views borrowing the PINNED inbuf
        with create_server(mlp_artifact, max_batch=1, deadline_us=200,
                           instances=1) as srv:
            cli = srv.client()
            rs = np.random.RandomState(11)
            reqs = [[rs.randn(1, 32).astype(np.float32)]
                    for _ in range(300)]
            res = cli.infer_many(reqs, depth=300)
            for req, out in zip(reqs, res):
                ref.set_input(ref.input_name(0), req[0])
                ref.run()
                np.testing.assert_allclose(out[0], ref.output(0),
                                           rtol=1e-5, atol=1e-6)
            st = srv.stats()
            assert st["server"]["requests"] == 300
            assert st["server"]["replies"] == 300
            assert st["server"]["req_errors"] == 0
            cli.close()
        ref.close()

    def test_conn_death_with_pinned_reply(self, wide_artifact,
                                          monkeypatch):
        from paddle_tpu.core.native import NativePredictor
        from paddle_tpu.inference import create_server

        monkeypatch.setenv("PTPU_NET_SOCKBUF", "32768")
        ref = NativePredictor(wide_artifact)
        with create_server(wide_artifact, max_batch=4, deadline_us=500,
                           instances=1) as srv:
            rs = np.random.RandomState(13)
            doomed = srv.client()
            doomed._send_frame(
                doomed._encode_request(7, [rs.randn(4, 32)
                                           .astype(np.float32)]))
            time.sleep(0.05)   # batch runs, reply jams the sockbufs
            doomed.close()     # ... die with the payload still pinned
            # the server shrugs it off: fresh client, exact answers,
            # and more rounds re-exercise the released pool slot
            ok = srv.client()
            for _ in range(3):
                x = rs.randn(4, 32).astype(np.float32)
                out = ok.infer(x)
                ref.set_input(ref.input_name(0), x)
                ref.run()
                np.testing.assert_allclose(out[0], ref.output(0),
                                           rtol=1e-5, atol=1e-6)
            st = srv.stats()
            assert st["server"]["requests"] == 4
            ok.close()
        ref.close()


_TOPO_SCRIPT = r"""
import json
import sys

import numpy as np

sys.path.insert(0, sys.argv[2])
from paddle_tpu.inference.serving import create_server

srv = create_server(sys.argv[1], max_batch=4, deadline_us=1500,
                    instances=2)
cli = srv.client()
rs = np.random.RandomState(0)
for rows in (1, 2, 3, 4, 1, 4):
    cli.infer(rs.randn(rows, 32).astype(np.float32))
st = srv.stats()
sv, bt = st["server"], st["batcher"]
print("TOPO " + json.dumps({
    "requests": sv["requests"], "replies": sv["replies"],
    "req_errors": sv["req_errors"],
    "bytes_in": sv["bytes_in"], "bytes_out": sv["bytes_out"],
    "batches": bt["batches"],
    "batched_requests": bt["batched_requests"],
    "bucket_miss": bt["bucket_miss"],
    "dynamic_shape_fallback": bt["dynamic_shape_fallback"],
    "batch_fill_sum": bt["batch_fill"]["sum"],
    "batch_fill_count": bt["batch_fill"]["count"],
}, sort_keys=True))
cli.close()
srv.stop()
"""


class TestTopologyPlacement:
    """ISSUE 17c: topology-aware placement is an optimization with a
    hard no-behavior-change contract — flipping PTPU_TOPO=0 vs the
    default probe must leave every serving counter identical for an
    identical request sequence (placement may move threads on a
    multi-node box, the wire/batcher arithmetic may never change; on a
    single-node box the probe degrades and both runs are the same code
    path end to end). The probe caches per process, so each side runs
    in a fresh subprocess."""

    def _counters(self, model, topo_env):
        import json
        import sys as _sys

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH",
                                                        "")
        env.pop("PTPU_TOPO", None)
        env.pop("XLA_FLAGS", None)
        if topo_env is not None:
            env["PTPU_TOPO"] = topo_env
        r = subprocess.run([_sys.executable, "-c", _TOPO_SCRIPT,
                            model, REPO], env=env, cwd=REPO,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, \
            f"stdout:{r.stdout[-2000:]}\nstderr:{r.stderr[-2000:]}"
        line = [ln for ln in r.stdout.splitlines()
                if ln.startswith("TOPO ")][-1]
        return json.loads(line[len("TOPO "):])

    def test_topo_off_vs_default_identical_counters(self,
                                                    mlp_artifact):
        default = self._counters(mlp_artifact, None)
        forced_off = self._counters(mlp_artifact, "0")
        assert default == forced_off, (default, forced_off)
        assert default["requests"] == 6
        assert default["replies"] == 6
        assert default["bucket_miss"] == 1


@pytest.fixture(scope="module")
def decode_artifacts(built, tmp_path_factory):
    """GPT-tiny decode artifact (batch 8, context 48) + its full-seq
    twin — the ISSUE r12 paged-engine fixture set."""
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       export_gpt_decode, gpt_tiny)

    pt.seed(0)
    cfg = gpt_tiny(dtype=jnp.float32, dropout=0.0)
    model = GPTForPretraining(cfg)
    model.eval()
    d = tmp_path_factory.mktemp("dec")
    dec = export_gpt_decode(model, str(d / "dec"), batch=8, context=48)
    return dec, cfg


class TestPagedDecode:
    """ISSUE r12: paged-KV continuous-batching generation engine —
    Python-chain twins of csrc/ptpu_serving_selftest.cc's paged legs
    (the C side drives the hand-rolled running-sum artifact; here the
    REAL GPT export exercises the PtpuPagedAttention direct path)."""

    def test_paged_meta_ladder_and_exact_parity(self, decode_artifacts,
                                                mlp_artifact):
        """The decode plane defaults to the paged engine with a full
        step-bucket ladder, the attention graph rewrites onto the
        block-table read path, and served logits are EXACTLY the
        unpaged (r9 kv_plan) engine's at the same step batch."""
        from paddle_tpu import inference
        from paddle_tpu.core.native import NativePredictor

        dec, _ = decode_artifacts
        srv = inference.create_server(mlp_artifact, max_batch=2,
                                      instances=1, decode_model=dec)
        try:
            meta = srv.config()["decode"]
            assert meta["paged"] == 1
            assert meta["direct"] == 1
            assert meta["step_buckets"] == [1, 2, 4, 8]
            cli = srv.client()
            toks = list(range(3, 23))
            # single-session steps run on bucket 1: reference is the
            # unpaged engine at batch_override=1
            sess = cli.decode_open()
            got = [np.asarray(cli.decode_step(sess, t)) for t in toks]
            with NativePredictor(dec, batch_override=1) as ref:
                ref.kv_plan(2)
                rs = ref.kv_open()
                want = [ref.decode_step([rs], [t]).copy()[0]
                        for t in toks]
            for g, w in zip(got, want):
                assert np.array_equal(g, w)
            cli.decode_close(sess)
            cli.close()
        finally:
            srv.stop()

    def test_open2_prefill_prefix_cache_and_fork(self, decode_artifacts,
                                                 mlp_artifact):
        """OPEN2 server-side prefill equals client-driven stepping;
        a repeated prompt adopts full pages from the prefix cache and
        measurably skips prefill compute; fork clones a session
        copy-on-write."""
        from paddle_tpu import inference

        dec, _ = decode_artifacts
        srv = inference.create_server(mlp_artifact, max_batch=2,
                                      instances=1, decode_model=dec)
        try:
            cli = srv.client()
            prompt = list(range(5, 41))   # 36 tokens = 2 full pages +
            s1, lg1, ad1 = cli.decode_open(prompt=prompt)
            assert ad1 == 0
            # teacher-forced reference: old-style open + steps
            s2 = cli.decode_open()
            for t in prompt:
                ref = cli.decode_step(s2, t)
            assert np.array_equal(lg1, np.asarray(ref))
            # warm open: two full 16-token pages adopted, same logits
            s3, lg3, ad3 = cli.decode_open(prompt=prompt)
            assert ad3 == 32
            assert np.array_equal(lg3, lg1)
            st = srv.stats()["decode"]
            assert st["prefills"] == 2
            assert st["prefill_adopted"] == 32
            assert st["pool"]["prefix_hits"] == 2
            assert st["pool"]["pages_in_use"] > 0
            assert st["pool"]["pages_total"] >= st["pool"]["pages_in_use"]
            # fork: same token steps to identical logits, then the
            # histories diverge independently (COW)
            f1 = cli.decode_fork(s1)
            a = cli.decode_step(s1, 7)
            b = cli.decode_step(f1, 7)
            assert np.array_equal(np.asarray(a), np.asarray(b))
            a2 = cli.decode_step(s1, 9)
            b2 = cli.decode_step(f1, 11)
            assert not np.array_equal(np.asarray(a2), np.asarray(b2))
            assert srv.stats()["decode"]["pool"]["cow_copies"] >= 1
            for s in (s1, s2, s3, f1):
                cli.decode_close(s)
            cli.close()
        finally:
            srv.stop()

    def test_pool_exhaustion_backpressure_and_eviction(
            self, decode_artifacts, mlp_artifact):
        """A full pool answers steps with a soft retryable error (the
        session survives); closing another session reclaims pages and
        unblocks it. Session eviction tombstones answer 'evicted'."""
        from paddle_tpu import inference
        from paddle_tpu.inference.serving import ServingError

        dec, _ = decode_artifacts
        os.environ["PTPU_KV_POOL_TOKENS"] = "64"   # 4 pages of 16
        os.environ["PTPU_KV_SESSIONS"] = "3"
        try:
            srv = inference.create_server(mlp_artifact, max_batch=2,
                                          instances=1, decode_model=dec)
        finally:
            del os.environ["PTPU_KV_POOL_TOKENS"]
            del os.environ["PTPU_KV_SESSIONS"]
        try:
            cli = srv.client()
            # two sessions fill all four pages (2 x 17 tokens)
            sa = cli.decode_open()
            sb = cli.decode_open()
            for t in range(17):
                cli.decode_step(sa, t)
                cli.decode_step(sb, t)
            # sa to a page boundary (len 32): its next step needs a
            # 5th page the 4-page pool cannot provide
            for t in range(15):
                cli.decode_step(sa, t)
            with pytest.raises(ServingError, match="kv pool exhausted"):
                cli.decode_step(sa, 99)
            assert srv.stats()["decode"]["pool_exhausted"] >= 1
            # reclaim: closing sb frees its pages; sa proceeds
            cli.decode_close(sb)
            cli.decode_step(sa, 99)
            # eviction at the session cap: sa is LRU after sc opens
            sc = cli.decode_open()
            sd = cli.decode_open()
            se = cli.decode_open()   # 4th live -> evicts LRU (sa)
            assert srv.stats()["decode"]["evictions"] == 1
            with pytest.raises(ServingError, match="evicted"):
                cli.decode_step(sa, 1)
            # the evicted session's pages returned to the pool
            cli.decode_step(sc, 1)
            for s in (sc, sd, se):
                cli.decode_close(s)
            cli.close()
        finally:
            srv.stop()

    def test_trim_rollback_edges(self, decode_artifacts):
        """ISSUE 13 satellite (Python twin of the C selftest's
        kv_trim legs, on the REAL GPT export + PtpuPagedAttention):
        trim to a mid-page boundary, trim back across a SHARED
        prefix-cache page (must COW on divergence, never mutate the
        published page), and trim-to-zero then continue — logits after
        every rollback are bit-identical to a fresh session fed the
        surviving history."""
        from paddle_tpu.core.native import KvPool, NativePredictor

        dec, _ = decode_artifacts
        pool = KvPool(pool_tokens=16 * 48, page_tokens=16,
                      max_sessions=16)
        p = NativePredictor(dec, batch_override=1)
        p.kv_attach(pool)
        assert p.kv_width() == 1

        def feed(sid, toks):
            out = None
            for t in toks:
                out = p.decode_step([sid], [t]).copy()
            return out

        hist = list(range(3, 23))          # 20 tokens: page + 4
        a = pool.open()
        feed(a, hist)
        assert pool.len(a) == 20
        # (a) mid-page trim: keep 10, re-decode the suffix — logits
        # match a fresh session with the same 10-token prefix exactly
        p.kv_trim(a, 10)
        assert pool.len(a) == 10
        got = feed(a, [40, 41])
        b = pool.open()
        want = feed(b, hist[:10] + [40, 41])
        assert np.array_equal(got, want)
        # (b) publish a 16-token page, adopt it, trim back INTO it,
        # then diverge: COW must fire and the published page must
        # still serve the ORIGINAL prefix to a later adopter
        prompt = hist[:10] + [40, 41] + list(range(50, 55))  # 17 toks
        feed(b, prompt[12:])               # b now holds the full prompt
        pool.publish(b, prompt[:17])
        cows0 = pool.stats()["cow_copies"]
        c = pool.open()
        assert pool.adopt(c, prompt) == 16
        p.kv_trim(c, 8)                    # back inside the shared page
        got = feed(c, prompt[8:10])        # diverging writes -> COW
        assert pool.stats()["cow_copies"] == cows0 + 1
        want = feed(pool.open(), prompt[:10])
        assert np.array_equal(got, want)
        d = pool.open()
        assert pool.adopt(d, prompt) == 16  # original page intact
        assert np.array_equal(feed(d, [prompt[16]]),
                              feed(pool.open(), prompt[:17]))
        # (c) trim to zero, then continue decoding from scratch
        p.kv_trim(d, 0)
        assert pool.len(d) == 0
        assert np.array_equal(feed(d, hist[:3]),
                              feed(pool.open(), hist[:3]))
        assert pool.stats()["trims"] >= 3
        p.close()
        pool.close()

    def test_legacy_fixed_slot_engine_env_fallback(
            self, decode_artifacts, mlp_artifact):
        """PTPU_KV_PAGED=0 keeps the r9 fixed-slot engine: no pool in
        the stats, single step bucket, old wire ops still exact."""
        from paddle_tpu import inference
        from paddle_tpu.inference.serving import ServingError

        dec, _ = decode_artifacts
        os.environ["PTPU_KV_PAGED"] = "0"
        try:
            srv = inference.create_server(mlp_artifact, max_batch=2,
                                          instances=1, decode_model=dec,
                                          kv_sessions=4)
        finally:
            del os.environ["PTPU_KV_PAGED"]
        try:
            meta = srv.config()["decode"]
            assert meta["paged"] == 0
            assert meta["step_buckets"] == [8]
            cli = srv.client()
            s = cli.decode_open()
            lg = cli.decode_step(s, 5)
            assert np.asarray(lg).size > 0
            assert "pool" not in srv.stats()["decode"]
            # the paged-only ops degrade with a clear error
            with pytest.raises(ServingError, match="paged KV engine"):
                cli.decode_fork(s)
            cli.decode_close(s)
            cli.close()
        finally:
            srv.stop()


@pytest.fixture(scope="module")
def spec_artifacts(built, decode_artifacts, tmp_path_factory):
    """Speculative-decoding artifact set (ISSUE 13): the target's
    width-1 step (shared with decode_artifacts), the target exported
    at width k+1 = 4 (the verify pass), and a SMALLER draft model's
    width-1 step — all at context 48."""
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import (GPTForPretraining,
                                       export_gpt_decode, gpt_tiny)

    dec, cfg = decode_artifacts
    pt.seed(0)
    model = GPTForPretraining(cfg)   # pt.seed(0) replays the SAME
    model.eval()                     # weights decode_artifacts traced
    pt.seed(7)
    dcfg = gpt_tiny(dtype=jnp.float32, dropout=0.0, hidden_size=32,
                    num_layers=1, num_heads=2)
    draft = GPTForPretraining(dcfg)
    draft.eval()
    d = tmp_path_factory.mktemp("spec")
    ver = export_gpt_decode(model, str(d / "ver"), batch=4,
                            context=48, width=4)
    drf = export_gpt_decode(draft, str(d / "drf"), batch=8,
                            context=48)
    return dec, ver, drf


class TestSpeculativeDecode:
    """ISSUE 13 tentpole: draft/verify speculative decoding with COW
    rollback — exact-parity and protocol-guard tests over the wire."""

    def _server(self, mlp_artifact, dec, ver, drf, **kw):
        from paddle_tpu import inference
        return inference.create_server(mlp_artifact, max_batch=2,
                                       instances=1, decode_model=dec,
                                       spec_model=drf,
                                       spec_verify_model=ver,
                                       kv_sessions=16, **kw)

    def test_greedy_parity_and_round_counters(self, spec_artifacts,
                                              mlp_artifact):
        """Speculatively generated greedy tokens are BYTE-IDENTICAL
        to the non-speculative greedy sequence from the same prompt,
        rounds commit accepted+1 tokens each, and the accept counters
        reconcile exactly."""
        dec, ver, drf = spec_artifacts
        srv = self._server(mlp_artifact, dec, ver, drf)
        try:
            meta = srv.config()["decode"]["spec"]
            assert meta["k"] == 3 and meta["verify_width"] == 4
            assert meta["verify_buckets"] == [1, 2, 4]
            cli = srv.client()
            prompt = [7, 3, 11, 2]
            N = 30
            s0, lg, _ = cli.decode_open(prompt=prompt)
            ref = [int(np.argmax(lg))]
            while len(ref) < N:
                ref.append(int(np.argmax(
                    cli.decode_step(s0, ref[-1]))))
            cli.decode_close(s0)
            s1, toks, _ = cli.spec_open(prompt)
            out = list(toks)
            rounds = 0
            accepted = 0
            while len(out) < N:
                t, a = cli.spec_step(s1)
                assert len(t) == a + 1
                out.extend(t)
                accepted += a
                rounds += 1
            assert out[:N] == ref
            st = srv.stats()["decode"]
            assert st["spec_rounds"] == rounds
            assert st["spec_accepted"] == accepted
            assert st["spec_tokens"] == accepted + rounds
            if st["spec_fallbacks"] == 0:
                assert st["spec_proposed"] == 3 * rounds
            assert st["spec_draft_steps"] >= rounds
            # the pool rolled back rejected suffixes via trims
            if accepted < 3 * rounds:
                assert st["pool"]["trims"] >= 1
            cli.decode_close(s1)
            cli.close()
        finally:
            srv.stop()

    def test_sampling_seeded_determinism(self, spec_artifacts,
                                         mlp_artifact):
        """The server-side modified-rejection sampler is a pure
        function of (prompt, seed): identical seeds replay the exact
        token stream, different seeds diverge."""
        dec, ver, drf = spec_artifacts
        srv = self._server(mlp_artifact, dec, ver, drf)
        try:
            cli = srv.client()

            def gen(seed, n=16):
                s, toks, _ = cli.spec_open([5, 9], seed=seed,
                                           sample=True)
                out = list(toks)
                while len(out) < n:
                    t, _ = cli.spec_step(s)
                    out.extend(t)
                cli.decode_close(s)
                return out[:n]

            a, b, c = gen(1234), gen(1234), gen(99)
            assert a == b
            assert a != c
            cli.close()
        finally:
            srv.stop()

    def test_protocol_guards(self, spec_artifacts, mlp_artifact):
        """Plane separation: plain steps on a spec session (and spec
        steps on a plain session) are refused; spec sessions cannot
        fork; pipelined spec rounds across sessions interleave through
        one flush."""
        from paddle_tpu.inference.serving import ServingError

        dec, ver, drf = spec_artifacts
        srv = self._server(mlp_artifact, dec, ver, drf)
        try:
            cli = srv.client()
            s1, t1, _ = cli.spec_open([3, 4])
            with pytest.raises(ServingError,
                               match="use DECODE_SPEC_STEP"):
                cli.decode_step(s1, 1)
            with pytest.raises(ServingError, match="fork"):
                cli.decode_fork(s1)
            plain = cli.decode_open()
            with pytest.raises(ServingError,
                               match="not a speculative session"):
                cli.spec_step(plain)
            # pipelined rounds across several spec sessions
            ss = [cli.spec_open([3, 4 + i])[0] for i in range(3)]
            outs = cli.spec_step_many([s1] + ss)
            assert len(outs) == 4
            for toks, acc in outs:
                assert len(toks) == acc + 1
            for s in [s1, plain] + ss:
                cli.decode_close(s)
            cli.close()
        finally:
            srv.stop()

    def test_spec_requires_paged_engine(self, spec_artifacts,
                                        mlp_artifact):
        """The r9 fixed-slot engine cannot share sessions across the
        verify/step predictors: starting a spec server under
        PTPU_KV_PAGED=0 fails with a clear error."""
        dec, ver, drf = spec_artifacts
        os.environ["PTPU_KV_PAGED"] = "0"
        try:
            with pytest.raises(RuntimeError, match="paged"):
                self._server(mlp_artifact, dec, ver, drf)
        finally:
            del os.environ["PTPU_KV_PAGED"]


class TestKvTiering:
    """ISSUE 19 tentpole: KV-cache tiering + session hibernation —
    spill idle sessions to the mmap'd disk tier, restore them
    transparently on the next step, persist the prefix-adopt index
    across restarts. Python-chain twins of csrc's
    test_kvpool_spill_hibernate, on the REAL GPT export."""

    def test_hibernate_restore_logits_exact(self, decode_artifacts,
                                            tmp_path):
        """Pool-level round trip: a hibernated-then-restored session
        continues its history with logits BIT-IDENTICAL to an
        uninterrupted twin; a corrupted record is rejected whole (the
        sleeping session survives); drop releases without restore."""
        from paddle_tpu.core.native import KvPool, NativePredictor

        dec, _ = decode_artifacts
        pool = KvPool(pool_tokens=16 * 48, page_tokens=16,
                      max_sessions=8)
        p = NativePredictor(dec, batch_override=1)
        p.kv_attach(pool)
        pool.spill_attach(str(tmp_path / "spill.bin"))

        def feed(sid, toks):
            out = None
            for t in toks:
                out = p.decode_step([sid], [t]).copy()
            return out

        hist = list(range(3, 23))          # 20 tokens: page + 4
        a = pool.open()
        feed(a, hist)
        rec = pool.hibernate(a)
        assert len(rec) > 0
        assert pool.hibernated() == 1
        assert pool.len(a) == -1           # the pool slot is gone
        # a flipped byte rejects WHOLE — and the record stays usable
        bad = bytearray(rec)
        bad[len(bad) // 2] ^= 0x40
        with pytest.raises(RuntimeError, match="corrupt"):
            pool.restore(bytes(bad))
        assert pool.hibernated() == 1
        a2 = pool.restore(rec)
        assert pool.hibernated() == 0
        assert pool.len(a2) == 20
        got = feed(a2, [40, 41])
        want = feed(pool.open(), hist + [40, 41])
        assert np.array_equal(got, want)
        st = pool.stats()
        assert st["hibernates"] == 1
        assert st["restores"] == 1
        assert st["spill_attached"] == 1
        assert st["spill_writes"] >= 1 and st["spill_reads"] >= 1
        # drop: the spill state releases without a restore
        b = pool.open()
        feed(b, [1, 2, 3])
        rec2 = pool.hibernate(b)
        assert pool.hibernated() == 1
        pool.hibernate_drop(rec2)
        assert pool.hibernated() == 0
        assert pool.stats()["hib_drops"] == 1
        p.close()
        pool.close()

    def test_server_hibernates_instead_of_evicting(
            self, decode_artifacts, mlp_artifact, tmp_path):
        """With PTPU_KV_SPILL_PATH set, session-table pressure
        hibernates the LRU session instead of tombstone-evicting it,
        and the next step on the sleeping session transparently
        restores it — logits exactly as if it never left RAM."""
        from paddle_tpu import inference
        from paddle_tpu.core.native import NativePredictor

        dec, _ = decode_artifacts
        os.environ["PTPU_KV_SPILL_PATH"] = str(tmp_path / "sv.spill")
        os.environ["PTPU_KV_SESSIONS"] = "3"
        try:
            srv = inference.create_server(mlp_artifact, max_batch=2,
                                          instances=1, decode_model=dec)
        finally:
            del os.environ["PTPU_KV_SPILL_PATH"]
            del os.environ["PTPU_KV_SESSIONS"]
        try:
            cli = srv.client()
            toks = list(range(3, 9))
            sa = cli.decode_open()
            got = [np.asarray(cli.decode_step(sa, t)).copy()
                   for t in toks[:5]]
            # fill the 3-slot table: sa (the LRU) must hibernate, not
            # tombstone
            others = [cli.decode_open() for _ in range(3)]
            st = srv.stats()["decode"]
            assert st["hibernates"] >= 1
            assert st["evictions"] == 0
            assert st["sessions_hibernated"] >= 1
            assert (st["sessions_resident"]
                    + st["sessions_hibernated"]) == 4
            # the hibernated session answers its next step as if it
            # never left (transparent restore, not 'evicted')
            got.append(np.asarray(cli.decode_step(sa, toks[5])).copy())
            st = srv.stats()["decode"]
            assert st["restores"] >= 1
            assert st["restore_us"]["count"] >= 1
            with NativePredictor(dec, batch_override=1) as ref:
                ref.kv_plan(2)
                rs = ref.kv_open()
                want = [ref.decode_step([rs], [t]).copy()[0]
                        for t in toks]
            for g, w in zip(got, want):
                assert np.array_equal(g, w)
            for s in [sa] + others:
                cli.decode_close(s)
            cli.close()
        finally:
            srv.stop()

    def test_spec_session_hibernate_restore_planes(
            self, spec_artifacts, mlp_artifact, tmp_path):
        """A speculative session hibernates BOTH planes (target +
        draft twin) and restores them together: the greedy stream
        across the sleep equals the non-speculative reference, and
        the plane guards survive the round trip."""
        from paddle_tpu import inference
        from paddle_tpu.inference.serving import ServingError

        dec, ver, drf = spec_artifacts
        os.environ["PTPU_KV_SPILL_PATH"] = str(tmp_path / "spec.spill")
        try:
            srv = inference.create_server(mlp_artifact, max_batch=2,
                                          instances=1, decode_model=dec,
                                          spec_model=drf,
                                          spec_verify_model=ver,
                                          kv_sessions=2)
        finally:
            del os.environ["PTPU_KV_SPILL_PATH"]
        try:
            cli = srv.client()
            prompt = [7, 3, 11, 2]
            N = 12
            s0, lg, _ = cli.decode_open(prompt=prompt)
            ref = [int(np.argmax(lg))]
            while len(ref) < N:
                ref.append(int(np.argmax(cli.decode_step(s0, ref[-1]))))
            cli.decode_close(s0)
            s1, toks, _ = cli.spec_open(prompt)
            out = list(toks)
            t, _ = cli.spec_step(s1)
            out.extend(t)
            # churn the 2-slot table: the idle spec session sleeps
            s2 = cli.decode_open()
            s3 = cli.decode_open()
            assert srv.stats()["decode"]["hibernates"] >= 1
            # next round transparently restores target AND draft
            while len(out) < N:
                t, _ = cli.spec_step(s1)
                out.extend(t)
            assert out[:N] == ref
            st = srv.stats()["decode"]
            assert st["restores"] >= 1
            assert st["evictions"] == 0
            # spec linkage survived the sleep: plane guard intact
            with pytest.raises(ServingError,
                               match="use DECODE_SPEC_STEP"):
                cli.decode_step(s1, 1)
            for s in (s1, s2, s3):
                cli.decode_close(s)
            cli.close()
        finally:
            srv.stop()

    def test_prefix_persist_restart_warm(self, decode_artifacts,
                                         mlp_artifact, tmp_path):
        """PTPU_KV_PREFIX_PERSIST survives a server restart: the
        second server adopts the full prompt pages cold-start (hit
        rate >= pre-restart) and serves byte-identical logits —
        the warmed cache can only miss, never serve wrong KV."""
        from paddle_tpu import inference

        dec, _ = decode_artifacts
        pp = str(tmp_path / "prefix.bin")
        prompt = list(range(5, 41))        # 36 tokens = 2 full pages
        os.environ["PTPU_KV_PREFIX_PERSIST"] = pp
        try:
            srv = inference.create_server(mlp_artifact, max_batch=2,
                                          instances=1, decode_model=dec)
            try:
                cli = srv.client()
                s1, lg1, ad1 = cli.decode_open(prompt=prompt)
                assert ad1 == 0            # cold
                lg1 = np.asarray(lg1).copy()
                cli.decode_close(s1)
                cli.close()
            finally:
                srv.stop()                 # persists the adopt index
            assert os.path.exists(pp)
            srv = inference.create_server(mlp_artifact, max_batch=2,
                                          instances=1, decode_model=dec)
            try:
                assert (srv.stats()["decode"]["pool"]
                        ["prefix_persist_loaded"]) >= 1
                cli = srv.client()
                s2, lg2, ad2 = cli.decode_open(prompt=prompt)
                assert ad2 == 32           # restart-warm full-page hit
                assert np.array_equal(np.asarray(lg2), lg1)
                assert (srv.stats()["decode"]["pool"]
                        ["prefix_hits"]) >= 1
                cli.decode_close(s2)
                cli.close()
            finally:
                srv.stop()
        finally:
            del os.environ["PTPU_KV_PREFIX_PERSIST"]
