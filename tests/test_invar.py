"""ptpu_invar — declarative counter-conservation laws (ISSUE 20).

The C internals (quiesce gate at every Stop(), stats_reset racing
live traffic, the ABI pair, the kill switch) are covered by
csrc/ptpu_serving_selftest.cc / ptpu_ps_selftest.cc via make
selftest; this module exercises the cross-language seams:

  * the manifest TWIN: profiler/stats.py INVAR_MANIFEST is
    byte-identical to what BOTH live .so's export via
    ptpu_invar_manifest() — the static checker proves token parity
    against the checkout, this proves it against the artifacts;
  * report parity: the Python evaluator (invar_check) and the C
    engine (ptpu_invar_check_json) produce the IDENTICAL report
    object for the same snapshot — clean and doctored;
  * a served workload's quiesced snapshot passes every law, and
    GET /invarz returns that same verdict over HTTP;
  * the runtime half of the end-to-end negative (a lost reply bump
    trips req_balance in both evaluators — the static half lives in
    tests/test_static_checks.py::TestInvarChecker);
  * stats_reset under live load stays law-preserving at the Python
    observation level (the by-construction property the C selftest
    hammers harder);
  * invar_assert (the gate form drill/bench tooling calls) raises
    with the violated law names, and PTPU_INVAR_OFF disables it.
"""
import ctypes
import json
import os
import socket
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
    lib = native._predictor_lib()
    if not hasattr(lib, "ptpu_invar_manifest"):
        pytest.skip("stale .so without the r20 invar ABI")
    return True


def _invar_abi(so_path):
    so = ctypes.CDLL(so_path)
    so.ptpu_invar_manifest.restype = ctypes.c_char_p
    so.ptpu_invar_check_json.restype = ctypes.c_char_p
    so.ptpu_invar_check_json.argtypes = [ctypes.c_char_p,
                                         ctypes.c_char_p]
    return so


def _c_check(snapshot, plane="serving",
             so_name="_native_predictor.so"):
    so = _invar_abi(os.path.join(REPO, "paddle_tpu", so_name))
    return json.loads(so.ptpu_invar_check_json(
        json.dumps(snapshot).encode(), plane.encode()).decode())


@pytest.fixture(scope="module")
def mlp_artifact(built, tmp_path_factory):
    import paddle_tpu as pt
    from paddle_tpu.onnx.converter import trace_to_onnx

    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(16, 32), pt.nn.ReLU(),
                           pt.nn.Linear(32, 8))
    net.eval()
    x = np.zeros((1, 16), np.float32)
    path = str(tmp_path_factory.mktemp("inv") / "mlp.onnx")
    with open(path, "wb") as f:
        f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
    return path


@pytest.fixture()
def server(mlp_artifact):
    from paddle_tpu.inference.serving import create_server

    srv = create_server(mlp_artifact, max_batch=4, deadline_us=1000,
                        instances=1, http_port=0)
    assert srv.http_port > 0
    yield srv
    srv.stop()


def _drain(srv, timeout=20.0):
    """Wait until the conn plane quiesces (async close bookkeeping)."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        snap = srv.stats()
        if snap["server"].get("conns_active", 0) == 0:
            return snap
        time.sleep(0.02)
    raise AssertionError("connections never drained")


def _http_json(port, path):
    s = socket.create_connection(("127.0.0.1", port), 10)
    try:
        s.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n\r\n".encode())
        buf = b""
        while b"\r\n\r\n" not in buf:
            c = s.recv(65536)
            assert c, "connection closed before headers"
            buf += c
        head, _, body = buf.partition(b"\r\n\r\n")
        status = head.decode().split("\r\n")[0]
        n = int([ln for ln in head.decode().split("\r\n")
                 if ln.lower().startswith("content-length")]
                [0].split(":")[1])
        while len(body) < n:
            c = s.recv(65536)
            assert c, "connection closed mid-body"
            body += c
        return status, json.loads(body[:n])
    finally:
        s.close()


class TestManifestTwin:
    def test_twin_matches_both_shipping_sos(self, built):
        """Byte parity against the ARTIFACTS — a rebuilt .so with an
        edited manifest fails here even if the checkout twin agrees
        with the checkout header."""
        from paddle_tpu.profiler.stats import INVAR_MANIFEST
        for name in ("_native_predictor.so", "_native_ps.so"):
            so = _invar_abi(os.path.join(REPO, "paddle_tpu", name))
            assert so.ptpu_invar_manifest().decode() \
                == INVAR_MANIFEST, name

    def test_manifest_names_every_advertised_law(self, built):
        from paddle_tpu.profiler.stats import _invar_laws
        names = {law["name"] for law in _invar_laws()}
        for expected in ("conn_balance", "req_balance", "err_split",
                         "session_balance", "page_balance"):
            assert expected in names


class TestServedWorkload:
    def test_quiesced_snapshot_clean_in_both_evaluators(self, server):
        from paddle_tpu.profiler.stats import invar_check

        cli = server.client()
        for _ in range(8):
            cli.infer(np.zeros((2, 16), np.float32))
        cli.close()
        snap = _drain(server)
        py = invar_check(snap, "serving")
        assert py["violations"] == {}, py
        assert py["checked"] > 0 and py["enabled"] == 1
        assert _c_check(snap) == py  # identical object, not just verdict

    def test_invarz_route_serves_the_verdict(self, server):
        cli = server.client()
        cli.infer(np.zeros((1, 16), np.float32))
        cli.close()
        _drain(server)
        status, rep = _http_json(server.http_port, "/invarz")
        assert status.split()[1] == "200"
        assert rep["enabled"] == 1 and rep["plane"] == "serving"
        assert rep["violations"] == {} and rep["checked"] > 0

    def test_doctored_snapshot_trips_both_evaluators(self, server):
        """Runtime half of the end-to-end negative: lose one reply
        bump from a REAL quiesced ledger — req_balance must trip in
        the C engine and the Python twin, with identical reports."""
        from paddle_tpu.profiler.stats import invar_check

        cli = server.client()
        for _ in range(4):
            cli.infer(np.zeros((1, 16), np.float32))
        cli.close()
        snap = _drain(server)
        assert snap["server"]["replies"] > 0
        bad = json.loads(json.dumps(snap))
        bad["server"]["replies"] -= 1
        py = invar_check(bad, "serving")
        assert "req_balance" in py["violations"], py
        assert _c_check(bad) == py

    def test_stats_reset_under_load_preserves_laws(self, server):
        """Satellite regression: resets racing live traffic must leave
        every law exact at quiesce (Counter::Rebase — reset is
        law-preserving by construction, no quiesce needed to reset)."""
        from paddle_tpu.profiler.stats import invar_assert

        stop = threading.Event()

        def resetter():
            while not stop.is_set():
                server.stats_reset()
                time.sleep(0.002)

        t = threading.Thread(target=resetter)
        t.start()
        try:
            cli = server.client()
            for _ in range(40):
                cli.infer(np.zeros((1, 16), np.float32))
            cli.close()
        finally:
            stop.set()
            t.join()
        server.stats_reset()  # final rebase with traffic done
        snap = _drain(server)
        invar_assert(snap, "reset_under_load")  # raises on violation


class TestGateForm:
    def test_invar_assert_names_the_violated_law(self):
        from paddle_tpu.profiler.stats import invar_assert

        bad = {"server": {"requests": 5, "replies": 3,
                          "req_errors": 1},
               "batcher": {}}
        with pytest.raises(AssertionError, match="req_balance"):
            invar_assert(bad, "unit")

    def test_kill_switch_disables_both_evaluators(self, built,
                                                  monkeypatch):
        """PTPU_INVAR_OFF=1: enabled:0, zero violations, from the
        Python twin AND the C engine (os.environ putenv is visible to
        the .so's getenv)."""
        from paddle_tpu.profiler.stats import invar_assert, invar_check

        bad = {"server": {"requests": 5, "replies": 3,
                          "req_errors": 1},
               "batcher": {}}
        monkeypatch.setenv("PTPU_INVAR_OFF", "1")
        rep = invar_check(bad, "serving")
        assert rep["enabled"] == 0 and rep["violations"] == {}
        invar_assert(bad, "unit")  # gate form is a no-op too
        crep = _c_check(bad)
        assert crep["enabled"] == 0 and crep["violations"] == {}
