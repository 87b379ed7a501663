"""Python-side coverage for the shared epoll network core
(csrc/ptpu_net.{h,cc}) under BOTH C servers — ISSUE 7 tentpole.

The C internals (state machine splits, churn, writev flushing, defer)
are covered natively by csrc/ptpu_net_selftest.cc; this module drives
the REAL servers over real sockets from Python:

* partial-frame client: a byte-at-a-time framed pull still
  round-trips (the nonblocking reassembly path);
* handshake deadline: a slow-loris client is cut and counted;
* idle timeout: an idle-but-authenticated conn is closed and counted;
* max-conns cap: excess connects shed at accept time, visible in
  stats;
* graceful drain: in-flight requests complete before the close, on
  the PS data plane AND the serving runtime;
* client connect retry-with-backoff (distributed/ps/table._DataConn,
  inference/serving.InferenceClient): transient refusals during start
  retry within the budget, then raise the documented error type.

Env knobs (PTPU_NET_*) are read at server start, so each test sets
them before starting its server and restores them after.
"""
import contextlib
import hashlib
import hmac
import os
import socket
import struct
import subprocess
import threading
import time

import numpy as np
import pytest

from _csrc import build_all

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_U32 = struct.Struct("<I")


def _build():
    build_all()


@pytest.fixture(scope="module")
def built():
    try:
        _build()
    except FileNotFoundError:
        pass
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    from paddle_tpu.core import native
    if not native.ps_server_available():
        pytest.skip("native PS data-plane server unavailable")
    return True


@contextlib.contextmanager
def _net_env(**knobs):
    """Set PTPU_NET_* env knobs for a server started inside the
    block; always restore (the C side reads them at start)."""
    saved = {}
    try:
        for k, v in knobs.items():
            saved[k] = os.environ.get(k)
            os.environ[k] = str(v)
        yield
    finally:
        for k, old in saved.items():
            if old is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = old


@contextlib.contextmanager
def _ps_server(rows=64, dim=4, **knobs):
    """A live C PS data-plane server with one registered table."""
    from paddle_tpu.core import native
    table = native.NativePsTable(rows, dim, "sgd", lr=1.0)
    table.data[:] = np.arange(rows * dim,
                              dtype=np.float32).reshape(rows, dim)
    key = b"net-test-key"
    with _net_env(**knobs):
        srv = native.PsDataServer(0, key)
    srv.register("t", table, lo=0)
    try:
        yield srv, table, key
    finally:
        srv.stop()
        table.close()


def _handshake(sock, key):
    nonce = _read_exact(sock, 16)
    mac = hmac.new(key, nonce, hashlib.sha256).digest()
    sock.sendall(_U32.pack(32) + mac)
    assert _read_exact(sock, 1) == b"\x01"


def _read_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise EOFError("connection closed")
        buf.extend(chunk)
    return bytes(buf)


def _eof_within(sock, seconds):
    """True when the peer closes the conn within `seconds`."""
    sock.settimeout(seconds)
    try:
        return sock.recv(1) == b""
    except socket.timeout:
        return False


class TestPsNetCore:
    def test_partial_frame_byte_at_a_time(self, built):
        """A pull request dribbled one byte per send (worst-case
        fragmentation for the nonblocking reassembly buffer) still
        round-trips exactly."""
        from paddle_tpu.distributed.ps import wire
        with _ps_server() as (srv, table, key):
            with socket.create_connection(("127.0.0.1", srv.port)) as s:
                s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
                _handshake(s, key)
                ids = np.asarray([3, 0, 7, 3], np.int64)
                payload = wire.build_pull_req("t", ids)
                framed = _U32.pack(len(payload)) + payload
                for i, b in enumerate(framed):
                    s.sendall(bytes([b]))
                    if i % 5 == 0:
                        time.sleep(0.001)  # force short reads
                n = _U32.unpack(_read_exact(s, 4))[0]
                rep = _read_exact(s, n)
                rows = wire.parse_pull_rep(rep)
                np.testing.assert_array_equal(rows, table.data[ids])
            st = srv.stats()["server"]
            assert st["pull_ops"] == 1
            assert st["pull_rows"] == 4
            assert st["proto_errors"] == 0

    def test_handshake_deadline_closes_slow_loris(self, built):
        with _ps_server(PTPU_NET_HANDSHAKE_US=100_000) as (srv, _, _k):
            with socket.create_connection(("127.0.0.1", srv.port)) as s:
                _read_exact(s, 16)      # take the nonce ...
                t0 = time.monotonic()
                assert _eof_within(s, 10.0)   # ... then stall: cut off
                assert time.monotonic() - t0 < 5.0  # our 100ms knob,
                # not the 5s default
            st = srv.stats()["server"]
            assert st["handshake_timeouts"] == 1
            assert st["handshake_fails"] == 0

    def test_idle_timeout_closes_and_counts(self, built):
        from paddle_tpu.distributed.ps import wire
        with _ps_server(PTPU_NET_IDLE_US=100_000) as (srv, table, key):
            with socket.create_connection(("127.0.0.1", srv.port)) as s:
                _handshake(s, key)
                payload = wire.build_pull_req(
                    "t", np.asarray([1], np.int64))
                s.sendall(_U32.pack(len(payload)) + payload)
                n = _U32.unpack(_read_exact(s, 4))[0]
                _read_exact(s, n)       # request served fine ...
                assert _eof_within(s, 10.0)  # ... then idle-closed
            st = srv.stats()["server"]
            assert st["idle_closes"] == 1
            assert st["pull_ops"] == 1

    def test_max_conns_shed_visible_in_stats(self, built):
        with _ps_server(PTPU_NET_MAX_CONNS=2) as (srv, _, key):
            socks, kept, shed = [], 0, 0
            for _ in range(5):
                s = socket.create_connection(("127.0.0.1", srv.port))
                s.settimeout(10.0)
                socks.append(s)
                try:
                    _handshake(s, key)
                    kept += 1
                except EOFError:
                    shed += 1
            # stats match what the clients observed, exactly
            assert (kept, shed) == (2, 3)
            st = srv.stats()["server"]
            assert st["conns_accepted"] == 2
            assert st["conns_shed"] == 3
            assert st["conns_active"] == 2
            for s in socks:
                s.close()

    def test_graceful_drain_completes_pipelined_pulls(self, built):
        """Stop() while replies are still queued: every pipelined
        request is answered BEFORE the close (drain ordering)."""
        from paddle_tpu.distributed.ps import wire
        depth = 16
        with _ps_server(rows=256, dim=64) as (srv, table, key):
            s = socket.create_connection(("127.0.0.1", srv.port))
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            _handshake(s, key)
            ids = np.arange(depth, dtype=np.int64)
            payload = wire.build_pull_req("t", ids)
            for _ in range(depth):      # burst without reading
                s.sendall(_U32.pack(len(payload)) + payload)
            stopper = threading.Thread(target=srv.stop)
            stopper.start()
            got = 0
            try:
                for _ in range(depth):
                    n = _U32.unpack(_read_exact(s, 4))[0]
                    rows = wire.parse_pull_rep(_read_exact(s, n))
                    np.testing.assert_array_equal(rows, table.data[ids])
                    got += 1
                # after the last reply the server closes the conn
                assert _eof_within(s, 10.0)
            finally:
                stopper.join()
                s.close()
            assert got == depth


@pytest.fixture(scope="module")
def serving_artifact(built, tmp_path_factory):
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.core import native
    from paddle_tpu.onnx.converter import trace_to_onnx
    if not native.serving_available():
        pytest.skip("native serving runtime unavailable")
    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(16, 32), pt.nn.ReLU(),
                           pt.nn.Linear(32, 4))
    net.eval()
    x = np.zeros((2, 16), np.float32)
    path = str(tmp_path_factory.mktemp("net_sv") / "mlp.onnx")
    with open(path, "wb") as f:
        f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
    return path


class TestServingNetCore:
    def test_graceful_drain_completes_in_flight_request(
            self, serving_artifact):
        """A request sitting in the micro-batcher when stop() lands is
        still answered (batcher drains, reply flushes, THEN close)."""
        from paddle_tpu.inference import create_server
        # a long flush deadline guarantees the request is still queued
        # (in flight) when stop() arrives
        srv = create_server(serving_artifact, max_batch=8,
                            deadline_us=300_000, instances=1)
        cli = srv.client()
        x = np.random.default_rng(0).normal(
            size=(1, 16)).astype(np.float32)
        result = {}

        def do_infer():
            try:
                result["outs"] = cli.infer(x)
            except Exception as e:  # noqa: BLE001 — recorded for assert
                result["err"] = e

        t = threading.Thread(target=do_infer)
        t.start()
        time.sleep(0.1)       # request is enqueued, deadline not hit
        srv.stop()            # drain: batcher flushes, reply lands
        t.join(timeout=30)
        assert not t.is_alive()
        assert "err" not in result, f"in-flight request failed: " \
                                    f"{result.get('err')}"
        assert result["outs"][0].shape == (1, 4)
        cli.close()

    def test_serving_stats_expose_net_counters(self, serving_artifact):
        from paddle_tpu.inference import create_server
        with create_server(serving_artifact, max_batch=4,
                           instances=1) as srv:
            cli = srv.client()
            cli.infer(np.zeros((1, 16), np.float32))
            st = srv.stats()["server"]
            for key in ("conns_accepted", "conns_active", "conns_shed",
                        "handshake_timeouts", "idle_closes",
                        "epoll_wakeups", "partial_write_flushes"):
                assert key in st, f"net counter {key} missing"
            assert st["conns_accepted"] == 1
            assert st["conns_active"] == 1
            assert st["epoll_wakeups"] > 0
            cli.close()


class TestConnectRetry:
    """Satellite: bounded connect retry-with-backoff in both clients —
    the sleep-before-dial dance every bench used to do is gone."""

    def test_serving_client_retries_until_server_up(
            self, serving_artifact):
        from paddle_tpu.inference import create_server
        from paddle_tpu.inference.serving import InferenceClient
        # reserve a port, release it, and only START the server there
        # after the client has already begun dialing
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        key = b"retry-key"
        holder = {}

        def start_later():
            time.sleep(0.4)
            holder["srv"] = create_server(serving_artifact, port=port,
                                          authkey=key, max_batch=4,
                                          instances=1)

        t = threading.Thread(target=start_later)
        t.start()
        try:
            # the dial starts BEFORE the listener exists and must ride
            # its ECONNREFUSED retries through to a live handshake
            t0 = time.monotonic()
            cli = InferenceClient(port, key, connect_retry_s=10.0)
            assert time.monotonic() - t0 < 10.0
            outs = cli.infer(np.zeros((1, 16), np.float32))
            assert outs[0].shape == (1, 4)
            cli.close()
        finally:
            t.join()
            if "srv" in holder:
                holder["srv"].stop()

    def test_serving_client_clear_error_after_budget(self):
        from paddle_tpu.inference.serving import (InferenceClient,
                                                  ServingError)
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()                 # nothing listens here
        t0 = time.monotonic()
        with pytest.raises(ServingError, match="not reachable"):
            InferenceClient(port, b"k", connect_retry_s=0.5)
        assert time.monotonic() - t0 < 10.0

    def test_ps_data_conn_clear_error_after_budget(self, built):
        from paddle_tpu.distributed.ps.table import _DataConn
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()
        old = _DataConn.CONNECT_RETRY_S
        _DataConn.CONNECT_RETRY_S = 0.5
        try:
            with pytest.raises(ConnectionError, match="not reachable"):
                _DataConn("127.0.0.1", port, b"k")
        finally:
            _DataConn.CONNECT_RETRY_S = old
