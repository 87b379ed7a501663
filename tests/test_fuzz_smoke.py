"""csrc/fuzz — the coverage-guided fuzzing subsystem's CI face
(ISSUE 11 tentpole, part 2).

Two layers:

* CORPUS REPLAY (warm-binary gated, like the sancheck legs in
  tests/test_native_selftest.py): every fuzz target replays its full
  checked-in corpus — seeds AND frozen crash regressions — under
  ASan+UBSan and must come back clean. A cold tree skips (six
  sanitized harness builds are minutes; tools/run_checks.sh is the
  unconditional gate); PTPU_FUZZ_BUILD=1 forces the build here.

* NAMED REGRESSIONS (no fuzz binaries needed): the bugs the fuzzers
  found in this PR, replayed through the SHIPPING artifacts from
  Python — the committed minimized inputs in csrc/fuzz/corpus/ must
  keep failing SOFT (error frame / load error), never crash:
    - pull/push id near INT64_MIN against a shard with a nonzero lo
      offset (signed-overflow UB in ptpu_ps_server.cc id arithmetic);
    - an ONNX initializer with a zero-element dim (memcpy(NULL,..,0)
      UB in ptpu_predictor.cc parse_tensor).
"""
import contextlib
import hashlib
import hmac
import os
import socket
import struct
import subprocess

import numpy as np
import pytest

from _csrc import build_all

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")
FUZZ = os.path.join(CSRC, "fuzz")
CORPUS = os.path.join(FUZZ, "corpus")

FUZZ_TARGETS = ["wire_ps", "wire_serving", "http", "onnx", "json",
                "frames"]

_U32 = struct.Struct("<I")


def _fuzz_binaries_warm() -> bool:
    src_mtime = max(
        os.path.getmtime(os.path.join(d, f))
        for d in (CSRC, FUZZ)
        for f in os.listdir(d)
        if f.endswith((".cc", ".h")) or f == "Makefile")
    for t in FUZZ_TARGETS:
        p = os.path.join(FUZZ, f"fuzz_{t}.fuzz")
        if not os.path.exists(p) or os.path.getmtime(p) < src_mtime:
            return False
    return True


@pytest.fixture(scope="module")
def fuzz_bins():
    if not _fuzz_binaries_warm() and \
            os.environ.get("PTPU_FUZZ_BUILD") != "1":
        pytest.skip("fuzz binaries need a sanitized rebuild "
                    "(~minutes) — set PTPU_FUZZ_BUILD=1 or run "
                    "tools/run_checks.sh")
    r = subprocess.run(["make", "-j4", "fuzz"], cwd=CSRC,
                       capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stdout + r.stderr
    return True


class TestCorpusReplay:
    @pytest.mark.parametrize("target", FUZZ_TARGETS)
    def test_corpus_replays_clean(self, fuzz_bins, target):
        """Seeds + frozen crash-* regressions, one pass, fail-fast
        sanitizers: any resurfacing finding fails CI here."""
        corpus = os.path.join(CORPUS, target)
        assert os.path.isdir(corpus), f"missing corpus for {target}"
        r = subprocess.run(
            [os.path.join(FUZZ, f"fuzz_{target}.fuzz"), corpus],
            capture_output=True, text=True, timeout=600, cwd=FUZZ)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "replay clean" in r.stdout

    def test_corpus_has_frozen_regressions(self):
        """The findings of this PR stay committed (crash-* files are
        never regenerated by gen_seeds.py)."""
        ps = os.listdir(os.path.join(CORPUS, "wire_ps"))
        onnx = os.listdir(os.path.join(CORPUS, "onnx"))
        assert "crash-pull-id-underflow.bin" in ps
        assert "crash-push-id-underflow.bin" in ps
        assert "crash-zero-elem-initializer.bin" in onnx


# ---------------------------------------------------------------------------
# named regressions through the shipping artifacts
# ---------------------------------------------------------------------------


def _build_shipping():
    build_all()


@pytest.fixture(scope="module")
def native():
    try:
        _build_shipping()
    except FileNotFoundError:
        pass
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    from paddle_tpu.core import native as nat
    if not nat.ps_server_available():
        pytest.skip("native PS data-plane server unavailable")
    return nat


def _read_exact(sock, n):
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise EOFError("connection closed")
        buf.extend(chunk)
    return bytes(buf)


def _handshake(sock, key):
    nonce = _read_exact(sock, 16)
    mac = hmac.new(key, nonce, hashlib.sha256).digest()
    sock.sendall(_U32.pack(32) + mac)
    assert _read_exact(sock, 1) == b"\x01"


def _frame(payload):
    return _U32.pack(len(payload)) + payload


def _recv_frame(sock):
    (n,) = _U32.unpack(_read_exact(sock, 4))
    return _read_exact(sock, n)


@contextlib.contextmanager
def _offset_ps_server(native, lo=1000):
    """A live C data-plane server whose shard sits at a NONZERO
    global-id offset — the precondition of the underflow findings."""
    table = native.NativePsTable(64, 4, "sgd", lr=1.0)
    table.data[:] = np.arange(64 * 4, dtype=np.float32).reshape(64, 4)
    key = b"fuzz-regression-key"
    srv = native.PsDataServer(0, key)
    srv.register("emb", table, lo=lo)
    try:
        yield srv, key
    finally:
        srv.stop()
        table.close()


class TestRegressionPullPushIdUnderflow:
    """ptpu_ps_server.cc id arithmetic: GetI64(id) - lo was SIGNED —
    an id near INT64_MIN underflowed (UB; a fail-fast build aborts the
    whole server on one hostile frame). Fixed to wrap in uint64 space
    and fail the range check. Minimized inputs:
    csrc/fuzz/corpus/wire_ps/crash-{pull,push}-id-underflow.bin."""

    def _crash_bytes(self, name):
        with open(os.path.join(CORPUS, "wire_ps", name), "rb") as f:
            return f.read()

    def test_pull_id_underflow_answers_err_and_survives(self, native):
        with _offset_ps_server(native) as (srv, key):
            with socket.create_connection(("127.0.0.1", srv.port)) as s:
                _handshake(s, key)
                s.sendall(_frame(
                    self._crash_bytes("crash-pull-id-underflow.bin")))
                rep = _recv_frame(s)
                assert rep[1] == 0x54, rep  # ERR frame, not a crash
                assert b"out of shard range" in rep
                # server alive: a well-formed pull on the same conn
                good = bytes([1, 0x50, 3]) + b"emb" + \
                    _U32.pack(1) + struct.pack("<q", 1000)
                s.sendall(_frame(good))
                rep = _recv_frame(s)
                assert rep[1] == 0x51  # PULL_REP

    def test_push_id_underflow_answers_err_and_survives(self, native):
        with _offset_ps_server(native) as (srv, key):
            with socket.create_connection(("127.0.0.1", srv.port)) as s:
                _handshake(s, key)
                s.sendall(_frame(
                    self._crash_bytes("crash-push-id-underflow.bin")))
                rep = _recv_frame(s)
                assert rep[1] == 0x54, rep  # ERR (out-of-range id)
                # and the conn still serves
                s.sendall(_frame(bytes([1, 0x50, 3]) + b"emb" +
                                 _U32.pack(0)))
                rep = _recv_frame(s)
                assert rep[1] == 0x51


class TestRegressionZeroElemInitializer:
    """ptpu_predictor.cc parse_tensor: a zero-element initializer
    (some dim == 0) made memcpy's destination NULL — UB by
    declaration. The minimized artifact must now load-fail (or load)
    WITHOUT crashing the process. Minimized input:
    csrc/fuzz/corpus/onnx/crash-zero-elem-initializer.bin."""

    def test_zero_elem_initializer_loads_or_errors_softly(
            self, native, tmp_path):
        src = os.path.join(CORPUS, "onnx",
                           "crash-zero-elem-initializer.bin")
        art = tmp_path / "zero_elem.onnx"
        art.write_bytes(open(src, "rb").read())
        from paddle_tpu import inference
        try:
            inference.Predictor(str(art))
        except Exception:
            pass  # a clean load error is the contract; crashing is not
