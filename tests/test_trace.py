"""End-to-end request tracing (ptpu_trace) + HTTP telemetry — ISSUE 10.

The C internals (span ring wraparound, sampling dice, slow ring,
Prometheus renderer vectors) are covered by csrc/ptpu_trace_selftest.cc
via make selftest; this module exercises the cross-language seams:

  * HTTP conformance on the net core's second listener: GET /metrics
    parses as valid Prometheus exposition (cumulative le buckets, one
    TYPE line per family), /healthz flips to 503 during the two-phase
    drain while existing framed conns still answer, /tracez matches
    the documented JSON schema, keep-alive + Connection: close.
  * Traced (v2) frame round trips: the 8-byte trace id survives at
    EVERY frame split point on both planes (serving INFER, PS PULL)
    and is echoed in replies; old-style v1 clients are untouched.
  * C /metrics bytes == profiler.stats.prometheus_text over the same
    /statsz snapshot (byte parity, via the quiescent ABI pair).
  * Slow-request ring capture and the client+server chrome-trace merge
    (>= 5 lifecycle spans for one INFER, and for one DECODE step).
"""
import json
import os
import re
import socket
import subprocess
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
    if not getattr(lib, "_ptpu_has_http", False):
        pytest.skip("stale .so without the r10 telemetry ABI")
    return True


@pytest.fixture(scope="module")
def mlp_artifact(built, tmp_path_factory):
    import paddle_tpu as pt
    from paddle_tpu.onnx.converter import trace_to_onnx

    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(16, 32), pt.nn.ReLU(),
                           pt.nn.Linear(32, 8))
    net.eval()
    x = np.zeros((1, 16), np.float32)
    path = str(tmp_path_factory.mktemp("tr") / "mlp.onnx")
    with open(path, "wb") as f:
        f.write(trace_to_onnx(lambda a: net(a), (jnp.asarray(x),)))
    return path


@pytest.fixture()
def server(mlp_artifact):
    from paddle_tpu.core.native import _predictor_lib
    from paddle_tpu.inference.serving import create_server

    # deterministic tracing for the whole fixture: every request
    # sampled, slow ring off (individual tests override)
    _predictor_lib().ptpu_trace_set(1, 0)
    srv = create_server(mlp_artifact, max_batch=4, deadline_us=1000,
                        instances=1, http_port=0)
    assert srv.http_port > 0
    yield srv
    _predictor_lib().ptpu_trace_set(64, 100000)  # defaults back
    srv.stop()


def http_get(port, path, extra_headers="", keep_sock=None):
    """Raw-socket GET -> (status_line, headers_dict, body_bytes)."""
    s = keep_sock or socket.create_connection(("127.0.0.1", port), 10)
    s.sendall(f"GET {path} HTTP/1.1\r\nHost: t\r\n{extra_headers}"
              f"\r\n".encode())
    buf = b""
    while b"\r\n\r\n" not in buf:
        c = s.recv(65536)
        assert c, "connection closed before headers"
        buf += c
    head, _, body = buf.partition(b"\r\n\r\n")
    lines = head.decode().split("\r\n")
    hdrs = {}
    for ln in lines[1:]:
        k, _, v = ln.partition(":")
        hdrs[k.strip().lower()] = v.strip()
    n = int(hdrs["content-length"])
    while len(body) < n:
        c = s.recv(65536)
        assert c, "connection closed mid-body"
        body += c
    if keep_sock is None:
        s.close()
    return lines[0], hdrs, body[:n]


# ---------------------------------------------------------------------------
# Prometheus exposition validity (a strict structural parser — no
# external promtool in this image)
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"
    r'(\{([a-zA-Z_][a-zA-Z0-9_]*="[^"]*")(,[a-zA-Z_][a-zA-Z0-9_]*='
    r'"[^"]*")*\})? (-?[0-9]+(\.[0-9]+)?|\+Inf|NaN)$')
_TYPE_RE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")


def assert_valid_prometheus(text: str):
    """Structural exposition-format check: every line is a TYPE or a
    sample, one TYPE per family (before its samples), histogram
    buckets cumulative with le ending at +Inf == _count."""
    families = {}           # family -> type
    hist = {}               # (family, labels-minus-le) -> [(le, val)]
    counts = {}             # (family, labels-minus-le) -> count value
    for line in text.splitlines():
        if not line:
            continue
        tm = _TYPE_RE.match(line)
        if tm:
            fam, typ = tm.group(1), tm.group(2)
            assert fam not in families, f"duplicate TYPE for {fam}"
            families[fam] = typ
            continue
        sm = _SAMPLE_RE.match(line)
        assert sm, f"malformed exposition line: {line!r}"
        name, labels = sm.group(1), sm.group(2) or ""
        value = sm.group(5)
        fam = re.sub(r"_(bucket|sum|count)$", "", name)
        owner = fam if fam in families else name
        assert owner in families, \
            f"sample {name} before/without its TYPE line"
        if families.get(fam) == "histogram":
            pairs = re.findall(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"]*)"',
                               labels)
            base = tuple(sorted(p for p in pairs if p[0] != "le"))
            if name.endswith("_bucket"):
                le = dict(pairs)["le"]
                hist.setdefault((fam, base), []).append(
                    (le, int(value)))
            elif name.endswith("_count"):
                counts[(fam, base)] = int(value)
    for (fam, base), buckets in hist.items():
        vals = [v for _, v in buckets]
        assert vals == sorted(vals), \
            f"{fam}{base}: buckets not cumulative"
        assert buckets[-1][0] == "+Inf", \
            f"{fam}{base}: last bucket le != +Inf"
        assert counts.get((fam, base)) == buckets[-1][1], \
            f"{fam}{base}: +Inf bucket != _count"


# ---------------------------------------------------------------------------
# HTTP conformance
# ---------------------------------------------------------------------------

class TestHttpEndpoint:
    def test_healthz_statsz_metrics_tracez(self, server):
        st, hdrs, body = http_get(server.http_port, "/healthz")
        assert st == "HTTP/1.1 200 OK"
        assert hdrs["content-type"].startswith("application/json")
        assert json.loads(body) == {"status": "ok"}

        st, hdrs, body = http_get(server.http_port, "/statsz")
        assert st == "HTTP/1.1 200 OK"
        snap = json.loads(body)
        assert "server" in snap and "batcher" in snap
        assert "http_reqs" in snap["server"]

        st, hdrs, body = http_get(server.http_port, "/metrics")
        assert st == "HTTP/1.1 200 OK"
        assert hdrs["content-type"].startswith("text/plain")
        assert_valid_prometheus(body.decode())
        assert "ptpu_serving_server_requests" in body.decode()

        st, _, body = http_get(server.http_port, "/tracez?n=16")
        assert st == "HTTP/1.1 200 OK"
        tz = json.loads(body)
        for key in ("sample", "slow_us", "ring", "recorded", "spans",
                    "slow"):
            assert key in tz
        for sp in tz["spans"]:
            assert set(sp) == {"kind", "t0_us", "t1_us", "trace_id",
                               "conn", "arg"}

        st, _, _ = http_get(server.http_port, "/nope")
        assert st.startswith("HTTP/1.1 404")

    def test_keep_alive_and_close(self, server):
        s = socket.create_connection(("127.0.0.1", server.http_port),
                                     10)
        # two requests on one connection (keep-alive default)
        st1, _, _ = http_get(server.http_port, "/healthz", keep_sock=s)
        st2, _, _ = http_get(server.http_port, "/healthz", keep_sock=s)
        assert st1 == st2 == "HTTP/1.1 200 OK"
        # Connection: close is honored with EOF after the body
        st3, hdrs, _ = http_get(server.http_port, "/healthz",
                                extra_headers="Connection: close\r\n",
                                keep_sock=s)
        assert st3 == "HTTP/1.1 200 OK"
        assert hdrs["connection"] == "close"
        assert s.recv(1) == b""
        s.close()

    def test_non_get_is_405(self, server):
        s = socket.create_connection(("127.0.0.1", server.http_port),
                                     10)
        s.sendall(b"POST /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
        assert s.recv(64).startswith(b"HTTP/1.1 405")
        s.close()

    def test_metrics_counts_http_requests(self, server):
        _, _, b1 = http_get(server.http_port, "/statsz")
        _, _, b2 = http_get(server.http_port, "/statsz")
        r1 = json.loads(b1)["server"]["http_reqs"]
        r2 = json.loads(b2)["server"]["http_reqs"]
        assert r2 == r1 + 1

    def test_healthz_survives_framed_saturation(self, mlp_artifact):
        """Telemetry conns are exempt from the framed max-conns cap:
        a saturated fleet is exactly when the LB probe must still
        answer (review finding r10)."""
        from paddle_tpu.inference.serving import create_server

        os.environ["PTPU_NET_MAX_CONNS"] = "1"
        try:
            srv = create_server(mlp_artifact, max_batch=2, instances=1,
                                http_port=0)
        finally:
            del os.environ["PTPU_NET_MAX_CONNS"]
        try:
            cli = srv.client()          # occupies the single slot
            cli.infer(np.zeros((1, 16), np.float32))
            # a second framed conn is shed at accept...
            s2 = socket.create_connection(("127.0.0.1", srv.port), 5)
            assert s2.recv(16) == b""   # EOF before the nonce
            s2.close()
            # ...but health probes still answer
            st, _, body = http_get(srv.http_port, "/healthz")
            assert st == "HTTP/1.1 200 OK"
            assert json.loads(body) == {"status": "ok"}
            # and telemetry conns never consume framed slots
            assert json.loads(http_get(srv.http_port, "/statsz")[2])[
                "server"]["conns_active"] == 1
            cli.close()
        finally:
            srv.stop()

    def test_healthz_during_drain_and_framed_refusal(self, mlp_artifact):
        from paddle_tpu.inference.serving import (InferenceClient,
                                                  ServingError,
                                                  create_server)
        srv = create_server(mlp_artifact, max_batch=2, instances=1,
                            http_port=0)
        try:
            cli = srv.client()
            x = np.zeros((1, 16), np.float32)
            cli.infer(x)
            srv.drain_begin()
            # health flips; the HTTP listener itself stays up
            st, _, body = http_get(srv.http_port, "/healthz")
            assert st.startswith("HTTP/1.1 503")
            assert json.loads(body) == {"status": "draining"}
            # existing framed connections still answer
            out = cli.infer(x)
            assert out[0].shape == (1, 8)
            # new framed connections are refused
            with pytest.raises((ServingError, ConnectionError)):
                InferenceClient(srv.port, srv.authkey,
                                connect_retry_s=0.5)
            cli.close()
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# /metrics byte parity with the Python renderer
# ---------------------------------------------------------------------------

class TestPromParity:
    def test_serving_metrics_byte_parity(self, server):
        from paddle_tpu.profiler.stats import prometheus_text

        cli = server.client()
        cli.infer(np.zeros((2, 16), np.float32))
        cli.close()
        # the quiescent ABI pair: no socket traffic between the two
        # snapshots, so the counters cannot move
        for _ in range(3):
            snap = server.stats()
            prom_c = server.prom_text()
            if server.stats() == snap:
                break
        assert prom_c == prometheus_text(snap, prefix="ptpu_serving")
        assert_valid_prometheus(prom_c)

    def test_ps_metrics_byte_parity(self, built):
        from paddle_tpu.core.native import (NativePsTable, PsDataServer,
                                            ps_table_available)
        from paddle_tpu.profiler.stats import prometheus_text

        if not ps_table_available():
            pytest.skip("native PS unavailable")
        srv = PsDataServer(0, b"k" * 8, http_port=0)
        try:
            tbl = NativePsTable(16, 4, optimizer="sgd", lr=0.1)
            srv.register("emb", tbl, 0)
            for _ in range(3):
                snap = srv.stats()
                prom_c = srv.prom_text()
                if srv.stats() == snap:
                    break
            assert prom_c == prometheus_text(snap, prefix="ptpu_ps")
            assert_valid_prometheus(prom_c)
            # per-table metrics ride a table label, one TYPE line
            assert prom_c.count(
                "# TYPE ptpu_ps_table_wire_pull_ops counter") == 1
            assert 'table="emb"' in prom_c
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# traced frames: round trips, misalignment, compatibility
# ---------------------------------------------------------------------------

class TestTracedFrames:
    def test_infer_trace_round_trip_every_split(self, server):
        """The v2 INFER frame parses identically at EVERY partial-read
        split point, and the reply echoes the trace id exactly."""
        from paddle_tpu.inference import serving as sv

        cli = server.client(trace=True)
        ref = cli.infer(np.ones((1, 16), np.float32))[0]
        x = np.ones((1, 16), np.float32)
        payload = cli._encode_request(12345, [x],
                                      trace_id=0xA1B2C3D4E5F60718)
        frame = sv._U32.pack(len(payload)) + payload
        raw = cli._sock
        for split in range(1, min(len(frame), 48)):
            raw.sendall(frame[:split])
            time.sleep(0.001)  # force a partial read server-side
            raw.sendall(frame[split:])
            f = cli._read_frame()
            assert sv._frame_trace_id(f) == 0xA1B2C3D4E5F60718
            rid, outs = cli._decode_reply(f)
            assert rid == 12345
            np.testing.assert_allclose(outs[0], ref, rtol=1e-6)
        cli.close()

    def test_ps_pull_trace_round_trip_every_split(self, built):
        import hashlib
        import hmac as hmac_mod
        import struct

        from paddle_tpu.core.native import (NativePsTable, PsDataServer,
                                            ps_table_available)
        from paddle_tpu.distributed.ps import wire

        if not ps_table_available():
            pytest.skip("native PS unavailable")
        key = b"trace-key"
        srv = PsDataServer(0, key)
        tbl = NativePsTable(32, 4, optimizer="sgd", lr=0.1)
        srv.register("emb", tbl, 0)
        try:
            s = socket.create_connection(("127.0.0.1", srv.port), 10)
            nonce = s.recv(16)
            mac = hmac_mod.new(key, nonce, hashlib.sha256).digest()
            s.sendall(struct.pack("<I", len(mac)) + mac)
            assert s.recv(1) == b"\x01"
            tid = 0x0102030405060708
            req = bytes(wire.build_pull_req("emb", np.arange(5),
                                            trace_id=tid))
            frame = struct.pack("<I", len(req)) + req
            want = tbl.pull(np.arange(5))
            for split in range(1, len(frame)):
                s.sendall(frame[:split])
                time.sleep(0.0005)
                s.sendall(frame[split:])
                n = struct.unpack("<I", s.recv(4))[0]
                rep = b""
                while len(rep) < n:
                    rep += s.recv(n - len(rep))
                assert wire.fast_tag(rep) == wire.TAG_PULL_REP
                assert wire.trace_id_of(rep) == tid
                np.testing.assert_array_equal(wire.parse_pull_rep(rep),
                                              want)
            s.close()
        finally:
            srv.stop()

    def test_old_client_new_server_and_v1_replies(self, server):
        """Compatibility both ways: a v1 (untraced) client round-trips
        unchanged, and its replies stay v1 byte layouts."""
        from paddle_tpu.inference import serving as sv

        cli = server.client(trace=False)   # the old wire, verbatim
        x = np.zeros((1, 16), np.float32)
        payload = cli._encode_request(7, [x])
        assert payload[0] == sv.WIRE_VERSION   # not the traced version
        cli._send_frame(payload)
        f = cli._read_frame()
        assert f[0] == sv.WIRE_VERSION and sv._frame_trace_id(f) == 0
        rid, outs = cli._decode_reply(f)
        assert rid == 7 and outs[0].shape == (1, 8)
        assert cli.trace_spans == []
        cli.close()

    def test_trace_kill_switch_still_echoes(self, server):
        """PTPU_TRACE_SAMPLE=0 (via ptpu_trace_set) disables span
        recording but the wire-level echo is unconditional — a traced
        client keeps working against a tracing-off server."""
        from paddle_tpu.core.native import _predictor_lib

        lib = _predictor_lib()
        lib.ptpu_trace_set(0, 0)
        try:
            before = json.loads(
                lib.ptpu_trace_json(4096).decode())["recorded"]
            cli = server.client(trace=True)
            cli.infer(np.zeros((1, 16), np.float32))
            cli.close()
            after = json.loads(
                lib.ptpu_trace_json(4096).decode())["recorded"]
            assert after == before   # zero recorder work
        finally:
            lib.ptpu_trace_set(1, 0)

    def test_infer_lifecycle_spans_and_merge(self, server):
        """Acceptance: one traced INFER renders >= 5 distinct
        lifecycle spans, merged with the client span into one chrome
        trace."""
        from paddle_tpu.profiler.timeline import (SPAN_KIND_NAMES,
                                                  merge_request_trace)

        cli = server.client(trace=True)
        cli.infer(np.zeros((1, 16), np.float32))
        tid = cli.trace_spans[-1]["trace_id"]
        deadline = time.time() + 5
        kinds = set()
        while time.time() < deadline:
            _, _, body = http_get(server.http_port, "/tracez?n=256")
            tz = json.loads(body)
            kinds = {sp["kind"] for sp in tz["spans"]
                     if sp["trace_id"] == tid}
            if len(kinds) >= 5:   # net.flush lands after the reply
                break
            time.sleep(0.02)
        assert kinds == {"net.read", "batch.queue", "batch.fill",
                         "predictor.run", "net.flush"}
        assert set(kinds) <= set(SPAN_KIND_NAMES.values())
        merged = merge_request_trace(cli.trace_spans, tz,
                                     trace_id=tid)
        evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        names = {e["name"] for e in evs}
        assert "client.infer" in names and len(names) == 6
        # client + server land in separate pid lanes, same clock
        client_ev = next(e for e in evs if e["name"] == "client.infer")
        run_ev = next(e for e in evs if e["name"] == "predictor.run")
        assert client_ev["pid"] == 0 and run_ev["pid"] == 1
        assert client_ev["ts"] <= run_ev["ts"]
        assert (run_ev["ts"] + run_ev["dur"] <=
                client_ev["ts"] + client_ev["dur"] + 1000)
        cli.close()

    def test_slow_request_ring_capture(self, server):
        """With PTPU_TRACE_SLOW_US=1 every request is 'slow': the ring
        captures the full span breakdown even for UNSAMPLED requests
        (v1 client, sampling off)."""
        from paddle_tpu.core.native import _predictor_lib

        lib = _predictor_lib()
        lib.ptpu_trace_set(0, 1)   # sampling OFF, slow threshold 1us
        try:
            cli = server.client(trace=False)
            cli.infer(np.zeros((1, 16), np.float32))
            cli.close()
            _, _, body = http_get(server.http_port, "/tracez")
            slow = json.loads(body)["slow"]
            assert slow, "slow ring empty"
            ent = slow[0]
            assert ent["e2e_us"] >= 1
            got = [sp["kind"] for sp in ent["spans"]]
            assert got == ["net.read", "batch.queue", "batch.fill",
                           "predictor.run"]
            for sp in ent["spans"]:
                assert sp["t1_us"] >= sp["t0_us"]
        finally:
            lib.ptpu_trace_set(1, 0)


# ---------------------------------------------------------------------------
# traced DECODE step (KV decode plane)
# ---------------------------------------------------------------------------

class TestTracedDecode:
    def test_decode_step_spans_and_merge(self, built, mlp_artifact,
                                         tmp_path):
        import paddle_tpu as pt
        from paddle_tpu.core.native import _predictor_lib
        from paddle_tpu.inference.serving import create_server
        from paddle_tpu.models.gpt import (GPTForPretraining,
                                           export_gpt_decode, gpt_tiny)
        from paddle_tpu.profiler.timeline import merge_request_trace

        lib = _predictor_lib()
        if not getattr(lib, "_ptpu_has_decode", False):
            pytest.skip("decode ABI unavailable")
        pt.seed(0)
        cfg = gpt_tiny(dtype=jnp.float32, dropout=0.0)
        model = GPTForPretraining(cfg)
        model.eval()
        dec = export_gpt_decode(model, str(tmp_path / "dec"), batch=2,
                                context=8)
        lib.ptpu_trace_set(1, 0)
        srv = create_server(mlp_artifact, max_batch=2, instances=1,
                            decode_model=dec, kv_sessions=4,
                            http_port=0)
        try:
            cli = srv.client(trace=True)
            sess = cli.decode_open()
            cli.decode_step(sess, 3)
            tid = cli.trace_spans[-1]["trace_id"]
            assert cli.trace_spans[-1]["name"] == "client.decode_step"
            deadline = time.time() + 5
            kinds = set()
            while time.time() < deadline:
                _, _, body = http_get(srv.http_port, "/tracez?n=256")
                tz = json.loads(body)
                kinds = {sp["kind"] for sp in tz["spans"]
                         if sp["trace_id"] == tid}
                if len(kinds) >= 5:
                    break
                time.sleep(0.02)
            assert kinds == {"net.read", "batch.queue", "batch.fill",
                             "decode.step", "net.flush"}
            merged = merge_request_trace(cli.trace_spans, tz,
                                         trace_id=tid)
            names = {e["name"] for e in merged["traceEvents"]
                     if e.get("ph") == "X"}
            assert "client.decode_step" in names and len(names) == 6
            cli.decode_close(sess)
            cli.close()
        finally:
            srv.stop()
            lib.ptpu_trace_set(64, 100000)


# ---------------------------------------------------------------------------
# stats CLI over the HTTP endpoint
# ---------------------------------------------------------------------------

class TestStatsCli:
    def test_http_fetch_and_rates(self, server):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "ps_stats", os.path.join(REPO, "tools", "ps_stats.py"))
        ps_stats = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ps_stats)

        ep = f"127.0.0.1:{server.http_port}"
        snap = ps_stats.fetch_http_stats(ep)
        assert "server" in snap and "batcher" in snap
        cli = server.client()
        cli.infer(np.zeros((1, 16), np.float32))
        cli.close()
        snap2 = ps_stats.fetch_http_stats(ep)
        line = ps_stats._rates(snap, snap2, 1.0)
        assert "infer" in line and "req/s" in line   # serving shape
        # --prom over HTTP returns the C-rendered exposition
        prom = ps_stats.http_get(ep, "/metrics").decode()
        assert_valid_prometheus(prom)

    def test_ps_shape_rates_line(self):
        prev = {"server": {"pull_ops": 0, "pull_rows": 0, "push_ops": 0,
                           "push_rows": 0, "bytes_in": 0,
                           "bytes_out": 0}}
        cur = {"server": {"pull_ops": 10, "pull_rows": 100,
                          "push_ops": 5, "push_rows": 50,
                          "bytes_in": 1000, "bytes_out": 2000,
                          "conns_active": 3}}
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "ps_stats2", os.path.join(REPO, "tools", "ps_stats.py"))
        ps_stats = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ps_stats)
        line = ps_stats._rates(prev, cur, 1.0)
        assert "pull 10 ops/s" in line and "conns 3" in line
