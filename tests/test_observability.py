"""Cross-stack observability layer (ISSUE 3): the shared stats core,
the instrumented C hot paths (native predictor + PS table/server), the
chrome-trace profiler contract, and the ABI-drift guard.

Covers the satellites explicitly:
* `RecordEvent` decorator usage (the docstring's promise);
* chrome-trace dumps are valid JSON with monotonic `ts` / non-negative
  `dur`, and `timeline.py --align` shifts ranks correctly;
* PS stats counters agree EXACTLY with client-side observed request
  counts, on both the native and the numpy backends;
* every C ABI symbol `core/native.py` declares (ABI_SYMBOLS) resolves
  in the built .so — ABI drift fails here, not at the first ctypes
  call in production.
"""
import ctypes
import json
import os
import re
import subprocess

import numpy as np
import pytest

from _csrc import build_all

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _build():
    build_all()


@pytest.fixture(scope="module")
def built():
    try:
        _build()
    except FileNotFoundError:
        pass  # no make: prebuilt .so (or skips below) take over
    except subprocess.CalledProcessError as e:
        raise RuntimeError(f"native build failed:\n{e.stderr}") from e
    return True


# ---------------------------------------------------------------------------
# profiler/stats.py — the Python twin of csrc/ptpu_stats.h
# ---------------------------------------------------------------------------

class TestStatsRegistry:
    def test_bucket_layout_matches_native(self):
        """Bucket boundaries mirror ptpu::HistBucketOf exactly (the
        same vectors the C selftest asserts) — native and Python
        histograms must merge bucket-for-bucket."""
        from paddle_tpu.profiler.stats import (HIST_BUCKETS,
                                               hist_bucket_of)
        assert HIST_BUCKETS == 32
        for v, b in [(0, 0), (1, 1), (2, 2), (3, 2), (4, 3),
                     (1023, 10), (1024, 11), (2 ** 62, 31)]:
            assert hist_bucket_of(v) == b, (v, b)

    def test_counter_histogram_snapshot_and_merge(self):
        from paddle_tpu.profiler import stats as S
        r = S.Registry()
        r.counter("ops").add(2)
        r.counter("ops").add(3)
        r.histogram("lat_us").observe(5)
        snap = r.snapshot()
        assert snap["ops"] == 5
        assert snap["lat_us"]["count"] == 1 and snap["lat_us"]["sum"] == 5
        assert snap["lat_us"]["buckets"][S.hist_bucket_of(5)] == 1
        merged = S.merge(snap, snap, None)   # None halves are skipped
        assert merged["ops"] == 10
        assert merged["lat_us"]["count"] == 2
        assert merged["lat_us"]["buckets"][S.hist_bucket_of(5)] == 2
        r.reset()
        assert r.snapshot()["ops"] == 0

    def test_merge_keeps_tags_and_flags(self):
        """Merging full stats_snapshot() dicts must never concatenate
        backend tags or add booleans — first occurrence wins."""
        from paddle_tpu.profiler import stats as S
        a = {"backend": "numpy", "native": True, "rows": 3}
        m = S.merge(a, a)
        assert m == {"backend": "numpy", "native": True, "rows": 6}

    def test_prometheus_text(self):
        from paddle_tpu.profiler import stats as S
        snap = {"wire": {"pull_ops": 7,
                         "pull_us": {"count": 2, "sum": 9,
                                     "buckets": [0, 1, 1] + [0] * 29}},
                "tables": {"emb": {"pull_rows": 40}}}
        txt = S.prometheus_text(snap, prefix="ptpu_ps",
                                labels={"rank": "0"})
        assert '# TYPE ptpu_ps_wire_pull_ops counter' in txt
        assert 'ptpu_ps_wire_pull_ops{rank="0"} 7' in txt
        # histogram: cumulative buckets + +Inf tail + sum/count
        assert 'ptpu_ps_wire_pull_us_bucket{rank="0",le="1"} 1' in txt
        assert 'ptpu_ps_wire_pull_us_bucket{rank="0",le="+Inf"} 2' in txt
        assert 'ptpu_ps_wire_pull_us_count{rank="0"} 2' in txt
        # per-table stats become a table label, not a metric name
        assert 'table="emb"' in txt


# ---------------------------------------------------------------------------
# RecordEvent + chrome trace + timeline (profiler satellites)
# ---------------------------------------------------------------------------

def _native_prof():
    from paddle_tpu.core import native
    return native.available()


class TestProfilerTrace:
    def test_record_event_decorator(self, built, tmp_path):
        """Satellite: the docstring promises decorator usage."""
        import paddle_tpu.profiler as prof
        calls = []

        @prof.RecordEvent("decorated_step")
        def step(x, k=1):
            calls.append(x)
            return x + k

        assert step.__name__ == "step"      # functools.wraps
        assert step(1, k=2) == 3            # args/result pass through
        if not _native_prof():
            pytest.skip("native runtime unavailable (no-op profiler)")
        prof.reset()
        prof.start_profiler()
        try:
            n0 = prof.event_count()
            step(1)
            step(2)
            assert prof.event_count() == n0 + 2
        finally:
            out = str(tmp_path / "trace.json")
            prof.stop_profiler(profile_path=out)
        with open(out) as f:
            trace = json.load(f)
        names = [e["name"] for e in trace["traceEvents"]]
        assert names.count("decorated_step") == 2

    def test_trace_dump_valid_json_monotonic(self, built, tmp_path):
        if not _native_prof():
            pytest.skip("native runtime unavailable")
        import paddle_tpu.profiler as prof
        prof.reset()
        prof.start_profiler()
        try:
            for i in range(5):
                with prof.RecordEvent(f"ev{i}"):
                    pass
        finally:
            out = str(tmp_path / "trace.json")
            prof.stop_profiler(profile_path=out)
        with open(out) as f:
            trace = json.load(f)          # valid JSON or this raises
        evs = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
        assert len(evs) >= 5
        ts = [e["ts"] for e in evs]
        # sequential same-thread scopes dump in begin order
        assert ts == sorted(ts)
        assert all(e["dur"] >= 0 for e in evs)
        assert all(isinstance(e["name"], str) and "ts" in e for e in evs)

    def test_timeline_align_shifts_ranks(self, tmp_path):
        """Satellite: --align must shift every rank so the marker
        starts at the same instant."""
        from paddle_tpu.profiler.timeline import merge_timelines
        r0 = [{"name": "sync", "ph": "X", "ts": 100, "dur": 5, "tid": 0},
              {"name": "work", "ph": "X", "ts": 110, "dur": 9, "tid": 0}]
        r1 = [{"name": "sync", "ph": "X", "ts": 400, "dur": 5, "tid": 0},
              {"name": "work", "ph": "X", "ts": 415, "dur": 7, "tid": 0}]
        p0, p1 = str(tmp_path / "r0.json"), str(tmp_path / "r1.json")
        for p, evs in ((p0, r0), (p1, r1)):
            with open(p, "w") as f:
                json.dump({"traceEvents": evs}, f)
        out = str(tmp_path / "merged.json")
        merged = merge_timelines([p0, p1], out, align_marker="sync")
        by_rank = {}
        for ev in merged["traceEvents"]:
            if ev.get("name") == "sync":
                by_rank[ev["pid"]] = ev["ts"]
        # both sync markers now start at the earliest one
        assert by_rank[0] == by_rank[1] == 100
        work1 = [ev for ev in merged["traceEvents"]
                 if ev.get("name") == "work" and ev["pid"] == 1]
        assert work1[0]["ts"] == 415 - 300     # same shift for rank 1
        with open(out) as f:
            assert json.load(f)["traceEvents"]  # file round-trips


# ---------------------------------------------------------------------------
# PS stats: server counters == client-side observed counts (both
# backends), live over the control plane "stats" op
# ---------------------------------------------------------------------------

class TestPsStatsExact:
    def _pair(self, port, monkeypatch, native_env):
        from paddle_tpu.distributed.ps import table as T
        monkeypatch.setenv("MASTER_PORT", str(port))
        if native_env is not None:
            monkeypatch.setenv("PTPU_PS_NATIVE", native_env)
        s0 = T.TableService(0, 2, port)
        s1 = T.TableService(1, 2, port)
        s0.register("emb", vocab=100, dim=4, lr=1.0, seed=5)
        s1.register("emb", vocab=100, dim=4, lr=1.0, seed=5)
        return s0, s1

    @pytest.mark.parametrize("native_env", [None, "0"])
    def test_counters_match_client_observed(self, built, monkeypatch,
                                            native_env):
        from paddle_tpu.core import native as N
        if native_env is None and not N.ps_table_available():
            pytest.skip("native PS table unavailable")
        port = 9700 if native_env is None else 9750
        s0, s1 = self._pair(port, monkeypatch, native_env)
        try:
            ids = np.arange(10)          # all rank0-owned (block 50)
            g = np.ones((10, 4), np.float32)
            s1.pull("emb", ids)                       # 1 frame, 10 rows
            s1.pull_many("emb", [ids, ids, ids], depth=2)   # 30 rows
            s1.push("emb", ids, g, sync=True)         # 10 rows
            s1.push("emb", ids, g, sync=False)        # async: 10 rows
            s1.flush()
            snap = s1._rpc(0, "stats", "", None)
            # exact client-observed totals, whichever plane served
            assert snap["wire"]["pull_rows"] == 40
            assert snap["wire"]["push_rows"] == 20
            assert snap["wire"]["push_ops"] == 2
            assert snap["tables"]["emb"]["pull_rows"] == 40
            assert snap["tables"]["emb"]["push_rows"] == 20
            backend = "native" if native_env is None else "numpy"
            assert snap["tables"]["emb"]["backend"] == backend
            assert snap["native_data_plane"] is (native_env is None)
            # serve latency was observed for every frame
            assert snap["wire"]["pull_us"]["count"] == \
                snap["wire"]["pull_ops"]
            # the snapshot renders as Prometheus text
            from paddle_tpu.profiler.stats import prometheus_text
            txt = prometheus_text(snap, prefix="ptpu_ps")
            assert "ptpu_ps_wire_pull_rows 40" in txt
            # reset zeroes both planes
            s1._rpc(0, "stats_reset", "", None)
            snap2 = s1._rpc(0, "stats", "", None)
            assert snap2["wire"].get("pull_rows", 0) == 0
            assert snap2["tables"]["emb"]["pull_rows"] == 0
        finally:
            s1.shutdown()
            s0.shutdown()

    def test_ps_stats_cli_fetch(self, built, monkeypatch):
        """tools/ps_stats.py fetch path against a live service."""
        import sys
        sys.path.insert(0, REPO)
        from tools.ps_stats import fetch_stats
        port = 9780
        s0, s1 = self._pair(port, monkeypatch, "0")
        try:
            ids = np.arange(7)
            s1.pull("emb", ids)
            snap = fetch_stats(port, rank=0, timeout_s=30)
            assert snap["wire"]["pull_rows"] == 7
            assert snap["rank"] == 0 and snap["world"] == 2
        finally:
            s1.shutdown()
            s0.shutdown()

    def test_client_pipeline_merge_counters(self, built, monkeypatch):
        port = 9790
        s0, s1 = self._pair(port, monkeypatch, "0")
        try:
            ids = np.arange(8)
            s1.pull_many("emb", [ids] * 4, depth=2)
            c = s1.stats_snapshot()["client"]
            assert c["pull_reqs"] == 4
            # 4 logical pulls of 8 rows merged into 1 frame (< 4096)
            assert c["pull_frames"] == 1
            assert c["pull_merged_reqs"] == 3
        finally:
            s1.shutdown()
            s0.shutdown()


# ---------------------------------------------------------------------------
# Native predictor stats + RecordEvent spans in the chrome trace
# ---------------------------------------------------------------------------

class TestPredictorStats:
    @pytest.fixture()
    def model_path(self, built, tmp_path):
        import jax.numpy as jnp
        from paddle_tpu.onnx.converter import trace_to_onnx
        rs = np.random.RandomState(0)
        w = jnp.asarray(rs.randn(8, 4).astype(np.float32))
        b = jnp.asarray(rs.randn(4).astype(np.float32))
        model_bytes = trace_to_onnx(
            lambda a: jnp.tanh(a @ w + b),
            (jnp.zeros((2, 8), jnp.float32),))
        path = os.path.join(str(tmp_path), "m.onnx")
        with open(path, "wb") as f:
            f.write(model_bytes)
        return path

    def test_stats_accumulate_and_reset(self, model_path):
        from paddle_tpu.core.native import NativePredictor
        x = np.random.RandomState(1).randn(2, 8).astype(np.float32)
        with NativePredictor(model_path) as p:
            if p.stats() is None:
                pytest.skip("predictor .so predates the stats ABI")
            for _ in range(3):
                p.set_input(p.input_name(0), x)
                p.run()
            s = p.stats()
            assert s["runs"] == 3
            assert s["run_us"]["count"] == 3
            assert s["total_run_us"] >= 0
            ops = s["ops"]
            assert ops, "no per-op stats recorded"
            # every executed node accounted: calls sum = 3 * node count
            assert sum(o["calls"] for o in ops.values()) == \
                3 * p.num_nodes
            assert all(o["bytes"] > 0 for o in ops.values())
            p.stats_reset()
            s2 = p.stats()
            assert s2["runs"] == 0 and s2["ops"] == {}

    def test_run_emits_record_event_spans(self, model_path, tmp_path):
        """Tentpole contract: with the host profiler on, a serving run
        lands in the same chrome trace as any RecordEvent user."""
        if not _native_prof():
            pytest.skip("native runtime unavailable")
        import paddle_tpu.profiler as prof
        from paddle_tpu.core.native import NativePredictor
        x = np.zeros((2, 8), np.float32)
        with NativePredictor(model_path) as p:
            if p.stats() is None:
                pytest.skip("predictor .so predates the stats ABI")
            prof.reset()
            prof.start_profiler()
            try:
                with prof.RecordEvent("serve_batch"):
                    p.set_input(p.input_name(0), x)
                    p.run()
            finally:
                out = str(tmp_path / "serve.json")
                prof.stop_profiler(profile_path=out)
        with open(out) as f:
            names = [e["name"] for e in json.load(f)["traceEvents"]]
        assert "predictor::run" in names
        assert "serve_batch" in names
        # per-op spans: at least one op name from the graph
        assert any(n not in ("predictor::run", "serve_batch")
                   for n in names)
        # profiler off -> no further spans recorded
        with NativePredictor(model_path) as p:
            prof.reset()
            p.set_input(p.input_name(0), x)
            p.run()
            assert prof.event_count() == 0


# ---------------------------------------------------------------------------
# ABI drift guard (CI satellite): every symbol core/native.py declares
# must resolve in the built .so
# ---------------------------------------------------------------------------

class TestAbiManifest:
    def test_every_declared_symbol_resolves(self, built):
        from paddle_tpu.core import native
        pkg = os.path.join(REPO, "paddle_tpu")
        missing = []
        for so_name, symbols in native.ABI_SYMBOLS.items():
            so_path = os.path.join(pkg, so_name)
            if not os.path.exists(so_path):
                pytest.skip(f"{so_name} not built and no toolchain")
            lib = ctypes.CDLL(so_path)
            for sym in symbols:
                try:
                    getattr(lib, sym)
                except AttributeError:
                    missing.append(f"{so_name}:{sym}")
        assert not missing, f"ABI drift — symbols vanished: {missing}"

    def test_manifest_covers_bindings(self):
        """Every `lib.ptpu_*` (or "ptpu_*" string) the binding layer
        references must be in ABI_SYMBOLS — adding a binding without
        extending the manifest fails here."""
        from paddle_tpu.core import native
        src = open(os.path.join(REPO, "paddle_tpu", "core",
                                "native.py")).read()
        referenced = set(re.findall(r"\.(ptpu_[a-z0-9_]+)", src))
        referenced |= set(re.findall(r"['\"](ptpu_[a-z0-9_]+)['\"]",
                                     src))
        declared = set()
        for syms in native.ABI_SYMBOLS.values():
            declared.update(syms)
        assert referenced <= declared, \
            f"bindings missing from ABI_SYMBOLS: " \
            f"{sorted(referenced - declared)}"


# ---------------------------------------------------------------------------
# hapi BenchmarkLogger — trainer-side step time/throughput
# ---------------------------------------------------------------------------

class TestBenchmarkLogger:
    def test_records_and_logs(self, capsys):
        from paddle_tpu.hapi.callbacks import BenchmarkLogger
        from paddle_tpu.profiler import stats as S
        cb = BenchmarkLogger(log_freq=2, batch_size=16)
        steps0 = S.REGISTRY.counter("train_steps").value
        for step in range(4):
            cb.on_train_batch_begin(step)
            cb.on_train_batch_end(step, logs={"loss": 0.5})
        cb.on_train_end()
        assert S.REGISTRY.counter("train_steps").value == steps0 + 4
        hist = S.REGISTRY.histogram("train_step_us")
        assert hist.count >= 4
        out = capsys.readouterr().out
        assert "steps/s" in out and "samples/s" in out
        assert "avg" in out   # on_train_end summary

    def test_fit_integration(self):
        """The callback rides Model.fit like any other hapi callback."""
        import paddle_tpu as pt
        from paddle_tpu.hapi.callbacks import BenchmarkLogger
        from paddle_tpu.profiler import stats as S
        pt.seed(0)
        net = pt.nn.Linear(4, 2)
        model = pt.Model(net)
        model.prepare(pt.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
                      pt.nn.CrossEntropyLoss())
        x = np.random.RandomState(0).randn(32, 4).astype(np.float32)
        y = np.random.RandomState(1).randint(0, 2, (32, 1))
        before = S.REGISTRY.counter("train_steps").value
        model.fit(pt.io.TensorDataset([x, y]), epochs=1, batch_size=8,
                  verbose=0, callbacks=[BenchmarkLogger(verbose=0)])
        assert S.REGISTRY.counter("train_steps").value > before
