"""Native (C++) unit tests — the reference's cc_test idiom.

Reference: gtest cc_test targets per CMakeLists (e.g.
`paddle/fluid/framework/data_type_test.cc`). Two dependency-free
binaries: `csrc/ptpu_selftest.cc` asserts the predictor TU's internal
kernels (sgemm vs naive incl. 0*NaN IEEE propagation, exact int32
igemm, the int8_exact overflow bound, broadcast walk, input-dim
validation, worker-pool coverage) plus the serving-stats accumulation
of run(); `csrc/ptpu_ps_selftest.cc` asserts the PS shard table +
data-plane server (gather/bounds, per-optimizer update formulas vs
naive references, duplicate coalescing, torn-read freedom under
concurrent pull/push, SHA-256/HMAC known vectors, a full socket
round-trip incl. bad-authkey rejection, and the csrc/ptpu_stats.h
counters/histograms: log2 bucket boundaries, exact relaxed-atomic sums
under threads, table + server wire stats JSON incl. reset);
`csrc/ptpu_serving_selftest.cc` asserts the serving runtime (batcher
deadline/full flushes, partial final batch, FIFO de-mux ordering,
batcher stats exactness, the two-instance >= 1.3x private-sub-pool
concurrency stress, HMAC handshake accept/reject, batched INFER
round-trips with row de-mux parity, bucket_miss accounting and
server-counter exactness — all over a hand-rolled ONNX artifact, no
Python in the loop).

The same binaries are also gated under sanitizers (`make sancheck`):
the ASan+UBSan and TSan legs run here whenever the sanitized binaries
are current (the normal state of a working tree — a warm re-run takes
seconds) or when PTPU_SANCHECK_BUILD=1 forces the full instrumented
rebuild. On a cold tree without the opt-in they skip with a reason:
the ~4 min of sanitizer compilation would blow the tier-1 time budget,
and `tools/run_checks.sh` is the unconditional gate that always builds
and runs every leg.
"""
import os
import subprocess
import tempfile

import pytest

from _csrc import make as _make

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")

SAN_BINARIES = {
    "asan,ubsan": ["ptpu_selftest.san-asan-ubsan",
                   "ptpu_ps_selftest.san-asan-ubsan",
                   "ptpu_serving_selftest.san-asan-ubsan",
                   "ptpu_net_selftest.san-asan-ubsan",
                   "ptpu_trace_selftest.san-asan-ubsan",
                   "ptpu_lockdep_selftest.san-asan-ubsan",
                   "ptpu_schedck_selftest.san-asan-ubsan",
                   "ptpu_schedck_fixture_lostwake.san-asan-ubsan",
                   "ptpu_schedck_fixture_closerace.san-asan-ubsan",
                   "ptpu_predictor_demo.san-asan-ubsan"],
    "tsan": ["ptpu_selftest.san-tsan", "ptpu_ps_selftest.san-tsan",
             "ptpu_serving_selftest.san-tsan",
             "ptpu_net_selftest.san-tsan",
             "ptpu_trace_selftest.san-tsan",
             "ptpu_lockdep_selftest.san-tsan",
             "ptpu_schedck_selftest.san-tsan",
             "ptpu_schedck_fixture_lostwake.san-tsan",
             "ptpu_schedck_fixture_closerace.san-tsan",
             "ptpu_predictor_demo.san-tsan"],
}


def _san_flag_available(kind: str) -> bool:
    """True when the toolchain can build AND run with -fsanitize=kind."""
    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "t.cc")
        exe = os.path.join(d, "t")
        with open(src, "w") as f:
            f.write("int main() { return 0; }\n")
        cxx = os.environ.get("CXX", "g++")  # same default as Makefile
        try:
            r = subprocess.run(
                [cxx, f"-fsanitize={kind}", "-o", exe, src],
                capture_output=True, timeout=120)
            if r.returncode != 0:
                return False
            return subprocess.run([exe], capture_output=True,
                                  timeout=60).returncode == 0
        except (OSError, subprocess.SubprocessError):
            return False


def _csrc_content_hash() -> str:
    """sha256 over every csrc source/header + Makefile, concatenated
    in LC_ALL=C sort order — the exact recipe the Makefile's sancheck
    stamp uses."""
    import hashlib
    names = sorted(f for f in os.listdir(CSRC)
                   if f.endswith((".cc", ".h", ".c")) or f == "Makefile")
    h = hashlib.sha256()
    for f in names:
        with open(os.path.join(CSRC, f), "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def _san_binaries_warm(san: str) -> bool:
    """True when every sanitized binary for this leg exists and was
    built from EXACTLY the current sources — i.e. `make sancheck` will
    only re-RUN, not re-compile.

    Currency is judged by the CONTENT-hash stamp the Makefile's
    sancheck target writes (.san-srchash-<leg>), not by mtimes: a
    `git checkout`/branch switch rewrites identical bytes with fresh
    mtimes, which used to mis-read a warm tree as cold and skip the
    sanitizer legs (r11 note). Trees whose binaries predate the stamp
    fall back to the old mtime comparison (conservative: may still
    misfire cold, never misfires warm)."""
    for b in SAN_BINARIES[san]:
        if not os.path.exists(os.path.join(CSRC, b)):
            return False
    stamp = os.path.join(CSRC,
                         ".san-srchash-" + san.replace(",", "-"))
    if os.path.exists(stamp):
        with open(stamp) as f:
            return f.read().strip() == _csrc_content_hash()
    # pre-stamp binaries (built by an older Makefile): mtime fallback
    src_mtime = max(
        os.path.getmtime(os.path.join(CSRC, f))
        for f in os.listdir(CSRC)
        if f.endswith((".cc", ".h", ".c")) or f == "Makefile")
    for b in SAN_BINARIES[san]:
        if os.path.getmtime(os.path.join(CSRC, b)) < src_mtime:
            return False
    return True


def _sancheck_leg(san: str, kinds: list):
    for kind in kinds:
        if not _san_flag_available(kind):
            pytest.skip(f"toolchain lacks lib{kind}san")
    if not _san_binaries_warm(san) and \
            os.environ.get("PTPU_SANCHECK_BUILD") != "1":
        pytest.skip(
            f"sanitized binaries for SAN={san} need a full rebuild "
            f"(~minutes) — set PTPU_SANCHECK_BUILD=1 or run "
            f"tools/run_checks.sh")
    r = _make(["sancheck", f"SAN={san}"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert f"sancheck[{san}]: selftests + demo clean" in r.stdout


def test_warm_gate_survives_touched_sources(tmp_path, monkeypatch):
    """The r11 misfire: `git checkout` rewrites identical source bytes
    with fresh mtimes, and the old mtime-based warm gate then skipped
    the sanitizer legs on a perfectly warm tree. The content-hash
    stamp must keep such a tree warm — and must go cold the moment a
    source actually changes."""
    import sys
    import time
    fake = tmp_path / "csrc"
    fake.mkdir()
    (fake / "a.cc").write_text("int x;\n")
    (fake / "util.h").write_text("#pragma once\n")
    (fake / "Makefile").write_text("all:\n")
    binname = "ptpu_selftest.san-asan-ubsan"
    (fake / binname).write_text("fake binary")
    mod = sys.modules[__name__]
    monkeypatch.setattr(mod, "CSRC", str(fake))
    monkeypatch.setitem(SAN_BINARIES, "asan,ubsan", [binname])
    (fake / ".san-srchash-asan-ubsan").write_text(
        _csrc_content_hash() + "\n")
    # a checkout-style touch: same bytes, NEWER mtime than the binary
    time.sleep(0.02)
    (fake / "a.cc").write_text("int x;\n")
    assert _san_binaries_warm("asan,ubsan"), \
        "identical sources with fresh mtimes must stay warm"
    # a real edit flips it cold
    (fake / "a.cc").write_text("int y;\n")
    assert not _san_binaries_warm("asan,ubsan")
    # a leg with no stamp and stale binaries is cold (mtime fallback)
    (fake / ".san-srchash-asan-ubsan").unlink()
    assert not _san_binaries_warm("asan,ubsan")


def test_native_selftest_passes():
    r = _make(["selftest"])
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all native unit tests passed" in r.stdout
    assert "all native ps-table unit tests passed" in r.stdout
    assert "all native serving unit tests passed" in r.stdout
    assert "ptpu_trace_selftest" in r.stdout
    assert "all native lockdep unit tests passed" in r.stdout
    assert "all native schedck unit tests passed" in r.stdout
    assert "all lostwake fixture checks passed" in r.stdout
    assert "all closerace fixture checks passed" in r.stdout


def test_sancheck_asan_ubsan_green():
    """The ASan+UBSan leg of `make sancheck` must be clean on this
    machine: all three selftests plus the pure-C demo, fail-fast
    (-fno-sanitize-recover), -Werror on."""
    _sancheck_leg("asan,ubsan", ["address", "undefined"])


def test_sancheck_tsan_green():
    """The TSan leg — the tree carries an EMPTY suppression list (see
    csrc/Makefile notes: timed condvar waits route through ptpu_sync.h
    so the uninstrumented pthread_cond_clockwait path is never taken
    under TSan)."""
    _sancheck_leg("tsan", ["thread"])
