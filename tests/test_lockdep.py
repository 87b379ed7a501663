"""ptpu_lockdep (csrc/ptpu_sync.h) — the ranked-mutex validator gate
(ISSUE 11 tentpole, part 1).

Three properties, mirroring the acceptance criteria:

* DETECTION: the seeded ABBA-deadlock fixture (and the rank /
  held-across-blocking / recursion fixtures) abort deterministically
  with BOTH acquisition stacks printed — csrc/ptpu_lockdep_selftest.cc
  is the fixture suite; this module builds and runs it (a small
  single-header binary, seconds even cold).
* LIVE TREE CLEAN: the full native selftest suite runs with the
  validator compiled in (LOCKDEP=1 is the Makefile default) and
  reports 0 violations — gated here whenever the selftest binaries
  are warm (same policy as the sancheck legs in
  tests/test_native_selftest.py; tools/run_checks.sh always builds).
* PASS-THROUGH: the shipping .so artifacts are built WITHOUT
  PTPU_LOCKDEP — proven by nm: no lockdep symbol may appear in any of
  the three .so's, while the fixture binary (always built with the
  validator) must carry them.
"""
import os
import subprocess

import pytest

from _csrc import make as _make

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")

SELFTEST_BINARIES = [
    "ptpu_selftest", "ptpu_ps_selftest", "ptpu_serving_selftest",
    "ptpu_net_selftest", "ptpu_trace_selftest", "ptpu_lockdep_selftest",
    "ptpu_schedck_selftest", "ptpu_schedck_fixture_lostwake",
    "ptpu_schedck_fixture_closerace",
]
SHIPPING_SOS = [
    "paddle_tpu/_native.so", "paddle_tpu/_native_predictor.so",
    "paddle_tpu/_native_ps.so",
]


def _selftests_warm() -> bool:
    """True when every plain selftest binary is at least as new as
    every csrc source — `make selftest` would only re-RUN."""
    src_mtime = max(
        os.path.getmtime(os.path.join(CSRC, f))
        for f in os.listdir(CSRC)
        if f.endswith((".cc", ".h", ".c")) or f == "Makefile")
    for b in SELFTEST_BINARIES:
        p = os.path.join(CSRC, b)
        if not os.path.exists(p) or os.path.getmtime(p) < src_mtime:
            return False
    return True


def _nm(path):
    r = subprocess.run(["nm", "-C", path], capture_output=True,
                       text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    return r.stdout


class TestSeededViolations:
    @pytest.fixture(scope="class")
    def fixture_bin(self):
        """Build just the (small, header-only) fixture binary."""
        r = _make(["ptpu_lockdep_selftest"], timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        return os.path.join(CSRC, "ptpu_lockdep_selftest")

    def test_abba_and_friends_detected_deterministically(
            self, fixture_bin):
        """The fixture suite forks each seeded violation and asserts
        (inside the binary) SIGABRT + both class names + two '>>>
        stack' blocks; a pass here means every fixture detected."""
        r = subprocess.run([fixture_bin], capture_output=True,
                           text=True, timeout=300)
        assert r.returncode == 0, r.stdout + r.stderr
        assert "seeded ABBA cycle: deterministic abort" in r.stdout
        assert "rank inversion: abort" in r.stdout
        assert "held-across-blocking wait: abort" in r.stdout
        assert "same-class double acquire: abort" in r.stdout
        assert "all native lockdep unit tests passed" in r.stdout

    def test_detection_is_repeatable(self, fixture_bin):
        """Deterministic means every run, not most runs."""
        for _ in range(3):
            r = subprocess.run([fixture_bin], capture_output=True,
                               text=True, timeout=300)
            assert r.returncode == 0, r.stdout + r.stderr


class TestLiveTreeClean:
    def test_selftests_run_lockdep_enabled_with_zero_reports(self):
        """The whole native suite under the validator: any cycle /
        rank inversion / held-across-blocking in the REAL lock graph
        aborts the run. Warm-gated like the sancheck legs (a cold
        build is minutes; tools/run_checks.sh is the unconditional
        gate); PTPU_LOCKDEP_BUILD=1 forces the build here."""
        if not _selftests_warm() and \
                os.environ.get("PTPU_LOCKDEP_BUILD") != "1":
            pytest.skip("selftest binaries need a rebuild (~minutes) — "
                        "set PTPU_LOCKDEP_BUILD=1 or run "
                        "tools/run_checks.sh")
        r = _make(["selftest"])
        assert r.returncode == 0, r.stdout + r.stderr
        assert "ptpu_lockdep:" not in r.stdout + r.stderr.replace(
            "ptpu_lockdep_selftest", "")
        assert "all native lockdep unit tests passed" in r.stdout


class TestShippingPassThrough:
    def test_shipping_sos_carry_no_lockdep_symbols(self):
        """PTPU_LOCKDEP never reaches a shipping artifact: the
        wrappers must compile to bare std::mutex (zero cost). The
        validator's inline state functions leave 'lockdep' symbols in
        any binary that compiled them in — none may exist here."""
        missing = [so for so in SHIPPING_SOS
                   if not os.path.exists(os.path.join(REPO, so))]
        if missing:
            r = _make(["all"])
            assert r.returncode == 0, r.stdout + r.stderr
        for so in SHIPPING_SOS:
            out = _nm(os.path.join(REPO, so))
            assert "lockdep" not in out.lower(), (
                f"{so} carries lockdep symbols — a shipping .so was "
                f"built with PTPU_LOCKDEP")

    def test_fixture_binary_carries_the_validator(self):
        """Control for the nm assertion above: the always-instrumented
        fixture binary DOES show the symbols, so an empty grep on the
        .so's means pass-through, not a broken probe."""
        p = os.path.join(CSRC, "ptpu_lockdep_selftest")
        if not os.path.exists(p):
            r = _make(["ptpu_lockdep_selftest"], timeout=300)
            assert r.returncode == 0, r.stdout + r.stderr
        assert "lockdep" in _nm(p).lower()
