"""csrc/ptpu_schedck — the deterministic concurrency model checker
(ISSUE 15).

What tier-1 proves here:
  * the two seeded historical-bug fixtures (r10 eventfd lost wakeup,
    r9 listen-fd close-before-join) rediscover their race at the SAME
    schedule number on every run — the exploration is deterministic,
    not merely successful — and their replay/negative-control checks
    pass;
  * the scenario suite itself is green (DFS-exhaustive small configs,
    PCT sweep large ones);
  * the shipping .so artifacts contain no schedck machinery: nm shows
    zero schedck symbols (with the always-instrumented selftest binary
    as the positive control), and the Makefile's shipping rules refuse
    a SCHEDCK=1 build outright;
  * tools/run_checks.sh carries the schedck leg.

Builds go through make (idempotent on a warm tree — `make selftest`
already produced these binaries).
"""
import importlib.util
import os
import re
import subprocess

import pytest

from _csrc import make as _make

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")

# The scenario/lock-class universe is DERIVED, never hand-bumped
# (ISSUE 20 satellite): the expected scenario count comes from the
# selftest's own registry, parsed with the sched checker's machinery
# so this test and tools/ptpu_check.py can never disagree about what
# exists.
_spec = importlib.util.spec_from_file_location(
    "_ptpu_check_for_schedck", os.path.join(REPO, "tools",
                                            "ptpu_check.py"))
ptpu_check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ptpu_check)


def scenario_registry():
    """The {"name", ...} rows of the selftest's scenario table — the
    exact parse check_sched runs over the same TU."""
    with open(os.path.join(REPO, ptpu_check.SCHED_SCENARIO_TU)) as fh:
        src = fh.read()
    return set(re.findall(
        r'\{\s*"([a-z][a-z0-9_]*)"\s*,',
        ptpu_check.strip_c_comments(src, keep_strings=True)))


def coverage_rows():
    """csrc/ptpu_schedck_coverage.txt as {lock class: [scenario...]}."""
    rows = {}
    with open(os.path.join(REPO, ptpu_check.SCHED_MANIFEST)) as fh:
        for raw in fh:
            line = raw.split("#", 1)[0].strip()
            if line:
                parts = line.split()
                rows[parts[0]] = parts[1:]
    return rows


def production_lock_classes():
    """Every PTPU_LOCK_CLASS declared in production csrc (the rank
    table), via the checker's own source walk and declaration regex."""
    classes = set()
    for rel, fname in ptpu_check._csrc_sources(REPO):
        if (ptpu_check._SCHED_TEST_TU.search(fname)
                or fname in ptpu_check.SCHED_ENGINE_FILES):
            continue
        src = ptpu_check._read(REPO, rel)
        if src is None:
            continue
        decls = ptpu_check.strip_c_comments(src, keep_strings=True)
        for m in ptpu_check._LOCK_CLASS_DECL.finditer(decls):
            classes.add(m.group(2))
    return classes

FIXTURES = {
    "lostwake": ("ptpu_schedck_fixture_lostwake",
                 r"rediscovered the r10 lost wakeup at schedule (\d+)"),
    "closerace": ("ptpu_schedck_fixture_closerace",
                  r"rediscovered the r9 close-before-join race at "
                  r"schedule (\d+)"),
}
SHIPPING_SOS = [
    "paddle_tpu/_native.so", "paddle_tpu/_native_predictor.so",
    "paddle_tpu/_native_ps.so",
]


def _built(binary):
    r = _make([binary])
    assert r.returncode == 0, r.stdout + r.stderr
    return os.path.join(CSRC, binary)


def _run(path, timeout=300):
    return subprocess.run([path], cwd=CSRC, capture_output=True,
                          text=True, timeout=timeout)


@pytest.mark.parametrize("name", sorted(FIXTURES))
def test_fixture_rediscovery_is_deterministic(name):
    """Same binary, three runs: the bug must be found at the SAME
    schedule index each time (both dfs and pct discoveries print one),
    and every run's full check suite — replay on schedule 0 included —
    must pass."""
    binary, pat = FIXTURES[name]
    path = _built(binary)
    schedules = []
    for _ in range(3):
        r = _run(path)
        assert r.returncode == 0, r.stdout + r.stderr
        found = re.findall(pat, r.stdout)
        assert len(found) == 2, f"expected dfs+pct discovery lines:\n" \
                                f"{r.stdout}"
        assert f"all {name} fixture checks passed" in r.stdout
        assert "on schedule 0" in r.stdout  # the replay check ran
        schedules.append(found)
    assert schedules[0] == schedules[1] == schedules[2], \
        f"discovery schedule drifted across runs: {schedules}"


def test_selftest_scenarios_green():
    """Engine unit tests + every registered production-protocol
    scenario: DFS-exhaustive small configs, PCT sweep large ones
    (budget via PTPU_SCHEDCK_SCHEDULES; the default 300 keeps tier-1
    fast — the run_checks.sh leg sweeps 10000). The expected count is
    DERIVED from the selftest's scenario registry — adding a scenario
    must not require touching this test."""
    registry = scenario_registry()
    assert registry, "scenario registry parse came up empty"
    path = _built("ptpu_schedck_selftest")
    r = _run(path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "all native schedck unit tests passed" in r.stdout
    assert (len(re.findall(r"\(exhaustive\)", r.stdout))
            == len(registry)), \
        "every registered scenario's small config must exhaust its " \
        "DFS space"


def test_coverage_manifest_consistent_with_sources():
    """The three derivation inputs agree with each other: every
    coverage-manifest scenario exists in the registry, and the
    manifest's lock-class rows are exactly the PTPU_LOCK_CLASS names
    declared in production csrc (the rank table) — the same closure
    check_sched enforces finding-by-finding, asserted here as set
    algebra so a drift fails tier-1 even without the checker leg."""
    registry = scenario_registry()
    rows = coverage_rows()
    classes = production_lock_classes()
    mapped = set().union(*rows.values()) if rows else set()
    assert mapped <= registry, \
        f"coverage maps unknown scenarios: {sorted(mapped - registry)}"
    assert classes == set(rows), \
        f"rank table vs coverage rows drifted: " \
        f"+{sorted(classes - set(rows))} -{sorted(set(rows) - classes)}"
    # scenarios that model no lock class (pure-engine protocols) are
    # fine; a manifest can never cover MORE scenarios than exist
    assert len(rows) >= 1 and len(registry) >= len(mapped)


def test_no_stray_trace_files_after_runs():
    """Failure traces are a debugging artifact; green runs (fixtures
    included — their children write and replay traces) must clean up
    after themselves."""
    for name in sorted(FIXTURES):
        _run(_built(FIXTURES[name][0]))
    stray = [f for f in os.listdir(CSRC)
             if f.endswith((".schedck-trace", ".trace"))]
    assert stray == [], f"leftover trace files: {stray}"


class TestShippingArtifactsStayClean:
    def _nm(self, path):
        r = subprocess.run(["nm", "-C", path], capture_output=True,
                           text=True, timeout=120)
        # dynamic-only .so may need -D; concat both views
        r2 = subprocess.run(["nm", "-CD", path], capture_output=True,
                            text=True, timeout=120)
        return r.stdout + r2.stdout

    def test_shipping_sos_carry_no_schedck_symbols(self):
        built = False
        for rel in SHIPPING_SOS:
            p = os.path.join(REPO, rel)
            if not os.path.exists(p):
                continue
            built = True
            assert "schedck" not in self._nm(p).lower(), \
                f"{rel} leaks schedck machinery"
        if not built:
            pytest.skip("shipping .so artifacts not built (run "
                        "`make -C csrc all`)")

    def test_selftest_binary_is_the_positive_control(self):
        """Proves the nm probe actually detects the machinery."""
        path = _built("ptpu_schedck_selftest")
        assert "schedck" in self._nm(path).lower()

    def test_shipping_rule_refuses_schedck_build(self):
        so = os.path.join(REPO, "paddle_tpu/_native.so")
        existed = os.path.exists(so)
        r = _make(["-B", "../paddle_tpu/_native.so", "SCHEDCK=1"])
        assert r.returncode != 0
        assert "refusing to build shipping" in r.stdout + r.stderr
        if existed:
            # the refusal fired before the compiler: artifact untouched
            assert os.path.exists(so)


def test_run_checks_carries_the_schedck_leg():
    with open(os.path.join(REPO, "tools", "run_checks.sh")) as f:
        sh = f.read()
    assert "schedck" in sh
    assert "SCHEDCK_SCHEDULES" in sh
