"""Test configuration.

Tests run on a virtual 8-device CPU mesh
(`--xla_force_host_platform_device_count=8`), the same trick the reference
uses to test distributed logic without a cluster (SURVEY.md §4 "Port
lesson"). The env must be set before jax initializes a backend; do NOT
import jax above these lines in any test module imported earlier.
"""
import os

# Tier-1 runs every registered IR pass under the jaxpr well-formedness
# verifier (paddle_tpu/ir/verify.py): a pass that breaks
# defs-before-uses / SSA / outvar wiring fails AT the pass, loudly,
# instead of miscompiling later. Off by default in production.
os.environ.setdefault("PTPU_IR_VERIFY", "1")

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")

# The persistent XLA compilation cache is OFF for the tests unless
# PTPU_XLA_CACHE=1 asks for it. With the installed jax 0.9.0 an XLA:CPU
# executable that came out of the cache runs its collectives in an order
# of its own: `test_sequence_parallel.py::test_sp_loss_parity` passes
# when its step was compiled in the process and deadlocks whenever the
# step was read back (all-reduce and collective-permute wait for each
# other, XLA aborts the process after 40 s; reproduced with a fresh
# directory: cold run passes, every warm run aborts). In a suite where six
# workers and their subprocesses share one directory, which test reads
# what another wrote is a matter of timing. Set via the environment (not
# jax.config) so that the subprocess tests (examples, launch, dist
# runners) inherit it. When asked for, the cache follows the rule of
# paddle_tpu/core/compile_cache.py: JAX_COMPILATION_CACHE_DIR wins when
# set, else the fixed git-ignored directory inside this checkout.
if os.environ.get("PTPU_XLA_CACHE") == "1":
    os.environ.setdefault(
        "JAX_COMPILATION_CACHE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache"))
    os.environ.setdefault(
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
else:
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import hashlib  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402

import pytest  # noqa: E402

# The suite has one clock (tier-1 is cut at its time limit, and a cut run
# counts only as far as it got), so no single test may keep it.
#
# 1. A test still running after TEST_LIMIT_S — the slowest takes ~2 min —
#    fails with TimeoutError raised in the main thread: that interrupts
#    the waits tests really get stuck in (a child, a socket, a lock, a
#    join). It cannot reach a thread spinning inside C++;
#    tests/test_tpu_compile.py, where the compiler can do that, ends its
#    process instead.
# 2. A test that ends its worker process (an abort inside XLA, a
#    segfault, the compile limit) is run again by xdist on the next
#    worker — `--dist loadfile` puts the whole unfinished file back in
#    the queue, the test that died included — and again, up to 24
#    workers: one XLA abort that takes 40 s to come can use up the whole
#    time limit that way. So every test leaves a note while it runs, and a
#    test that finds its own note from this run fails at once instead.
TEST_LIMIT_S = 600
_DIED_BEFORE = pytest.StashKey[bool]()


def _too_long(signum, frame):
    raise TimeoutError(f"test still running after {TEST_LIMIT_S} s")


def _running_note(nodeid):
    run = os.environ.get("PYTEST_XDIST_TESTRUNUID")
    if not run:     # no xdist: a dead process ends the run, nothing retries
        return None
    name = hashlib.sha1(f"{run} {nodeid}".encode()).hexdigest()[:20]
    return os.path.join(tempfile.gettempdir(), f"ptpu_test_running_{name}")


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    note = _running_note(item.nodeid)
    if note:
        item.stash[_DIED_BEFORE] = os.path.exists(note)
        open(note, "w").close()
    signal.signal(signal.SIGALRM, _too_long)
    signal.alarm(TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        if note:
            os.unlink(note)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if item.stash.get(_DIED_BEFORE, False):
        pytest.fail("this test ended its worker process in an earlier "
                    "attempt of this run; not run again", pytrace=False)


# xdist hands out whole files in collection order (--dist loadfile), and
# a four-minute file that starts last sets the wall time of the run. The
# files that take longest, longest first (junit times of a whole run
# under six workers: 713, 631, 380, 372, 358, 286, 285, 264, 253, 253,
# 234, 220, 187, 179, 167 s):
_LONGEST_FIRST = ("test_flash_attention.py", "test_rcnn.py", "test_afmoe.py",
                  "test_perf_benchmark.py", "test_examples.py",
                  "test_deepseek_v3.py", "test_keye.py", "test_parallel.py",
                  "test_yolo.py", "test_trinity_cell.py",
                  "test_sequence_parallel.py", "test_keye_cell.py",
                  "test_contrib_ops.py", "test_kanana_cell.py",
                  "test_onnx_export.py")


def pytest_collection_modifyitems(items):
    rank = {name: i for i, name in enumerate(_LONGEST_FIRST)}
    items.sort(key=lambda it: rank.get(it.path.name, len(rank)))


@pytest.fixture
def rng_seed():
    import paddle_tpu
    paddle_tpu.seed(0)
    return 0


@pytest.fixture
def mesh8():
    """A 2x2x2 (data, pipe, model) test mesh on virtual CPU devices."""
    from paddle_tpu.distributed import build_mesh
    return build_mesh(dp=2, pp=2, mp=2)
