"""bench.py must not hide the device (ISSUE 23).

One process owns the chip, so the script that measures on it either is
that process or never touches JAX before its children: bench.py is one
process and starts none. Off-chip it fails — no CPU arm, no number, exit
code non-zero — and a device whose peak is not on record is an error.
"""
import ast
import importlib.util
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location("bench_under_test", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestPeakTable:
    def test_v5e_as_the_runtime_names_it(self, bench):
        assert bench.peak_flops("TPU v5 lite") == 197e12

    @pytest.mark.parametrize("kind", ["cpu", "TPU v5", "TPU v9 lite", ""])
    def test_unknown_kind_raises(self, bench, kind):
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            bench.peak_flops(kind)


class TestOneProcess:
    def test_import_does_not_touch_jax(self):
        """Importing bench.py (what a parent would do before deciding
        anything) must leave JAX unimported: checked in a fresh
        interpreter, since this one has JAX already."""
        code = ("import sys; sys.path.insert(0, %r); import bench; "
                "assert 'jax' not in sys.modules, 'bench imported jax'; "
                "assert 'paddle_tpu' not in sys.modules" % REPO)
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr

    def test_starts_no_child_process(self):
        """The run is one process: nothing in bench.py can start a child
        that would find the chip taken."""
        with open(BENCH) as f:
            tree = ast.parse(f.read())
        imported = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                imported |= {a.name.split(".")[0] for a in node.names}
            elif isinstance(node, ast.ImportFrom):
                imported.add((node.module or "").split(".")[0])
        assert not imported & {"subprocess", "multiprocessing",
                               "concurrent"}
        called = {n.func.attr for n in ast.walk(tree)
                  if isinstance(n, ast.Call)
                  and isinstance(n.func, ast.Attribute)}
        assert not called & {"system", "popen", "fork", "execv", "execve",
                             "spawnv", "posix_spawn"}

    def test_off_chip_run_fails_without_a_number(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        r = subprocess.run([sys.executable, BENCH], capture_output=True,
                           text=True, env=env, cwd=REPO, timeout=300)
        assert r.returncode != 0
        assert r.stdout.strip() == "", r.stdout
        assert "needs a TPU" in r.stderr

    def test_unknown_spec_is_an_error(self, bench):
        with pytest.raises(ValueError, match="unknown bench"):
            bench.run_spec("nosuch:3")
