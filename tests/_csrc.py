"""`make` in csrc/ for the tests that build or run the native stack.

Every fixture that needs the shipping libraries and every test that
builds and runs a C selftest goes through here, under the locks that
`paddle_tpu.core.native.build_lock` explains (that docstring is the one
place that says what the Makefile's rename and what the lock guards).
"""
import os
import signal
import subprocess

from paddle_tpu.core.native import build_lock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CSRC = os.path.join(REPO, "csrc")

# targets that RUN the binaries they build: one at a time, all kinds
_RUNS = ("selftest", "sancheck", "schedck", "ptpu_")


def make(args, jobs=4, timeout=600):
    """`make -j<jobs> <args>` in csrc/ -> CompletedProcess (text). The
    time limit ends the whole process group, so a selftest that hangs
    costs its own test and leaves nothing running."""
    runs = any(a.startswith(_RUNS) for a in args)
    with build_lock("selftest" if runs else "all"):
        p = subprocess.Popen(["make", f"-j{jobs}", *args], cwd=CSRC,
                             stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                             text=True, start_new_session=True)
        try:
            out, err = p.communicate(timeout=timeout)
        except BaseException:   # this limit, or the suite's (conftest.py)
            os.killpg(p.pid, signal.SIGKILL)
            p.wait()
            raise
    return subprocess.CompletedProcess(p.args, p.returncode, out, err)


def build_all():
    """The shipping libraries and the demo, as the fixtures want them:
    raises CalledProcessError (stderr attached) when the build fails,
    FileNotFoundError when there is no `make`."""
    r = make(["all"])
    if r.returncode:
        raise subprocess.CalledProcessError(r.returncode, r.args,
                                            r.stdout, r.stderr)
