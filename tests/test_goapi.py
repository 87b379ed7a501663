"""Go binding over the C inference ABI (VERDICT r4 item 9).

Reference: `/root/reference/paddle/fluid/inference/goapi/` — a cgo
wrapper over the C API. `goapi/predictor.go` is the equivalent here.
The build image has no Go toolchain, so the full `go test` runs only
where `go` exists (skipped otherwise); this module always checks the
cgo surface stays in sync with the C header it wraps.
"""
import os
import re
import shutil
import subprocess

import pytest

from _csrc import build_all

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GOAPI = os.path.join(REPO, "goapi")


def test_go_source_covers_c_abi():
    """Every ptpu_predictor_* symbol in the C header is called from the
    Go wrapper — drift between the two surfaces fails here even without
    a Go toolchain."""
    hdr = open(os.path.join(REPO, "csrc", "ptpu_inference_api.h")).read()
    go = open(os.path.join(GOAPI, "predictor.go")).read()
    symbols = set(re.findall(r"\b(ptpu_predictor_\w+)\s*\(", hdr))
    assert symbols, "header parse failed"
    missing = [s for s in symbols if f"C.{s}(" not in go]
    assert not missing, f"Go wrapper missing C calls: {missing}"


@pytest.mark.skipif(shutil.which("go") is None,
                    reason="no Go toolchain in this image")
def test_go_round_trip(tmp_path):
    """Where Go exists: export a fixture, build and run `go test`."""
    import numpy as np  # noqa: F401

    import paddle_tpu as pt
    from paddle_tpu.static import InputSpec

    # ensure the .so exists (fresh checkout): same build the predictor
    # tests use
    build_all()
    td = os.path.join(GOAPI, "testdata")
    os.makedirs(td, exist_ok=True)
    pt.seed(0)
    net = pt.nn.Sequential(pt.nn.Linear(8, 4))
    pt.onnx.export(net, os.path.join(td, "lin"),
                   input_spec=[InputSpec([2, 8], "float32")])
    env = dict(os.environ)
    env["CGO_CFLAGS"] = f"-I{os.path.join(REPO, 'csrc')}"
    env["CGO_LDFLAGS"] = (
        f"-L{os.path.join(REPO, 'paddle_tpu')} -l:_native_predictor.so "
        f"-Wl,-rpath,{os.path.join(REPO, 'paddle_tpu')}")
    r = subprocess.run(["go", "test", "./..."], cwd=GOAPI, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stdout + r.stderr
