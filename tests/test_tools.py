"""Developer tooling: op benchmark harness + regression gate + flops.
Reference bars: `op_tester.cc`, `check_op_benchmark_result.py`,
`hapi/dynamic_flops.py`."""
import json
import os
import subprocess
import sys

import numpy as np

import paddle_tpu as pt
from paddle_tpu.tools.op_bench import bench_ops, check_regression

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestOpBench:
    def test_bench_subset_produces_timings(self):
        res = bench_ops(["softmax", "reduce_sum"], iters=3)
        assert set(res) == {"softmax", "reduce_sum"}
        assert all(r["ms"] > 0 for r in res.values())

    def test_regression_gate(self):
        cur = {"matmul": {"ms": 1.0}, "softmax": {"ms": 2.0}}
        base = {"matmul": {"ms": 1.0}, "softmax": {"ms": 1.0}}
        ok, fails = check_regression(cur, base, tolerance=0.15)
        assert not ok and len(fails) == 1 and "softmax" in fails[0]
        ok2, _ = check_regression(base, base, tolerance=0.15)
        assert ok2
        ok3, fails3 = check_regression({}, base)
        assert not ok3 and len(fails3) == 2  # missing ops flagged

    def test_cli_write_and_compare(self, tmp_path):
        env = dict(os.environ)
        env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
        out = str(tmp_path / "ops.json")
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.op_bench",
             "--ops", "reduce_sum", "--iters", "2", "--out", out],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
        assert r.returncode == 0, r.stderr
        with open(out) as f:
            data = json.load(f)
        assert "reduce_sum" in data
        # compare against itself: no regression, rc 0
        r2 = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.tools.op_bench",
             "--ops", "reduce_sum", "--iters", "2", "--compare", out,
             "--tolerance", "5.0"],
            capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
        assert r2.returncode == 0, r2.stderr


class TestFlops:
    def test_linear_flops_exact(self):
        n = pt.nn.Linear(64, 128, bias_attr=False)
        f = pt.flops(n, (2, 64))
        assert f == 2 * 2 * 64 * 128  # 2*m*k*n

    def test_conv_model_flops_positive_and_scales_with_batch(self):
        from paddle_tpu.vision.models import LeNet
        net = LeNet()
        f1 = pt.flops(net, (1, 1, 28, 28))
        f2 = pt.flops(net, (2, 1, 28, 28))
        assert f1 > 1e5
        assert abs(f2 - 2 * f1) / (2 * f1) < 0.05
