"""CPU rehearsal of chip_smoke.py's control flow (ISSUE 23).

The script proves the main path on the chip; this keeps a later PR from
breaking the script unnoticed. Every phase runs here at a tiny size, on
the virtual CPU devices, with the Pallas interpreter asked for by the
test. What only the chip can show (the compiled TPU kernel, memory_stats)
is switched off by the one argument the phases have for it; the contract
line is never printed off-chip.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod        # dataclasses looks the module up
    spec.loader.exec_module(mod)
    yield mod
    del sys.modules[spec.name]


@pytest.fixture(scope="module")
def compiles(smoke):
    return smoke.CompileLog()


@pytest.fixture
def tiny(smoke):
    from paddle_tpu.models import gpt_tiny
    return smoke.TrainSize(cfg=gpt_tiny(), batch=4, seq=128, steps=5,
                           loss_chunks=4)


@pytest.fixture
def fresh_mesh(monkeypatch):
    from paddle_tpu.distributed import topology
    monkeypatch.setattr(topology, "_GLOBAL_MESH", None)


def test_full_size_is_the_published_gpt_345m(smoke):
    size = smoke.TrainSize.full()
    cfg = size.cfg
    assert (cfg.num_layers, cfg.hidden_size, cfg.num_heads,
            cfg.vocab_size) == (24, 1024, 16, 50304)
    assert (size.batch, size.seq, size.loss_chunks) == (8, 1024, 8)
    assert size.steps >= 5


def test_off_chip_the_script_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for args in ([], ["--chips", "4"]):
        r = subprocess.run([sys.executable, SCRIPT, *args], env=env,
                           cwd=REPO, capture_output=True, text=True,
                           timeout=300)
        assert r.returncode != 0
        assert '"ok"' not in r.stdout and "[train]" not in r.stdout
        assert "found no TPU" in r.stderr


def test_alone_in_a_directory_the_script_fails(tmp_path):
    """The script without the program proves nothing, and says so."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_bytes(open(SCRIPT, "rb").read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, str(alone)], env=env,
                       cwd=str(tmp_path), capture_output=True, text=True,
                       timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_train_phase(smoke, tiny, compiles, fresh_mesh, capsys):
    losses = smoke.phase_train(tiny, 0, compiles, expect_tpu_kernel=False)
    assert len(losses) == 5 and losses[-1] < losses[0]
    out = capsys.readouterr().out
    assert "[train] compiled in" in out and '"ok"' not in out


def test_train_phase_demands_the_kernel_by_default(smoke, tiny, compiles,
                                                   fresh_mesh):
    """On the CPU the dispatch takes the XLA path, which is exactly what
    the chip run must not accept in silence."""
    with pytest.raises(AssertionError, match="XLA path"):
        smoke.phase_train(tiny, 0, compiles)


def test_kernel_phase(smoke, monkeypatch, capsys):
    from paddle_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    smoke.phase_kernels(cases=(
        ("causal", (2, 256, 2, 64), True, False),
        ("ragged kv mask", (2, 256, 2, 64), False, True)))
    assert capsys.readouterr().out.count("[kernels]") == 2


def test_kernel_phase_catches_a_wrong_kernel(smoke, monkeypatch):
    """The tolerance must fail a kernel that ignores its mask."""
    from paddle_tpu.ops import flash_attention as fa
    monkeypatch.setattr(fa, "_interpret", lambda: True)
    real = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal=False, scale=None, kv_mask=None:
        real(q, k, v, causal=causal, scale=scale))
    with pytest.raises(AssertionError):
        smoke.phase_kernels(cases=(
            ("ragged kv mask", (2, 256, 2, 64), False, True),))


def test_api_phase(smoke, capsys):
    import paddle_tpu as pt
    was = pt.get_device()
    try:
        smoke.phase_api(0, device="cpu")
    finally:
        pt.set_device(was)
    out = capsys.readouterr().out
    assert "Model.fit on LeNet" in out and "inference.Predictor" in out


def test_sharded_phase(smoke, tiny, compiles, fresh_mesh, capsys):
    size = dataclasses.replace(
        tiny, steps=2, cfg=dataclasses.replace(tiny.cfg,
                                               dtype=jnp.float32))
    smoke.phase_sharded(size, 0, compiles, expect_tpu_kernel=False)
    out = capsys.readouterr().out
    for name, _, _ in smoke.MESHES:
        assert f"[sharded] {name} vs one device" in out
    assert '"ok"' not in out
