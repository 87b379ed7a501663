#!/usr/bin/env python3
"""The quickest proof that the training main path runs on the chip.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # only the step sharded over 4 chips

ONE process, the only one that touches JAX (a chip belongs to one
process at a time), through the entry points a user calls. Default run:

  1. device    — platform must be `tpu`; versions and cache directory.
  2. trainer   — GPT-345M at its published width and depth (24 layers,
                 hidden 1024, 16 heads, vocab 50304), batch 8 x seq 1024,
                 random weights and data from --seed, through
                 `build_train_step` as the README shows it: compiled
                 once, >= 5 steps on one fixed batch, finite falling
                 loss, the Pallas flash kernel in the compiled program.
  3. kernels   — `ops.flash_attention` against the XLA attention it
                 replaces: outputs and q/k/v gradients, bf16, at the
                 GPT-345M shape (causal) and the BERT-base shape with a
                 ragged k-side padding mask.
  4. API       — `paddle_tpu.Model.prepare/fit` on LeNet, then
                 `jit.save` -> `inference.Predictor` with output parity,
                 arrays on the TPU device.

`--chips 4` runs only the same GPT-345M step on two four-device meshes
(dp2 x mp2, and ZeRO-3 sharding2 x mp2) against one device of the same
host: losses compared, state spread over the devices, collectives in
the program.

Times, rates and bytes are printed as information about this run; they
are not benchmark metrics. Any failed check raises: the exit code is
non-zero and the contract line is not printed. The last line of a run
that passed is exactly
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import statistics
import sys
import tempfile
import time

import numpy as np

# Kernel-vs-XLA tolerance, as max|a-b| / max|reference| per tensor. Both
# sides multiply in bf16 (eps 2^-8 = 3.9e-3) and accumulate in fp32, and
# round differently (the kernel rounds unnormalised probabilities, the
# XLA path normalised ones), so a few bf16 ulps of the largest element
# is agreement. A wrong mask, offset or scale is off by O(1).
KERNEL_TOL = 2e-2
# Predictor-vs-eager tolerance for the fp32 LeNet: the same ops on the
# same device, fused differently by the exported program.
PARITY_RTOL, PARITY_ATOL = 1e-3, 1e-4
# Sharded-vs-one-device tolerance on the loss (relative). The meshes
# split bf16 matmuls and their fp32 reductions differently; the loss is
# a mean over batch x seq tokens, so the differences average out.
MESH_LOSS_RTOL = 2e-3


def log(msg: str) -> None:
    print(msg, flush=True)


def gib(n: float) -> str:
    return f"{n / 2**30:.2f} GiB"


class CompileLog:
    """Counts XLA compile requests (persistent-cache hits included) and
    cache hits in this process, from JAX's own monitoring events."""

    def __init__(self):
        import jax
        self.requests = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, _secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1

    def _event(self, name, **_kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


@dataclasses.dataclass
class TrainSize:
    """What phase 2 and the four-chip phase train. `full()` is what the
    script runs; tests/test_chip_smoke.py rehearses with a tiny one."""
    cfg: object
    batch: int
    seq: int
    steps: int
    loss_chunks: int

    @classmethod
    def full(cls, steps: int = 6) -> "TrainSize":
        from paddle_tpu.models import gpt_345m
        return cls(cfg=gpt_345m(), batch=8, seq=1024, steps=steps,
                   loss_chunks=8)


def phase_device(chips: int) -> dict:
    import importlib.metadata as md
    import jax
    import jaxlib
    from paddle_tpu.core import compile_cache
    cache_dir = compile_cache.enable()
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (platform {dev.platform!r}, "
            f"kind {dev.device_kind!r}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} "
                         f"devices, JAX reports {len(devs)}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs)}
    log(f"[device] {device}")
    log(f"[device] jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {md.version('libtpu')}, python "
        f"{sys.version.split()[0]}")
    log(f"[device] compile cache: {cache_dir}")
    return device


def _build_gpt_step(size: TrainSize, seed: int, mesh, **step_kw):
    """The README's path: model, AdamW with global-norm clipping, the
    one compiled step (the settings of the benchmark's GPT cells)."""
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForPretraining
    from paddle_tpu.trainer import build_train_step
    pt.seed(seed)
    model = GPTForPretraining(size.cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    step, state = build_train_step(model, opt, mesh, remat=True,
                                   remat_policy="dots",
                                   loss_chunks=size.loss_chunks, **step_kw)
    return model, step, state


def _batch(size: TrainSize, seed: int, mesh):
    """One seeded (input_ids, labels) batch, placed the way the step
    shards it: rows over data x sharding."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    rng = np.random.RandomState(seed)
    shape = (size.batch, size.seq)
    ids = rng.randint(0, size.cfg.vocab_size, shape).astype(np.int32)
    labels = rng.randint(0, size.cfg.vocab_size, shape).astype(np.int32)
    return jax.device_put(
        (ids, labels), NamedSharding(mesh, P(("data", "sharding"), None)))


def _run_steps(step, state, batch, steps: int, compiles: CompileLog):
    """steps calls of the compiled step on one batch; each ends in
    block_until_ready. No compile request may happen in here."""
    import jax
    before = compiles.requests
    losses, secs = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        state, loss = step(state, batch)
        jax.block_until_ready((state, loss))
        secs.append(time.perf_counter() - t0)
        losses.append(float(np.asarray(loss)))
    recompiles = compiles.requests - before
    assert recompiles == 0, f"{recompiles} compilations after the first"
    assert all(np.isfinite(losses)), f"non-finite loss: {losses}"
    return state, losses, secs


def phase_train(size: TrainSize, seed: int, compiles: CompileLog,
                expect_tpu_kernel: bool = True) -> list:
    """Returns the per-step losses."""
    import jax
    from paddle_tpu.core.device import memory_stats
    from paddle_tpu.distributed import build_mesh
    cfg = size.cfg
    log(f"[train] GPT layers={cfg.num_layers} hidden={cfg.hidden_size} "
        f"heads={cfg.num_heads} vocab={cfg.vocab_size} "
        f"batch={size.batch} seq={size.seq} dtype={np.dtype(cfg.dtype).name} "
        f"remat=dots loss_chunks={size.loss_chunks}")
    assert size.steps >= 5
    t0 = time.perf_counter()
    mesh = build_mesh(dp=1)
    model, step, state = _build_gpt_step(size, seed, mesh)
    n_params = sum(int(np.prod(v.shape))
                   for v in jax.tree.leaves(state[:2]))
    log(f"[train] built in {time.perf_counter() - t0:.1f} s, "
        f"{n_params / 1e6:.1f}M parameters")
    batch = _batch(size, seed, mesh)

    hits0 = compiles.cache_hits
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    log(f"[train] compiled in {compile_s:.1f} s (persistent-cache hits: "
        f"{compiles.cache_hits - hits0}); tpu_custom_call x{kernels}")
    ma = compiled.memory_analysis()
    if ma is not None:
        need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        log(f"[train] the program's own account (memory_analysis): "
            f"arguments {gib(ma.argument_size_in_bytes)}, temporaries "
            f"{gib(ma.temp_size_in_bytes)}, aliased outputs "
            f"{gib(ma.alias_size_in_bytes)}, in all {gib(need)}")
    if expect_tpu_kernel:
        assert kernels > 0, ("no tpu_custom_call in the compiled step: "
                             "attention took the XLA path")

    state, losses, secs = _run_steps(step, state, batch, size.steps,
                                     compiles)
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    steady = statistics.median(secs[1:])
    log(f"[train] losses {[round(x, 4) for x in losses]}")
    log(f"[train] seconds per step {[round(s, 4) for s in secs]} (the "
        f"first call also traces); median of the rest {steady:.4f} s = "
        f"{size.batch * size.seq / steady:.0f} tokens/s")
    stats = memory_stats()
    peak = stats.get("peak_bytes_in_use")
    log("[train] peak_bytes_in_use: "
        + (f"{peak} ({gib(peak)})" if peak else "not reported")
        + f"; memory_stats: {stats}")
    return losses


def _ragged_mask(rs, batch: int, seq: int):
    """A BERT batch of ragged rows: lengths in [0.7 seq, seq]."""
    lengths = rs.randint(int(seq * 0.7), seq + 1, (batch,))
    return np.arange(seq)[None, :] < lengths[:, None]


# (name, [b, s, h, d], causal, k-side mask)
KERNEL_CASES = (
    ("gpt345m causal", (8, 1024, 16, 64), True, False),
    ("bert_base ragged kv mask", (32, 512, 12, 64), False, True),
)


def phase_kernels(cases=KERNEL_CASES, seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    from paddle_tpu.nn.functional.attention import _xla_attention
    from paddle_tpu.ops.flash_attention import flash_attention
    for name, shape, causal, masked in cases:
        rs = np.random.RandomState(seed)
        q, k, v, ct = (jnp.asarray(rs.randn(*shape) * 0.5, jnp.bfloat16)
                       for _ in range(4))
        mask = jnp.asarray(_ragged_mask(rs, shape[0], shape[1])) \
            if masked else None

        def kernel(q, k, v, mask):
            return flash_attention(q, k, v, causal=causal, kv_mask=mask)

        def reference(q, k, v, mask):
            m4 = None if mask is None else mask[:, None, None, :]
            return _xla_attention(q, k, v, m4, 0.0, causal, False, None)

        def out_and_grads(fn):
            # everything is an argument: a closed-over array would be
            # baked into the program as a constant of its full size
            def run(q, k, v, ct, mask):
                out, vjp = jax.vjp(lambda q, k, v: fn(q, k, v, mask),
                                   q, k, v)
                return (out,) + vjp(ct)
            return jax.jit(run)(q, k, v, ct, mask)

        got = out_and_grads(kernel)
        ref = out_and_grads(reference)
        errs = {}
        for label, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
            a = np.asarray(a.astype(jnp.float32))
            b = np.asarray(b.astype(jnp.float32))
            assert np.isfinite(a).all(), f"{name}: {label} not finite"
            errs[label] = float(np.abs(a - b).max() / np.abs(b).max())
        log(f"[kernels] {name} {list(shape)} bf16: max|a-b|/max|ref| "
            + ", ".join(f"{k_} {e:.2e}" for k_, e in errs.items())
            + f" (tolerance {KERNEL_TOL:.0e})")
        assert max(errs.values()) <= KERNEL_TOL, (name, errs)


def phase_api(seed: int, device: str = "tpu") -> None:
    import jax
    import paddle_tpu as pt
    from paddle_tpu import inference
    from paddle_tpu.io import TensorDataset
    from paddle_tpu.static import InputSpec
    from paddle_tpu.vision.models import LeNet
    place = pt.set_device(device)
    dev = place.jax_device()
    pt.seed(seed)
    rs = np.random.RandomState(seed)
    x = rs.randn(256, 1, 28, 28).astype(np.float32)
    # a learnable rule: the class is the strongest of ten fixed projections
    y = (x.reshape(256, -1) @ rs.randn(784, 10)).argmax(-1).astype(np.int64)
    net = LeNet()
    model = pt.Model(net)
    model.prepare(pt.optimizer.Adam(learning_rate=2e-3,
                                    parameters=net.parameters()),
                  pt.nn.CrossEntropyLoss())
    hist = model.fit(TensorDataset([x, y]), epochs=3, batch_size=64,
                     shuffle=False, verbose=0)
    losses = [float(h["loss"]) for h in hist]
    log(f"[api] Model.fit on LeNet, {device}: epoch losses "
        f"{[round(v, 4) for v in losses]}")
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    for p in net.parameters():
        assert p.value.devices() == {dev}, (p.name, p.value.devices())

    net.eval()
    probe = x[:8]
    want = np.asarray(net(jax.numpy.asarray(probe)))
    with tempfile.TemporaryDirectory() as tmp:
        path = f"{tmp}/lenet"
        pt.jit.save(net, path,
                    input_spec=[InputSpec([8, 1, 28, 28], "float32")])
        pred = inference.create_predictor(inference.Config(path))
        got = pred.run([probe])[0]
    assert pred._dropout_scrubbed, "exported artifact samples randomness"
    out = next(iter(pred._outputs.values()))
    assert out.devices() == {dev}, out.devices()
    np.testing.assert_allclose(got, want, rtol=PARITY_RTOL,
                               atol=PARITY_ATOL)
    log(f"[api] jit.save -> inference.Predictor on {dev}: output "
        f"{got.shape} matches the eager net (max |diff| "
        f"{float(np.abs(got - want).max()):.2e})")


# (name, build_mesh axes, build_train_step options)
MESHES = (
    ("dp2 x mp2", dict(dp=2, mp=2), {}),
    ("zero3 sharding2 x mp2", dict(sharding=2, mp=2), dict(zero_stage=3)),
)
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def _bytes_per_device(state, devices) -> list:
    import jax
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    return [held[d] for d in devices]


def phase_sharded(size: TrainSize, seed: int, compiles: CompileLog,
                  expect_tpu_kernel: bool = True) -> None:
    """The same step on four-device meshes with real collectives, and on
    one device of the same host for comparison; two steps each."""
    import jax
    from paddle_tpu.core.device import memory_stats
    from paddle_tpu.distributed import build_mesh
    devices = jax.devices()[:4]
    cfg = size.cfg
    log(f"[sharded] GPT layers={cfg.num_layers} hidden={cfg.hidden_size} "
        f"batch={size.batch} seq={size.seq}, {size.steps} steps per mesh")
    results, failures = {}, []
    runs = MESHES + (("one device", dict(dp=1), {}),)
    for name, axes, step_kw in runs:
        n = int(np.prod(list(axes.values())))
        mesh = build_mesh(devices=devices[:n], **axes)
        model, step, state = _build_gpt_step(size, seed, mesh, **step_kw)
        batch = _batch(size, seed, mesh)
        # the copies from device 0 to the shards are asynchronous, and
        # their sources stay allocated until they are done
        jax.block_until_ready(state)
        gc.collect()
        eager = sum(p.value.nbytes for p in model.parameters())
        total = sum(v.nbytes for v in jax.tree.leaves(state))
        held = _bytes_per_device(state, devices[:n])
        in_use = [memory_stats(d).get("bytes_in_use") for d in devices[:n]]
        peaks = [memory_stats(d).get("peak_bytes_in_use")
                 for d in devices[:n]]
        log(f"[sharded] {name}: state {gib(total)} (params + AdamW m/v); "
            f"per device {[gib(b) for b in held]}")
        if all(b is not None for b in in_use):
            log(f"[sharded] {name}: memory_stats bytes_in_use "
                f"{[gib(b) for b in in_use]}, peak so far "
                f"{[gib(b) for b in peaks]}; the eager model's own "
                f"{gib(eager)} rests on device 0")
        if n > 1:
            # spread: no device holds much more than an even split of
            # what its mesh replicates, and none rests the whole state
            replicas = axes.get("dp", 1)
            share = total * replicas / n
            assert max(held) <= 1.1 * share, (name, held, share)
            assert min(held) >= 0.9 * share, (name, held, share)
            if all(b is not None for b in in_use):
                # the devices really hold it, and beyond its share
                # device 0 carries at most the eager model it was built
                # from (build_train_step's closures keep that alive)
                assert all(u >= 0.9 * h for u, h in zip(in_use, held)), \
                    (name, in_use, held)
                extra = in_use[0] - max(in_use[1:])
                log(f"[sharded] {name}: device 0 holds {gib(extra)} more "
                    f"than the fullest other device")
                if extra > 1.1 * eager + (64 << 20):
                    failures.append((name, "device 0 rests more than the "
                                     "eager model", in_use, eager))

        t0 = time.perf_counter()
        text = step.lower(state, batch).compile().as_text()
        found = {c: text.count(f"{c}(") + text.count(f"{c}-start(")
                 for c in COLLECTIVES}
        kernels = text.count("tpu_custom_call")
        log(f"[sharded] {name}: compiled in "
            f"{time.perf_counter() - t0:.1f} s; tpu_custom_call "
            f"x{kernels}; collectives {found}")
        if expect_tpu_kernel:
            assert kernels > 0, f"{name}: attention took the XLA path"
        if n > 1:
            assert found["all-reduce"] + found["reduce-scatter"] > 0 \
                and found["all-gather"] > 0, (name, found)
        else:
            assert not any(found.values()), (name, found)

        state, losses, secs = _run_steps(step, state, batch, size.steps,
                                         compiles)
        log(f"[sharded] {name}: losses {[round(x, 5) for x in losses]}, "
            f"seconds per step {[round(s, 3) for s in secs]}")
        results[name] = losses
        peaks = [memory_stats(d).get("peak_bytes_in_use")
                 for d in devices[:n]]
        if all(b is not None for b in peaks):
            log(f"[sharded] {name}: peak_bytes_in_use after the steps "
                f"{[gib(b) for b in peaks]} (process-wide high-water "
                f"marks)")
        del model, step, state
        gc.collect()

    want = results["one device"]
    for name, _, _ in MESHES:
        rel = max(abs(a - b) / abs(b) for a, b in zip(results[name], want))
        log(f"[sharded] {name} vs one device: max relative loss "
            f"difference {rel:.2e} (tolerance {MESH_LOSS_RTOL:.0e})")
        if rel > MESH_LOSS_RTOL:
            failures.append((name, "loss differs", results[name], want))
    assert not failures, failures


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the step sharded over four chips and "
                         "its one-device comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    t0 = time.perf_counter()
    device = phase_device(args.chips)
    compiles = CompileLog()
    if args.chips == 4:
        phase_sharded(TrainSize.full(steps=2), args.seed, compiles)
    else:
        phase_train(TrainSize.full(), args.seed, compiles)
        gc.collect()
        phase_kernels(seed=args.seed)
        phase_api(args.seed)
    log(f"[done] all checks passed in {time.perf_counter() - t0:.0f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
