"""One-shot perf sweep for the BASELINE conv configs + GPT headline.

Run on the real chip when available:
    python tools/perf_sweep.py [resnet|yolo|gpt] ...

Prints one line per configuration; used to pick the bench.py defaults
(BASELINE.md configs 1/3/4). Timing protocol matches bench.py: every
timed region ends in block_until_ready. One process; fails without a
chip whose peak is on record (bench.PEAK_FLOPS).
"""
from __future__ import annotations

import functools
import sys

import numpy as np

# single source of truth for chip peaks and the timing loop
import os as _os
import sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(
    _os.path.abspath(__file__))))
from bench import _timed_steps, peak_flops  # noqa: E402


def peak():
    import jax
    return peak_flops(jax.devices()[0].device_kind)


def timed(step, state, args, steps, warmup):
    return _timed_steps(lambda s: step(s, *args), state, steps, warmup)[1]


def resnet(batch=64, level="O1", steps=10, warmup=2, channels_last=False):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.vision.models import resnet50
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     trainable_state)

    model = resnet50()
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    params = trainable_state(model)
    buffers = buffer_state(model)
    opt_state = opt.init_state(params)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, 3, 224, 224), jnp.float32)
    y = jnp.asarray(rs.randint(0, 1000, (batch,)), jnp.int32)
    ce = pt.nn.CrossEntropyLoss()

    def loss_fn(params, buffers, x, y):
        with pt.amp.auto_cast(level=level):
            out, new_buf = functional_call(model, params, x,
                                           buffers=buffers)
        return ce(out, y), new_buf

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x, y):
        params, buffers, opt_state = state
        (loss, new_buf), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, buffers, x, y)
        new_p, new_s = opt.apply(params, g, opt_state)
        return (new_p, new_buf, new_s), loss

    dt = timed(step, (params, buffers, opt_state), (x, y), steps, warmup)
    imgs = batch * steps / dt
    mfu = imgs * 3 * 4.1e9 / peak()
    print(f"resnet50 batch={batch} {level}: {imgs:.0f} imgs/s "
          f"MFU={mfu * 100:.1f}%", flush=True)
    return imgs


def yolo(batch=8, size=320, level="O1", steps=8, warmup=2):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.vision.models import yolov3_darknet53, yolo_loss
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     trainable_state)

    model = yolov3_darknet53(num_classes=80)
    model.train()
    opt = pt.optimizer.Momentum(learning_rate=0.01, momentum=0.9)
    params = trainable_state(model)
    buffers = buffer_state(model)
    opt_state = opt.init_state(params)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, 3, size, size), jnp.float32)
    gt_box = jnp.asarray(rs.uniform(0.2, 0.8, (batch, 16, 4)), jnp.float32)
    gt_cls = jnp.asarray(rs.randint(0, 80, (batch, 16)), jnp.int32)

    def loss_fn(params, buffers, x):
        with pt.amp.auto_cast(level=level):
            outs, new_buf = functional_call(model, params, x,
                                            buffers=buffers)
        return yolo_loss(outs, gt_box, gt_cls, num_classes=80), new_buf

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x):
        params, buffers, opt_state = state
        (loss, new_buf), g = jax.value_and_grad(loss_fn, has_aux=True)(
            params, buffers, x)
        new_p, new_s = opt.apply(params, g, opt_state)
        return (new_p, new_buf, new_s), loss

    dt = timed(step, (params, buffers, opt_state), (x,), steps, warmup)
    imgs = batch * steps / dt
    mfu = imgs * 3 * 39e9 / peak()
    print(f"yolov3 batch={batch}@{size} {level}: {imgs:.0f} imgs/s "
          f"MFU={mfu * 100:.1f}%", flush=True)
    return imgs


def gpt(batch=8, seq=1024, chunks=8, steps=12, warmup=2):
    """Per-chip tokens/s; `batch` is the GLOBAL batch, sharded
    over the dp mesh (throughput divides by device count)."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.distributed import build_mesh
    from paddle_tpu.models import (GPTForPretraining, build_train_step,
                                   gpt_345m)

    cfg = gpt_345m(max_position_embeddings=max(seq, 1024))
    mesh = build_mesh(dp=len(jax.devices()))
    model = GPTForPretraining(cfg)
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             grad_clip=pt.nn.ClipGradByGlobalNorm(1.0))
    step, state = build_train_step(model, opt, mesh, num_microbatches=1,
                                   remat=True, remat_policy="dots",
                                   loss_chunks=chunks)
    rs = np.random.RandomState(0)
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)),
                      jnp.int32)
    labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (batch, seq)),
                         jnp.int32)
    dt = timed(lambda s, a: step(s, a), state, ((ids, labels),), steps,
               warmup)
    toks = batch * seq * steps / dt / len(jax.devices())  # per chip
    d, L, V, f = cfg.hidden_size, cfg.num_layers, cfg.vocab_size, \
        cfg.ffn_hidden
    fl = 6.0 * (L * (4 * d * d + 2 * d * f) + V * d) + 12.0 * L * d * seq
    mfu = fl * toks / peak()
    print(f"gpt345m batch={batch} seq={seq} chunks={chunks}: "
          f"{toks:.0f} tok/s MFU={mfu * 100:.1f}%", flush=True)
    return toks


def main():
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    peak()      # no chip, or one without a recorded peak: fail first
    which = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    if which == "resnet":
        for b in (64, 128, 256):
            resnet(batch=b)
        resnet(batch=256, level="O2")
    elif which == "yolo":
        for b in (8, 16, 32):
            yolo(batch=b)
    elif which == "gpt":
        for b in (8, 16):
            gpt(batch=b)
        gpt(batch=8, seq=2048)
    else:
        raise SystemExit(f"unknown sweep {which}")


if __name__ == "__main__":
    main()
