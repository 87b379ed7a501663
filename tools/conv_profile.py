"""Decomposition timing for the conv-heavy bench configs (ResNet-50).

Breaks the train step into parts and times each directly on the chip
(a host-clock decomposition, not a profiler trace):

  1. full train step (ResNet-50, BASELINE config 1)
  2. forward-only, loss-only
  3. per-stage forward (stem, layer1..4, head)
  4. conv microbench: every distinct (shape, stride) conv2d in ResNet-50
     fwd, vs its bf16 roofline

Usage (on TPU):  python tools/conv_profile.py [batch]
Each section prints one line per measurement; every timed region ends in
block_until_ready. Fails on a device whose peak is not in
bench.PEAK_FLOPS.
"""
from __future__ import annotations

import functools
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def timed(fn, *args, steps=6, warmup=2):
    import jax
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def main(batch=256):
    import jax
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.nn.layer import (buffer_state, functional_call,
                                     trainable_state)
    from paddle_tpu.vision.models import resnet50

    from bench import peak_flops
    from paddle_tpu.core import compile_cache
    compile_cache.enable()
    dev = jax.devices()[0]
    peak = peak_flops(dev.device_kind)   # unknown device: fail first
    print(f"device: {dev.platform} {dev.device_kind} "
          f"x{len(jax.devices())}", flush=True)
    fmt = "NHWC"
    model = resnet50(data_format=fmt)
    params = trainable_state(model)
    buffers = buffer_state(model)
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(batch, 224, 224, 3), jnp.float32)
    y = jnp.asarray(rs.randint(0, 1000, (batch,)), jnp.int32)
    ce = pt.nn.CrossEntropyLoss()
    opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init_state(params)

    def loss_fn(p, b, xx, yy):
        with pt.amp.auto_cast(level="O1"):
            out, nb = functional_call(model, p, xx, buffers=b)
        return ce(out, yy), nb

    @functools.partial(jax.jit, donate_argnums=(0,))
    def full_step(state, xx, yy):
        p, b, s = state
        (loss, nb), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b,
                                                                  xx, yy)
        np_, ns = opt.apply(p, g, s)
        return (np_, nb, ns), loss

    @jax.jit
    def fwd_loss(p, b, xx, yy):
        return loss_fn(p, b, xx, yy)[0]

    @jax.jit
    def grads_only(p, b, xx, yy):
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b,
                                                                 xx, yy)
        return loss, g

    state = (params, buffers, opt_state)
    for _ in range(2):
        state, loss = full_step(state, x, y)
    jax.block_until_ready((state, loss))
    t0 = time.perf_counter()
    n = 4
    for _ in range(n):
        state, loss = full_step(state, x, y)
    jax.block_until_ready((state, loss))
    dt = (time.perf_counter() - t0) / n
    params, buffers, opt_state = state  # donated chain: fresh buffers
    print(f"full step      : {dt * 1e3:8.2f} ms  "
          f"({batch / dt:8.1f} imgs/s)", flush=True)

    dt = timed(lambda: fwd_loss(params, buffers, x, y), steps=6)
    print(f"fwd+loss       : {dt * 1e3:8.2f} ms", flush=True)
    dt_g = timed(lambda: grads_only(params, buffers, x, y), steps=4)
    print(f"fwd+bwd        : {dt_g * 1e3:8.2f} ms", flush=True)

    @jax.jit
    def opt_only(p, g, s):
        return opt.apply(p, g, s)

    _, g = grads_only(params, buffers, x, y)
    dt = timed(lambda: opt_only(params, g, opt_state), steps=6)
    print(f"optimizer      : {dt * 1e3:8.2f} ms", flush=True)

    # ---- per-stage forward (eval-mode BN: frozen running stats) ----
    model.eval()

    def sub_tree(tree, prefix):
        return {k[len(prefix) + 1:]: v for k, v in tree.items()
                if k.startswith(prefix + ".")}

    def stem_fn(p, b, hh):
        with pt.amp.auto_cast(level="O1"):
            out, _ = functional_call(model.conv1, sub_tree(p, "conv1"), hh)
            out, _ = functional_call(model.bn1, sub_tree(p, "bn1"), out,
                                     buffers=sub_tree(b, "bn1"))
            return model.maxpool(jnp.maximum(out, 0))

    h = x
    jitted = jax.jit(stem_fn)
    h = jitted(params, buffers, h)
    dt = timed(lambda: jitted(params, buffers, x), steps=6)
    print(f"stage stem   : {dt * 1e3:8.2f} ms", flush=True)
    for name in ("layer1", "layer2", "layer3", "layer4"):
        layer = getattr(model, name)

        def stage_fn(p, b, hh, layer=layer, name=name):
            with pt.amp.auto_cast(level="O1"):
                out, _ = functional_call(layer, sub_tree(p, name), hh,
                                         buffers=sub_tree(b, name))
            return out
        jitted = jax.jit(stage_fn)
        h2 = jitted(params, buffers, h)
        dt = timed(lambda: jitted(params, buffers, h), steps=6)
        print(f"stage {name:7s}: {dt * 1e3:8.2f} ms", flush=True)
        h = h2
    model.train()

    # ---- conv microbench over ResNet-50 shapes ----
    shapes = [
        # (H, Cin, Cout, k, stride)  NHWC fwd shapes of ResNet-50
        (224, 3, 64, 7, 2),
        (56, 64, 64, 1, 1), (56, 64, 64, 3, 1), (56, 64, 256, 1, 1),
        (56, 256, 128, 1, 1), (56, 128, 128, 3, 2),
        (28, 128, 512, 1, 1), (28, 512, 256, 1, 1), (28, 256, 256, 3, 2),
        (14, 256, 1024, 1, 1), (14, 1024, 512, 1, 1),
        (14, 512, 512, 3, 2), (7, 512, 2048, 1, 1),
    ]
    import jax.lax as lax
    for (H, ci, co, k, s) in shapes:
        xx = jnp.asarray(rs.randn(batch, H, H, ci), jnp.bfloat16)
        ww = jnp.asarray(rs.randn(co, ci, k, k) * 0.05, jnp.bfloat16)

        @jax.jit
        def conv(a, w, s=s, k=k):
            return lax.conv_general_dilated(
                a, w, window_strides=(s, s),
                padding=[(k // 2, k // 2)] * 2,
                dimension_numbers=("NHWC", "OIHW", "NHWC"))
        out = conv(xx, ww)
        dt = timed(lambda: conv(xx, ww), steps=8)
        flops = 2 * batch * out.shape[1] * out.shape[2] * co * ci * k * k
        print(f"conv {H:3d}x{H:<3d} {ci:4d}->{co:4d} k{k} s{s}: "
              f"{dt * 1e3:7.3f} ms  {flops / dt / peak * 100:5.1f}% peak",
              flush=True)


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 256)
