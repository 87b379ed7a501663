"""The plain reference of a training step: float32 `jax.numpy`, matrix
products at `highest` precision, global-norm clipping and AdamW written
out. It imports nothing of the program and takes nothing the program has
made: its weights come from the family's `init_weights` and the seed.

`lower` puts the reference in the program's place in the nearest
precision below the configuration's bf16: every weight matmul takes
per-tensor-scaled fp8 operands (e4m3 forward, e5m2 for the incoming
gradient) and accumulates in float32. That is the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def matmul_f32(x, w):
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _fake_quant(x, dtype):
    """Per-tensor scaling into the fp8 type's range and back."""
    top = float(jnp.finfo(dtype).max)
    scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def matmul_fp8(x, w):
    return matmul_f32(_fake_quant(x, jnp.float8_e4m3fn),
                      _fake_quant(w, jnp.float8_e4m3fn))


def _fp8_fwd(x, w):
    xq = _fake_quant(x, jnp.float8_e4m3fn)
    wq = _fake_quant(w, jnp.float8_e4m3fn)
    return matmul_f32(xq, wq), (xq, wq)


def _fp8_bwd(res, dy):
    xq, wq = res
    dq = _fake_quant(dy, jnp.float8_e5m2)
    dx = matmul_f32(dq, wq.T)
    x2 = xq.reshape(-1, xq.shape[-1])
    dw = matmul_f32(x2.T, dq.reshape(-1, dq.shape[-1]))
    return dx, dw


matmul_fp8.defvjp(_fp8_fwd, _fp8_bwd)

MATMULS = {"float32": matmul_f32, "fp8": matmul_fp8}


QKV = ("q", "k", "v")   # the thirds of a fused qkv leaf's last axis


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf; a leaf stacked over layers ("blocks.<kind>",
    [L, ...]) gives one norm per layer. A fused qkv leaf counts as three
    leaves, "<name>.q", ".k", ".v": under softmax the key's bias has no
    gradient at all, and inside one leaf that would hide behind the
    query's and the value's. Jittable; float32."""
    out = {}
    for name, v in tree.items():
        sq = jnp.square(v.astype(jnp.float32))
        lead = sq.shape[:1] if _stacked(name) else ()
        if name.split(".")[-2:-1] == ["qkv"]:
            sq = sq.reshape(lead + (-1, 3, sq.shape[-1] // 3))
            sq = jnp.moveaxis(sq, -2, 0).reshape((3,) + lead + (-1,))
            for part, x in zip(QKV, sq):
                out[f"{name}.{part}"] = jnp.sqrt(jnp.sum(x, -1))
        else:
            out[name] = jnp.sqrt(jnp.sum(sq.reshape(lead + (-1,)), -1))
    return out


def _stacked(name: str) -> bool:
    parts = name.split(".")
    return parts[0] == "blocks" and not parts[1].isdigit()


def delta_norms(now: dict, start: dict) -> dict:
    """Per-leaf norm of (now - start). `start` is the reference's stacked
    layout; `now` may name a layer's leaf "blocks.<i>.<kind>"."""
    diff = {}
    for name, v in now.items():
        parts = name.split(".")
        if parts[0] == "blocks" and parts[1].isdigit():
            s0 = start["blocks." + ".".join(parts[2:])][int(parts[1])]
        else:
            s0 = start[name]
        diff[name] = v.astype(jnp.float32) - s0
    return leaf_norms(diff)


def flatten_norms(norms: dict) -> dict:
    """{leaf id: float}, stacked leaves spread to "blocks.<i>.<kind>"."""
    import numpy as np
    out = {}
    for name, v in norms.items():
        v = np.asarray(v, np.float64)
        if v.ndim == 0:
            out[name] = float(v)
        else:
            kind = name.split(".", 1)[1]
            for i, x in enumerate(v):
                out[f"blocks.{i}.{kind}"] = float(x)
    return out


def make_step(loss_fn, opt: dict, blocks: int = 1):
    """One AdamW step of the reference: (w, m, v, t, batch) ->
    (w, m, v, t, loss, norms of the gradient as the optimizer gets it).

    `blocks` > 1 takes the batch in that many blocks of rows, one after
    the other, and averages their losses and gradients, so that a batch
    made for four chips fits one: the batch's own mean only where every
    block holds as many real positions (`run_cell` checks the mix)."""
    lr, wd = opt["lr"], opt["weight_decay"]
    b1, b2, eps = opt["beta1"], opt["beta2"], opt["epsilon"]
    clip = opt.get("clip_global_norm")

    def loss_and_grad(w, batch):
        if blocks == 1:
            return jax.value_and_grad(loss_fn)(w, batch)
        parts = jax.tree.map(
            lambda a: a.reshape((blocks, a.shape[0] // blocks)
                                + a.shape[1:]), batch)

        def one(acc, part):
            loss, g = jax.value_and_grad(loss_fn)(w, part)
            return (acc[0] + loss / blocks,
                    jax.tree.map(lambda a, x: a + x / blocks, acc[1], g)
                    ), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, w))
        return jax.lax.scan(one, zero, parts)[0]

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(w, m, v, t, batch):
        loss, g = loss_and_grad(w, batch)
        if clip:
            total = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                 for x in jax.tree.leaves(g)))
            scale = jnp.minimum(clip / jnp.maximum(total, 1e-12), 1.0)
            g = jax.tree.map(lambda x: x * scale, g)
        t = t + 1
        tf = t.astype(jnp.float32)
        m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)

        def upd(p, a, b):
            mhat = a / (1 - b1 ** tf)
            vhat = b / (1 - b2 ** tf)
            return p - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p)
        w = jax.tree.map(upd, w, m, v)
        return w, m, v, t, loss, leaf_norms(g)
    return step


def run(reference, config: dict, batches: list, seed: int,
        precision: str = "float32", rows: slice | None = None,
        blocks: int = 1, devices=None) -> dict:
    """Follow the first `len(batches)` steps from the seed's weights.
    Returns the losses, the first step's gradient norms and the norms of
    the parameters' change after the last step, by leaf.

    `rows` keeps only those rows of every batch (the half-batch fault,
    planted in the reference put in the program's place). Over several
    `devices` the rows of a batch are spread (weights and Adam's state
    whole on each; the compiler adds the gradients up), and each device
    takes its share of the `blocks` one after the other."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    devices = list(devices or jax.devices()[:1])
    mesh = Mesh(np.array(devices), ("rows",))
    whole, by_rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("rows"))
    mm = MATMULS[precision]
    loss_fn = functools.partial(reference.loss, config, mm=mm)
    step = make_step(lambda w, b: loss_fn(w, b), config["optimizer"],
                     max(blocks // len(devices), 1))
    init = jax.jit(functools.partial(reference.init_weights, config),
                   out_shardings=whole)
    zeros = jax.jit(lambda w: jax.tree.map(jnp.zeros_like, w),
                    out_shardings=whole)
    key = jax.random.key(seed)
    w = init(key)
    m, v = zeros(w), zeros(w)
    t = jnp.zeros((), jnp.int32)
    losses, grad = [], None
    for i, batch in enumerate(batches):
        if rows is not None:
            batch = {k: a[rows] for k, a in batch.items()}
        w, m, v, t, loss, gn = step(w, m, v, t,
                                    jax.device_put(batch, by_rows))
        losses.append(float(loss))
        if i == 0:
            grad = flatten_norms(jax.device_get(gn))
    change = jax.jit(delta_norms)(w, init(key))
    return {"losses": losses, "grad": grad,
            "change": flatten_norms(jax.device_get(change))}
