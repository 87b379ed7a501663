"""Activation functions.

Mirrors `python/paddle/nn/functional/activation.py` (reference kernels:
`operators/activation_op.*`). All are single XLA HLOs or small fusions — the
compiler fuses them into neighbouring matmuls, which is what the reference's
`fuse_elewise_add_act_pass` did manually.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def relu(x, name=None):
    return jax.nn.relu(x)


def relu6(x, name=None):
    return jax.nn.relu6(x)


def leaky_relu(x, negative_slope=0.01, name=None):
    return jax.nn.leaky_relu(x, negative_slope)


def prelu(x, weight, name=None):
    w = weight.value if hasattr(weight, "value") else weight
    return jnp.where(x > 0, x, w * x)


def elu(x, alpha=1.0, name=None):
    return jax.nn.elu(x, alpha)


def selu(x, scale=1.0507009873554805, alpha=1.6732632423543772, name=None):
    return scale * jnp.where(x > 0, x, alpha * jnp.expm1(x))


def celu(x, alpha=1.0):
    return jax.nn.celu(x, alpha)


def gelu(x, approximate=False, name=None):
    return jax.nn.gelu(x, approximate=approximate)


def silu(x, name=None):
    return jax.nn.silu(x)


swish = silu


def swiglu(gate, up):
    """SiLU-gated linear unit, silu(gate) * up: the two halves of a gated
    MLP's (or a gated expert's) first product, in float32 whatever the
    operands, returned in `up`'s dtype."""
    out = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    return out.astype(up.dtype)


def mish(x):
    return x * jnp.tanh(jax.nn.softplus(x))


def sigmoid(x):
    return jax.nn.sigmoid(x)


def hardsigmoid(x, slope=0.1666667, offset=0.5, name=None):
    return jnp.clip(slope * x + offset, 0.0, 1.0)


def hardswish(x, name=None):
    return x * jnp.clip(x / 6.0 + 0.5, 0.0, 1.0)


def hardtanh(x, min=-1.0, max=1.0, name=None):
    return jnp.clip(x, min, max)


def hardshrink(x, threshold=0.5, name=None):
    return jnp.where(jnp.abs(x) > threshold, x, 0.0)


def softshrink(x, threshold=0.5, name=None):
    return jnp.where(x > threshold, x - threshold,
                     jnp.where(x < -threshold, x + threshold, 0.0))


def tanhshrink(x, name=None):
    return x - jnp.tanh(x)


def tanh(x, name=None):
    return jnp.tanh(x)


def softplus(x, beta=1.0, threshold=20.0, name=None):
    return jnp.where(beta * x > threshold, x,
                     jnp.log1p(jnp.exp(beta * jnp.minimum(x, threshold / beta))) / beta)


def softsign(x, name=None):
    return jax.nn.soft_sign(x)


def maxout(x, groups, axis=1, name=None):
    shape = list(x.shape)
    ch = shape[axis]
    shape[axis] = ch // groups
    shape.insert(axis + 1, groups)
    return jnp.max(jnp.reshape(x, shape), axis=axis + 1)


def softmax(x, axis=-1, dtype=None, name=None):
    from ...core.dtypes import convert_dtype
    if dtype is not None:
        x = x.astype(convert_dtype(dtype))
    return jax.nn.softmax(x, axis=axis)


def log_softmax(x, axis=-1, dtype=None, name=None):
    from ...core.dtypes import convert_dtype
    if dtype is not None:
        x = x.astype(convert_dtype(dtype))
    return jax.nn.log_softmax(x, axis=axis)


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1):
    from ...framework.random import next_key
    g = jax.random.gumbel(next_key(), x.shape, dtype=x.dtype)
    y = jax.nn.softmax((x + g) / temperature, axis=axis)
    if hard:
        idx = jnp.argmax(y, axis=axis, keepdims=True)
        y_hard = jnp.zeros_like(y)
        y_hard = jnp.put_along_axis(y_hard, idx, 1.0, axis=axis,
                                    inplace=False)
        # straight-through: value y_hard, gradient of the soft sample
        y = y_hard - jax.lax.stop_gradient(y) + y
    return y


def glu(x, axis=-1, name=None):
    a, b = jnp.split(x, 2, axis=axis)
    return a * jax.nn.sigmoid(b)


def thresholded_relu(x, threshold=1.0, name=None):
    return jnp.where(x > threshold, x, 0.0)


def log_sigmoid(x, name=None):
    return jax.nn.log_sigmoid(x)


# In-place variants: plain ops in a functional world (reference exposes
# them as mutation-fused kernels; semantics here are the returned array).
relu_ = relu
elu_ = elu
softmax_ = softmax


def tanh_(x, name=None):
    return jnp.tanh(x)
