"""PipelineParallel model wrapper — API parity with the reference's
`fleet/meta_parallel/pipeline_parallel.py` (`PipelineParallel.train_batch:109`
micro-batch F-then-B loop with activation send/recv + shape handshake).

Semantics: `train_batch(data, optimizer, lr_scheduler)` runs one global
batch as `accumulate_steps` microbatches (scan-based gradient
accumulation — numerically the F-then-B schedule) and applies the
optimizer once. This wrapper is the API-parity path for arbitrary
heterogeneous PipelineLayers; the *performance* pipeline — stage weights
sharded over the 'pipe' mesh axis with the CollectivePermute microbatch
schedule — is the stacked-stage engine (stacked_pipeline.py), used by
`trainer.build_train_step` for uniform-trunk models.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ...nn.layer import (Layer, buffer_state, functional_call,
                         load_state, trainable_state)
from .pp_layers import PipelineLayer


class PipelineParallel(Layer):
    """Reference: pipeline_parallel.py:61. Wraps a `PipelineLayer`."""

    def __init__(self, layers: PipelineLayer, hcg=None, strategy=None):
        super().__init__()
        if not isinstance(layers, PipelineLayer):
            raise TypeError(
                "The Layer should be a derived class of PipelineLayer.")
        self._layers = layers
        self._hcg = hcg
        self.accumulate_steps = 1
        if strategy is not None:
            conf = getattr(strategy, "pipeline_configs", None) or {}
            self.accumulate_steps = int(conf.get("accumulate_steps", 1))
        self.add_sublayer("pipeline", layers)
        self._jit_step = None
        self._jit_step_opt = None  # optimizer the cached step was built for

    def forward(self, x):
        return self._layers(x)

    def _build_step(self, optimizer):
        layers = self._layers
        M = self.accumulate_steps

        def loss_of(params, buffers, x, label):
            out, _ = functional_call(layers, params, x, buffers=buffers)
            loss = layers.loss(out, label)
            return jnp.mean(loss)

        def step(params, buffers, opt_state, x, label):
            B = x.shape[0]
            mbs = jax.tree.map(
                lambda a: a.reshape((M, B // M) + tuple(a.shape[1:])),
                (x, label))

            def micro(carry, mb):
                gsum, lsum = carry
                xi, yi = mb
                li, gi = jax.value_and_grad(loss_of)(params, buffers, xi, yi)
                return (jax.tree.map(jnp.add, gsum, gi), lsum + li), None

            zeros = jax.tree.map(jnp.zeros_like, params)
            (gsum, lsum), _ = jax.lax.scan(micro, (zeros, 0.0), mbs)
            grads = jax.tree.map(lambda g: g / M, gsum)
            new_params, new_opt = optimizer.apply(params, grads, opt_state)
            return new_params, new_opt, lsum / M

        return jax.jit(step)

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        """One F-then-B global batch (reference: pipeline_parallel.py:109)."""
        x, label = data
        x = jnp.asarray(x)
        label = jnp.asarray(label)
        if self._jit_step is None or self._jit_step_opt is not optimizer:
            self._jit_step = self._build_step(optimizer)
            self._jit_step_opt = optimizer
        params = trainable_state(self._layers)
        buffers = buffer_state(self._layers)
        if optimizer._accumulators is None:
            # key the state by the structured names used for grads here
            optimizer._accumulators = optimizer.init_state(params)
        new_params, new_opt, loss = self._jit_step(
            params, buffers, optimizer._accumulators, x, label)
        optimizer._accumulators = new_opt
        optimizer._step_count += 1
        load_state(self._layers, new_params)
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    def eval_batch(self, data, compute_loss=True):
        x, label = data
        out = self._layers(jnp.asarray(x))
        if compute_loss:
            return jnp.mean(self._layers.loss(out, jnp.asarray(label)))
        return out
