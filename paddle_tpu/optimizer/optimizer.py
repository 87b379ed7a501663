"""Optimizer base + the full update-rule family.

Mirrors `python/paddle/optimizer/` (reference: per-param C++ optimizer ops in
`operators/optimizers/` — sgd_op, momentum_op, adam_op(+multi-precision),
lamb_op, lars_momentum_op, rmsprop_op, adagrad_op, adadelta_op, adamax_op).

TPU-native design: one pure function `apply(params, grads, state, step)`
updates the whole parameter pytree at once inside the compiled step — the
reference needed a `fuse_adam_op_pass` to coalesce per-param ops; here XLA
fuses everything by construction. The stateful `minimize`/`step` API is kept
for eager parity and writes results back into the Layer.

Master weights: with `multi_precision=True` and bf16/fp16 params, fp32 master
copies live in optimizer state (reference: adam_op multi-precision mode).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from ..nn.layer import Layer, Parameter
from ..profiler import CLIP, OPTIMIZER
from .lr import LRScheduler


def _is_float(x):
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


class Optimizer:
    """Base class. Subclasses implement `_init_slot(p)` and
    `_update(p, g, slots, lr, step)` returning (new_p, new_slots)."""

    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        if isinstance(parameters, Layer):
            self._layer = parameters
            self._params = OrderedDict(
                (n, p) for n, p in parameters.named_parameters()
                if p.trainable)
        elif parameters is not None:
            self._layer = None
            # p.name is not unique after copy.deepcopy (stacked transformer
            # layers) — deduplicate or silently drop params from training
            self._params = OrderedDict()
            for i, p in enumerate(parameters):
                if not p.trainable:
                    continue
                key = p.name or f"param_{i}"
                if key in self._params:
                    key = f"{key}__{i}"
                self._params[key] = p
        else:
            self._layer = None
            self._params = OrderedDict()
        self._lr = learning_rate
        self._weight_decay = weight_decay if not isinstance(
            weight_decay, (int, float)) else float(weight_decay)
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        self._accumulators: Optional[Dict[str, Any]] = None
        self._step_count = 0

    @property
    def _param_regularizers(self):
        """Per-param regularizer overrides, read at apply time so
        assignments AFTER optimizer construction are honored (reference
        `append_regularization_ops` reads param.regularizer at minimize
        time). Note: a jit-compiled step only re-reads these on retrace."""
        return {n: p.regularizer for n, p in self._params.items()
                if getattr(p, "regularizer", None) is not None}

    # --- learning rate ---

    def get_lr(self) -> float:
        if isinstance(self._lr, LRScheduler):
            return float(self._lr())
        return float(self._lr)

    def _lr_value(self, step):
        """Traceable LR: scheduler as a function of the (traced) step."""
        if isinstance(self._lr, LRScheduler):
            return self._lr.lr_fn(step)
        return jnp.asarray(self._lr, dtype=jnp.float32)

    def set_lr(self, value: float):
        self._lr = float(value)

    # --- state ---

    def init_state(self, params: Dict[str, jax.Array]) -> Dict[str, Any]:
        """Build the optimizer-state pytree for a params pytree."""
        state: Dict[str, Any] = {"step": jnp.zeros((), jnp.int32)}
        slots = {}
        for name, p in params.items():
            s = self._init_slot(p)
            if self._multi_precision and p.dtype in (jnp.bfloat16,
                                                     jnp.float16):
                s["master"] = p.astype(jnp.float32)
            slots[name] = s
        state["slots"] = slots
        return state

    def _ensure_state(self):
        if self._accumulators is None:
            self._accumulators = self.init_state(
                {n: p.value for n, p in self._params.items()})

    # --- functional core (jit-friendly) ---

    def apply(self, params: Dict[str, jax.Array],
              grads: Dict[str, jax.Array],
              state: Dict[str, Any]):
        """Pure update: returns (new_params, new_state). Call inside jit."""
        with jax.named_scope(OPTIMIZER):
            step = state["step"] + 1
            if self._grad_clip is not None:
                with jax.named_scope(CLIP):
                    grads = self._grad_clip(grads)
            new_params, new_slots = self.apply_named(params, grads,
                                                     state["slots"], step)
        return new_params, {"step": step, "slots": new_slots}

    def apply_named(self, params: Dict[str, jax.Array],
                    grads: Dict[str, jax.Array],
                    slots_map: Dict[str, Dict[str, jax.Array]],
                    step: jax.Array):
        """Update one named subset of params with an already-bumped step
        counter and already-clipped grads. The chunk-level core of
        `apply`, exposed so host-offloaded steps can stream optimizer
        slots through HBM one chunk at a time (reference:
        `fleet/meta_optimizers/sharding/offload_helper.py:1`) — global
        clip and the step bump happen once in the caller, this runs per
        chunk. The update math is elementwise per param, so a chunk may
        be a [k, ...] stack of k block-params updated as one tensor."""
        lr = self._lr_value(step)
        # regularization (coupled, reference: regularizer appended to grad;
        # per-param Parameter.regularizer overrides the optimizer-global
        # weight_decay — `fluid/regularizer.py append_regularization_ops`)
        from ..regularizer import WeightDecayRegularizer
        wd = self._weight_decay
        per_param = getattr(self, "_param_regularizers", None) or {}
        new_params, new_slots = {}, {}
        for name, p in params.items():
            g = grads.get(name)
            slots = dict(slots_map[name])
            if g is None:
                new_params[name] = p
                new_slots[name] = slots
                continue
            master = slots.get("master")
            p_eff = master if master is not None else p
            g = g.astype(p_eff.dtype)
            reg = per_param.get(name)
            if reg is not None:
                g = g + reg.grad(p_eff).astype(p_eff.dtype)
            elif isinstance(wd, WeightDecayRegularizer):
                # regularizers are coupled-into-grad by definition
                # (append_regularization_ops) even for AdamW, whose
                # decoupling applies only to its float coefficient
                g = g + wd.grad(p_eff).astype(p_eff.dtype)
            elif isinstance(wd, float) and wd != 0.0 and self._couple_wd:
                g = g + wd * p_eff
            new_p, slots = self._update(p_eff, g, slots, lr, step, name)
            if master is not None:
                slots["master"] = new_p
                new_params[name] = new_p.astype(p.dtype)
            else:
                new_params[name] = new_p.astype(p.dtype)
            new_slots[name] = slots
        return new_params, new_slots

    _couple_wd = True  # AdamW overrides (decoupled)
    # True when _update is elementwise over the param tensor, which lets
    # offloaded steps batch k stacked block-params through one chunk
    # update. Norm-based rules (LARS/Lamb trust ratios) are NOT — their
    # result depends on the tensor partitioning they are handed.
    _elementwise_update = True

    # --- eager/imperative API (paddle parity) ---

    def step(self, grads: Optional[Dict[str, jax.Array]] = None):
        """Apply an update to the bound Layer/parameters in place.

        `grads`: dict keyed like named_parameters; in the functional training
        style grads come from `value_and_grad` over `nn.functional_call`.
        """
        if grads is None:
            raise ValueError(
                "step() needs grads: autograd is functional on TPU — compute "
                "grads with paddle_tpu.value_and_grad and pass them here.")
        self._ensure_state()
        params = {n: p.value for n, p in self._params.items()}
        new_params, self._accumulators = self.apply(params, grads,
                                                    self._accumulators)
        for n, p in self._params.items():
            p.value = new_params[n]
        self._step_count += 1

    def minimize(self, loss_fn: Callable, *args):
        """Reference `minimize(loss)`. Two forms:
        - static mode: `minimize(loss_var)` with a `static.Variable` marks
          the program for training — `Executor.run` then differentiates the
          whole replay and applies this optimizer (executor.py);
        - functional: takes a loss *function* over the bound layer's
          params, computes grads, steps."""
        from ..static.program import Variable as _StaticVar
        if isinstance(loss_fn, _StaticVar):
            loss_fn.program._train_spec = (loss_fn, self)
            loss_fn.program._bump()
            return [], [(p, p.name + "@GRAD")
                        for p in loss_fn.program._params.values()]
        from ..nn.layer import functional_call, trainable_state
        assert self._layer is not None, "minimize needs a Layer-bound optimizer"

        def wrapped(params):
            out, _ = functional_call(self._layer, params, *args)
            return out if jnp.ndim(out) == 0 else jnp.sum(out)

        loss, grads = jax.value_and_grad(wrapped)(
            trainable_state(self._layer))
        self.step(grads)
        return loss

    def clear_grad(self):
        """No-op: grads are values, not buffers (parity with
        `optimizer.clear_grad`)."""

    clear_gradients = clear_grad

    # --- persistence (reference: optimizer state in state_dict) ---

    def state_dict(self):
        self._ensure_state()
        out = {"step": self._accumulators["step"],
               "LR_Scheduler": (self._lr.state_dict()
                                if isinstance(self._lr, LRScheduler) else {})}
        for pname, slots in self._accumulators["slots"].items():
            for sname, v in slots.items():
                out[f"{pname}/{sname}"] = v
        return out

    def set_state_dict(self, state):
        self._ensure_state()
        if isinstance(self._lr, LRScheduler) and state.get("LR_Scheduler"):
            self._lr.set_state_dict(state["LR_Scheduler"])
        if "step" in state:
            self._accumulators["step"] = jnp.asarray(state["step"],
                                                     jnp.int32)
        matched = 0
        for pname, slots in self._accumulators["slots"].items():
            for sname in list(slots.keys()):
                key = f"{pname}/{sname}"
                if key in state:
                    slots[sname] = jnp.asarray(state[key])
                    matched += 1
        n_slot_entries = sum(1 for k in state
                             if k not in ("step", "LR_Scheduler"))
        if n_slot_entries and not matched:
            import warnings
            warnings.warn(
                "optimizer set_state_dict matched no slot keys — the "
                "checkpoint was saved under a different param key scheme; "
                "accumulators (e.g. Adam moments) remain reinitialized",
                stacklevel=2)

    # --- subclass hooks ---

    def _init_slot(self, p) -> Dict[str, jax.Array]:
        return {}

    def _update(self, p, g, slots, lr, step, name):
        raise NotImplementedError


class SGD(Optimizer):
    """Reference: sgd_op."""

    def _update(self, p, g, slots, lr, step, name):
        return p - lr * g, slots


class Momentum(Optimizer):
    """Reference: momentum_op (use_nesterov attr)."""

    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, rescale_grad=1.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._nesterov = use_nesterov
        self._rescale_grad = float(rescale_grad)

    def _init_slot(self, p):
        return {"velocity": jnp.zeros_like(
            p.astype(jnp.float32) if self._multi_precision else p)}

    def _update(self, p, g, slots, lr, step, name):
        if self._rescale_grad != 1.0:
            g = g * self._rescale_grad
        v = self._momentum * slots["velocity"].astype(p.dtype) + g
        if self._nesterov:
            new_p = p - lr * (g + self._momentum * v)
        else:
            new_p = p - lr * v
        return new_p, {**slots, "velocity": v}


class Adam(Optimizer):
    """Reference: adam_op (+ beta pow accumulators, multi-precision)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_slot(self, p):
        # distinct buffers: aliased arrays break jit buffer donation
        dt = jnp.float32 if self._multi_precision else p.dtype
        return {"moment1": jnp.zeros(p.shape, dt),
                "moment2": jnp.zeros(p.shape, dt)}

    def _update(self, p, g, slots, lr, step, name):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment1"] + (1 - b1) * g
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        new_p = p - lr * mhat / (jnp.sqrt(vhat) + self._eps)
        return new_p, {**slots, "moment1": m, "moment2": v}


class AdamW(Adam):
    """Reference: `paddle.optimizer.AdamW` — Python subclass of Adam with
    decoupled decay (`optimizer/adamw.py:25`; there is no adamw C++ op)."""

    _couple_wd = False

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 apply_decay_param_fun=None, name=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name)
        self._decay_fn = apply_decay_param_fun

    def _update(self, p, g, slots, lr, step, name):
        wd = self._weight_decay if isinstance(self._weight_decay, float) \
            else 0.0
        # a per-param regularizer (already folded into g by apply())
        # overrides the optimizer-global decay — don't double-penalize
        if wd and name not in self._param_regularizers and \
                (self._decay_fn is None or self._decay_fn(name)):
            p = p * (1.0 - lr * wd)
        return super()._update(p, g, slots, lr, step, name)


class Adamax(Optimizer):
    """Reference: adamax_op."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon

    def _init_slot(self, p):
        return {"moment": jnp.zeros_like(p), "inf_norm": jnp.zeros_like(p)}

    def _update(self, p, g, slots, lr, step, name):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment"] + (1 - b1) * g
        u = jnp.maximum(b2 * slots["inf_norm"], jnp.abs(g))
        t = step.astype(jnp.float32)
        new_p = p - (lr / (1 - b1 ** t)) * m / (u + self._eps)
        return new_p, {"moment": m, "inf_norm": u}


class Adagrad(Optimizer):
    """Reference: adagrad_op."""

    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None,
                 initial_accumulator_value=0.0, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._eps = epsilon
        self._init_acc = initial_accumulator_value

    def _init_slot(self, p):
        return {"moment": jnp.full(p.shape, self._init_acc, p.dtype)}

    def _update(self, p, g, slots, lr, step, name):
        acc = slots["moment"] + jnp.square(g)
        new_p = p - lr * g / (jnp.sqrt(acc) + self._eps)
        return new_p, {"moment": acc}


class Adadelta(Optimizer):
    """Reference: adadelta_op."""

    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._eps, self._rho = epsilon, rho

    def _init_slot(self, p):
        return {"avg_squared_grad": jnp.zeros_like(p),
                "avg_squared_update": jnp.zeros_like(p)}

    def _update(self, p, g, slots, lr, step, name):
        rho, eps = self._rho, self._eps
        asg = rho * slots["avg_squared_grad"] + (1 - rho) * jnp.square(g)
        update = g * jnp.sqrt(slots["avg_squared_update"] + eps) / \
            jnp.sqrt(asg + eps)
        asu = rho * slots["avg_squared_update"] + \
            (1 - rho) * jnp.square(update)
        return p - lr * update, {"avg_squared_grad": asg,
                                 "avg_squared_update": asu}


class RMSProp(Optimizer):
    """Reference: rmsprop_op (centered variant supported)."""

    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)
        self._rho, self._eps = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _init_slot(self, p):
        s = {"mean_square": jnp.zeros_like(p),
             "momentum": jnp.zeros_like(p)}
        if self._centered:
            s["mean_grad"] = jnp.zeros_like(p)
        return s

    def _update(self, p, g, slots, lr, step, name):
        rho = self._rho
        ms = rho * slots["mean_square"] + (1 - rho) * jnp.square(g)
        slots_out = {"mean_square": ms, "momentum": slots["momentum"]}
        if self._centered:
            mg = rho * slots["mean_grad"] + (1 - rho) * g
            denom = jnp.sqrt(ms - jnp.square(mg) + self._eps)
            slots_out["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + self._eps)
        mom = self._momentum * slots["momentum"] + lr * g / denom
        slots_out["momentum"] = mom
        return p - mom, slots_out


class Lamb(Optimizer):
    """Reference: lamb_op — layerwise trust-ratio Adam (BERT large-batch)."""

    _elementwise_update = False  # trust ratio is a whole-tensor norm

    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._beta1, self._beta2, self._eps = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _init_slot(self, p):
        dt = jnp.float32 if self._multi_precision else p.dtype
        return {"moment1": jnp.zeros(p.shape, dt),
                "moment2": jnp.zeros(p.shape, dt)}

    def _update(self, p, g, slots, lr, step, name):
        b1, b2 = self._beta1, self._beta2
        m = b1 * slots["moment1"] + (1 - b1) * g
        v = b2 * slots["moment2"] + (1 - b2) * jnp.square(g)
        t = step.astype(jnp.float32)
        mhat = m / (1 - b1 ** t)
        vhat = v / (1 - b2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._eps)
        wd = self._lamb_wd
        if self._exclude_fn is not None and self._exclude_fn(name):
            wd = 0.0
        update = r + wd * p
        w_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        u_norm = jnp.sqrt(jnp.sum(jnp.square(update)))
        trust = jnp.where((w_norm > 0) & (u_norm > 0),
                          w_norm / u_norm, 1.0)
        return p - lr * trust * update, {**slots, "moment1": m,
                                         "moment2": v}


class LarsMomentum(Optimizer):
    """Reference: lars_momentum_op — layerwise LR scaling (ResNet
    large-batch)."""

    _elementwise_update = False  # local LR is a whole-tensor norm ratio

    def __init__(self, learning_rate=0.001, momentum=0.9,
                 lars_coeff=0.001, lars_weight_decay=0.0005, parameters=None,
                 grad_clip=None, exclude_from_weight_decay=None,
                 multi_precision=False, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         multi_precision, name)
        self._momentum = momentum
        self._lars_coeff = lars_coeff
        self._lars_wd = lars_weight_decay
        self._exclude = exclude_from_weight_decay or []

    def _init_slot(self, p):
        return {"velocity": jnp.zeros_like(
            p.astype(jnp.float32) if self._multi_precision else p)}

    def _update(self, p, g, slots, lr, step, name):
        wd = 0.0 if any(e in name for e in self._exclude) else self._lars_wd
        p_norm = jnp.sqrt(jnp.sum(jnp.square(p)))
        g_norm = jnp.sqrt(jnp.sum(jnp.square(g)))
        local_lr = jnp.where(
            (p_norm > 0) & (g_norm > 0),
            self._lars_coeff * p_norm / (g_norm + wd * p_norm + 1e-12),
            1.0)
        v = self._momentum * slots["velocity"].astype(p.dtype) + \
            lr * local_lr * (g + wd * p)
        return p - v, {**slots, "velocity": v}


class Ftrl(Optimizer):
    """Reference: ftrl_op — Follow The Regularized Leader
    (McMahan et al.): z/n accumulators with l1/l2 shrinkage."""

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0,
                 lr_power=-0.5, parameters=None, grad_clip=None,
                 name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         False, name)
        self._l1, self._l2, self._lr_power = l1, l2, lr_power

    def _init_slot(self, p):
        return {"squared": jnp.zeros_like(p),
                "linear": jnp.zeros_like(p)}

    def _update(self, p, g, slots, lr, step, name):
        n, z = slots["squared"], slots["linear"]
        new_n = n + jnp.square(g)
        if self._lr_power == -0.5:
            sigma = (jnp.sqrt(new_n) - jnp.sqrt(n)) / lr
        else:
            sigma = (jnp.power(new_n, -self._lr_power)
                     - jnp.power(n, -self._lr_power)) / lr
        new_z = z + g - sigma * p
        # reference ftrl_op.h:92: the quadratic term is 2*l2
        if self._lr_power == -0.5:
            denom = 2.0 * self._l2 + jnp.sqrt(new_n) / lr
        else:
            denom = 2.0 * self._l2 + jnp.power(new_n, -self._lr_power) / lr
        pre = jnp.clip(new_z, -self._l1, self._l1) - new_z
        new_p = jnp.where(jnp.abs(new_z) > self._l1, pre / denom, 0.0)
        return new_p, {"squared": new_n, "linear": new_z}


class Dpsgd(Optimizer):
    """Reference: dpsgd_op.h — differentially-private SGD: scale the
    grad down when its l2 norm exceeds `clip`, then step on
    grad + N(0, sigma)/batch_size (the reference adds the raw Gaussian
    divided by batch_size; privacy accounting is the caller's)."""

    # per-tensor DP clip norm + name-derived noise key: chunk streaming
    # would change the clip scale AND correlate noise across chunks
    _elementwise_update = False

    def __init__(self, learning_rate=0.001, clip=10.0, batch_size=16.0,
                 sigma=1.0, parameters=None, seed=0, name=None):
        super().__init__(learning_rate, parameters, None, None, False,
                         name)
        self._clip = clip
        self._batch = batch_size
        self._sigma = sigma
        self._seed = seed

    def _init_slot(self, p):
        return {}

    def _update(self, p, g, slots, lr, step, name):
        import zlib
        gn = jnp.linalg.norm(jnp.ravel(g))
        g = g / jnp.maximum(1.0, gn / self._clip)
        # key derived from (seed, step, param name) — NOT the global RNG
        # stream, which may not be scoped inside a jitted train step
        key = jax.random.fold_in(jax.random.key(self._seed), step)
        key = jax.random.fold_in(key, zlib.crc32(name.encode()) &
                                 0x7FFFFFFF)
        noise = self._sigma * jax.random.normal(key, g.shape, g.dtype)
        return p - lr * (g + noise / self._batch), slots


class ProximalAdagrad(Optimizer):
    """Reference: proximal_adagrad_op — adagrad step followed by the
    proximal l1/l2 shrinkage operator."""

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0,
                 parameters=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         False, name)
        self._l1, self._l2 = l1, l2

    def _init_slot(self, p):
        return {"moment": jnp.zeros_like(p)}

    def _update(self, p, g, slots, lr, step, name):
        acc = slots["moment"] + jnp.square(g)
        # reference proximal_adagrad_op.h:51-57: ADAPTIVE lr for the
        # gradient step, PLAIN lr for the l1/l2 shrinkage
        prox = p - lr * g / (jnp.sqrt(acc) + 1e-10)
        new_p = jnp.sign(prox) * jnp.maximum(
            jnp.abs(prox) - lr * self._l1, 0.0) / (1.0 + lr * self._l2)
        return new_p, {"moment": acc}


class ProximalGD(Optimizer):
    """Reference: proximal_gd_op — plain GD + proximal shrinkage."""

    def __init__(self, learning_rate=0.001, l1=0.0, l2=0.0,
                 parameters=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         False, name)
        self._l1, self._l2 = l1, l2

    def _init_slot(self, p):
        return {}

    def _update(self, p, g, slots, lr, step, name):
        prox = p - lr * g
        new_p = jnp.sign(prox) * jnp.maximum(
            jnp.abs(prox) - lr * self._l1, 0.0) / (1.0 + lr * self._l2)
        return new_p, slots


class DecayedAdagrad(Optimizer):
    """Reference: decayed_adagrad_op — adagrad with a decaying
    accumulator: acc = decay*acc + (1-decay)*g^2."""

    def __init__(self, learning_rate=0.001, decay=0.95, epsilon=1e-6,
                 parameters=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, None, grad_clip,
                         False, name)
        self._decay, self._eps = decay, epsilon

    def _init_slot(self, p):
        return {"moment": jnp.zeros_like(p)}

    def _update(self, p, g, slots, lr, step, name):
        acc = self._decay * slots["moment"] + \
            (1.0 - self._decay) * jnp.square(g)
        return p - lr * g / (jnp.sqrt(acc) + self._eps), {"moment": acc}
