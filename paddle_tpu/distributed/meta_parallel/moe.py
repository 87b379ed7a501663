"""Mixture-of-Experts: top-k routing and a dropless grouped expert product
(beyond-reference).

The reference snapshot has NO MoE layers (SURVEY §2.3: expert parallel ✗;
its only hook is the `alltoall` collective, `operators/collective/
alltoall_op.cc`). This layer is one chip's share of an expert-parallel
layer: the router scores ALL `num_experts`, every token takes its `top_k`,
and the chip computes the assignments that fall on the experts it holds
(`expert_offset` .. `expert_offset + experts_held`). What the absent
experts would add is their chips' to add: the exchange across chips is
not here (ROADMAP "Reach"), and nothing stands in for it.

Two ways of scoring (`MoEMLP(scoring=...)`, a model's published key):
a softmax over all experts with the chosen weights renormalised
(`topk_gating`), and DeepSeek-V3's sigmoid scores, where the CHOICE is by
score + a per-expert bias (a leaf that no gradient reaches: the source
moves it by the load, outside the loss) and the WEIGHT the unbiased
score, renormalised and scaled (`sigmoid_gating`). A shared expert
(`shared_width`) is one gated MLP that every token takes beside its
routed ones; it is whole on every chip.

Routing is sort-based, not mask-based: the assignments are ordered by held
expert (absent ones last), their tokens' rows are gathered into a row
buffer, and the three expert matrices are applied as grouped products over
that buffer (`jax.lax.ragged_dot`, which the TPU compiler turns into a
tiled grouped matmul). The buffer is static and sized for the load the
router is expected to send here with half as much again (`rows_buffer`);
nothing is dropped, because the ordered assignments are taken a buffer at
a time for as many rounds as they need (`grouped_experts`: loops whose
trip count is the data's, one round while the load stays under the buffer,
tokens x min(top_k, held) / buffer rounds and `TAIL_ROUNDS` at most), so
all rows routed to one expert still come out right, and memory is bounded
by the buffer whatever the router does. Dispatch is a row gather and
combine a scatter-add by token over the buffer's rows, each the other's
transpose, so their cost is the buffer's; the products' is the load's.
That is why the last rounds are short (`_schedule`): a load just over the
buffer leaves a second buffer nearly empty and would pay for all of it
(8.9 ms a layer at 24576 rows of 2048, 3.3 ms for 3072:
`tools/moe_rounds_step0.py`).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ...profiler import MOE_EXPERTS, MOE_ROUTE, MOE_SHARED, count, stats
from .mp_layers import ColumnParallelLinear, RowParallelLinear

# the rounds behind the first buffer: a short one takes 1 / TAIL_SHARE of
# the buffer, and where TAIL_ROUNDS of them do not hold what is left, a
# whole buffer is taken first
TAIL_SHARE = 8
TAIL_ROUNDS = 2


def topk_gating(logits, top_k: int, norm_topk_prob: bool = True):
    """Router logits [t, e] -> (experts [t, k] int32, weights [t, k] fp32,
    probs [t, e]): a softmax over all experts in float32, the `top_k`
    largest (ties to the smaller id), renormalised over themselves where
    `norm_topk_prob`."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, experts = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return experts, weights, probs


def sigmoid_gating(logits, top_k: int, bias=None,
                   norm_topk_prob: bool = True, scaling: float = 1.0):
    """Router logits [t, e] -> (experts, weights, scores) as
    `topk_gating` gives them, DeepSeek-V3's way: scores are sigmoids in
    float32, the `top_k` are chosen by score + `bias` [e] (which takes no
    gradient), the weights are the chosen experts' scores WITHOUT the
    bias, over their sum + 1e-20 where `norm_topk_prob`, times
    `scaling`."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    choice = scores if bias is None else \
        scores + jax.lax.stop_gradient(bias.astype(jnp.float32))
    _, experts = jax.lax.top_k(choice, top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True)
                             + 1e-20)
    return experts, weights * scaling, scores


def balance_loss(experts, probs):
    """Switch / GShard load-balancing loss: e x sum_e (share of the
    assignments expert e got) x (mean router probability of e); 1 at
    balance."""
    e = probs.shape[-1]
    share = jnp.mean(jax.nn.one_hot(experts, e, dtype=probs.dtype),
                     axis=(0, 1))
    return e * jnp.sum(share * jnp.mean(probs, axis=0))


class Dispatch(NamedTuple):
    """Which assignment each row of the row buffer holds."""
    token: jax.Array    # [rows] the token a buffer row holds
    choice: jax.Array   # [rows] which of its token's k choices it is
    filled: jax.Array   # [rows] the row holds an assignment held here
    sizes: jax.Array    # [held] rows of each held expert, in buffer order


def dispatch_plan(experts, offset: int, held: int, rows: int) -> Dispatch:
    """Order the [t, k] assignments by held expert (a stable sort: within
    an expert by token), absent experts last, and lay the first `rows` of
    them out as buffer rows. `rows` >= t x min(k, held) holds every
    assignment that can fall here."""
    t, k = experts.shape
    local = experts - offset
    key = jnp.where((local >= 0) & (local < held), local, held).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(jax.nn.one_hot(key, held + 1, dtype=jnp.int32),
                    axis=0)[:held]
    if rows < t * k:
        order = order[:rows]
    else:
        order = jnp.pad(order, (0, rows - t * k))
    filled = jnp.arange(rows) < jnp.sum(sizes)
    return Dispatch(order // k, order % k, filled, sizes)


def _round_of(plan: Dispatch, start, rows: int) -> Dispatch:
    """The plan of the buffer rows [start, start + rows) alone, as a plan
    of its own: a group keeps the rows it has inside."""
    ends = jnp.cumsum(plan.sizes)
    inside = jnp.clip(ends - start, 0, rows) \
        - jnp.clip(ends - plan.sizes - start, 0, rows)
    cut = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=start,
                            slice_size=rows)
    return Dispatch(cut(plan.token), cut(plan.choice), cut(plan.filled),
                    inside)


@jax.custom_vjp
def _take_rows(x, plan: Dispatch):
    """x [t, d] -> [rows, d], row r from token[r]."""
    return x[plan.token]


def _take_rows_fwd(x, plan):
    return x[plan.token], (plan, x.shape[0])


def _take_rows_bwd(res, g):
    # a token's gradient is the sum over its rows, added up in float32
    # whatever the rows' dtype; what comes back for the rows of no group
    # was never computed
    plan, tokens = res
    rows = jnp.where(plan.filled[:, None], g.astype(jnp.float32), 0.0)
    dx = jnp.zeros((tokens, g.shape[-1]), jnp.float32).at[plan.token].add(
        rows)
    return dx.astype(g.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


def _one_round(x, weights, w_gate, w_up, w_down, plan: Dispatch):
    """The held experts over one buffer of rows: [t, d] fp32, the sum over
    a token's assignments in the buffer of weight x expert(x). The rows
    behind the last assignment belong to no group: the grouped product
    skips their tiles and what it leaves there is not a number to use, so
    they are masked on the way out (here) and on the way back
    (`_take_rows`)."""
    rows = _take_rows(x, plan)

    def product(a, w):
        return jax.lax.ragged_dot(a, w, plan.sizes,
                                  preferred_element_type=jnp.float32)
    hidden = F.swiglu(product(rows, w_gate),
                      product(rows, w_up)).astype(x.dtype)
    # masked before the weight: nought times what is not a number is not
    # a number either, in the weight's gradient
    y = jnp.where(plan.filled[:, None], product(hidden, w_down), 0.0)
    w_row = weights[plan.token, plan.choice]
    return jnp.zeros(x.shape, jnp.float32).at[plan.token].add(
        y * w_row[:, None])


def _schedule(plan: Dispatch, rows: int, tail: int):
    """(whole buffers, short rounds) that hold the assignments behind the
    first buffer: short rounds of `tail` rows where `TAIL_ROUNDS` of them
    hold what is left, a whole buffer of `rows` while more is."""
    behind = jnp.maximum(jnp.sum(plan.sizes) - rows, 0)
    whole = jnp.maximum(behind + rows - 1 - TAIL_ROUNDS * tail, 0) // rows
    return whole, (behind - whole * rows + tail - 1) // tail


def round_counts(plan: Dispatch, rows: int, tail: int) -> dict:
    """What a call of `grouped_experts` does, as int32 values: `routed`,
    the assignments that fell on the held experts; `computed`, the rows
    its rounds gathered; `whole` and `short`, the rounds behind the first
    buffer (none where the plan is one buffer)."""
    routed = jnp.sum(plan.sizes)
    if rows == plan.token.shape[0]:
        whole = short = jnp.zeros((), jnp.int32)
    else:
        # a whole buffer that holds all that is left leaves `_schedule` a
        # negative count of short rounds, which its loop runs no times
        whole, short = _schedule(plan, rows, tail)
        short = jnp.maximum(short, 0)
    return {"routed": routed, "computed": (1 + whole) * rows + short * tail,
            "whole": whole, "short": short}


def plan_rows(worst: int, rows: int, tail: int) -> int:
    """Rows of a plan whose every round lies inside it, for at most
    `worst` assignments: whole buffers, and a short round behind the last
    that is full."""
    return -(-worst // rows) * rows + (tail if tail < rows else 0)


def _over_rounds(plan: Dispatch, rows: int, tail: int, one, first):
    """Fold `one(carry, start, size)` over the plan's rounds: the first
    buffer, the whole buffers behind it, then the short rounds."""
    whole, short = _schedule(plan, rows, tail)
    acc = jax.lax.fori_loop(
        0, whole, lambda i, acc: one(acc, (1 + i) * rows, rows), first)
    return jax.lax.fori_loop(
        0, short,
        lambda i, acc: one(acc, (1 + whole) * rows + i * tail, tail), acc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _rounds(x, weights, w_gate, w_up, w_down, plan: Dispatch, rows: int,
            tail: int):
    """`_one_round` over the plan's rows, a buffer of `rows` at a time and
    the last of them `tail` at a time, for as many rounds as hold an
    assignment: loops with the data's trip count, which reverse mode
    cannot go through, so the backward is loops of its own that replay a
    round and take its gradient. A round's gather and scatter-add cost
    what its buffer holds, filled or not: a load just over the buffer
    pays for a short round, not for a second buffer."""
    def one(start, size):
        return _one_round(x, weights, w_gate, w_up, w_down,
                          _round_of(plan, start, size))
    return _over_rounds(plan, rows, tail,
                        lambda y, start, size: y + one(start, size),
                        one(0, rows))


def _rounds_fwd(x, weights, w_gate, w_up, w_down, plan, rows, tail):
    return _rounds(x, weights, w_gate, w_up, w_down, plan, rows, tail), \
        (x, weights, w_gate, w_up, w_down, plan)


def _rounds_bwd(rows, tail, res, g):
    *args, plan = res

    def one(start, size):
        _, vjp = jax.vjp(
            lambda *a: _one_round(*a, _round_of(plan, start, size)), *args)
        return vjp(g)
    grads = _over_rounds(
        plan, rows, tail,
        lambda acc, start, size: jax.tree.map(jnp.add, acc,
                                              one(start, size)),
        one(0, rows))
    return (*grads, None)


_rounds.defvjp(_rounds_fwd, _rounds_bwd)


def grouped_experts(x, plan: Dispatch, weights, w_gate, w_up, w_down,
                    compute_dtype=None, rows: Optional[int] = None,
                    tail: Optional[int] = None):
    """The held experts over their rows. x [t, d]; weights [t, k] fp32;
    w_gate / w_up [held, d, f], w_down [held, f, d]. Returns [t, d] fp32:
    sum over a token's held assignments of weight x expert(x). `rows`:
    rows of the buffer (default: the whole plan in one round); `tail`:
    of a short round (default: `rows`); the plan has `plan_rows` rows."""
    dt = compute_dtype or x.dtype
    total = plan.token.shape[0]
    rows = rows or total
    tail = tail or rows
    assert tail <= rows <= total, (total, rows, tail)
    args = (x.astype(dt), weights, w_gate.astype(dt), w_up.astype(dt),
            w_down.astype(dt))
    if rows == total:
        return _one_round(*args, plan)
    return _rounds(*args, plan, rows, tail)


class GatedMLP(Layer):
    """down(silu(gate(x)) * up(x)), no biases: a dense layer's MLP, or
    the shared expert of an expert layer."""

    def __init__(self, d_model: int, d_ff: int, compute_dtype=None,
                 initializer_range: float = 0.02):
        super().__init__()
        init = I.Normal(0.0, initializer_range)

        def column():
            return ColumnParallelLinear(
                d_model, d_ff, weight_attr=init, has_bias=False,
                gather_output=False, compute_dtype=compute_dtype)
        self.gate_proj, self.up_proj = column(), column()
        self.down_proj = RowParallelLinear(
            d_ff, d_model, weight_attr=init, has_bias=False,
            input_is_parallel=True, compute_dtype=compute_dtype)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class MoEMLP(Layer):
    """Top-k routed, SiLU-gated experts; one chip's share of the layer.

    `experts_held` of the `num_experts` live here, from `expert_offset`
    (default: all of them). The router is `num_experts` wide whatever is
    held. `scoring` "softmax" or "sigmoid" (module docstring); with
    "sigmoid", `choice_bias=True` adds the leaf `choice_bias`
    [num_experts] to the scores for the choice alone, and
    `routed_scaling_factor` scales the weights. `shared_width`: a gated
    MLP of that width (`shared`) that every token takes beside its routed
    experts. `aux_loss=True` keeps the load-balancing loss of the last
    call in a buffer (it survives `functional_call` / jit as a new
    buffer)."""

    def __init__(self, d_model: int, d_ff: int, num_experts: int,
                 top_k: int = 2, experts_held: Optional[int] = None,
                 expert_offset: int = 0, norm_topk_prob: bool = True,
                 compute_dtype=None, initializer_range: float = 0.02,
                 aux_loss: bool = False, rows_buffer: Optional[int] = None,
                 scoring: str = "softmax", choice_bias: bool = False,
                 routed_scaling_factor: float = 1.0,
                 shared_width: Optional[int] = None):
        super().__init__()
        if scoring not in ("softmax", "sigmoid"):
            raise ValueError(f"unknown scoring {scoring!r}")
        if choice_bias and scoring != "sigmoid":
            raise ValueError("a choice bias goes with sigmoid scoring")
        held = num_experts if experts_held is None else experts_held
        if not 0 <= expert_offset <= num_experts - held:
            raise ValueError(f"experts {expert_offset}..{expert_offset + held}"
                             f" of {num_experts}")
        self.num_experts, self.top_k = num_experts, top_k
        self.experts_held, self.expert_offset = held, expert_offset
        self.norm_topk_prob = norm_topk_prob
        init = I.Normal(0.0, initializer_range)
        self.gate_weight = self.create_parameter(
            (d_model, num_experts), default_initializer=init)
        self.w_gate = self.create_parameter((held, d_model, d_ff),
                                            default_initializer=init)
        self.w_up = self.create_parameter((held, d_model, d_ff),
                                          default_initializer=init)
        self.w_down = self.create_parameter((held, d_ff, d_model),
                                            default_initializer=init)
        self.scoring = scoring
        self.routed_scaling_factor = routed_scaling_factor
        self.choice_bias = self.create_parameter(
            (num_experts,), default_initializer=I.Constant(0.0)) \
            if choice_bias else None
        self.shared = GatedMLP(d_model, shared_width, compute_dtype,
                               initializer_range) if shared_width else None
        self._cdt = compute_dtype
        self._rows = rows_buffer
        if aux_loss:
            self.register_buffer("aux_loss", jnp.zeros((), jnp.float32))
        self._aux = aux_loss

    def rows_buffer(self, tokens: int) -> tuple:
        """(rows of the static buffer, rows of a short round, rows of the
        plan: what holds every assignment that can fall on the held
        experts). The buffer: what balanced routing sends here, tokens x
        top_k x held / experts, and half as much again, in whole tiles of
        the grouped product (512 rows); never more than the worst case,
        tokens x min(top_k, held). A short round: 1 / `TAIL_SHARE` of the
        buffer, in whole tiles."""
        worst = tokens * min(self.top_k, self.experts_held)
        rows = self._rows or -(-3 * tokens * self.top_k * self.experts_held
                               // (2 * self.num_experts * 512)) * 512
        rows = min(rows, worst)
        tail = min(rows, -(-rows // (TAIL_SHARE * 512)) * 512)
        return rows, tail, plan_rows(worst, rows, tail)

    def forward(self, x):
        b, s, d = x.shape
        xt = x.reshape(b * s, d)
        with jax.named_scope(MOE_ROUTE):
            # the router in float32, operands too: a flipped expert is
            # not a rounding error
            logits = jnp.matmul(
                xt.astype(jnp.float32),
                jnp.asarray(self.gate_weight).astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "sigmoid":
                bias = None if self.choice_bias is None else \
                    jnp.asarray(self.choice_bias)
                experts, weights, probs = sigmoid_gating(
                    logits, self.top_k, bias, self.norm_topk_prob,
                    self.routed_scaling_factor)
            else:
                experts, weights, probs = topk_gating(logits, self.top_k,
                                                      self.norm_topk_prob)
            if self._aux:
                self.aux_loss.value = balance_loss(experts, probs)
            rows, tail, total = self.rows_buffer(b * s)
            plan = dispatch_plan(experts, self.expert_offset,
                                 self.experts_held, total)
        stats.static("moe.experts_held", self.experts_held)
        stats.static("moe.rows_buffer", rows)
        stats.static("moe.top_k", self.top_k)
        for name, value in round_counts(plan, rows, tail).items():
            count("moe." + name, value)
        with jax.named_scope(MOE_EXPERTS):
            y = grouped_experts(xt, plan, weights, jnp.asarray(self.w_gate),
                                jnp.asarray(self.w_up),
                                jnp.asarray(self.w_down), self._cdt, rows,
                                tail)
        if self.shared is not None:
            stats.static("moe.shared_width",
                         self.shared.gate_proj.out_features)
            with jax.named_scope(MOE_SHARED):
                y = y + self.shared(xt)
        return y.reshape(b, s, d).astype(x.dtype)
