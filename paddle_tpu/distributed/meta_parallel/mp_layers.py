"""Megatron-style tensor-parallel layers — GSPMD-native.

Mirrors `fleet/meta_parallel/parallel_layers/mp_layers.py` of the reference
(`VocabParallelEmbedding:30`, `ColumnParallelLinear:97`,
`RowParallelLinear:170`, `ParallelCrossEntropy:249`).

The reference shards weights by hand on each rank and wires explicit NCCL
ops (`c_identity` fwd / `c_allreduce_sum` bwd for column input,
`c_allreduce_sum` fwd for row output, vocab-sharded softmax-CE kernel
`c_softmax_with_cross_entropy_op.cu`). On TPU each layer keeps the *full*
logical weight and attaches a `PartitionSpec` over the 'model' mesh axis;
activations get `with_sharding_constraint` hints. GSPMD partitions the
matmuls onto the MXU per chip and inserts the identity/all-reduce/all-gather
collectives over ICI — the same math, derived by the compiler instead of
hand-placed. One collective is written out: with two chips on 'model' the
sum of a row-parallel product, and its mirror in the backward of a
column-parallel one, is one exchange (`_summed_product`), because the
compiler's all-reduce holds the core for its whole length and a
collective-permute does not (PERF.md, PR 30).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import NamedSharding, PartitionSpec as P

from ...amp.auto_cast import maybe_autocast
from ...nn import functional as F
from ...nn import initializer as I
from ...nn.layer import Layer
from ..topology import get_mesh_or_none, scoped_mesh_or_none


def _constrain(x, *spec):
    """with_sharding_constraint if a hybrid mesh is active; no-op otherwise
    (single-device eager / tests without a mesh)."""
    mesh = get_mesh_or_none()
    if mesh is None or "model" not in mesh.axis_names:
        return x
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(*spec)))
    except ValueError:
        # not inside a jit trace over this mesh (pure eager): skip the hint
        return x


def model_axis_size() -> int:
    """Size of the 'model' axis of the mesh model code shards for (1
    without a mesh): what decides whether tensor parallelism is on."""
    mesh = get_mesh_or_none()
    if mesh is None or "model" not in mesh.axis_names:
        return 1
    return mesh.shape["model"]


def _exchange_mesh():
    """The mesh whose sums over 'model' are written as explicit
    exchanges, else None. With two chips on the axis a sum is ONE
    exchange, `p + ppermute(p)`: a copy the TPU runs beside its compute,
    where GSPMD's all-reduce holds the core until it is done. Other sizes
    keep the all-reduce (a ring of exchanges would be the same bytes in
    2(n-1) dependent steps). So does everything outside a step builder's
    `mesh_scope`: a global mesh may be left over from another program,
    and a sharding hint that does not apply is dropped where a
    `shard_map` is an error (an eager call, an export's trace)."""
    mesh = scoped_mesh_or_none()
    if mesh is not None and mesh.shape.get("model", 1) == 2:
        return mesh
    return None


# the name a row-parallel sum is saved under by a remat policy that saves
# the weight matmuls (`trainer/trunk.py checkpoint_policy`: it IS a weight
# matmul's output, but the product sits in a shard_map where a policy
# cannot see it)
TP_SUM = "tp_sum"


def _summed_product(mesh, a, b, dims: int, a_spec, b_spec):
    """tensordot(a, b, dims) over contracted dims that are split over the
    two chips of `mesh`'s 'model' axis (`a_spec`, `b_spec`: where, by
    'model' alone): each chip's partial product, summed by one exchange.
    Both chips add the same two numbers, so the result is replicated to
    the bit."""
    def local(al, bl):
        part = jnp.tensordot(al, bl, dims)
        return part + jax.lax.ppermute(part, "model", ((0, 1), (1, 0)))
    return jax.shard_map(
        local, mesh=mesh, in_specs=(P(*a_spec), P(*b_spec)),
        out_specs=P(), axis_names={"model"}, check_vma=False)(a, b)


def _weight_grad(x, g, lead: int):
    """x^T g over the `lead` leading (row) dims both share."""
    rows = tuple(range(lead))
    return jnp.tensordot(x, g, (rows, rows))


# `mesh` rides along as a static argument so that the backward, traced
# whenever the caller differentiates, exchanges over the forward's mesh
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _column_product(mesh, split: int, x, w):
    """x [..., k] @ w [k, *n], dim `split` of w split over 'model': the
    column-parallel product. Forward needs nothing from the other chip;
    backward sums the two partial input gradients (Megatron's f)."""
    return jnp.tensordot(x, w, 1)


def _column_fwd(mesh, split, x, w):
    return _column_product(mesh, split, x, w), (x, w)


def _column_bwd(mesh, split, res, g):
    x, w = res
    lead, n = x.ndim - 1, w.ndim - 1
    by_model = tuple("model" if i == split - 1 else None for i in range(n))
    dx = _summed_product(mesh, g, jnp.moveaxis(w, 0, -1), n,
                         (None,) * lead + by_model, by_model + (None,))
    return dx.astype(x.dtype), _weight_grad(x, g, lead).astype(w.dtype)


_column_product.defvjp(_column_fwd, _column_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _row_product(mesh, x, w):
    """x [..., k] @ w [k, n], k split over 'model': the row-parallel
    product, summed forward; backward needs nothing from the other chip
    (Megatron's g)."""
    return _summed_product(mesh, x, w, 1,
                           (None,) * (x.ndim - 1) + ("model",),
                           ("model", None))


def _row_fwd(mesh, x, w):
    return _row_product(mesh, x, w), (x, w)


def _row_bwd(mesh, res, g):
    x, w = res
    dx = jax.lax.with_sharding_constraint(
        jnp.tensordot(g, w.T, 1),
        NamedSharding(mesh, P(("data", "sharding"),
                              *(None,) * (x.ndim - 2), "model")))
    return dx.astype(x.dtype), _weight_grad(x, g, x.ndim - 1).astype(w.dtype)


_row_product.defvjp(_row_fwd, _row_bwd)


def _operands(layer, x):
    """(x, w, b) as a layer's product takes them: fp32 master params and
    the input cast to the layer's compute dtype (the cast fuses into the
    matmul; masters stay fp32 for the optimizer — the reference's
    multi-precision pattern, `adam_op` master weights), then to the AMP
    dtype where AMP is on, as `F.linear` would."""
    w = jnp.asarray(layer.weight)
    b = None if layer.bias is None else jnp.asarray(layer.bias)
    dtype = layer._compute_dtype
    if dtype is not None:
        w = w.astype(dtype)
        b = None if b is None else b.astype(dtype)
        x = x.astype(dtype)
    x, w = maybe_autocast(x, w, op="linear")
    return x, w, b


class VocabParallelEmbedding(Layer):
    """Reference: mp_layers.py:30 — vocab dim sharded over 'model'.

    The reference masks out-of-shard ids and allreduces the partial lookup;
    GSPMD derives the same from the table's PartitionSpec.
    """

    def __init__(self, num_embeddings, embedding_dim, weight_attr=None,
                 mp_group=None, name=None):
        super().__init__()
        self.num_embeddings = num_embeddings
        self.embedding_dim = embedding_dim
        self.weight = self.create_parameter(
            (num_embeddings, embedding_dim),
            default_initializer=weight_attr
            if isinstance(weight_attr, I.Initializer) else I.Normal(0., 0.02))
        self.weight.sharding_spec = P("model", None)

    def forward(self, x):
        out = F.embedding(x, self.weight)
        return _constrain(out, ("data", "sharding"), None, None)

    def extra_repr(self):
        return f"{self.num_embeddings}, {self.embedding_dim} [vocab-sharded]"


class ColumnParallelLinear(Layer):
    """Reference: mp_layers.py:97 — out_features split over 'model'.

    gather_output=False leaves the activation sharded on its last dim (fed
    to a RowParallelLinear); True re-replicates it (GSPMD all-gather).

    The weight's shards are CONTIGUOUS runs of columns. A fused projection
    whose columns are laid `[groups, heads, head_dim]` (q, k and v in one
    matmul) must not be reshaped to heads after `forward`: a contiguous
    half of the columns is all of q and half of k, not half the heads, and
    the partitioner repairs that with an all-gather of the whole
    activation, forward, replay and backward. Such a layer calls
    `project_heads`, whose product is sharded by heads from the start and
    goes to the RowParallelLinear partner without crossing the link.
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, gather_output=True, fuse_matmul_bias=False,
                 mp_group=None, name=None, compute_dtype=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.gather_output = gather_output
        self._compute_dtype = compute_dtype
        self.weight = self.create_parameter(
            (in_features, out_features),
            default_initializer=weight_attr
            if isinstance(weight_attr, I.Initializer) else None)
        self.weight.sharding_spec = P(None, "model")
        if has_bias:
            self.bias = self.create_parameter((out_features,), is_bias=True)
            self.bias.sharding_spec = P("model")
        else:
            self.bias = None

    def forward(self, x):
        x, w, b = _operands(self, x)
        mesh = _exchange_mesh()
        if mesh is not None:
            out = _column_product(mesh, 1, x, _constrain(w, None, "model"))
            if b is not None:
                out = out + b.astype(out.dtype)
        else:
            out = F.linear(x, w, b)
        if self.gather_output:
            return _constrain(out, ("data", "sharding"), None, None)
        return _constrain(out, ("data", "sharding"), None, "model")

    def project_heads(self, x, groups: int, heads: int):
        """`forward` for a fused projection: x [..., in] against columns
        laid `[groups, heads, head_dim]`, returned as [..., groups, heads,
        head_dim] with the heads split over 'model'. Parameter names,
        shapes, stored spec and column order are `forward`'s. Under a
        'model' axis of more than one chip the weight is viewed
        `[in, groups, heads, head_dim]` and that view is resharded by
        heads (weight-sized, where reshaping the product would gather the
        activation); without one this is `forward` and a reshape."""
        shape = (groups, heads, self.out_features // (groups * heads))
        if model_axis_size() == 1:
            out = self.forward(x)
            return jnp.reshape(out, out.shape[:-1] + shape)
        x, w, b = _operands(self, x)
        by_heads = (None, "model", None)
        w = _constrain(jnp.reshape(w, (self.in_features,) + shape),
                       None, *by_heads)
        mesh = _exchange_mesh()
        out = jnp.tensordot(x, w, 1) if mesh is None \
            else _column_product(mesh, 2, x, w)
        if b is not None:
            out = out + _constrain(jnp.reshape(b, shape),
                                   *by_heads).astype(out.dtype)
        return _constrain(out, ("data", "sharding"),
                          *(None,) * (out.ndim - 4), *by_heads)

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features} "
                f"[column-sharded]")


class RowParallelLinear(Layer):
    """Reference: mp_layers.py:170 — in_features split over 'model'.

    input_is_parallel=True expects the input already sharded on its last dim
    (the ColumnParallelLinear partner); the partial matmul products are
    summed by a GSPMD all-reduce (the reference's explicit
    `c_allreduce_sum` fwd). That sum, and its mirror in the backward, are
    all a block should exchange at activation size: an input that comes
    from a fused projection stays sharded on the way here only if it was
    made by `ColumnParallelLinear.project_heads` and its `[heads,
    head_dim]` dims are merged again, heads outermost.
    """

    def __init__(self, in_features, out_features, weight_attr=None,
                 has_bias=True, input_is_parallel=False,
                 fuse_matmul_bias=False, mp_group=None, name=None,
                 compute_dtype=None):
        super().__init__()
        self.in_features = in_features
        self.out_features = out_features
        self.input_is_parallel = input_is_parallel
        self._compute_dtype = compute_dtype
        self.weight = self.create_parameter(
            (in_features, out_features),
            default_initializer=weight_attr
            if isinstance(weight_attr, I.Initializer) else None)
        self.weight.sharding_spec = P("model", None)
        if has_bias:
            # bias replicated — added once after the sum (reference adds it
            # only on the allreduced output, mp_layers.py:236)
            self.bias = self.create_parameter((out_features,), is_bias=True)
        else:
            self.bias = None

    def forward(self, x):
        if self.input_is_parallel:
            x = _constrain(x, ("data", "sharding"), None, "model")
        x, w, b = _operands(self, x)
        mesh = _exchange_mesh()
        if mesh is not None:
            out = checkpoint_name(
                _row_product(mesh, x, _constrain(w, "model", None)), TP_SUM)
        else:
            out = F.linear(x, w, None)
        out = _constrain(out, ("data", "sharding"), None, None)
        if b is not None:
            out = out + b
        return out

    def extra_repr(self):
        return (f"in={self.in_features}, out={self.out_features} "
                f"[row-sharded]")


class ParallelCrossEntropy(Layer):
    """Reference: mp_layers.py:249 → `c_softmax_with_cross_entropy_op.cu`
    (vocab-sharded softmax cross-entropy: local max/sum + allreduce, gather
    of the label logit from the owning shard).

    TPU: compute the stable log-softmax CE on logits whose last (vocab) dim
    is sharded over 'model'; the reductions over vocab become GSPMD
    all-reduces over ICI. No gather of a [B,S,V] replicated tensor ever
    materializes.
    """

    def __init__(self, mp_group=None, name=None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, input, label):
        logits = _constrain(input, ("data", "sharding"), None, "model")
        logits = logits.astype(jnp.float32)
        m = jnp.max(logits, axis=-1, keepdims=True)
        lse = m[..., 0] + jnp.log(
            jnp.sum(jnp.exp(logits - m), axis=-1))
        safe_label = label
        if self.ignore_index is not None:
            # clamp before gather: negative ignore ids (-1, -100) would
            # wrap to valid vocab rows in take_along_axis
            safe_label = jnp.where(label == self.ignore_index, 0, label)
        label_logit = jnp.take_along_axis(
            logits, safe_label[..., None].astype(jnp.int32), axis=-1)[..., 0]
        loss = lse - label_logit
        if self.ignore_index is not None:
            loss = jnp.where(label == self.ignore_index, 0.0, loss)
        return loss[..., None]
