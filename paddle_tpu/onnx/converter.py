"""jaxpr → ONNX graph converter.

Reference: python/paddle/onnx/export.py (delegates to the external
paddle2onnx converter, which walks the ProgramDesc op graph). The TPU-native
equivalent walks the *jaxpr* of the layer's forward — the same IR every
other transform here uses — and emits one ONNX node (or a small cluster)
per primitive. Parameters closed over the trace arrive as jaxpr consts and
become ONNX initializers, so the exported file is self-contained.

Static shapes only (ONNX dims are taken from traced avals). Higher-order
primitives (jit/custom_jvp/remat/closed_call) are inlined recursively.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from . import proto


class UnsupportedPrimitive(NotImplementedError):
    pass


class _Graph:
    """Accumulates nodes/initializers and names jaxpr vars."""

    def __init__(self):
        self.nodes: List[bytes] = []
        self.initializers: List[bytes] = []
        self.names: Dict[int, str] = {}   # id(var) -> name
        self._counter = 0
        self._init_cache: Dict[bytes, str] = {}

    def fresh(self, hint: str = "t") -> str:
        self._counter += 1
        return f"{hint}_{self._counter}"

    def name_of(self, var) -> str:
        if isinstance(var, jcore.Literal):
            arr = np.asarray(var.val)
            return self.constant(arr)
        key = id(var)
        if key not in self.names:
            self.names[key] = self.fresh("v")
        return self.names[key]

    def constant(self, arr: np.ndarray, hint: str = "const") -> str:
        arr = np.asarray(arr)
        if arr.dtype == np.dtype(jnp.bfloat16):
            arr = arr.astype(np.float32)
        cache_key = arr.tobytes() + str(arr.dtype).encode() \
            + str(arr.shape).encode()
        if cache_key in self._init_cache:
            return self._init_cache[cache_key]
        name = self.fresh(hint)
        self.initializers.append(proto.tensor_proto(name, arr))
        self._init_cache[cache_key] = name
        return name

    def add(self, op_type: str, inputs: List[str], n_out: int = 1,
            outputs=None, **attrs) -> List[str]:
        if outputs is None:
            outputs = [self.fresh(op_type.lower()) for _ in range(n_out)]
        self.nodes.append(proto.node_proto(op_type, inputs, outputs,
                                           name=self.fresh("n"), **attrs))
        return outputs

    def set_name(self, var, name: str):
        self.names[id(var)] = name


_ELEMENTWISE = {
    "add": "Add", "sub": "Sub", "mul": "Mul", "div": "Div",
    "max": "Max", "min": "Min", "neg": "Neg", "abs": "Abs",
    "exp": "Exp", "log": "Log", "tanh": "Tanh", "logistic": "Sigmoid",
    "sqrt": "Sqrt", "erf": "Erf", "pow": "Pow", "sign": "Sign",
    "floor": "Floor", "ceil": "Ceil", "round": "Round",
    "sin": "Sin", "cos": "Cos", "tan": "Tan",
    "asin": "Asin", "acos": "Acos", "atan": "Atan",
    "sinh": "Sinh", "cosh": "Cosh",
    "asinh": "Asinh", "acosh": "Acosh", "atanh": "Atanh",
    "stop_gradient": "Identity", "copy": "Identity",
    # sharding annotations are compile-time placement hints; the
    # serialized inference graph is single-host, so they erase
    "sharding_constraint": "Identity",
    # name_p is a debug-labelling no-op
    "name": "Identity",
}

# ONNX And/Or/Not/Xor are boolean-only; jax's primitives are bitwise
_LOGICAL = {"and": "And", "or": "Or", "not": "Not", "xor": "Xor"}

_COMPARE = {"eq": "Equal", "lt": "Less", "le": "LessOrEqual",
            "gt": "Greater", "ge": "GreaterOrEqual"}

_REDUCE = {"reduce_sum": "ReduceSum", "reduce_max": "ReduceMax",
           "reduce_min": "ReduceMin", "reduce_prod": "ReduceProd"}

def _conv(g: _Graph, eqn, ins):
    p = eqn.params
    dn = p["dimension_numbers"]
    if any(d != 1 for d in p.get("lhs_dilation") or ()):
        raise UnsupportedPrimitive("conv with lhs_dilation (transpose conv)")
    if p.get("batch_group_count", 1) != 1:
        raise UnsupportedPrimitive("conv batch_group_count != 1")
    n_sp = len(dn.lhs_spec) - 2
    lhs_perm = (dn.lhs_spec[0], dn.lhs_spec[1]) + tuple(dn.lhs_spec[2:])
    rhs_perm = (dn.rhs_spec[0], dn.rhs_spec[1]) + tuple(dn.rhs_spec[2:])
    x, w = ins
    if lhs_perm != tuple(range(n_sp + 2)):
        x = g.add("Transpose", [x], perm=list(lhs_perm))[0]
    if rhs_perm != tuple(range(n_sp + 2)):
        w = g.add("Transpose", [w], perm=list(rhs_perm))[0]
    pads = [int(b) for b, _ in p["padding"]] + [int(e) for _, e in
                                               p["padding"]]
    y = g.add("Conv", [x, w],
              strides=[int(s) for s in p["window_strides"]],
              pads=pads,
              dilations=[int(d) for d in p.get("rhs_dilation")
                         or (1,) * n_sp],
              group=int(p.get("feature_group_count", 1)))[0]
    out_spec = (dn.out_spec[0], dn.out_spec[1]) + tuple(dn.out_spec[2:])
    if out_spec != tuple(range(n_sp + 2)):
        inv = [0] * (n_sp + 2)
        for i, s in enumerate(out_spec):
            inv[s] = i
        y = g.add("Transpose", [y], perm=inv)[0]
    return [y]


def _pool(g: _Graph, eqn, ins, kind: str):
    p = eqn.params
    wd = tuple(int(d) for d in p["window_dimensions"])
    ws = tuple(int(s) for s in (p["window_strides"] or (1,) * len(wd)))
    pad = tuple(p["padding"])
    if any(d != 1 for d in p.get("base_dilation") or ()):
        raise UnsupportedPrimitive("reduce_window base_dilation")
    if any(d != 1 for d in p.get("window_dilation") or ()):
        raise UnsupportedPrimitive("reduce_window window_dilation")
    post_perm = None
    if len(wd) == 4 and wd[0] == 1 and wd[-1] == 1 and ws[0] == 1 \
            and ws[-1] == 1 and pad[0] == (0, 0) and pad[-1] == (0, 0) \
            and (wd[1] != 1 or wd[2] != 1):
        # channels-last window (NHWC trunks): pool in NCHW between
        # transposes — ONNX pooling is channels-first only
        ins = [g.add("Transpose", ins, perm=[0, 3, 1, 2])[0]]
        wd = (1, 1, wd[1], wd[2])
        ws = (1, 1, ws[1], ws[2])
        pad = ((0, 0), (0, 0), pad[1], pad[2])
        post_perm = [0, 2, 3, 1]
    if wd[0] != 1 or wd[1] != 1 or ws[0] != 1 or ws[1] != 1 \
            or pad[0] != (0, 0) or pad[1] != (0, 0):
        raise UnsupportedPrimitive(
            f"reduce_window over non-spatial dims: {wd}")
    pads = [int(b) for b, _ in pad[2:]] + [int(e) for _, e in pad[2:]]
    if kind == "max":
        y = g.add("MaxPool", ins, kernel_shape=list(wd[2:]),
                  strides=list(ws[2:]), pads=pads)[0]
    else:
        # sum pool = AveragePool(count_include_pad) * prod(window)
        y = g.add("AveragePool", ins, kernel_shape=list(wd[2:]),
                  strides=list(ws[2:]), pads=pads, count_include_pad=1)[0]
        out_dt = np.dtype(eqn.outvars[0].aval.dtype)
        if out_dt == np.dtype(jnp.bfloat16):
            out_dt = np.dtype(np.float32)
        scale = g.constant(np.asarray(float(np.prod(wd)), out_dt),
                           "winsize")
        y = g.add("Mul", [y, scale])[0]
    if post_perm is not None:
        return g.add("Transpose", [y], perm=post_perm)
    return [y]


def _gather(g: _Graph, eqn, ins):
    """jnp.take(operand, idx, axis=k) pattern → ONNX Gather."""
    p = eqn.params
    dn = p["dimension_numbers"]
    operand, start = eqn.invars
    op_shape = tuple(operand.aval.shape)
    slice_sizes = tuple(int(s) for s in p["slice_sizes"])
    if len(dn.start_index_map) != 1 or getattr(
            dn, "operand_batching_dims", ()):
        raise UnsupportedPrimitive("general gather")
    axis = dn.start_index_map[0]
    if dn.collapsed_slice_dims != (axis,) or slice_sizes[axis] != 1:
        raise UnsupportedPrimitive("general gather (non-take pattern)")
    for d in range(len(op_shape)):
        if d != axis and slice_sizes[d] != op_shape[d]:
            raise UnsupportedPrimitive("general gather (partial slice)")
    idx_shape = tuple(start.aval.shape)
    if idx_shape[-1] != 1:
        raise UnsupportedPrimitive("gather with index vector > 1")
    idx = g.add("Reshape", [ins[1], g.constant(
        np.asarray(idx_shape[:-1], np.int64), "shape")])[0]
    batch_rank = len(idx_shape) - 1
    # ONNX Gather(axis=k) output = op[:k] + idx_shape + op[k+1:]; the jaxpr
    # gather matches only when its offset dims sit at exactly those slots.
    out_rank = len(op_shape) - 1 + batch_rank
    expect_offset = tuple(range(axis)) \
        + tuple(range(axis + batch_rank, out_rank))
    if tuple(dn.offset_dims) != expect_offset:
        raise UnsupportedPrimitive("gather offset dims not take-like")
    return g.add("Gather", [ins[0], idx], axis=int(axis))


def _convert_eqn(g: _Graph, eqn):
    prim = eqn.primitive.name
    ins = [g.name_of(v) for v in eqn.invars]

    if prim in ("jit", "closed_call", "custom_jvp_call",
                "custom_vjp_call", "remat2"):
        inner = eqn.params.get("jaxpr") or eqn.params.get("call_jaxpr")
        if inner is None:
            raise UnsupportedPrimitive(f"{prim} without inner jaxpr")
        if hasattr(inner, "jaxpr"):          # ClosedJaxpr
            consts, inner = inner.consts, inner.jaxpr
        else:
            consts = ()
        for cv, cval in zip(inner.constvars, consts):
            g.set_name(cv, g.constant(np.asarray(cval), "const"))
        for iv, outer in zip(inner.invars, eqn.invars):
            g.set_name(iv, g.name_of(outer))
        for ieq in inner.eqns:
            _convert_eqn(g, ieq)
        for ov, outer in zip(inner.outvars, eqn.outvars):
            # alias: emit Identity so the outer name exists as node output
            g.add("Identity", [g.name_of(ov)],
                  outputs=[g.name_of(outer)])
        return

    def out(names):
        for v, n in zip(eqn.outvars, names):
            g.set_name(v, n)

    if prim in _ELEMENTWISE:
        out(g.add(_ELEMENTWISE[prim], ins))
    elif prim in _LOGICAL:
        if np.dtype(eqn.invars[0].aval.dtype) != np.bool_:
            raise UnsupportedPrimitive(
                f"bitwise {prim} on non-bool inputs (ONNX opset 13 has "
                "no integer bitwise ops)")
        out(g.add(_LOGICAL[prim], ins))
    elif prim in _COMPARE:
        out(g.add(_COMPARE[prim], ins))
    elif prim == "ne":
        e = g.add("Equal", ins)[0]
        out(g.add("Not", [e]))
    elif prim == "rsqrt":
        s = g.add("Sqrt", ins)[0]
        out(g.add("Reciprocal", [s]))
    elif prim == "log1p":
        one = g.constant(np.asarray(1.0, eqn.invars[0].aval.dtype))
        s = g.add("Add", [ins[0], one])[0]
        out(g.add("Log", [s]))
    elif prim == "expm1":
        e = g.add("Exp", ins)[0]
        one = g.constant(np.asarray(1.0, eqn.invars[0].aval.dtype))
        out(g.add("Sub", [e, one]))
    elif prim == "erfc":
        e = g.add("Erf", ins)[0]
        one = g.constant(np.asarray(1.0, eqn.invars[0].aval.dtype))
        out(g.add("Sub", [one, e]))
    elif prim == "square":
        out(g.add("Mul", [ins[0], ins[0]]))
    elif prim == "integer_pow":
        y = eqn.params["y"]
        exp = g.constant(np.asarray(float(y), eqn.invars[0].aval.dtype))
        out(g.add("Pow", [ins[0], exp]))
    elif prim == "rem":
        out(g.add("Mod", ins, fmod=1))
    elif prim == "clamp":
        lo, x, hi = ins
        out(g.add("Clip", [x, lo, hi]))
    elif prim == "select_n":
        if len(ins) != 3:
            raise UnsupportedPrimitive("select_n with >2 cases")
        out(g.add("Where", [ins[0], ins[2], ins[1]]))
    elif prim == "convert_element_type":
        dt = proto.NP_TO_ONNX.get(np.dtype(eqn.params["new_dtype"]))
        if dt is None:   # bf16 → export as f32
            dt = proto.FLOAT
        out(g.add("Cast", ins, to=int(dt)))
    elif prim == "dot_general":
        dn = eqn.params["dimension_numbers"]
        lhs_rank = len(eqn.invars[0].aval.shape)
        rhs_rank = len(eqn.invars[1].aval.shape)
        (lc, rc), (lb, rb) = dn
        # MatMul only when rhs is a plain matrix/vector: for rhs rank >= 3
        # with no batch dims, XLA's output layout (lhs free dims then rhs
        # free dims) differs from numpy/ONNX MatMul broadcasting.
        if not lb and rhs_rank <= 2 and len(lc) == 1 \
                and lc[0] == lhs_rank - 1 \
                and rc[0] == rhs_rank - 2 + (rhs_rank == 1):
            out(g.add("MatMul", ins))
        else:
            # general case: transpose each side to
            # [batch..., free..., contract...] / [batch, contract, free],
            # flatten to rank-3, batched MatMul, reshape to XLA's output
            # order (batch, lhs free, rhs free). Standard ops only —
            # ONNX Einsum is opset-12+ and absent from many runtimes
            # (incl. csrc/ptpu_predictor.cc)
            lshape = tuple(eqn.invars[0].aval.shape)
            rshape = tuple(eqn.invars[1].aval.shape)
            lfree = [d for d in range(lhs_rank)
                     if d not in lb and d not in lc]
            rfree = [d for d in range(rhs_rank)
                     if d not in rb and d not in rc]

            def prod(dims, shape):
                p = 1
                for d in dims:
                    p *= shape[d]
                return p

            bsz = prod(lb, lshape)
            msz, ksz = prod(lfree, lshape), prod(lc, lshape)
            nsz = prod(rfree, rshape)
            lt = g.add("Transpose", [ins[0]],
                       perm=[int(d) for d in (*lb, *lfree, *lc)])[0]
            l3 = g.add("Reshape", [lt, g.constant(
                np.asarray([bsz, msz, ksz], np.int64), "lshape")])[0]
            rt = g.add("Transpose", [ins[1]],
                       perm=[int(d) for d in (*rb, *rc, *rfree)])[0]
            r3 = g.add("Reshape", [rt, g.constant(
                np.asarray([bsz, ksz, nsz], np.int64), "rshape")])[0]
            mm = g.add("MatMul", [l3, r3])[0]
            oshape = np.asarray(eqn.outvars[0].aval.shape, np.int64)
            out(g.add("Reshape", [mm, g.constant(oshape, "oshape")]))
    elif prim == "conv_general_dilated":
        out(_conv(g, eqn, ins))
    elif prim == "reduce_window_max":
        out(_pool(g, eqn, ins, "max"))
    elif prim == "reduce_window_sum":
        out(_pool(g, eqn, ins, "sum"))
    elif prim in _REDUCE:
        axes = [int(a) for a in eqn.params["axes"]]
        if prim == "reduce_sum":
            ax = g.constant(np.asarray(axes, np.int64), "axes")
            out(g.add("ReduceSum", [ins[0], ax], keepdims=0))
        else:
            out(g.add(_REDUCE[prim], ins, axes=axes, keepdims=0))
    elif prim in ("argmax", "argmin"):
        axes = eqn.params["axes"]
        if len(axes) != 1:
            raise UnsupportedPrimitive(f"{prim} over multiple axes")
        op = "ArgMax" if prim == "argmax" else "ArgMin"
        y = g.add(op, ins, axis=int(axes[0]), keepdims=0)[0]
        dt = proto.NP_TO_ONNX[np.dtype(eqn.params["index_dtype"])]
        out(g.add("Cast", [y], to=int(dt)))
    elif prim in ("reshape", "squeeze", "expand_dims"):
        shape = g.constant(np.asarray(eqn.outvars[0].aval.shape, np.int64),
                           "shape")
        out(g.add("Reshape", [ins[0], shape]))
    elif prim == "transpose":
        out(g.add("Transpose", ins,
                  perm=[int(p) for p in eqn.params["permutation"]]))
    elif prim == "broadcast_in_dim":
        in_shape = tuple(eqn.invars[0].aval.shape)
        out_shape = tuple(eqn.outvars[0].aval.shape)
        bdims = tuple(eqn.params["broadcast_dimensions"])
        mid = [1] * len(out_shape)
        for i, d in enumerate(bdims):
            mid[d] = in_shape[i]
        x = ins[0]
        if tuple(mid) != in_shape:
            x = g.add("Reshape", [x, g.constant(
                np.asarray(mid, np.int64), "shape")])[0]
        if tuple(mid) != out_shape:
            x = g.add("Expand", [x, g.constant(
                np.asarray(out_shape, np.int64), "shape")])[0]
            out([x])
        elif x == ins[0]:
            out(g.add("Identity", [x]))
        else:
            out([x])
    elif prim == "concatenate":
        out(g.add("Concat", ins, axis=int(eqn.params["dimension"])))
    elif prim == "slice":
        p = eqn.params
        rank = len(eqn.invars[0].aval.shape)
        starts = g.constant(np.asarray(p["start_indices"], np.int64), "st")
        ends = g.constant(np.asarray(p["limit_indices"], np.int64), "en")
        axes = g.constant(np.asarray(range(rank), np.int64), "ax")
        steps = g.constant(np.asarray(p["strides"] or [1] * rank,
                                      np.int64), "sp")
        out(g.add("Slice", [ins[0], starts, ends, axes, steps]))
    elif prim == "rev":
        # Reverse via Slice with negative steps
        rank = len(eqn.invars[0].aval.shape)
        dims = [int(d) for d in eqn.params["dimensions"]]
        starts = g.constant(np.asarray([-1] * len(dims), np.int64), "st")
        ends = g.constant(np.asarray([np.iinfo(np.int64).min + 1]
                                     * len(dims), np.int64), "en")
        axes = g.constant(np.asarray(dims, np.int64), "ax")
        steps = g.constant(np.asarray([-1] * len(dims), np.int64), "sp")
        out(g.add("Slice", [ins[0], starts, ends, axes, steps]))
    elif prim == "pad":
        p = eqn.params["padding_config"]
        if any(i != 0 for _, _, i in p):
            raise UnsupportedPrimitive("pad with interior padding")
        if any(lo < 0 or hi < 0 for lo, hi, _ in p):
            raise UnsupportedPrimitive("negative padding")
        pads = [lo for lo, _, _ in p] + [hi for _, hi, _ in p]
        out(g.add("Pad", [ins[0],
                          g.constant(np.asarray(pads, np.int64), "pads"),
                          ins[1]]))
    elif prim == "iota":
        dt = np.dtype(eqn.params["dtype"])
        shape = tuple(eqn.params["shape"])
        dim = int(eqn.params["dimension"])
        arr = np.arange(shape[dim], dtype=dt if dt != np.dtype(
            jnp.bfloat16) else np.float32)
        # store only the 1-D arange; broadcast with graph ops so a
        # (1,1,S,S) position/mask iota doesn't embed an S*S initializer
        mid = [shape[dim] if i == dim else 1 for i in range(len(shape))]
        x = g.constant(arr, "iota")
        x = g.add("Reshape", [x, g.constant(
            np.asarray(mid, np.int64), "shape")])[0]
        if tuple(mid) != shape:
            x = g.add("Expand", [x, g.constant(
                np.asarray(shape, np.int64), "shape")])[0]
        out([x])
    elif prim == "gather":
        out(_gather(g, eqn, ins))
    elif prim == "cumsum":
        ax = g.constant(np.asarray(eqn.params["axis"], np.int64), "axis")
        if eqn.params.get("reverse"):
            raise UnsupportedPrimitive("reverse cumsum")
        out(g.add("CumSum", [ins[0], ax]))
    elif prim == "dynamic_slice":
        starts = []
        for v in eqn.invars[1:]:
            if not isinstance(v, jcore.Literal):
                raise UnsupportedPrimitive("dynamic_slice (dynamic start)")
            starts.append(int(v.val))
        sizes = eqn.params["slice_sizes"]
        rank = len(sizes)
        st = g.constant(np.asarray(starts, np.int64), "st")
        en = g.constant(np.asarray([s + z for s, z in zip(starts, sizes)],
                                   np.int64), "en")
        ax = g.constant(np.asarray(range(rank), np.int64), "ax")
        out(g.add("Slice", [ins[0], st, en, ax]))
    elif prim == "split":
        # one Slice per piece: ONNX Split exists, but Slice keeps the
        # artifact runnable on the minimal runtimes
        axis = int(eqn.params["axis"])
        sizes = [int(v) for v in eqn.params["sizes"]]
        ax = g.constant(np.asarray([axis], np.int64), "ax")
        offset = 0
        names = []
        for sz in sizes:
            st = g.constant(np.asarray([offset], np.int64), "st")
            en = g.constant(np.asarray([offset + sz], np.int64), "en")
            names.append(g.add("Slice", [ins[0], st, en, ax])[0])
            offset += sz
        out(names)
    elif prim == "scan":
        _scan_unroll(g, eqn, ins)
    else:
        raise UnsupportedPrimitive(
            f"primitive '{prim}' has no ONNX mapping")


_SCAN_UNROLL_MAX = 512


def _scan_unroll(g: _Graph, eqn, ins):
    """lax.scan → static unroll (length is a traced constant). ONNX has
    Scan/Loop, but unrolling keeps artifacts runnable on minimal
    runtimes (the C predictor, the numpy reference) — RNN/LSTM/GRU
    layers run time steps through scan (`nn/layer_rnn.py RNN.forward`),
    so this is what makes CRNN-class models exportable. Body vars are
    REBOUND each iteration (names are keyed by var identity)."""
    p = eqn.params
    length = int(p["length"])
    if length == 0:
        raise UnsupportedPrimitive("scan with length 0 (empty unroll "
                                   "would emit a zero-input Concat)")
    if length > _SCAN_UNROLL_MAX:
        raise UnsupportedPrimitive(
            f"scan length {length} > unroll limit {_SCAN_UNROLL_MAX}")
    closed = p["jaxpr"]
    consts_j, body = closed.consts, closed.jaxpr
    n_consts = int(p["num_consts"])
    n_carry = int(p["num_carry"])
    reverse = bool(p.get("reverse", False))
    const_names = list(ins[:n_consts])
    carry_names = list(ins[n_consts:n_consts + n_carry])
    xs_names = list(ins[n_consts + n_carry:])
    n_ys = len(eqn.outvars) - n_carry
    ys_steps = [[] for _ in range(n_ys)]
    order = range(length - 1, -1, -1) if reverse else range(length)
    for t in order:
        xt_names = []
        for xi, xn in enumerate(xs_names):
            idx = g.constant(np.asarray(t, np.int64), "t")
            xt = g.add("Gather", [xn, idx], axis=0)[0]
            # 0-d index round-trips as [1] through the wire format on
            # some runtimes; pin the step slice to the body's static
            # input shape
            bshape = tuple(
                body.invars[n_consts + n_carry + xi].aval.shape)
            xt = g.add("Reshape", [xt, g.constant(
                np.asarray(bshape, np.int64), "xshape")])[0]
            xt_names.append(xt)
        # clear every body binding from the previous iteration
        for v in list(body.invars) + list(body.constvars):
            g.names.pop(id(v), None)
        for beq in body.eqns:
            for ov in beq.outvars:
                g.names.pop(id(ov), None)
        for cv, cval in zip(body.constvars, consts_j):
            g.set_name(cv, g.constant(np.asarray(cval), "const"))
        for bv, nm in zip(body.invars,
                          const_names + carry_names + xt_names):
            g.set_name(bv, nm)
        for beq in body.eqns:
            _convert_eqn(g, beq)
        outs_names = [g.name_of(ov) for ov in body.outvars]
        carry_names = list(outs_names[:n_carry])
        for yi in range(n_ys):
            ys_steps[yi].append(outs_names[n_carry + yi])
    for ci in range(n_carry):
        g.add("Identity", [carry_names[ci]],
              outputs=[g.name_of(eqn.outvars[ci])])
    for yi in range(n_ys):
        steps = ys_steps[yi]
        if reverse:
            steps = steps[::-1]
        y_shape = tuple(eqn.outvars[n_carry + yi].aval.shape)
        step_shape = g.constant(
            np.asarray((1,) + y_shape[1:], np.int64), "yshape")
        expanded = [g.add("Reshape", [s_, step_shape])[0] for s_ in steps]
        if len(expanded) == 1:
            g.add("Identity", expanded,
                  outputs=[g.name_of(eqn.outvars[n_carry + yi])])
        else:
            g.add("Concat", expanded, axis=0,
                  outputs=[g.name_of(eqn.outvars[n_carry + yi])])


def jaxpr_to_onnx_graph(closed_jaxpr, input_names=None,
                        graph_name="paddle_tpu"):
    """Convert a ClosedJaxpr (static shapes) to a serialized GraphProto."""
    jaxpr = closed_jaxpr.jaxpr
    g = _Graph()
    for cv, cval in zip(jaxpr.constvars, closed_jaxpr.consts):
        g.set_name(cv, g.constant(np.asarray(cval), "param"))
    inputs = []
    for i, iv in enumerate(jaxpr.invars):
        name = (input_names[i] if input_names and i < len(input_names)
                else f"input_{i}")
        g.set_name(iv, name)
        dt = np.dtype(iv.aval.dtype)
        if dt == np.dtype(jnp.bfloat16):
            dt = np.dtype(np.float32)
        inputs.append(proto.value_info(name, dt, tuple(iv.aval.shape)))
    for eqn in jaxpr.eqns:
        _convert_eqn(g, eqn)
    outputs = []
    for i, ov in enumerate(jaxpr.outvars):
        name = g.name_of(ov)
        if isinstance(ov, (jcore.Literal,)) or name in (
                g.name_of(iv) for iv in jaxpr.invars):
            name2 = g.add("Identity", [name],
                          outputs=[g.fresh("output")])[0]
            name = name2
        dt = np.dtype(ov.aval.dtype)
        if dt == np.dtype(jnp.bfloat16):
            dt = np.dtype(np.float32)
        outputs.append(proto.value_info(name, dt, tuple(ov.aval.shape)))
    return proto.graph_proto(graph_name, g.nodes, g.initializers,
                             inputs, outputs)


def trace_to_onnx(fn, example_args, input_names=None, opset=13):
    """Trace `fn(*example_args)` and return serialized ONNX ModelProto."""
    closed = jax.make_jaxpr(fn)(*example_args)
    graph = jaxpr_to_onnx_graph(closed, input_names=input_names)
    return proto.model_proto(graph, opset=opset)
