"""Op-level IR + pass framework.

Reference mapping:
  * ProgramDesc/BlockDesc/OpDesc (`framework/framework.proto:43-207`) —
    the serialized op-level program;
  * `framework/ir/` Pass framework + GraphPatternDetector
    (`ir/graph_pattern_detector.cc`, 72+ passes).

TPU-native: the op-level program IS the jaxpr — typed, SSA, already the
form every jax transform manipulates. `Program` wraps a ClosedJaxpr with
a Paddle-flavored surface: `ops()` lists OpDesc-like views,
`find_pattern` is the GraphPatternDetector, passes are functions from
eqn-list to eqn-list registered in a `PassRegistry`, and the result
compiles straight back through XLA (`to_callable`). Serialization rides
StableHLO (`jit.save`), the same artifact the inference engine loads —
unlike the reference there is no second proto format to keep in sync.

Most reference passes (fusion, memory reuse, layout) are subsumed by
XLA; the infra here exists for the passes XLA can NOT see: framework-
level rewrites — `dead_code_elimination`, and `dropout_removal` (the
inference rewrite `jit.save` applies before export and
`inference.Predictor` checks on load; reference:
`delete_dropout_op_pass.cc`). Quant/dequant insertion and DCE after
head-pruning are further candidates on the same registry.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Sequence

import jax


class OpView:
    """OpDesc-like read view of one jaxpr eqn (reference:
    `framework.proto:43` OpDesc {type, inputs, outputs, attrs})."""

    def __init__(self, eqn):
        self._eqn = eqn

    @property
    def type(self) -> str:
        return self._eqn.primitive.name

    @property
    def inputs(self) -> List[str]:
        return [str(v) for v in self._eqn.invars]

    @property
    def outputs(self) -> List[str]:
        return [str(v) for v in self._eqn.outvars]

    @property
    def attrs(self) -> dict:
        return dict(self._eqn.params)

    def __repr__(self):
        return (f"OpView({self.type}: {', '.join(self.inputs)} -> "
                f"{', '.join(self.outputs)})")


class Program:
    """A captured op-level program (reference: ProgramDesc)."""

    def __init__(self, closed_jaxpr):
        self.closed = closed_jaxpr

    # -- capture ----------------------------------------------------------

    @classmethod
    def capture(cls, fn: Callable, *example_args, **example_kwargs):
        """Trace `fn` into a Program (reference: Program construction via
        `program_guard` + append_op; here one jax trace)."""
        closed = jax.make_jaxpr(fn)(*example_args, **example_kwargs)
        return cls(closed)

    # -- inspection -------------------------------------------------------

    def ops(self) -> List[OpView]:
        return [OpView(e) for e in self.closed.jaxpr.eqns]

    def op_types(self) -> List[str]:
        return [o.type for o in self.ops()]

    def find_pattern(self, pattern: Sequence[str]) -> List[List[OpView]]:
        """GraphPatternDetector-lite: consecutive def-use chains whose
        primitive names match `pattern` (each op's output feeds the
        next)."""
        eqns = self.closed.jaxpr.eqns
        hits = []
        for i, e in enumerate(eqns):
            if e.primitive.name != pattern[0]:
                continue
            chain = [e]
            for want in pattern[1:]:
                nxt = None
                outs = set(map(id, chain[-1].outvars))
                for e2 in eqns[i + 1:]:
                    if e2.primitive.name == want and \
                            any(id(v) in outs for v in e2.invars):
                        nxt = e2
                        break
                if nxt is None:
                    break
                chain.append(nxt)
            if len(chain) == len(pattern):
                hits.append([OpView(e) for e in chain])
        return hits

    # -- passes -----------------------------------------------------------

    def apply_pass(self, name_or_fn) -> "Program":
        """Run a registered pass (or a callable eqns->eqns) and return a
        NEW Program (reference: `ir/pass.h` Pass::Apply). A pass may
        return either the new eqn list or an (eqns, outvars) pair —
        rewrites that replace a program OUTPUT (e.g. dropout as the
        last op) need to retarget outvars as well.

        When IR verification is on (PTPU_IR_VERIFY=1 or
        `ir.verify.set_verify(True)`; tier-1 runs with it on), the
        result is checked against the jaxpr well-formedness invariants
        (defs-before-uses, SSA, no dangling outvars, fused-op arity)
        IMMEDIATELY — a buggy pass fails here with the pass named,
        instead of miscompiling at the next trace."""
        from . import verify as _verify
        fn = PassRegistry.get(name_or_fn) if isinstance(name_or_fn, str) \
            else name_or_fn
        jaxpr = self.closed.jaxpr
        res = fn(list(jaxpr.eqns), jaxpr)
        if isinstance(res, tuple):
            new_eqns, new_outvars = res
            new_jaxpr = jaxpr.replace(eqns=new_eqns,
                                      outvars=list(new_outvars))
        else:
            new_jaxpr = jaxpr.replace(eqns=res)
        out = Program(self.closed.replace(jaxpr=new_jaxpr))
        pass_name = name_or_fn if isinstance(name_or_fn, str) else \
            getattr(fn, "__name__", repr(fn))
        return _verify.maybe_verify(out, pass_name=pass_name)

    # -- execution / export ----------------------------------------------

    def to_callable(self) -> Callable:
        closed = self.closed

        def run(*args):
            flat = jax.tree.leaves(args)
            out = jax.core.eval_jaxpr(closed.jaxpr, closed.consts, *flat)
            return out[0] if len(out) == 1 else tuple(out)
        return run

    def __call__(self, *args):
        return self.to_callable()(*args)

    def __repr__(self):
        return f"Program({len(self.closed.jaxpr.eqns)} ops)"

    def __str__(self):
        return str(self.closed)


class PassRegistry:
    """Reference: `ir/pass.h` PassRegistry + REGISTER_PASS."""

    _passes: Dict[str, Callable] = {}

    @classmethod
    def register(cls, name: str):
        def deco(fn):
            cls._passes[name] = fn
            return fn
        return deco

    @classmethod
    def get(cls, name: str) -> Callable:
        if name not in cls._passes:
            raise KeyError(f"unknown pass {name!r}; registered: "
                           f"{sorted(cls._passes)}")
        return cls._passes[name]

    @classmethod
    def list(cls) -> List[str]:
        return sorted(cls._passes)


# --------------------------------------------------------------------------
# Built-in passes
# --------------------------------------------------------------------------

@PassRegistry.register("dead_code_elimination")
def dead_code_elimination(eqns, jaxpr):
    """Drop eqns none of whose outputs are used (reference:
    `ir/memory_optimize_pass/eager_deletion_pass.cc` spirit; here a
    classic backward liveness sweep)."""
    from jax.extend.core import Literal
    live = {id(v) for v in jaxpr.outvars}
    kept = []
    for e in reversed(eqns):
        used = any(id(v) in live for v in e.outvars)
        # keep possibly-effectful primitives conservatively
        effectful = bool(getattr(e, "effects", ()))
        if used or effectful:
            kept.append(e)
            for v in e.invars:
                if not isinstance(v, Literal):
                    live.add(id(v))
    return list(reversed(kept))


_RNG_PRIMS = frozenset({
    "random_seed", "random_split", "random_bits", "random_wrap",
    "random_fold_in", "random_unwrap", "random_gamma", "threefry2x32"})


def _inner_jaxprs(params: dict):
    for v in params.values():
        if hasattr(v, "jaxpr"):        # ClosedJaxpr (jit, custom_* ...)
            yield v.jaxpr
        elif hasattr(v, "eqns"):       # raw Jaxpr
            yield v


def _jaxpr_has_rng(jaxpr) -> bool:
    for e in jaxpr.eqns:
        if e.primitive.name in _RNG_PRIMS:
            return True
        for inner in _inner_jaxprs(e.params):
            if _jaxpr_has_rng(inner):
                return True
    return False


def has_rng_ops(closed_jaxpr) -> bool:
    """True when the program samples randomness (dropout and friends) —
    the load/save hooks use this to decide whether `dropout_removal`
    has anything to do."""
    return _jaxpr_has_rng(closed_jaxpr.jaxpr)


def _is_zero(v, producers, depth: int = 0) -> bool:
    from jax.extend.core import Literal
    if isinstance(v, Literal):
        try:
            import numpy as np
            return float(np.asarray(v.val)) == 0.0
        except (TypeError, ValueError):
            return False
    if depth > 4:
        return False
    e = producers.get(id(v))
    if e is not None and e.primitive.name in ("broadcast_in_dim",
                                              "convert_element_type"):
        return _is_zero(e.invars[0], producers, depth + 1)
    return False


def _keep_prob(pred, producers, depth: int = 0):
    """The bernoulli keep probability behind a dropout mask predicate,
    or None when it cannot be established. jax.random.bernoulli traces
    as `jit[name=_bernoulli](key, p)` with p a scalar literal; the
    mask may pass through broadcasts/converts on its way to the
    select."""
    from jax.extend.core import Literal
    if depth > 4 or isinstance(pred, Literal):
        return None
    e = producers.get(id(pred))
    if e is None:
        return None
    name = e.primitive.name
    if name == "jit" and e.params.get("name") == "_bernoulli" and \
            len(e.invars) == 2 and isinstance(e.invars[1], Literal):
        try:
            import numpy as np
            return float(np.asarray(e.invars[1].val))
        except (TypeError, ValueError):
            return None
    if name in ("broadcast_in_dim", "convert_element_type", "reshape"):
        return _keep_prob(e.invars[0], producers, depth + 1)
    return None


@PassRegistry.register("dropout_removal")
def dropout_removal(eqns, jaxpr):
    """Remove train-mode dropout for inference (reference:
    `delete_dropout_op_pass.cc`; here over the jaxpr).

    A dropout site is a select whose PREDICATE is RNG-derived
    (`where(bernoulli(key, keep), x / keep, 0)` in the default
    upscale_in_train mode): taint vars forward from the RNG primitives,
    find select_n / jit-`_where` eqns with a tainted predicate and a
    zero branch, VERIFY the kept branch is `x / keep` with the divisor
    equal to the bernoulli keep probability, and rewire consumers to x
    — exactly the eval-mode (training=False) semantics. Sites that
    don't match the proven pattern (downscale_in_infer, whose eval
    semantics is x*(1-p), or a div that is user arithmetic rather than
    the upscale) are conservatively LEFT IN PLACE — never a silent
    numerics change — and `has_rng_ops` still reports them. The
    orphaned RNG chain then falls to DCE. A site whose result is a
    direct program output (dropout as the model's last op) retargets
    the outvar via the (eqns, outvars) pass return form.
    """
    from jax.extend.core import Literal
    tainted: set = set()

    def is_tainted(v) -> bool:
        return not isinstance(v, Literal) and id(v) in tainted

    producers = {}
    for e in eqns:
        rng_src = e.primitive.name in _RNG_PRIMS or any(
            _jaxpr_has_rng(inner) for inner in _inner_jaxprs(e.params))
        if rng_src or any(is_tainted(v) for v in e.invars):
            for v in e.outvars:
                tainted.add(id(v))
        for v in e.outvars:
            producers[id(v)] = e

    subst = {}          # id(select outvar) -> replacement var
    drop: set = set()   # id(eqn) to delete
    for e in eqns:
        name = e.primitive.name
        if name == "select_n" and len(e.invars) == 3:
            pred, on_false, on_true = e.invars
            cases = [on_false, on_true]
        elif name == "jit" and e.params.get("name") == "_where" and \
                len(e.invars) == 3:
            pred, on_true, on_false = e.invars
            cases = [on_false, on_true]
        else:
            continue
        if not is_tainted(pred):
            continue
        zero = [c for c in cases if _is_zero(c, producers)]
        kept = [c for c in cases if not _is_zero(c, producers)]
        if len(zero) != 1 or len(kept) != 1:
            continue
        v = kept[0]
        if isinstance(v, Literal):
            continue
        # Only rewrite the PROVEN upscale_in_train shape
        # where(bern(keep), x / keep, 0): the kept branch must be a div
        # whose literal divisor equals the bernoulli keep probability.
        # Anything else — downscale_in_infer (eval semantics x*(1-p),
        # not x) or a kept branch whose div is the USER's arithmetic —
        # is left in place rather than silently changing numerics; the
        # save hook's has_rng_ops recheck then warns.
        keep = _keep_prob(pred, producers)
        pe = producers.get(id(v))
        if keep is None or pe is None or pe.primitive.name != "div" \
                or not isinstance(pe.invars[1], Literal):
            continue
        try:
            import numpy as np
            divisor = float(np.asarray(pe.invars[1].val))
        except (TypeError, ValueError):
            continue
        if abs(divisor - keep) > 1e-6 * max(1.0, abs(keep)):
            continue
        v = pe.invars[0]    # x / keep -> x (exact eval-mode value)
        if len(e.outvars) != 1:
            continue
        subst[id(e.outvars[0])] = v
        drop.add(id(e))
    if not subst:
        return eqns

    def resolve(v):
        while not isinstance(v, Literal) and id(v) in subst:
            v = subst[id(v)]
        return v

    new_eqns = []
    for e in eqns:
        if id(e) in drop:
            continue
        if any(not isinstance(v, Literal) and id(v) in subst
               for v in e.invars):
            e = e.replace(invars=[resolve(v) for v in e.invars])
        new_eqns.append(e)
    new_outvars = [resolve(v) for v in jaxpr.outvars]
    return (dead_code_elimination(new_eqns,
                                  jaxpr.replace(outvars=new_outvars)),
            new_outvars)


# the ISSUE/VERDICT spelling — same pass object under both names
PassRegistry._passes["dropout-removal"] = dropout_removal


@PassRegistry.register("op_stats")
def op_stats(eqns, jaxpr):
    """Identity pass that prints an op histogram (reference:
    `graph_viz_pass` class of diagnostics)."""
    import collections
    hist = collections.Counter(e.primitive.name for e in eqns)
    for name, n in hist.most_common():
        print(f"{name:24s} {n}")
    return eqns
