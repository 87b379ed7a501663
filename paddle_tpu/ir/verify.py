"""IR well-formedness verifier (reference: the graph checks
`paddle/fluid/framework/ir/graph_helper.cc` runs after passes —
`HasCircle`, dangling-node detection — restated over the jaxpr IR).

A buggy pass does not fail where it runs; it produces a jaxpr that
miscompiles (or crashes deep inside XLA lowering) at the NEXT use, with
an error pointing nowhere near the pass. `verify_jaxpr` pins the
invariants every pass must preserve, immediately after the pass:

  * defs-before-uses — every eqn input is a program input, constvar,
    literal, or the output of an EARLIER eqn (jaxprs are topologically
    ordered SSA; a pass that reorders or rewires eqns breaks this
    first);
  * single assignment — no var is defined twice;
  * no dangling outvars — every program output is actually defined
    (dropout_removal retargets outvars through its substitution map; a
    bug there leaves an output pointing at a deleted eqn);
  * no empty eqns — every eqn defines at least one output;
  * fused-op arity — call-style eqns carrying a subgraph (`jit`,
    `closed_call`, `call` — the jaxpr spelling of a fused op, e.g.
    the `_where`/`_bernoulli` sites dropout_removal rewrites) must bind
    exactly as many invars/outvars as their inner jaxpr declares.

Wiring: `Program.apply_pass` calls `maybe_verify` after EVERY
registered pass when verification is on. The switch is the
`PTPU_IR_VERIFY` env var (default off in production — the walk is
O(eqns) cheap but not free) or an explicit `set_verify(True)`;
tests/conftest.py turns it on for the whole tier-1 suite.
"""
from __future__ import annotations

import os
from typing import List, Optional

__all__ = ["IRVerificationError", "verify_jaxpr", "verify_program",
           "maybe_verify", "set_verify", "enabled"]

_FLAG: Optional[bool] = None  # explicit override; None defers to env


class IRVerificationError(RuntimeError):
    """A pass produced an ill-formed jaxpr (the message lists every
    violated invariant and the pass that produced it)."""


def set_verify(on: Optional[bool]) -> None:
    """Force verification on/off; None restores the env-var default."""
    global _FLAG
    _FLAG = on


def enabled() -> bool:
    if _FLAG is not None:
        return _FLAG
    return os.environ.get("PTPU_IR_VERIFY", "0").lower() not in (
        "0", "", "false", "off")


# call-style primitives whose params carry the fused subgraph and whose
# eqn arity must match it exactly (scan/while/cond pack extra operands
# around their bodies, so they are checked structurally, not by arity)
_ARITY_CHECKED = {"jit", "closed_call", "call"}


def _inner_jaxpr(params: dict):
    for key in ("jaxpr", "call_jaxpr"):
        v = params.get(key)
        if v is None:
            continue
        return v.jaxpr if hasattr(v, "jaxpr") else v
    return None


def verify_jaxpr(jaxpr, pass_name: Optional[str] = None) -> None:
    """Raise IRVerificationError if `jaxpr` violates an invariant."""
    from jax.extend.core import Literal

    errors: List[str] = []
    where = f" after pass {pass_name!r}" if pass_name else ""

    defined = set()
    for v in list(jaxpr.constvars) + list(jaxpr.invars):
        if id(v) in defined:
            errors.append(f"program binder {v} appears twice")
        defined.add(id(v))

    for i, e in enumerate(jaxpr.eqns):
        prim = e.primitive.name
        for v in e.invars:
            if isinstance(v, Literal):
                continue
            if id(v) not in defined:
                errors.append(
                    f"eqn {i} ({prim}): input {v} is used before any "
                    f"definition — defs-before-uses violated")
        if not e.outvars:
            errors.append(f"eqn {i} ({prim}) defines no outputs")
        for v in e.outvars:
            if type(v).__name__ == "DropVar":
                continue
            if id(v) in defined:
                errors.append(
                    f"eqn {i} ({prim}): output {v} redefines an "
                    f"existing var — single assignment violated")
            defined.add(id(v))
        if prim in _ARITY_CHECKED:
            inner = _inner_jaxpr(e.params)
            if inner is not None:
                if len(e.invars) != len(inner.invars):
                    errors.append(
                        f"eqn {i} ({prim}): binds {len(e.invars)} "
                        f"inputs but its subgraph declares "
                        f"{len(inner.invars)} — fused-op arity broken")
                if len(e.outvars) != len(inner.outvars):
                    errors.append(
                        f"eqn {i} ({prim}): binds {len(e.outvars)} "
                        f"outputs but its subgraph declares "
                        f"{len(inner.outvars)} — fused-op arity broken")

    for v in jaxpr.outvars:
        if isinstance(v, Literal):
            continue
        if id(v) not in defined:
            errors.append(
                f"program output {v} is dangling — no binder or eqn "
                f"defines it")

    if errors:
        raise IRVerificationError(
            f"ill-formed jaxpr{where}: " + "; ".join(errors))


def verify_program(program, pass_name: Optional[str] = None) -> None:
    verify_jaxpr(program.closed.jaxpr, pass_name=pass_name)


def maybe_verify(program, pass_name: Optional[str] = None):
    """Verify when enabled; always returns `program` so apply_pass can
    tail-call it."""
    if enabled():
        verify_program(program, pass_name=pass_name)
    return program
