"""Error enforcement.

TPU-native equivalent of the reference's `paddle/fluid/platform/enforce.h`
(PADDLE_ENFORCE_* macros) and `platform/errors.cc` error classes. Python
exceptions replace the C++ macro machinery; the error categories are kept so
user-facing messages stay recognisable.
"""
from __future__ import annotations


class EnforceNotMet(RuntimeError):
    """Base error, mirrors `platform::EnforceNotMet`."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PermissionDeniedError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class ExecutionTimeoutError(EnforceNotMet):
    pass


class FatalError(EnforceNotMet):
    pass


def enforce(condition, message="", error_cls=InvalidArgumentError):
    """PADDLE_ENFORCE analogue: raise `error_cls` when `condition` is falsy.

    Only call on Python-level (static) conditions — inside a jitted trace use
    `check_numerics`/`jax.debug` instead, since traced booleans are abstract.
    """
    if not condition:
        raise error_cls(message)


def enforce_eq(a, b, message="", error_cls=InvalidArgumentError):
    if a != b:
        raise error_cls(f"Expected {a!r} == {b!r}. {message}")


def enforce_gt(a, b, message="", error_cls=InvalidArgumentError):
    if not a > b:
        raise error_cls(f"Expected {a!r} > {b!r}. {message}")


def enforce_ge(a, b, message="", error_cls=InvalidArgumentError):
    if not a >= b:
        raise error_cls(f"Expected {a!r} >= {b!r}. {message}")


def not_none(value, name="value", error_cls=NotFoundError):
    if value is None:
        raise error_cls(f"{name} must not be None")
    return value
