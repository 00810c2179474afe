"""Error-reporting helpers.

TPU-native analogue of the reference's PADDLE_ENFORCE macro family
(paddle/fluid/platform/enforce.h, paddle/phi/core/enforce.h): typed error
classes with readable messages. Python stack traces replace the reference's
demangled C++ stacks; the error hierarchy mirrors paddle's error types so
user code catching them ports over.
"""

from __future__ import annotations


class EnforceNotMet(RuntimeError):
    """Base framework error (paddle's ``EnforceNotMet``)."""


class InvalidArgumentError(EnforceNotMet, ValueError):
    pass


class NotFoundError(EnforceNotMet, KeyError):
    pass


class OutOfRangeError(EnforceNotMet, IndexError):
    pass


class AlreadyExistsError(EnforceNotMet):
    pass


class PreconditionNotMetError(EnforceNotMet):
    pass


class UnimplementedError(EnforceNotMet, NotImplementedError):
    pass


class UnavailableError(EnforceNotMet):
    pass


class ExecutionTimeoutError(EnforceNotMet, TimeoutError):
    pass


def enforce(cond: bool, msg: str = "", err_cls=EnforceNotMet) -> None:
    """PADDLE_ENFORCE analogue: raise ``err_cls`` when ``cond`` is false."""
    if not cond:
        raise err_cls(msg or "Enforce condition failed.")


def enforce_eq(a, b, msg: str = "") -> None:
    if a != b:
        raise InvalidArgumentError(f"{msg} (expected {a!r} == {b!r})")


def enforce_shape_eq(shape_a, shape_b, msg: str = "") -> None:
    if tuple(shape_a) != tuple(shape_b):
        raise InvalidArgumentError(
            f"{msg} (shape mismatch: {tuple(shape_a)} vs {tuple(shape_b)})"
        )


def not_implemented(what: str) -> None:
    raise UnimplementedError(
        f"{what} is not implemented in paddle_tpu. "
        "If this is load-bearing for your workload, file an issue."
    )
