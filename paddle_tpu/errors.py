"""Structured error classification.

Reference parity: paddle/fluid/platform/enforce.h (PADDLE_ENFORCE* macros) and
errors.{h,cc} / error_codes.proto error-code classification. Python-side enforce raises typed
exceptions with the failing expression context instead of aborting.
"""


class EnforceNotMet(RuntimeError):
    pass


class InvalidArgumentError(ValueError):
    pass


class NotFoundError(KeyError):
    pass


class OutOfRangeError(IndexError):
    pass


class AlreadyExistsError(RuntimeError):
    pass


class PermissionDeniedError(RuntimeError):
    pass


class UnimplementedError(NotImplementedError):
    pass


class UnavailableError(RuntimeError):
    pass


class PreconditionNotMetError(RuntimeError):
    pass


class ExecutionTimeoutError(RuntimeError):
    pass


def enforce(cond, msg="", exc=EnforceNotMet):
    if not cond:
        raise exc(msg)


def enforce_eq(a, b, msg=""):
    if a != b:
        raise EnforceNotMet(f"Expected {a!r} == {b!r}. {msg}")


def enforce_gt(a, b, msg=""):
    if not a > b:
        raise EnforceNotMet(f"Expected {a!r} > {b!r}. {msg}")


def enforce_shape_match(shape_a, shape_b, msg=""):
    if list(shape_a) != list(shape_b):
        raise InvalidArgumentError(f"Shape mismatch {shape_a} vs {shape_b}. {msg}")
