"""Declarative type-support signatures driving plan tagging.

TPU re-design of the reference's TypeSig/TypeChecks
(ref: sql-plugin/.../TypeChecks.scala:129 TypeSig, :483 TypeChecks —
every replacement rule declares which input types it accelerates, the
tagging pass checks declarations instead of trusting operator code, and
the registry generates docs/supported_ops.md).

A signature is a set of type *kinds*; an expression rule carries one
uniform input signature (parameter-position granularity can narrow it
later, as the reference does).  Tagging walks each expression tree and
turns every unsupported child dtype into a will-not-work reason — so a
decimal multiply or an array-typed comparison falls back to the CPU
engine with an explanation instead of silently computing wrong results
or crashing mid-kernel.

A decimal past precision 18 is its own kind, `decimal128`: it is held
in two int64 words (`types.py`), and only the rules that list the kind
take it, so every other operator refuses it by name."""

from __future__ import annotations

import dataclasses
from typing import Optional

from spark_rapids_tpu import types as T

_KIND_OF = {
    T.BooleanType: "boolean",
    T.ByteType: "byte",
    T.ShortType: "short",
    T.IntegerType: "int",
    T.LongType: "long",
    T.FloatType: "float",
    T.DoubleType: "double",
    T.StringType: "string",
    T.DateType: "date",
    T.TimestampType: "timestamp",
    T.DecimalType: "decimal",
    T.NullType: "null",
    T.ListType: "array",
    T.StructType: "struct",
    T.MapType: "map",
}

KIND_ORDER = ["boolean", "byte", "short", "int", "long", "float",
              "double", "decimal", "decimal128", "string", "date", "timestamp",
              "null", "array", "struct", "map"]


def kind_of(dtype: T.DataType) -> str:
    if T.is_wide_decimal(dtype):
        return "decimal128"
    return _KIND_OF[type(dtype)]


@dataclasses.dataclass(frozen=True)
class TypeSig:
    kinds: frozenset

    def supports(self, dtype: T.DataType) -> bool:
        return kind_of(dtype) in self.kinds

    def __add__(self, other: "TypeSig") -> "TypeSig":
        return TypeSig(self.kinds | other.kinds)

    def describe(self) -> str:
        return ", ".join(k for k in KIND_ORDER if k in self.kinds)

    @staticmethod
    def of(*kinds: str) -> "TypeSig":
        unknown = set(kinds) - set(KIND_ORDER)
        assert not unknown, f"unknown type kinds {unknown}"
        return TypeSig(frozenset(kinds))


BOOLEAN = TypeSig.of("boolean")
INTEGRAL = TypeSig.of("byte", "short", "int", "long")
NUMERIC = INTEGRAL + TypeSig.of("float", "double")
STRING = TypeSig.of("string")
DATETIME = TypeSig.of("date", "timestamp")
DECIMAL = TypeSig.of("decimal")
DECIMAL128 = TypeSig.of("decimal128")
NULLSIG = TypeSig.of("null")
ARRAY = TypeSig.of("array")
STRUCT = TypeSig.of("struct")
MAP = TypeSig.of("map")
NESTED = ARRAY + STRUCT + MAP

#: the commonCudfTypes analog (ref: TypeSig.commonCudfTypes :427):
#: everything the columnar kernels handle uniformly
COMMON = NUMERIC + BOOLEAN + STRING + DATETIME
COMMON_N = COMMON + NULLSIG
ORDERABLE = COMMON + DECIMAL + NULLSIG  # sort/compare/group keys
ALL = ORDERABLE + ARRAY


@dataclasses.dataclass(frozen=True)
class ExprSig:
    """Input signature of one expression rule: the types its children
    may produce for the TPU version to engage."""

    inputs: TypeSig
    note: str = ""


def check_inputs(expr, sig: Optional[ExprSig], reasons: set) -> None:
    """Tag unsupported child dtypes (the tagging side of TypeChecks)."""
    if sig is None:
        return
    for c in expr.children:
        try:
            dt = c.dtype
        except Exception:
            continue  # unresolved: binding errors surface elsewhere
        if not sig.inputs.supports(dt):
            reasons.add(
                f"expression {type(expr).__name__} does not support "
                f"input type {dt.name} on TPU "
                f"(supported: {sig.inputs.describe()})")
