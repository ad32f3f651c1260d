"""SQL data-type system and TPU physical-type mapping.

Mirrors the role of the reference's Spark `DataType` handling plus the
GPU-physical mapping in GpuColumnVector.getNonNestedRapidsType
(ref: sql-plugin/src/main/java/com/nvidia/spark/rapids/GpuColumnVector.java:497)
and the declarative per-operator type signatures of TypeChecks/TypeSig
(ref: sql-plugin/.../TypeChecks.scala:129,483).

Physical mapping (TPU-first, not a cudf translation):
- fixed-width SQL types -> a single JAX array plus a boolean validity array;
- DATE -> int32 days since epoch; TIMESTAMP -> int64 microseconds UTC
  (the reference is likewise UTC-only, GpuOverrides.scala:439);
- DECIMAL(p<=18, s) -> int64 unscaled values (the reference uses
  DECIMAL64, DecimalUtil.scala);
- DECIMAL(18<p<=38, s) -> a `(capacity, 2)` int64 array per column: the
  128-bit two's-complement unscaled value as `[:, 0]` its low word (the
  bits of a uint64) and `[:, 1]` its high word, signed.  That is
  Arrow's decimal128 memory order, so a fetch is one buffer view; the
  arithmetic over it lives in `ops/int128.py`;
- STRING -> fixed-width uint8 byte matrix (n, width) + int32 lengths.
  XLA wants static shapes, so variable-width UTF-8 is padded to the
  batch's max byte length instead of cudf's offset+chars layout.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


class DataType:
    """Base class for SQL-level data types."""

    #: short name used in TypeSig strings and explain output
    name: str = "?"

    def __repr__(self) -> str:
        return self.name

    def __eq__(self, other) -> bool:
        return type(self) is type(other)

    def __hash__(self) -> int:
        return hash(type(self))

    @property
    def is_numeric(self) -> bool:
        return False

    @property
    def is_fixed_width(self) -> bool:
        return True


class BooleanType(DataType):
    name = "boolean"


class IntegralType(DataType):
    bits = 64

    @property
    def is_numeric(self) -> bool:
        return True


class ByteType(IntegralType):
    name = "tinyint"
    bits = 8


class ShortType(IntegralType):
    name = "smallint"
    bits = 16


class IntegerType(IntegralType):
    name = "int"
    bits = 32


class LongType(IntegralType):
    name = "bigint"
    bits = 64


class FractionalType(DataType):
    @property
    def is_numeric(self) -> bool:
        return True


class FloatType(FractionalType):
    name = "float"


class DoubleType(FractionalType):
    name = "double"


class StringType(DataType):
    name = "string"

    @property
    def is_fixed_width(self) -> bool:
        return False


class DateType(DataType):
    """Days since unix epoch, int32."""

    name = "date"


class TimestampType(DataType):
    """Microseconds since unix epoch, UTC only (parity with the reference:
    GpuOverrides.scala:439 UTC_TIMEZONE_ID)."""

    name = "timestamp"


@dataclasses.dataclass(frozen=True)
class DecimalType(DataType):
    """Decimal with precision <= 38: unscaled values in one int64 up to
    precision NARROW_PRECISION, in two int64 words past it."""

    precision: int = 10
    scale: int = 0
    MAX_PRECISION = 38
    #: widest precision one int64 holds
    NARROW_PRECISION = 18

    def __post_init__(self):
        if self.precision > self.MAX_PRECISION:
            raise ValueError(
                f"decimal precision {self.precision} > {self.MAX_PRECISION}"
            )

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"decimal({self.precision},{self.scale})"

    @property
    def is_numeric(self) -> bool:
        return True

    @property
    def wide(self) -> bool:
        """Held in two int64 words (precision past NARROW_PRECISION)."""
        return self.precision > self.NARROW_PRECISION

    def __eq__(self, other) -> bool:
        return (
            isinstance(other, DecimalType)
            and other.precision == self.precision
            and other.scale == self.scale
        )

    def __hash__(self) -> int:
        return hash((DecimalType, self.precision, self.scale))


class NullType(DataType):
    name = "null"


class ListType(DataType):
    """list<element> with fixed-width primitive elements; the device
    layout is a dense (capacity, max_len) element matrix + per-row
    lengths (the same dense-matrix answer to ragged data the string
    column uses — XLA wants static shapes, cudf's offset encoding does
    not map)."""

    def __init__(self, element: DataType):
        # string elements are representable LOGICALLY (schemas flowing
        # through CPU-fallback plans, e.g. collect_list over strings);
        # the DEVICE layout supports primitives only — TypeSig /
        # check_supported route string-element lists to the CPU engine
        if isinstance(element, ListType):
            raise TypeError(
                f"list element type {element} not supported (no nested "
                "lists)")
        self.element = element

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"array<{self.element.name}>"

    def __eq__(self, other) -> bool:
        return isinstance(other, ListType) and other.element == self.element

    def __hash__(self) -> int:
        return hash((ListType, self.element))


class StructType(DataType):
    """struct<name: type, ...> — device layout is struct-of-columns: one
    child column per field plus a row validity, so every field access is
    zero-copy and field-wise ops stay dense vector code (ref: the
    reference's nested TypeSig support, TypeChecks.scala:129, and
    complexTypeExtractors.scala GpuGetStructField)."""

    def __init__(self, fields):
        self.fields = tuple(fields)

    @property
    def name(self) -> str:  # type: ignore[override]
        inner = ", ".join(f"{f.name}: {f.dtype.name}" for f in self.fields)
        return f"struct<{inner}>"

    def field_index(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def __eq__(self, other) -> bool:
        return isinstance(other, StructType) and other.fields == self.fields

    def __hash__(self) -> int:
        return hash((StructType, self.fields))


class MapType(DataType):
    """map<key, value> — device layout is two aligned dense list
    matrices (keys + values sharing per-row lengths).  Lookup is a
    vectorized compare + argmax over the key matrix (ref:
    GpuGetMapValue, complexTypeExtractors.scala)."""

    def __init__(self, key: DataType, value: DataType):
        self.key = key
        self.value = value

    @property
    def name(self) -> str:  # type: ignore[override]
        return f"map<{self.key.name},{self.value.name}>"

    def __eq__(self, other) -> bool:
        return (isinstance(other, MapType) and other.key == self.key
                and other.value == self.value)

    def __hash__(self) -> int:
        return hash((MapType, self.key, self.value))


# Singletons
BOOLEAN = BooleanType()
BYTE = ByteType()
SHORT = ShortType()
INT = IntegerType()
LONG = LongType()
FLOAT = FloatType()
DOUBLE = DoubleType()
STRING = StringType()
DATE = DateType()
TIMESTAMP = TimestampType()
NULL = NullType()

INTEGRAL_TYPES = (BYTE, SHORT, INT, LONG)
NUMERIC_TYPES = INTEGRAL_TYPES + (FLOAT, DOUBLE)
ALL_BASIC_TYPES = NUMERIC_TYPES + (BOOLEAN, STRING, DATE, TIMESTAMP)

#: decimal integral digits needed to hold each integral type losslessly
#: (Spark's DecimalType.forType precision counts).  Shared by
#: common_type and Cast.cast_supported: they MUST agree, or union
#: widening would pick a target the cast then rejects.
INTEGRAL_DECIMAL_DIGITS = {ByteType: 3, ShortType: 5, IntegerType: 10,
                           LongType: 19}


_NUMPY_DTYPES = {
    BooleanType: np.bool_,
    ByteType: np.int8,
    ShortType: np.int16,
    IntegerType: np.int32,
    LongType: np.int64,
    FloatType: np.float32,
    DoubleType: np.float64,
    DateType: np.int32,
    TimestampType: np.int64,
    DecimalType: np.int64,
    NullType: np.bool_,
}


def is_wide_decimal(dt: DataType) -> bool:
    return isinstance(dt, DecimalType) and dt.wide


def adjust_precision_scale(precision: int, scale: int) -> DecimalType:
    """Spark's `DecimalType.adjustPrecisionScale` under
    `spark.sql.decimalOperations.allowPrecisionLoss=true`: a result
    wider than 38 digits keeps its integral digits and gives up scale,
    down to min(scale, 6)."""
    if precision <= DecimalType.MAX_PRECISION:
        return DecimalType(precision, scale)
    integral = precision - scale
    min_scale = min(scale, 6)
    return DecimalType(DecimalType.MAX_PRECISION,
                       max(DecimalType.MAX_PRECISION - integral, min_scale))


def decimal_of_literal(value) -> "DecimalType":
    """The decimal type Spark gives a literal beside a decimal operand:
    an integer's fewest digits (`DecimalType.fromLiteral` under
    `spark.sql.legacy.literal.pickMinimumPrecision=true`), a
    `decimal.Decimal`'s own digits and scale."""
    import decimal

    if isinstance(value, decimal.Decimal):
        sign, digits, exp = value.as_tuple()
        scale = max(-exp, 0)
        return DecimalType(max(len(digits) + max(exp, 0), scale, 1), scale)
    return DecimalType(max(len(str(abs(int(value)))), 1), 0)


def to_numpy_dtype(dt: DataType) -> np.dtype:
    """Physical numpy/JAX dtype backing a fixed-width SQL type."""
    try:
        return np.dtype(_NUMPY_DTYPES[type(dt)])
    except KeyError:
        raise TypeError(f"no fixed-width physical type for {dt}") from None


def from_arrow_type(at) -> DataType:
    """Map a pyarrow DataType to ours."""
    import pyarrow as pa

    if pa.types.is_dictionary(at):
        # dictionary encoding is a physical detail (fastpar keeps the
        # Parquet dict); the logical type is the value type
        return from_arrow_type(at.value_type)
    if pa.types.is_boolean(at):
        return BOOLEAN
    if pa.types.is_int8(at):
        return BYTE
    if pa.types.is_int16(at):
        return SHORT
    if pa.types.is_int32(at):
        return INT
    if pa.types.is_int64(at):
        return LONG
    if pa.types.is_float32(at):
        return FLOAT
    if pa.types.is_float64(at):
        return DOUBLE
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return STRING
    if pa.types.is_date32(at):
        return DATE
    if pa.types.is_timestamp(at):
        return TIMESTAMP
    if pa.types.is_decimal(at):
        if at.precision > DecimalType.MAX_PRECISION \
                or not pa.types.is_decimal128(at):
            raise TypeError(f"decimal precision {at.precision} unsupported")
        return DecimalType(at.precision, at.scale)
    if pa.types.is_list(at) or pa.types.is_large_list(at):
        return ListType(from_arrow_type(at.value_type))
    if pa.types.is_struct(at):
        return StructType([Field(at.field(i).name,
                                 from_arrow_type(at.field(i).type),
                                 at.field(i).nullable)
                           for i in range(at.num_fields)])
    if pa.types.is_map(at):
        return MapType(from_arrow_type(at.key_type),
                       from_arrow_type(at.item_type))
    raise TypeError(f"unsupported arrow type {at}")


def to_arrow_type(dt: DataType):
    import pyarrow as pa

    m = {
        BooleanType: pa.bool_(),
        ByteType: pa.int8(),
        ShortType: pa.int16(),
        IntegerType: pa.int32(),
        LongType: pa.int64(),
        FloatType: pa.float32(),
        DoubleType: pa.float64(),
        StringType: pa.string(),
        DateType: pa.date32(),
        TimestampType: pa.timestamp("us", tz="UTC"),
    }
    if isinstance(dt, DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, ListType):
        return pa.list_(to_arrow_type(dt.element))
    if isinstance(dt, StructType):
        return pa.struct([pa.field(f.name, to_arrow_type(f.dtype),
                                   nullable=f.nullable)
                          for f in dt.fields])
    if isinstance(dt, MapType):
        return pa.map_(to_arrow_type(dt.key), to_arrow_type(dt.value))
    try:
        return m[type(dt)]
    except KeyError:
        raise TypeError(f"unsupported type {dt}") from None


@dataclasses.dataclass(frozen=True)
class Field:
    name: str
    dtype: DataType
    nullable: bool = True

    def __repr__(self) -> str:
        n = "" if self.nullable else " not null"
        return f"{self.name}: {self.dtype}{n}"


@dataclasses.dataclass(frozen=True)
class Schema:
    fields: tuple[Field, ...]

    def __init__(self, fields):
        object.__setattr__(self, "fields", tuple(fields))

    @property
    def names(self) -> list[str]:
        return [f.name for f in self.fields]

    @property
    def dtypes(self) -> list[DataType]:
        return [f.dtype for f in self.fields]

    def field(self, name: str) -> Field:
        for f in self.fields:
            if f.name == name:
                return f
        raise KeyError(name)

    def index_of(self, name: str) -> int:
        for i, f in enumerate(self.fields):
            if f.name == name:
                return i
        raise KeyError(name)

    def __len__(self) -> int:
        return len(self.fields)

    def __iter__(self):
        return iter(self.fields)

    def __repr__(self) -> str:
        return "Schema(" + ", ".join(map(repr, self.fields)) + ")"


def common_type(a: DataType, b: DataType) -> Optional[DataType]:
    """Numeric widening a la Spark's implicit cast promotion; NULL
    widens to anything (a NULL literal branch takes the other side's
    type, as in Spark's TypeCoercion)."""
    if a == b:
        return a
    if isinstance(a, NullType):
        return b
    if isinstance(b, NullType):
        return a
    order = {ByteType: 0, ShortType: 1, IntegerType: 2, LongType: 3,
             FloatType: 4, DoubleType: 5}
    ta, tb = type(a), type(b)
    if ta in order and tb in order:
        return [BYTE, SHORT, INT, LONG, FLOAT, DOUBLE][max(order[ta], order[tb])]
    if ta is DecimalType and tb is DecimalType:
        # Spark's DecimalPrecision.widerDecimalType: keep every integral
        # digit and every fractional digit of both sides.  Widening
        # (unions, conditionals, comparisons) stays within one int64:
        # past NARROW_PRECISION the pair has no common type here, and a
        # comparison of two decimals rescales them itself
        scale = max(a.scale, b.scale)
        integral = max(a.precision - a.scale, b.precision - b.scale)
        if integral + scale > DecimalType.NARROW_PRECISION:
            return None
        return DecimalType(integral + scale, scale)
    if ta is DecimalType or tb is DecimalType:
        dec, other = (a, b) if ta is DecimalType else (b, a)
        if type(other) in (FloatType, DoubleType):
            # Spark's DecimalPrecision: decimal + fractional -> double
            return DOUBLE
        # integral -> decimal via DecimalType.forType digit counts;
        # LONG needs 19 integral digits, past NARROW_PRECISION, so
        # decimal+long has no common type for widening
        digits = INTEGRAL_DECIMAL_DIGITS.get(type(other))
        if digits is None:
            return None
        integral = max(dec.precision - dec.scale, digits)
        if integral + dec.scale > DecimalType.NARROW_PRECISION:
            return None
        return DecimalType(integral + dec.scale, dec.scale)
    if {ta, tb} == {DateType, TimestampType}:
        # Spark's findWiderTypeForTwo promotes date+timestamp to
        # timestamp (the date side casts to midnight UTC)
        return TIMESTAMP
    return None
