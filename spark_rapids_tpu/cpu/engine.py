"""Pyarrow-based executor for logical plans.

Deliberately an *independent implementation* of the SQL semantics (built
on pyarrow.compute kernels + numpy for the gaps), so a TPU kernel bug
cannot be masked by sharing code with the device path.  Where Spark
semantics differ from pyarrow defaults (Kleene logic, NULL on zero
divisors, NaN ordering, IN-list NULLs, If's NULL predicate), the Spark
behavior is implemented here explicitly — mirroring the compatibility
contract the reference documents in docs/compatibility.md."""

from __future__ import annotations

from typing import Optional

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.arrow import schema_to_arrow
from spark_rapids_tpu.exprs import arithmetic as A
from spark_rapids_tpu.exprs import bitwise as BW
from spark_rapids_tpu.exprs import datetime as DT
from spark_rapids_tpu.exprs import decimal as DEC
from spark_rapids_tpu.exprs import math as M
from spark_rapids_tpu.exprs import predicates as P
from spark_rapids_tpu.exprs import strings as S
from spark_rapids_tpu.exprs import base as B
from spark_rapids_tpu.exprs.cast import Cast
from spark_rapids_tpu.exprs.hashing import Md5, Murmur3Hash
from spark_rapids_tpu.plan import logical as L

_PC_UNARY = {
    M.Sqrt: pc.sqrt, M.Exp: pc.exp, M.Sin: pc.sin, M.Cos: pc.cos,
    M.Tan: pc.tan, M.Asin: pc.asin, M.Acos: pc.acos, M.Atan: pc.atan,
    M.Signum: pc.sign,
}
_NP_UNARY = {
    M.Cbrt: np.cbrt, M.Expm1: np.expm1, M.Sinh: np.sinh,
    M.Cosh: np.cosh, M.Tanh: np.tanh, M.Asinh: np.arcsinh,
    M.Acosh: np.arccosh, M.Atanh: np.arctanh, M.Rint: np.rint,
    M.ToDegrees: np.degrees, M.ToRadians: np.radians,
    M.Cot: lambda d: 1.0 / np.tan(d),
}


# ---------------------------------------------------------------------- #
# Expression evaluation
# ---------------------------------------------------------------------- #

def _arr(x, n: int, atype=None) -> pa.Array:
    if isinstance(x, pa.ChunkedArray):
        return x.combine_chunks()
    return x


def cpu_eval(e: B.Expression, table: pa.Table) -> pa.Array:
    n = table.num_rows
    out = _dispatch(e, table, n)
    return _arr(out, n)


def _widen_type(e: B.Expression) -> pa.DataType:
    return T.to_arrow_type(e.dtype)


def _plain(arr):
    """Decode dictionary encodings at the engine boundary: the CPU
    oracle computes over plain arrays (fastpar ships scan columns as
    pa.DictionaryArray to keep the wire cheap)."""
    t = arr.type
    if pa.types.is_dictionary(t):
        return arr.cast(t.value_type)
    return arr


def _binary_operands(e, table, n):
    l = cpu_eval(e.left, table)
    r = cpu_eval(e.right, table)
    return l, r


def _np_vals(arr: pa.Array, dtype) -> tuple[np.ndarray, np.ndarray]:
    valid = np.asarray(arr.is_valid())
    filled = arr.fill_null(0).cast(dtype) if arr.null_count else \
        arr.cast(dtype)
    return filled.to_numpy(zero_copy_only=False), valid


def _from_np(vals: np.ndarray, valid: np.ndarray, atype) -> pa.Array:
    mask = ~valid if (~valid).any() else None
    return pa.array(vals, type=atype, mask=mask)


def _string_batch3(e, table, n):
    """Python-string reference semantics for the batch-3 string ops
    (the oracle definitions; java.lang.String behavior where Spark
    delegates there)."""
    import re

    if isinstance(e, S.ConcatWs):
        sep = e.sep.value
        cols = [cpu_eval(c, table).to_pylist() for c in e.exprs]
        if sep is None:
            return pa.array([None] * n, pa.string())
        out = []
        for i in range(n):
            parts = [c[i] for c in cols if c[i] is not None]
            out.append(sep.join(parts))
        return pa.array(out, pa.string())

    vals = cpu_eval(e.child, table).to_pylist()

    def mapped(fn):
        return pa.array([None if v is None else fn(v) for v in vals],
                        pa.string())

    if isinstance(e, S.RegExpReplace):
        pat, rep = e.search.value, e.replacement.value or ""
        return mapped(lambda s: re.sub(pat, rep, s))
    if isinstance(e, S.StringReplace):
        search, rep = e.search.value or "", e.replacement.value or ""
        if not search:
            return mapped(lambda s: s)
        return mapped(lambda s: s.replace(search, rep))
    if isinstance(e, S.StringLPad):
        tgt = int(e.length.value)
        p = e.pad.value or ""
        left = e._left

        def padfn(s):
            if tgt <= 0:
                return ""
            if len(s) >= tgt:
                return s[:tgt]
            if not p:
                return s
            fill = (p * tgt)[: tgt - len(s)]
            return fill + s if left else s + fill

        return mapped(padfn)
    if isinstance(e, S.StringLocate):
        sub = e.substr.value or ""
        start = int(e.start.value)

        def locfn(s):
            if start <= 0:
                return 0
            if sub == "":
                return min(start, len(s) + 1)
            return s.find(sub, start - 1) + 1

        return pa.array([None if v is None else locfn(v) for v in vals],
                        pa.int32())
    if isinstance(e, S.SubstringIndex):
        d = e.delim.value or ""
        cnt = int(e.count.value)

        def sifn(s):
            if cnt == 0 or not d:
                return ""
            pos, hits = 0, []
            while True:
                j = s.find(d, pos)
                if j < 0:
                    break
                hits.append(j)
                pos = j + len(d)
            if cnt > 0:
                return s if len(hits) < cnt else s[: hits[cnt - 1]]
            k = len(hits) + cnt
            return s if k < 0 else s[hits[k] + len(d):]

        return mapped(sifn)
    if isinstance(e, S.InitCap):
        def icfn(s):
            out, prev = [], " "
            for ch in s:
                out.append(ch.upper() if prev == " " else ch.lower())
                prev = ch
            return "".join(out)

        return mapped(icfn)
    raise AssertionError(type(e))


def _dispatch(e, table, n):  # noqa: C901 - a dispatcher is a big switch
    from spark_rapids_tpu.exprs import collections as COLL

    if isinstance(e, B.Alias):
        return cpu_eval(e.child, table)
    from spark_rapids_tpu.udf.exprs import JaxScalarUDF, OpaquePythonUDF

    if isinstance(e, OpaquePythonUDF):
        # row-wise in-process evaluation (the python-worker analog);
        # NULLs pass through to the function, as Spark's python UDFs do
        cols = [cpu_eval(a, table).to_pylist() for a in e.args]
        out = [e.fn(*vals) for vals in zip(*cols)] if cols \
            else [e.fn() for _ in range(n)]
        return pa.array(out, T.to_arrow_type(e.dtype))
    if isinstance(e, JaxScalarUDF):
        # mirror the device eval: fn over data arrays (NULL slots hold
        # fill values), result NULL iff any input NULL
        arrs = [cpu_eval(a, table) for a in e.args]
        datas, valid = [], np.ones(n, bool)
        for a, ax in zip(e.args, arrs):
            atype = T.to_arrow_type(a.dtype)
            v, ok = _np_vals(ax, atype)
            datas.append(v)
            valid &= ok
        res = np.asarray(e.fn(*datas))
        if res.shape != (n,):
            raise ValueError(
                f"jax UDF {e.fn_name!r} returned shape {res.shape}, "
                f"expected ({n},)")
        return _from_np(res.astype(T.to_numpy_dtype(e.dtype)), valid,
                       T.to_arrow_type(e.dtype))
    if isinstance(e, COLL.Size):
        c = cpu_eval(e.child, table)
        return pc.list_value_length(c).cast(pa.int32())
    if isinstance(e, COLL.GetArrayItem):
        c = cpu_eval(e.child, table)
        k = int(e.index.value)
        out = [None if (v is None or k < 0 or k >= len(v)) else v[k]
               for v in c.to_pylist()]
        return pa.array(out, T.to_arrow_type(e.dtype))
    from spark_rapids_tpu.exprs import complex as CX

    if isinstance(e, CX.GetStructField):
        c = cpu_eval(e.child, table)
        dt = e.child.dtype
        idx = dt.field_index(e.field_name)
        field = pc.struct_field(c, [idx])
        if c.null_count:
            # null parent rows must surface as null fields
            field = pc.if_else(pc.is_valid(c), field,
                               pa.scalar(None, field.type))
        return field
    if isinstance(e, CX.CreateNamedStruct):
        kids = [cpu_eval(v, table) for v in e.values]
        return pc.make_struct(*kids, field_names=list(e.names))
    if isinstance(e, (CX.GetMapValue, CX.ElementAt)) and isinstance(
            e.child.dtype, T.MapType):
        c = cpu_eval(e.child, table)
        key = e.key.value if isinstance(e, CX.GetMapValue) \
            else e.index.value
        out = []
        for row in c.to_pylist():
            if row is None:
                out.append(None)
            else:
                d = dict(row) if not isinstance(row, dict) else row
                out.append(d.get(key))
        return pa.array(out, T.to_arrow_type(e.dtype))
    if isinstance(e, CX.ElementAt):
        c = cpu_eval(e.child, table)
        k = int(e.index.value)
        if k == 0:
            # Spark contract: index 0 is an error in EVERY mode
            raise ValueError("SQL array indices start at 1")
        out = []
        for row in c.to_pylist():
            if row is None:
                out.append(None)
            else:
                pos = k - 1 if k > 0 else len(row) + k
                out.append(row[pos] if 0 <= pos < len(row) else None)
        return pa.array(out, T.to_arrow_type(e.dtype))
    if isinstance(e, COLL.ArrayContains):
        c = cpu_eval(e.child, table)
        v = e.value.value
        out = []
        for row in c.to_pylist():
            if row is None:
                out.append(None)
            elif v in row:
                out.append(True)
            elif None in row:
                out.append(None)
            else:
                out.append(False)
        return pa.array(out, pa.bool_())
    from spark_rapids_tpu.exprs import nondeterministic as ND

    if isinstance(e, ND.InputFileName):
        # no file context on this path: Spark's documented defaults
        return pa.array([e.DEFAULT] * n, T.to_arrow_type(e.dtype))
    if isinstance(e, B.BoundReference):
        return _plain(table.column(e.ordinal).combine_chunks())
    if isinstance(e, B.ColumnReference):
        return _plain(table.column(e.col_name).combine_chunks())
    if isinstance(e, B.Literal):
        if e.value is None:
            return pa.nulls(n, type=T.to_arrow_type(e.dtype)
                            if not isinstance(e.dtype, T.NullType)
                            else pa.bool_())
        return pa.array([e.value] * n, type=T.to_arrow_type(e.dtype))

    # arithmetic --------------------------------------------------------- #
    if isinstance(e, (A.Add, A.Subtract, A.Multiply)):
        l, r = _binary_operands(e, table, n)
        at = _widen_type(e)
        if isinstance(e.dtype, T.DecimalType):
            # exact python-Decimal reference for decimal arithmetic
            # (arrow's own promotion rules differ from Spark's).  The
            # declared type is capped at this engine's MAX_PRECISION;
            # exact results that cannot fit become NULL — the
            # nullOnOverflow contract for precision the engine cannot
            # represent (Spark with p<=38 would hold them; documented
            # 18-digit divergence)
            import decimal as _dec
            import operator as _op

            dt = e.dtype
            q = _dec.Decimal(1).scaleb(-dt.scale)
            bound = _dec.Decimal(10) ** (dt.precision - dt.scale)
            lv, rv = l.to_pylist(), r.to_pylist()
            op = {A.Add: _op.add, A.Subtract: _op.sub,
                  A.Multiply: _op.mul}[type(e)]
            out = []
            # wide context: the default 28-digit context would RAISE
            # (or double-round) on products wider than 28 digits —
            # exactly the values the overflow contract must NULL
            with _dec.localcontext() as ctx:
                ctx.prec = 76
                for a, b in zip(lv, rv):
                    if a is None or b is None:
                        out.append(None)
                        continue
                    v = op(a, b).quantize(q,
                                          rounding=_dec.ROUND_HALF_UP)
                    out.append(None if abs(v) >= bound else v)
            return pa.array(out, at)
        from spark_rapids_tpu.exprs.base import AnsiError, ansi_enabled

        if ansi_enabled() and pa.types.is_integer(at):
            fn = {A.Add: pc.add_checked, A.Subtract: pc.subtract_checked,
                  A.Multiply: pc.multiply_checked}[type(e)]
            try:
                return fn(l.cast(at), r.cast(at))
            except pa.ArrowInvalid as exc:
                msg = "long overflow" if pa.types.is_int64(at) \
                    else "integer overflow"
                raise AnsiError(
                    msg + ". If necessary set "
                    "spark.rapids.tpu.sql.ansi.enabled to false to "
                    "bypass this error.") from exc
        fn = {A.Add: pc.add, A.Subtract: pc.subtract,
              A.Multiply: pc.multiply}[type(e)]
        return fn(l.cast(at), r.cast(at))
    if isinstance(e, A.Divide):
        l, r = _binary_operands(e, table, n)
        l = l.cast(pa.float64())
        r = r.cast(pa.float64())
        zero = pc.equal(r, 0.0)
        # both-valid gating matches the device check (a NULL operand
        # row never raises; Spark's right-only gating differs on the
        # (NULL, 0) corner — documented engine behavior)
        _cpu_ansi_div_check(l, pc.and_kleene(
            pc.fill_null(zero, False), pc.is_valid(l)))
        safe = pc.if_else(pc.fill_null(zero, False), pa.scalar(1.0), r)
        out = pc.divide(l, safe)
        return pc.if_else(pc.fill_null(zero, True), pa.nulls(
            n, pa.float64()), out)
    if isinstance(e, (A.IntegralDivide, A.Remainder, A.Pmod)):
        l, r = _binary_operands(e, table, n)
        at = _widen_type(e)
        npdt = at.to_pandas_dtype()
        lv, lva = _np_vals(l, at)
        rv, rva = _np_vals(r, at)
        valid = lva & rva
        _cpu_ansi_div_check(None, pa.array((rv == 0) & valid))
        if np.issubdtype(npdt, np.floating):
            zero = rv == 0.0
            rv = np.where(zero, 1.0, rv)
            rem = np.fmod(lv, rv)
            if isinstance(e, A.Pmod):
                rem = np.where(rem < 0, np.fmod(rem + rv, rv), rem)
            out = rem
        else:
            zero = rv == 0
            rv = np.where(zero, 1, rv)
            q = np.where((lv < 0) != (rv < 0),
                         -(np.abs(lv) // np.abs(rv)), lv // rv)
            rem = lv - q * rv
            if isinstance(e, A.IntegralDivide):
                out = q
            elif isinstance(e, A.Pmod):
                out = np.where(rem < 0, (rem + rv) % rv if False else
                               _np_java_mod(rem + rv, rv), rem)
            else:
                out = rem
        return _from_np(out.astype(npdt), valid & ~zero, at)
    if isinstance(e, A.UnaryMinus):
        return pc.negate(cpu_eval(e.child, table))
    if isinstance(e, A.UnaryPositive):
        return cpu_eval(e.child, table)
    if isinstance(e, A.Abs):
        return pc.abs(cpu_eval(e.child, table))
    if isinstance(e, (A.Least, A.Greatest)):
        return _least_greatest(e, table, n)

    # predicates --------------------------------------------------------- #
    if isinstance(e, P.BinaryComparison):
        l, r = _binary_operands(e, table, n)
        # the engine's physical view lets dates compare against their
        # day counts (int literals); pyarrow has no date-vs-int kernel
        for a, b in ((l, r), (r, l)):
            if pa.types.is_date32(a.type) and pa.types.is_integer(b.type):
                if a is l:
                    l = a.cast(pa.int32()).cast(b.type)
                else:
                    r = a.cast(pa.int32()).cast(b.type)
        if isinstance(e, P.EqualNullSafe):
            ln, rn = pc.is_null(l), pc.is_null(r)
            eq = pc.fill_null(pc.equal(l, r), False)
            both_null = pc.and_(ln, rn)
            one_null = pc.xor(ln, rn)
            return pc.if_else(one_null, pa.scalar(False),
                              pc.or_(both_null, eq))
        fn = {P.EqualTo: pc.equal, P.LessThan: pc.less,
              P.LessThanOrEqual: pc.less_equal, P.GreaterThan: pc.greater,
              P.GreaterThanOrEqual: pc.greater_equal}[type(e)]
        out = fn(l, r)
        # Spark NaN comparison semantics (docs/compatibility.md: NaN is
        # larger than any other value and NaN = NaN) — raw IEEE from
        # pyarrow says the opposite for every NaN operand
        if pa.types.is_floating(l.type) or pa.types.is_floating(r.type):
            fl = l.cast(pa.float64()) if not pa.types.is_floating(l.type) \
                else l
            fr = r.cast(pa.float64()) if not pa.types.is_floating(r.type) \
                else r
            lnan = pc.fill_null(pc.is_nan(fl), False)
            rnan = pc.fill_null(pc.is_nan(fr), False)
            either = pc.or_(lnan, rnan)
            if pc.any(either).as_py():
                nan_lt = pc.and_(pc.invert(lnan), rnan)   # l < r
                nan_eq = pc.and_(lnan, rnan)              # l == r
                repl = {
                    P.EqualTo: nan_eq,
                    P.LessThan: nan_lt,
                    P.LessThanOrEqual: pc.or_(nan_lt, nan_eq),
                    P.GreaterThan: pc.and_(lnan, pc.invert(rnan)),
                    P.GreaterThanOrEqual: pc.or_(
                        pc.and_(lnan, pc.invert(rnan)), nan_eq),
                }[type(e)]
                valid = pc.and_(pc.is_valid(l), pc.is_valid(r))
                out = pc.if_else(pc.and_(either, valid), repl, out)
        return out
    if isinstance(e, P.And):
        l, r = _binary_operands(e, table, n)
        return pc.and_kleene(l, r)
    if isinstance(e, P.Or):
        l, r = _binary_operands(e, table, n)
        return pc.or_kleene(l, r)
    if isinstance(e, P.Not):
        return pc.invert(cpu_eval(e.child, table))
    if isinstance(e, P.IsNull):
        return pc.is_null(cpu_eval(e.child, table))
    if isinstance(e, P.IsNotNull):
        return pc.is_valid(cpu_eval(e.child, table))
    if isinstance(e, P.IsNaN):
        c = cpu_eval(e.child, table)
        return pc.fill_null(pc.is_nan(c), False)
    if isinstance(e, P.In):
        c = cpu_eval(e.child, table)
        has_null = any(v is None for v in e.values)
        vals = [v for v in e.values if v is not None]
        match = pc.is_in(c, value_set=pa.array(vals, type=c.type))
        if has_null:
            # no match + NULL in list -> NULL
            match = pc.if_else(match, pa.scalar(True),
                               pa.nulls(n, pa.bool_()))
        return pc.if_else(pc.is_valid(c), match, pa.nulls(n, pa.bool_()))
    if isinstance(e, P.Coalesce):
        arrs = [cpu_eval(x, table) for x in e.exprs]
        at = _widen_type(e)
        return pc.coalesce(*[a.cast(at) for a in arrs])
    if isinstance(e, P.If):
        p = pc.fill_null(cpu_eval(e.pred, table), False)
        at = _widen_type(e)
        return pc.if_else(p, cpu_eval(e.then, table).cast(at),
                          cpu_eval(e.otherwise, table).cast(at))
    if isinstance(e, P.CaseWhen):
        at = _widen_type(e)
        out = cpu_eval(e.else_value, table).cast(at)
        for cond, val in reversed(e.branches):
            p = pc.fill_null(cpu_eval(cond, table), False)
            out = pc.if_else(p, cpu_eval(val, table).cast(at), out)
        return out
    from spark_rapids_tpu.exprs.subquery import ScalarSubquery

    if isinstance(e, ScalarSubquery):
        sub = execute_cpu(e.plan)
        if sub.num_rows != 1 or sub.num_columns != 1:
            raise ValueError(
                f"scalar subquery must return 1x1, got "
                f"{sub.num_rows}x{sub.num_columns}")
        v = sub.column(0)[0].as_py()
        return pa.array([v] * n, T.to_arrow_type(e.dtype))
    if isinstance(e, COLL.CreateArray):
        arrs = [cpu_eval(x, table) for x in e.exprs]
        et = T.to_arrow_type(e.dtype.element)
        rows = list(zip(*[a.cast(et).to_pylist() for a in arrs]))
        return pa.array([list(r) for r in rows], pa.list_(et))
    if isinstance(e, (DT.FromUnixTime, DT.DateFormatClass)):
        import datetime as _dt

        c = cpu_eval(e.child, table)
        py_fmt = e.fmt.replace("yyyy", "%Y").replace(
            "MM", "%m").replace("dd", "%d").replace(
            "HH", "%H").replace("mm", "%M").replace("ss", "%S")
        out = []
        for v in c.to_pylist():
            if v is None:
                out.append(None)
                continue
            if isinstance(e, DT.FromUnixTime):
                t = _dt.datetime.fromtimestamp(int(v), _dt.timezone.utc)
            elif isinstance(v, _dt.datetime):
                t = v
            elif isinstance(v, _dt.date):
                t = _dt.datetime(v.year, v.month, v.day,
                                 tzinfo=_dt.timezone.utc)
            else:
                t = _dt.datetime.fromtimestamp(int(v) / 1e6,
                                               _dt.timezone.utc)
            out.append(t.strftime(py_fmt))
        return pa.array(out, pa.string())
    from spark_rapids_tpu.exprs import nondeterministic as ND

    if isinstance(e, ND.SparkPartitionID):
        # the CPU engine is a single partition
        return pa.array(np.zeros(n, np.int32))
    if isinstance(e, ND.MonotonicallyIncreasingID):
        return pa.array(np.arange(n, dtype=np.int64))
    if isinstance(e, ND.Rand):
        import jax

        from spark_rapids_tpu.exprs.nondeterministic import _rand_uniform

        with jax.default_device(jax.devices("cpu")[0]):
            vals = np.asarray(_rand_uniform(
                e.seed, 0, np.arange(n, dtype=np.int64)))
        return pa.array(vals)
    if isinstance(e, M.NaNvl):
        at = T.to_arrow_type(e.dtype)
        a = cpu_eval(e.left, table).cast(at)
        b = cpu_eval(e.right, table).cast(at)
        take_b = pc.fill_null(pc.is_nan(a), False)
        return pc.if_else(take_b, b, a)
    if isinstance(e, M.NormalizeNaNAndZero):
        a = cpu_eval(e.child, table)
        v, ok = _np_vals(a, a.type)
        v = np.where(np.isnan(v), np.nan, v) + 0.0
        return _from_np(v, ok, a.type)
    if isinstance(e, M.KnownFloatingPointNormalized):
        return cpu_eval(e.child, table)
    if isinstance(e, P.AtLeastNNonNulls):
        count = np.zeros(n, np.int32)
        for x in e.exprs:
            a = cpu_eval(x, table)
            ok = np.asarray(a.is_valid())
            if pa.types.is_floating(a.type):
                ok = ok & ~np.asarray(
                    pc.fill_null(pc.is_nan(a), False))
            count += ok.astype(np.int32)
        return pa.array(count >= e.n)

    if isinstance(e, Murmur3Hash):
        return _murmur3_cpu(e, table, n)
    from spark_rapids_tpu.exprs.decimal import CheckOverflow, PromotePrecision

    if isinstance(e, PromotePrecision):
        import decimal as _dec

        vals = cpu_eval(e.child, table).to_pylist()
        q = _dec.Decimal(1).scaleb(-e.target.scale)
        return pa.array(
            [None if v is None else v.quantize(q) for v in vals],
            pa.decimal128(e.target.precision, e.target.scale))
    if isinstance(e, CheckOverflow):
        import decimal as _dec

        vals = cpu_eval(e.child, table).to_pylist()
        q = _dec.Decimal(1).scaleb(-e.target.scale)
        bound = _dec.Decimal(10) ** (e.target.precision - e.target.scale)
        out = []
        with _dec.localcontext() as ctx:
            ctx.prec = 76  # wide children must NULL, not raise
            for v in vals:
                if v is None:
                    out.append(None)
                    continue
                r = v.quantize(q, rounding=_dec.ROUND_HALF_UP)
                out.append(None if abs(r) >= bound else r)
        return pa.array(out, pa.decimal128(e.target.precision,
                                           e.target.scale))
    if isinstance(e, Md5):
        import hashlib

        vals = cpu_eval(e.child, table).to_pylist()
        return pa.array(
            [None if v is None
             else hashlib.md5(str(v).encode()).hexdigest()
             for v in vals], pa.string())

    out = _dispatch_extended(e, table, n)
    if out is NotImplemented:
        raise NotImplementedError(
            f"CPU engine: unsupported expression {type(e).__name__}")
    return out


def _dispatch_extended(e, table, n):  # noqa: C901
    # math ---------------------------------------------------------------- #
    if type(e) in _PC_UNARY:
        c = cpu_eval(e.child, table).cast(pa.float64())
        return pc.cast(_PC_UNARY[type(e)](c), pa.float64())
    if type(e) in _NP_UNARY:
        c = cpu_eval(e.child, table).cast(pa.float64())
        v, ok = _np_vals(c, pa.float64())
        with np.errstate(all="ignore"):
            return _from_np(_NP_UNARY[type(e)](v), ok, pa.float64())
    if isinstance(e, M._LogBase):
        c = cpu_eval(e.child, table).cast(pa.float64())
        v, ok = _np_vals(c, pa.float64())
        bad = v <= (-1.0 if isinstance(e, M.Log1p) else 0.0)
        fn = {M.Log: np.log, M.Log10: np.log10, M.Log2: np.log2,
              M.Log1p: np.log1p}[type(e)]
        with np.errstate(all="ignore"):
            return _from_np(fn(np.where(bad, 1.0, v)), ok & ~bad,
                            pa.float64())
    if isinstance(e, M.Logarithm):
        b = cpu_eval(e.base, table).cast(pa.float64())
        c = cpu_eval(e.child, table).cast(pa.float64())
        bv, bok = _np_vals(b, pa.float64())
        cv, cok = _np_vals(c, pa.float64())
        bad = (cv <= 0) | (bv <= 0)
        with np.errstate(all="ignore"):
            out = np.log(np.where(cv <= 0, 1.0, cv)) / \
                np.log(np.where(bv <= 0, 2.0, bv))
        return _from_np(out, bok & cok & ~bad, pa.float64())
    if isinstance(e, M.Pow):
        l = cpu_eval(e.left, table).cast(pa.float64())
        r = cpu_eval(e.right, table).cast(pa.float64())
        return pc.power(l, r)
    if isinstance(e, M.Ceil):  # Floor subclasses Ceil
        c = cpu_eval(e.child, table)
        if not pa.types.is_floating(c.type):
            return c
        # Spark: ceil/floor(double) -> LONG via the Java (long) cast:
        # NaN -> 0, +/-inf and out-of-range saturate at Long.MIN/MAX
        v, ok = _np_vals(c.cast(pa.float64()), pa.float64())
        r = np.floor(v) if isinstance(e, M.Floor) else np.ceil(v)
        r = np.where(np.isnan(r), 0.0, r)
        i64 = np.iinfo(np.int64)
        hi_f, lo_f = float(i64.max) + 1.0, float(i64.min)
        out = np.where((r > lo_f) & (r < hi_f), r, 0.0).astype(np.int64)
        out = np.where(r >= hi_f, i64.max, out)
        out = np.where(r <= lo_f, i64.min, out)
        return _from_np(out, ok, pa.int64())
    if isinstance(e, M.Round):  # BRound subclasses Round
        c = cpu_eval(e.child, table)
        # Spark HALF_UP rounds half away from zero
        mode = "half_to_even" if e.half_even else "half_towards_infinity"
        if pa.types.is_floating(c.type):
            return pc.round(c, ndigits=e.scale, round_mode=mode).cast(
                c.type)
        if e.scale >= 0:
            return c
        return pc.round(c, ndigits=e.scale, round_mode=mode).cast(c.type)

    # bitwise ------------------------------------------------------------- #
    if isinstance(e, BW.BitwiseBinary):
        l, r = cpu_eval(e.left, table), cpu_eval(e.right, table)
        at = T.to_arrow_type(e.dtype)
        fn = {BW.BitwiseAnd: pc.bit_wise_and, BW.BitwiseOr: pc.bit_wise_or,
              BW.BitwiseXor: pc.bit_wise_xor}[type(e)]
        return fn(l.cast(at), r.cast(at))
    if isinstance(e, BW.BitwiseNot):
        return pc.bit_wise_not(cpu_eval(e.child, table))
    if isinstance(e, BW.ShiftLeft):  # covers Right/RightUnsigned
        l = cpu_eval(e.left, table)
        r = cpu_eval(e.right, table)
        bits = 64 if pa.types.is_int64(l.type) else 32
        npdt = np.int64 if bits == 64 else np.int32
        lv, lok = _np_vals(l, l.type)
        rv, rok = _np_vals(r.cast(pa.int32()), pa.int32())
        amount = rv.astype(npdt) & (bits - 1)
        lv = lv.astype(npdt)
        if isinstance(e, BW.ShiftRightUnsigned):
            u = np.uint64 if bits == 64 else np.uint32
            out = (lv.view(u) >> amount.astype(u)).view(npdt)
        elif isinstance(e, BW.ShiftRight):
            out = lv >> amount
        else:
            with np.errstate(over="ignore"):
                out = lv << amount
        return _from_np(out, lok & rok, l.type)

    # datetime ------------------------------------------------------------ #
    if isinstance(e, DT._DateField):
        c = cpu_eval(e.child, table)
        fns = {DT.Year: pc.year, DT.Month: pc.month,
               DT.DayOfMonth: pc.day, DT.Quarter: pc.quarter,
               DT.DayOfYear: pc.day_of_year}
        if type(e) in fns:
            return fns[type(e)](c).cast(pa.int32())
        if isinstance(e, DT.DayOfWeek):
            # Spark: Sunday=1..Saturday=7
            return pc.add(pc.day_of_week(c, count_from_zero=True,
                                         week_start=7), 1).cast(pa.int32())
        if isinstance(e, DT.WeekDay):
            return pc.day_of_week(c, count_from_zero=True,
                                  week_start=1).cast(pa.int32())
        return NotImplemented
    if isinstance(e, DT.LastDay):
        c = cpu_eval(e.child, table)
        v, ok = _np_vals(c.cast(pa.int32()), pa.int32())
        d = v.astype("datetime64[D]")
        m = d.astype("datetime64[M]")
        last = (m + 1).astype("datetime64[D]") - 1
        return _from_np(last.astype(np.int32), ok,
                        pa.int32()).cast(pa.date32())
    if isinstance(e, DT.TimeAdd):  # TimeSub subclasses TimeAdd
        c = cpu_eval(e.child, table)
        v, ok = _np_vals(c.cast(pa.int64()), pa.int64())
        if e.interval.months:
            # calendar month arithmetic (day-of-month clamped to the
            # target month's end, Spark's add_months rule) — the case
            # the device path rejects and this fallback exists for
            out = np.array([_add_interval_us(
                int(x), e.interval.months * e._sign,
                e.interval.days * e._sign,
                e.interval.microseconds * e._sign) for x in v],
                np.int64)
            return _from_np(out, ok, pa.int64()).cast(
                T.to_arrow_type(T.TIMESTAMP))
        delta = (e.interval.days * 86_400_000_000
                 + e.interval.microseconds) * e._sign
        return _from_np(v + delta, ok, pa.int64()).cast(
            T.to_arrow_type(T.TIMESTAMP))
    if isinstance(e, DT.DateAddInterval):
        c = cpu_eval(e.child, table)
        v, ok = _np_vals(c.cast(pa.int32()), pa.int32())
        if e.interval.months:
            us_day = 86_400_000_000
            out = np.array([
                _add_interval_us(int(x) * us_day, e.interval.months,
                                 e.interval.days,
                                 e.interval.microseconds) // us_day
                for x in v], np.int32)
            return _from_np(out, ok, pa.int32()).cast(pa.date32())
        days = e.interval.days + int(
            e.interval.microseconds / 86_400_000_000)
        return _from_np((v + days).astype(np.int32), ok,
                        pa.int32()).cast(pa.date32())
    if isinstance(e, DEC.UnscaledValue):
        import decimal as _dec

        c = cpu_eval(e.child, table)
        scale = e.child.dtype.scale
        out = [None if v is None else int(v.scaleb(scale))
               for v in c.to_pylist()]
        return pa.array(out, pa.int64())
    if isinstance(e, DEC.MakeDecimal):
        import decimal as _dec

        c = cpu_eval(e.child, table)
        bound = 10 ** e.precision
        out = [None if (v is None or not (-bound < v < bound))
               else _dec.Decimal(int(v)).scaleb(-e.scale)
               for v in c.cast(pa.int64()).to_pylist()]
        return pa.array(out, T.to_arrow_type(e.dtype))
    if isinstance(e, DT.AddMonths):
        import calendar as _cal
        import datetime as _pydt

        c = cpu_eval(e.child, table)
        v, ok = _np_vals(c.cast(pa.int32()), pa.int32())
        epoch = _pydt.date(1970, 1, 1)

        def _shift(x: int) -> int:
            d = epoch + _pydt.timedelta(days=int(x))
            mi = d.year * 12 + (d.month - 1) + e.months
            y, m = divmod(mi, 12)
            day = min(d.day, _cal.monthrange(y, m + 1)[1])
            return (_pydt.date(y, m + 1, day) - epoch).days

        out = np.array([_shift(x) for x in v], np.int32)
        return _from_np(out, ok, pa.int32()).cast(pa.date32())
    if isinstance(e, (DT.DateAdd, DT.DateSub)):
        l = cpu_eval(e.left, table).cast(pa.int32())
        r = cpu_eval(e.right, table).cast(pa.int32())
        sign = -1 if isinstance(e, DT.DateSub) else 1
        out = pc.add(l, pc.multiply(r, sign))
        return out.cast(pa.int32()).view(pa.date32())
    if isinstance(e, DT.DateDiff):
        l = cpu_eval(e.left, table).cast(pa.int32())
        r = cpu_eval(e.right, table).cast(pa.int32())
        return pc.subtract(l, r)
    if isinstance(e, DT._TimeField):
        c = cpu_eval(e.child, table)
        fn = {DT.Hour: pc.hour, DT.Minute: pc.minute,
              DT.Second: pc.second}[type(e)]
        return fn(c).cast(pa.int32())
    if isinstance(e, DT.UnixTimestampFromTs):
        c = cpu_eval(e.child, table).cast(pa.int64())
        v, ok = _np_vals(c, pa.int64())
        return _from_np(v // 1_000_000, ok, pa.int64())

    # cast ---------------------------------------------------------------- #
    if isinstance(e, Cast):
        return _cast_cpu(e, table, n)

    # strings -------------------------------------------------------------- #
    if isinstance(e, (S.StringReplace, S.RegExpReplace, S.StringLPad,
                      S.StringLocate, S.SubstringIndex, S.InitCap,
                      S.ConcatWs)):
        return _string_batch3(e, table, n)
    if isinstance(e, S.Length):
        return pc.utf8_length(cpu_eval(e.child, table)).cast(pa.int32())
    if isinstance(e, S.Upper):  # Lower subclasses Upper
        c = cpu_eval(e.child, table)
        return pc.utf8_lower(c) if isinstance(e, S.Lower) else \
            pc.utf8_upper(c)
    if isinstance(e, S.StartsWith):  # EndsWith/Contains subclass it
        c = cpu_eval(e.left, table)
        needle = e.right.value or ""
        fn = {S.StartsWith: pc.starts_with, S.EndsWith: pc.ends_with,
              S.Contains: pc.match_substring}[type(e)]
        out = fn(c, pattern=needle)
        rnull = e.right.value is None
        if rnull:
            return pa.nulls(n, pa.bool_())
        return out
    if isinstance(e, S.Like):
        c = cpu_eval(e.left, table)
        return pc.match_like(c, pattern=e.pattern)
    if isinstance(e, S.Substring):
        c = cpu_eval(e.child, table)
        if e.pos > 0:
            start = e.pos - 1
            stop = None if e.length is None else start + max(e.length, 0)
            return pc.utf8_slice_codeunits(c, start=start, stop=stop)
        if e.pos == 0:
            stop = None if e.length is None else max(e.length, 0)
            return pc.utf8_slice_codeunits(c, start=0, stop=stop)
        # negative pos: python oracle path.  Spark counts the length
        # window from the UNCLAMPED start (substring('abc',-5,3)=='a')
        out = []
        for v in c.to_pylist():
            if v is None:
                out.append(None)
                continue
            start = len(v) + e.pos
            end = len(v) if e.length is None else start + max(e.length, 0)
            out.append(v[max(start, 0):max(end, 0)])
        return pa.array(out, pa.string())
    if isinstance(e, S.GetJsonObject):
        import json as _json

        c = cpu_eval(e.child, table)
        path = e.path.value
        if any(tok in path for tok in ("*", "..")):
            raise NotImplementedError(
                f"get_json_object path {path!r}: wildcard/recursive "
                "descent is not implemented (simple $.a.b[i] paths "
                "only) — refusing rather than returning wrong NULLs")
        steps = S.GetJsonObject.parse_path(path)
        out = []
        for v in c.to_pylist():
            if v is None or steps is None:
                out.append(None)
                continue
            try:
                cur = _json.loads(v)
                for st in steps:
                    if isinstance(st, int):
                        cur = cur[st] if isinstance(cur, list) \
                            and 0 <= st < len(cur) else None
                    else:
                        cur = cur.get(st) if isinstance(cur, dict) \
                            else None
                    if cur is None:
                        break
                if cur is None:
                    out.append(None)
                elif isinstance(cur, str):
                    out.append(cur)  # Spark strips quotes on scalars
                elif isinstance(cur, bool):
                    out.append("true" if cur else "false")
                else:
                    out.append(_json.dumps(
                        cur, separators=(",", ":"),
                        ensure_ascii=False))
            except (ValueError, TypeError):
                out.append(None)
        return pa.array(out, pa.string())
    if isinstance(e, S.SplitPart):
        import re as _re

        c = cpu_eval(e.child, table)
        d = e.delim.value
        out = [None if v is None else
               (lambda parts: parts[e.index]
                if 0 <= e.index < len(parts) else None)(
                   _java_split(_re.escape(d), v, -1))
               for v in c.to_pylist()]
        return pa.array(out, pa.string())
    if isinstance(e, S.StringSplit):
        c = cpu_eval(e.child, table)
        d = e.delim.value
        if d is None:
            return pa.nulls(n, pa.list_(pa.string()))
        out = [None if v is None else _java_split(d, v, e.limit)
               for v in c.to_pylist()]
        return pa.array(out, pa.list_(pa.string()))
    if isinstance(e, S.StringTrim):
        c = cpu_eval(e.child, table)
        if isinstance(e, S.StringTrimLeft):
            return pc.utf8_ltrim(c, characters=" ")
        if isinstance(e, S.StringTrimRight):
            return pc.utf8_rtrim(c, characters=" ")
        return pc.utf8_trim(c, characters=" ")
    if isinstance(e, S.Concat):
        arrs = [cpu_eval(x, table) for x in e.exprs]
        return pc.binary_join_element_wise(
            *arrs, "", null_handling="emit_null")

    return NotImplemented


def _cpu_ansi_div_check(_l, zero_mask) -> None:
    """Raise the ANSI division-by-zero error when the conf is on."""
    from spark_rapids_tpu.exprs.base import AnsiError, ansi_enabled

    if not ansi_enabled():
        return
    z = zero_mask
    any_zero = bool(pc.any(pc.fill_null(z, False)).as_py()) \
        if isinstance(z, (pa.Array, pa.ChunkedArray)) \
        else bool(np.asarray(z).any())
    if any_zero:
        raise AnsiError(
            "Division by zero. If necessary set "
            "spark.rapids.tpu.sql.ansi.enabled to false to bypass "
            "this error.")


def _cast_cpu(e, table, n):
    from spark_rapids_tpu.exprs.base import AnsiError, ansi_enabled
    from spark_rapids_tpu.exprs.cast import Cast  # noqa: F401

    src = e.child.dtype
    dst = e.to
    c = cpu_eval(e.child, table)
    if src == dst:
        return c
    at = T.to_arrow_type(dst)
    ansi = ansi_enabled()
    if isinstance(src, T.StringType):
        out = _cast_cpu_from_string(c, dst, at)
        if ansi and out.null_count > c.null_count:
            raise AnsiError(
                f"invalid input syntax for type {dst.name} (ANSI "
                "cast). If necessary set "
                "spark.rapids.tpu.sql.ansi.enabled to false to "
                "bypass this error.")
        return out
    if ansi and isinstance(dst, T.IntegralType):
        info = np.iinfo(T.to_numpy_dtype(dst))
        bad = None
        if isinstance(src, (T.FloatType, T.DoubleType)):
            v, ok = _np_vals(c.cast(pa.float64()), pa.float64())
            t = np.trunc(v)
            bad = ok & (np.isnan(v) | (t > float(info.max))
                        | (t < float(info.min)))
        elif isinstance(src, T.IntegralType):
            # integer-space compare: a float64 round-trip would lose
            # precision past 2^53 (and pyarrow's safe cast would raise
            # its own non-ANSI error first)
            v, ok = _np_vals(c, T.to_arrow_type(src))
            bad = ok & ((v > info.max) | (v < info.min))
        if bad is not None and bad.any():
            raise AnsiError(
                f"value out of range for {dst.name} (ANSI cast "
                "overflow). If necessary set "
                "spark.rapids.tpu.sql.ansi.enabled to false to "
                "bypass this error.")
    if isinstance(dst, T.StringType):
        return pc.cast(c, pa.string())
    if isinstance(dst, T.BooleanType):
        return pc.not_equal(c, pa.scalar(0).cast(c.type))
    if isinstance(src, T.BooleanType):
        return pc.cast(c, at)
    if isinstance(src, T.DateType) and isinstance(dst, T.TimestampType):
        v, ok = _np_vals(c.cast(pa.int32()), pa.int32())
        return _from_np(v.astype(np.int64) * 86_400_000_000, ok,
                        pa.int64()).cast(at)
    if isinstance(src, T.TimestampType) and isinstance(dst, T.DateType):
        v, ok = _np_vals(c.cast(pa.int64()), pa.int64())
        return _from_np((v // 86_400_000_000).astype(np.int32), ok,
                        pa.int32()).cast(at)
    if isinstance(src, T.TimestampType) and isinstance(dst, T.LongType):
        v, ok = _np_vals(c.cast(pa.int64()), pa.int64())
        return _from_np(v // 1_000_000, ok, pa.int64())
    if isinstance(src, T.LongType) and isinstance(dst, T.TimestampType):
        v, ok = _np_vals(c, pa.int64())
        return _from_np(v * 1_000_000, ok, pa.int64()).cast(at)
    npdt = T.to_numpy_dtype(dst)
    if isinstance(src, (T.FloatType, T.DoubleType)) and \
            isinstance(dst, T.IntegralType):
        v, ok = _np_vals(c.cast(pa.float64()), pa.float64())
        info = np.iinfo(npdt)
        # float64 cannot represent int64 MAX exactly: saturate by
        # threshold compare, never by clip-then-cast (which overflows)
        hi_f = float(info.max) + 1.0  # exact power of two
        lo_f = float(info.min)
        t = np.trunc(np.where(np.isnan(v), 0.0, v))
        interior = (t > lo_f) & (t < hi_f)
        with np.errstate(invalid="ignore"):
            res = np.where(interior, t, 0.0).astype(npdt)
        res = np.where(t >= hi_f, info.max, res)
        res = np.where(t <= lo_f, info.min, res)
        return _from_np(res.astype(npdt), ok, at)
    v, ok = _np_vals(c, c.type)
    with np.errstate(over="ignore"):
        return _from_np(v.astype(npdt), ok, at)


def _np_java_mod(l, r):
    q = np.where((l < 0) != (r < 0), -(np.abs(l) // np.abs(r)), l // r)
    return l - q * r


def _least_greatest(e, table, n):
    is_least = isinstance(e, A.Least)
    at = _widen_type(e)
    npdt = at.to_pandas_dtype()
    acc_v = acc_ok = None
    for x in e.exprs:
        a = cpu_eval(x, table).cast(at)
        v, ok = _np_vals(a, at)
        if acc_v is None:
            acc_v, acc_ok = v.copy(), ok.copy()
            continue
        if np.issubdtype(npdt, np.floating):
            # NaN counts as the greatest value (Spark ordering)
            a_nan = np.isnan(acc_v)
            b_nan = np.isnan(v)
            if is_least:
                cmp = np.where(a_nan, True, np.where(b_nan, False,
                                                     v < acc_v))
            else:
                cmp = np.where(b_nan, True, np.where(a_nan, False,
                                                     v > acc_v))
        else:
            cmp = (v < acc_v) if is_least else (v > acc_v)
        take = ok & (~acc_ok | cmp)
        acc_v = np.where(take, v, acc_v)
        acc_ok = acc_ok | ok
    return _from_np(acc_v.astype(npdt), acc_ok, at)


def _murmur3_cpu(e: Murmur3Hash, table, n):
    """Numpy Spark murmur3 (independent of the XLA implementation; the
    scalar-python oracle in tests/test_hashing.py checks both)."""
    h = np.full(n, e.seed, np.uint32)
    with np.errstate(over="ignore"):
        for x in e.exprs:
            a = cpu_eval(x, table)
            h = _np_hash_col(a, h)
    return pa.array(h.astype(np.int32))


def _np_rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _np_mix_k1(k1):
    k1 = k1 * np.uint32(0xCC9E2D51)
    k1 = _np_rotl(k1, 15)
    return k1 * np.uint32(0x1B873593)


def _np_mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _np_rotl(h1, 13)
    return h1 * np.uint32(5) + np.uint32(0xE6546B64)


def _np_fmix(h1, length):
    h1 = h1 ^ np.uint32(length) if np.isscalar(length) else \
        h1 ^ length.astype(np.uint32)
    h1 ^= h1 >> np.uint32(16)
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 ^= h1 >> np.uint32(13)
    h1 = h1 * np.uint32(0xC2B2AE35)
    h1 ^= h1 >> np.uint32(16)
    return h1


def _np_hash_col(a: pa.Array, seed: np.ndarray) -> np.ndarray:
    t = a.type
    valid = np.asarray(a.is_valid())
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        out = seed.copy()
        for i, v in enumerate(a.to_pylist()):
            if v is None:
                continue
            bs = v.encode("utf-8")
            h1 = np.uint32(seed[i])
            aligned = len(bs) - len(bs) % 4
            for j in range(0, aligned, 4):
                word = np.uint32(int.from_bytes(bs[j:j + 4], "little"))
                h1 = _np_mix_h1(h1, _np_mix_k1(word))
            for j in range(aligned, len(bs)):
                b = bs[j] - 256 if bs[j] >= 128 else bs[j]
                h1 = _np_mix_h1(h1, _np_mix_k1(np.uint32(b)))
            out[i] = _np_fmix(h1, len(bs))
        return out
    if pa.types.is_floating(t) and t.bit_width == 64:
        v, _ = _np_vals(a, pa.float64())
        v = np.where(v == 0.0, 0.0, v)
        bits = v.view(np.int64)
        bits = np.where(np.isnan(v), np.int64(0x7FF8000000000000), bits)
        h = _np_hash_i64(bits, seed)
    elif pa.types.is_floating(t):
        v, _ = _np_vals(a, pa.float32())
        v = np.where(v == 0.0, np.float32(0.0), v)
        bits = v.view(np.int32)
        bits = np.where(np.isnan(v), np.int32(0x7FC00000), bits)
        h = _np_fmix(_np_mix_h1(seed, _np_mix_k1(bits.astype(np.uint32))), 4)
    elif pa.types.is_int64(t) or pa.types.is_timestamp(t):
        v, _ = _np_vals(a.cast(pa.int64()) if not pa.types.is_int64(t)
                        else a, pa.int64())
        h = _np_hash_i64(v, seed)
    else:
        v, _ = _np_vals(a.cast(pa.int32()), pa.int32())
        h = _np_fmix(_np_mix_h1(seed, _np_mix_k1(v.astype(np.uint32))), 4)
    return np.where(valid, h, seed)


def _np_hash_i64(v: np.ndarray, seed: np.ndarray) -> np.ndarray:
    low = (v & np.int64(0xFFFFFFFF)).astype(np.uint32)
    high = ((v >> np.int64(32)) & np.int64(0xFFFFFFFF)).astype(np.uint32)
    h1 = _np_mix_h1(seed, _np_mix_k1(low))
    h1 = _np_mix_h1(h1, _np_mix_k1(high))
    return _np_fmix(h1, 8)


# ---------------------------------------------------------------------- #
# Plan execution
# ---------------------------------------------------------------------- #

_AGG_MAP = {
    "sum": "sum", "count": "count", "count_star": "count_all",
    "min": "min", "max": "max", "first": "first", "last": "last",
}


def _read_scan_file(plan: L.LogicalPlan, path: str) -> pa.Table:
    """One file's (projected) columns as a host table; preserves row
    counts even for an empty projection."""
    if isinstance(plan, L.ParquetRelation):
        import pyarrow.parquet as pq

        return pq.read_table(path, columns=plan.columns)
    if isinstance(plan, L.OrcRelation):
        import pyarrow.orc as paorc

        f = paorc.ORCFile(path)
        if plan.columns == []:
            # ORC read(columns=[]) loses num_rows (unlike parquet):
            # read one column and drop it to keep the row count
            names = [fl.name for fl in f.schema]
            t = f.read(columns=names[:1]) if names else f.read()
            return t.select([])
        return f.read(columns=plan.columns)
    import pyarrow.csv as pacsv

    return pacsv.read_csv(path).cast(schema_to_arrow(plan.file_schema))


def _scan_cpu(plan: L.LogicalPlan) -> pa.Table:
    """File-relation leaf on the CPU engine, with trailing Hive
    partition-value columns (same layout as the TPU scan's appender)."""
    aschema = schema_to_arrow(plan.schema)
    tables = []
    for i, p in enumerate(plan.paths):
        t = _read_scan_file(plan, p)
        for f in plan.partition_fields:
            v = plan.partition_values[i].get(f.name) \
                if i < len(plan.partition_values) else None
            if v is not None and isinstance(f.dtype, T.LongType):
                v = int(v)
            t = t.append_column(
                pa.field(f.name, aschema.field(f.name).type, True),
                pa.array([v] * t.num_rows,
                         aschema.field(f.name).type))
        tables.append(t)
    return pa.concat_tables(tables).cast(aschema)


def execute_cpu(plan: L.LogicalPlan) -> pa.Table:
    if isinstance(plan, L.InMemoryRelation):
        return plan.table
    if isinstance(plan, (L.ParquetRelation, L.OrcRelation,
                         L.CsvRelation)):
        return _scan_cpu(plan)
    if isinstance(plan, L.RangeRel):
        total = max(0, -(-(plan.end - plan.start) // plan.step))
        ids = plan.start + np.arange(total, dtype=np.int64) * plan.step
        return pa.table({"id": ids})
    if isinstance(plan, L.Project):
        child = execute_cpu(plan.children[0])
        arrays = [cpu_eval(e, child) for e in plan.exprs]
        return pa.Table.from_arrays(arrays,
                                    schema=schema_to_arrow(plan.schema))
    if isinstance(plan, L.Cached):
        # CPU engine caches the materialized table in the same slot
        with plan.slot.lock:
            if plan.slot.cpu_table is not None:
                return plan.slot.cpu_table
        t = execute_cpu(plan.children[0])
        with plan.slot.lock:
            if plan.slot.cpu_table is None:
                plan.slot.cpu_table = t
        return t
    if isinstance(plan, L.Filter):
        child = execute_cpu(plan.children[0])
        mask = pc.fill_null(cpu_eval(plan.condition, child), False)
        return child.filter(mask)
    if isinstance(plan, L.MapInArrow):
        child = execute_cpu(plan.children[0])
        if getattr(plan, "pandas", False):
            from spark_rapids_tpu.execs.python_exec import (
                _map_in_pandas_wrapper,
            )

            aschema = schema_to_arrow(plan.schema)
            return _map_in_pandas_wrapper(
                child, fn=plan.fn, aschema=aschema).cast(aschema)
        out = plan.fn(child)
        if isinstance(out, pa.RecordBatch):
            out = pa.Table.from_batches([out])
        return out.cast(schema_to_arrow(plan.schema))
    if isinstance(plan, L.CoGroupedPandas):
        import functools

        from spark_rapids_tpu.execs import python_exec as PE

        lt = execute_cpu(plan.children[0])
        rt = execute_cpu(plan.children[1])
        aschema = schema_to_arrow(plan.schema)
        side = pa.array(np.concatenate(
            [np.zeros(lt.num_rows, np.int8),
             np.ones(rt.num_rows, np.int8)]))
        arrays = [side]
        names = ["__side"]
        for i, f in enumerate(lt.schema):
            arrays.append(pa.concat_arrays(
                [lt.column(i).combine_chunks(),
                 pa.nulls(rt.num_rows, f.type)]))
            names.append(f"__l_{f.name}")
        for i, f in enumerate(rt.schema):
            arrays.append(pa.concat_arrays(
                [pa.nulls(lt.num_rows, f.type),
                 rt.column(i).combine_chunks()]))
            names.append(f"__r_{f.name}")
        combined = pa.Table.from_arrays(arrays, names)
        fn = functools.partial(
            PE._cogroup_wrapper, fn=plan.fn,
            left_keys=plan.left_key_names,
            right_keys=plan.right_key_names,
            aschema=aschema, n_left_cols=lt.num_columns,
            left_names=lt.column_names, right_names=rt.column_names)
        return fn(combined).cast(aschema)
    if isinstance(plan, L.GroupedPandas):
        import functools

        from spark_rapids_tpu.execs import python_exec as PE

        child = execute_cpu(plan.children[0])
        aschema = schema_to_arrow(plan.schema)
        if plan.kind == "flatmap":
            fn = functools.partial(PE._grouped_apply_wrapper,
                                   fn=plan.payload,
                                   key_names=plan.key_names,
                                   aschema=aschema)
        elif plan.kind == "agg":
            fn = functools.partial(PE._grouped_agg_wrapper,
                                   aggs=plan.payload,
                                   key_names=plan.key_names,
                                   aschema=aschema)
        else:
            fn = functools.partial(PE._window_in_pandas_wrapper,
                                   fns=plan.payload,
                                   key_names=plan.key_names,
                                   aschema=aschema)
        return fn(child).cast(aschema)
    if isinstance(plan, L.Generate):
        child = execute_cpu(plan.children[0])
        gen = plan.generator
        aschema = schema_to_arrow(plan.schema)
        arr = cpu_eval(gen.child, child)
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        lens = pc.fill_null(pc.list_value_length(arr), 0).to_numpy(
            zero_copy_only=False).astype(np.int64)
        n = len(arr)
        if gen.outer:
            rep = np.maximum(lens, 1)
        else:
            rep = lens
        parent = np.repeat(np.arange(n), rep)
        pos_list = []
        elems = []
        py = arr.to_pylist()
        for i in range(n):
            vals = py[i]
            if vals:
                for j, v in enumerate(vals):
                    pos_list.append(j)
                    elems.append(v)
            elif gen.outer:
                pos_list.append(None)
                elems.append(None)
        arrays = [child.column(cname).take(pa.array(parent))
                  for cname in child.schema.names]
        if gen.pos:
            arrays.append(pa.array(pos_list, pa.int32()))
        arrays.append(pa.array(
            elems, aschema.field(plan.out_name).type))
        return pa.Table.from_arrays(arrays, schema=aschema)
    if isinstance(plan, L.Expand):
        child = execute_cpu(plan.children[0])
        aschema = schema_to_arrow(plan.schema)
        parts = []
        for proj in plan.projections:
            arrays = []
            for e, f in zip(proj, aschema):
                a = cpu_eval(e, child)
                if a.type != f.type:
                    a = a.cast(f.type)
                arrays.append(a)
            parts.append(pa.Table.from_arrays(arrays, schema=aschema))
        return pa.concat_tables(parts)
    if isinstance(plan, L.Aggregate):
        return _aggregate_cpu(plan)
    if isinstance(plan, L.Sort):
        return _sort_cpu(plan)
    if isinstance(plan, L.Limit):
        return execute_cpu(plan.children[0]).slice(0, plan.n)
    if isinstance(plan, L.Union):
        tables = [execute_cpu(c) for c in plan.children]
        schema = tables[0].schema
        tables = [t.rename_columns(schema.names) for t in tables]
        return pa.concat_tables(tables)
    if isinstance(plan, L.Join):
        return _join_cpu(plan)
    if isinstance(plan, L.Window):
        return _window_cpu(plan)
    raise NotImplementedError(f"CPU engine: {plan.name}")


class _RevCmp:
    """Reverses comparison order for descending sort keys (works for any
    comparable payload, unlike numeric negation)."""

    __slots__ = ("v",)

    def __init__(self, v):
        self.v = v

    def __lt__(self, o):
        return o.v < self.v

    def __eq__(self, o):
        return self.v == o.v


def _canon_key(v):
    """Canonicalize a value for grouping/peers: NULL==NULL, NaN==NaN."""
    if isinstance(v, float) and np.isnan(v):
        return ("nan",)
    return v


def _sort_entry(v, descending, nulls_last):
    null_flag = (1 if nulls_last else 0) if v is None else \
        (0 if nulls_last else 1)
    if v is None:
        return (null_flag, 0)
    if isinstance(v, float) and np.isnan(v):
        # Spark sorts NaN greatest among values
        v = _NaNGreatest()
    return (null_flag, _RevCmp(v) if descending else v)


class _NaNGreatest:
    __slots__ = ()

    def __lt__(self, o):
        return False  # nothing is greater than NaN

    def __gt__(self, o):
        return not isinstance(o, _NaNGreatest)

    def __eq__(self, o):
        return isinstance(o, _NaNGreatest)


def _window_cpu(plan: L.Window) -> pa.Table:
    """Reference implementation with explicit per-group python loops —
    deliberately simple and independent of the TPU kernels (the oracle
    role of 'CPU Spark' in the differential harness)."""
    from spark_rapids_tpu.exprs import window as WX

    child = execute_cpu(plan.children[0])
    n = child.num_rows
    spec = plan.window_exprs[0][0].spec
    pvals = [cpu_eval(e, child).to_pylist() for e in spec.partition_by]
    ovals = [cpu_eval(k.expr, child).to_pylist() for k in spec.order_by]

    def sort_key(i):
        parts = [_sort_entry(c[i], False, False) for c in pvals]
        parts += [_sort_entry(c[i], k.descending, k.nulls_last)
                  for c, k in zip(ovals, spec.order_by)]
        return tuple(parts)

    order = sorted(range(n), key=sort_key)
    pkey = [tuple(_canon_key(c[i]) for c in pvals) for i in range(n)]
    okey = [tuple(_canon_key(c[i]) for c in ovals) for i in range(n)]

    # group boundaries over the sorted order
    groups: list[list[int]] = []
    for pos, i in enumerate(order):
        if pos == 0 or pkey[i] != pkey[order[pos - 1]]:
            groups.append([])
        groups[-1].append(i)

    out_cols: dict[str, list] = {name: [None] * n
                                 for _we, name in plan.window_exprs}
    for we, name in plan.window_exprs:
        fn = we.fn
        vals = None
        dvals = None
        if fn.inputs():
            vals = cpu_eval(fn.inputs()[0], child).to_pylist()
        if isinstance(fn, WX.Lead) and fn.default is not None:
            dvals = cpu_eval(fn.default, child).to_pylist()
        col = out_cols[name]
        for g in groups:
            m = len(g)
            gok = [okey[i] for i in g]
            for pos, i in enumerate(g):
                if isinstance(fn, WX.RowNumber):
                    col[i] = pos + 1
                elif isinstance(fn, WX.Rank):
                    col[i] = gok.index(gok[pos]) + 1
                elif isinstance(fn, WX.DenseRank):
                    seen, dr = None, 0
                    for q in range(pos + 1):
                        if gok[q] != seen:
                            dr += 1
                            seen = gok[q]
                    col[i] = dr
                elif isinstance(fn, WX.Lead):  # Lag subclasses Lead
                    j = pos + fn.shift
                    if 0 <= j < m:
                        col[i] = vals[g[j]]
                    elif dvals is not None:
                        col[i] = dvals[i]
                elif isinstance(fn, WX.WindowAgg):
                    frame = we.spec.resolved_frame()
                    if frame.mode == "rows":
                        lo = 0 if frame.start is None else max(
                            0, pos + frame.start)
                        hi = m - 1 if frame.end is None else min(
                            m - 1, pos + frame.end)
                        if hi < lo or hi < 0:  # empty frame (e.g. end
                            lo, hi = 0, -1  # still before the partition)
                    elif frame.start is None and frame.end in (0, None):
                        lo = 0
                        if frame.end is None:
                            hi = m - 1
                        else:  # current peer group's last row
                            hi = pos
                            while hi + 1 < m and gok[hi + 1] == gok[pos]:
                                hi += 1
                    else:
                        # bounded value-based RANGE frame: one numeric
                        # order key; descending measures the offset the
                        # other way; a null-key row's frame is its null
                        # peer block (Spark RangeFrame semantics)
                        sval = ovals[0]
                        desc = spec.order_by[0].descending
                        v = sval[g[pos]]

                        def _ordnum(x):
                            import datetime

                            if isinstance(x, datetime.datetime):
                                if x.tzinfo is None:
                                    # Arrow hands back naive UTC; a
                                    # bare .timestamp() would apply
                                    # the machine's local timezone/DST
                                    x = x.replace(
                                        tzinfo=datetime.timezone.utc)
                                return int(x.timestamp() * 1e6)
                            if isinstance(x, datetime.date):
                                return x.toordinal()
                            return x

                        def in_frame(q):
                            import math as _m

                            u = sval[g[q]]
                            if v is None or u is None:
                                return v is None and u is None
                            v_nan = isinstance(v, float) and _m.isnan(v)
                            u_nan = isinstance(u, float) and _m.isnan(u)
                            if v_nan or u_nan:
                                # Spark total order: all NaN are equal
                                # and greatest — a NaN row's frame is
                                # the NaN peer block, nothing else
                                return v_nan and u_nan
                            un, vn = _ordnum(u), _ordnum(v)
                            d = (un - vn) if not desc else (vn - un)
                            if frame.start is not None and d < frame.start:
                                return False
                            if frame.end is not None and d > frame.end:
                                return False
                            return True

                        members = [q for q in range(m) if in_frame(q)]
                        if members:
                            lo, hi = members[0], members[-1]
                        else:
                            lo, hi = 0, -1
                    col[i] = _frame_agg(fn.agg, vals, g, lo, hi)
        # order within ties of the TPU sort may differ; that is fine — the
        # differential harness compares row sets, and ranking fns only
        # depend on key values
    arrays = [child.column(j) for j in range(child.num_columns)]
    names = list(child.schema.names)
    aschema = schema_to_arrow(plan.schema)
    for we, name in plan.window_exprs:
        arrays.append(pa.array(out_cols[name],
                               type=aschema.field(name).type))
        names.append(name)
    return pa.Table.from_arrays(arrays, names=names).cast(aschema)


def _frame_agg(agg, vals, g, lo, hi):
    from spark_rapids_tpu.exprs import aggregates as AGG

    window_rows = g[lo:hi + 1] if hi >= lo >= 0 else []
    if isinstance(agg, AGG.CountStar):
        return len(window_rows)
    xs = [vals[i] for i in window_rows if vals[i] is not None]
    if isinstance(agg, AGG.Count):
        return len(xs)
    if not xs:
        return None
    import math as _math

    def _nan(x):
        return isinstance(x, float) and _math.isnan(x)

    if isinstance(agg, AGG.Sum):
        return sum(xs)
    if isinstance(agg, AGG.Min):
        # Spark float total order: NaN greatest — min ignores NaN
        # unless the whole frame is NaN
        non_nan = [x for x in xs if not _nan(x)]
        return min(non_nan) if non_nan else float("nan")
    if isinstance(agg, AGG.Max):
        if any(_nan(x) for x in xs):
            return float("nan")
        return max(xs)
    if isinstance(agg, AGG.Average):
        return sum(float(x) for x in xs) / len(xs)
    raise NotImplementedError(type(agg).__name__)


def _aggregate_cpu(plan: L.Aggregate) -> pa.Table:
    child = execute_cpu(plan.children[0])
    n_keys = len(plan.groups)
    # project keys + agg inputs with partial-dtype casts applied
    cols, names, agg_specs = [], [], []
    for i, g in enumerate(plan.groups):
        arr = cpu_eval(g, child)
        if pa.types.is_floating(arr.type):
            # Spark's NormalizeFloatingNumbers under grouping keys:
            # -0.0 groups (and reports) as 0.0; NaNs as one canonical
            # NaN (pyarrow already groups NaNs together)
            zero = pa.scalar(0.0, arr.type)
            arr = pc.if_else(pc.equal(arr, zero), zero, arr)
        cols.append(arr)
        names.append(plan.schema.fields[i].name)
    seen = 0
    for na in plan.aggs:
        fn = na.fn
        ins = fn.inputs()
        if not ins:
            agg_specs.append(([], "count_all", na.out_name, fn))
            continue
        in_name = f"__a{seen}"
        seen += 1
        arr = cpu_eval(ins[0], child)
        op = fn.update_ops()[0]
        if op == "sum":
            arr = arr.cast(T.to_arrow_type(fn.partial_dtypes()[0]))
        if fn.name == "average":
            arr = arr.cast(pa.float64())
        cols.append(arr)
        names.append(in_name)
        agg_specs.append(([in_name], fn.name, na.out_name, fn))

    if cols:
        proj = pa.Table.from_arrays(cols, names=names)
    else:
        # COUNT(*)-only grand aggregate: a zero-column table would
        # report zero rows; count against the child's row count (the
        # TPU exec pads with a constant column for the same reason)
        proj = child
    if n_keys == 0:
        out_cols, out_names = [], []
        for in_names, fname, out_name, fn in agg_specs:
            out_cols.append(_grand_agg(proj, in_names, fname, fn))
            out_names.append(out_name)
        return pa.Table.from_arrays(
            [pa.array([v.as_py()], type=v.type) for v in out_cols],
            names=out_names).cast(schema_to_arrow(plan.schema))

    aggs = []
    nan_fix: dict[int, str] = {}  # spec index -> '__aK__nan' source
    for si, (in_names, fname, out_name, fn) in enumerate(agg_specs):
        if fname == "count_all":
            aggs.append(([], "count_all"))
        elif fname == "count":
            aggs.append((in_names[0], "count"))
        elif fname == "average":
            aggs.append((in_names[0], "mean"))
        elif fname in ("first", "last"):
            # Spark defaults ignoreNulls=false; pyarrow defaults skip
            aggs.append((in_names[0], fname, pc.ScalarAggregateOptions(
                skip_nulls=fn.ignore_nulls, min_count=0)))
        elif fname in ("collectlist", "collectset"):
            aggs.append((in_names[0], "list"))
            nan_fix[si] = ("collect", in_names[0], fname)
        elif fname in ("min", "max") and pa.types.is_floating(
                proj.column(in_names[0]).type):
            # Spark float total order: NaN greatest.  Aggregate the
            # NaN-cleaned values plus a per-group any-NaN flag, then
            # recompose (max: NaN if any NaN; min: NaN only when every
            # non-null value is NaN).
            src = in_names[0]
            x = proj.column(src)
            xnan = pc.fill_null(pc.is_nan(x), False)
            clean = pc.if_else(xnan, pa.scalar(None, x.type), x)
            proj = proj.append_column(f"{src}__clean", clean)
            proj = proj.append_column(f"{src}__nan", xnan)
            aggs.append((f"{src}__clean", fname))
            aggs.append((f"{src}__nan", "any"))
            nan_fix[si] = src
        else:
            aggs.append((in_names[0], fname))
    gb = proj.group_by(names[:n_keys], use_threads=False)
    res = gb.aggregate(aggs)
    # rename to output schema order: keys first in our schema, aggregates
    # come back named '<col>_<agg>'
    out_arrays = []
    aschema = schema_to_arrow(plan.schema)
    for i in range(n_keys):
        out_arrays.append(res.column(names[i]))
    ai = 0
    for si, (in_names, fname, out_name, fn) in enumerate(agg_specs):
        spec = aggs[ai]
        src, op = (spec[0], spec[1]) if spec[0] else ("", spec[1])
        if isinstance(nan_fix.get(si), tuple):
            _tag, base, fname2 = nan_fix[si]
            lists = res.column(f"{base}_list").to_pylist()
            out = []
            for lv in lists:
                xs = [x for x in (lv or []) if x is not None]
                if fname2 == "collectset":
                    xs = _dedup_total_order(xs)
                out.append(xs)
            out_arrays.append(pa.array(
                out, type=aschema.field(n_keys + si).type))
            ai += 1
            continue
        if si in nan_fix:
            base = nan_fix[si]
            vals = res.column(f"{base}__clean_{fname}")
            anynan = res.column(f"{base}__nan_any")
            nan_scalar = pa.scalar(float("nan"), vals.type)
            if fname == "max":
                out = pc.if_else(pc.fill_null(anynan, False),
                                 nan_scalar, vals)
            else:  # min: NaN only when no non-NaN value existed
                out = pc.if_else(
                    pc.and_(pc.is_null(vals),
                            pc.fill_null(anynan, False)),
                    nan_scalar, vals)
            out_arrays.append(out)
            ai += 2
            continue
        col_name = f"{src}_{op}" if src else f"{op}"
        if col_name not in res.column_names:
            col_name = f"{'_'.join(in_names)}_{op}" if in_names else op
        out_arrays.append(res.column(col_name))
        ai += 1
    return pa.Table.from_arrays(out_arrays,
                                names=aschema.names).cast(aschema)


def _grand_agg(proj: pa.Table, in_names, fname, fn=None) -> pa.Scalar:
    if fname == "count_all":
        return pa.scalar(proj.num_rows, pa.int64())
    col = proj.column(in_names[0])
    if fname == "count":
        return pa.scalar(len(col) - col.null_count, pa.int64())
    if fname == "average":
        return pc.mean(col)
    if fname == "sum":
        return pc.sum(col)
    if fname in ("min", "max") and pa.types.is_floating(col.type):
        # Spark float total order: NaN greatest (see _aggregate_cpu)
        xnan = pc.fill_null(pc.is_nan(col), False)
        any_nan = pc.any(xnan).as_py()
        clean = pc.if_else(xnan, pa.scalar(None, col.type), col)
        v = pc.min(clean) if fname == "min" else pc.max(clean)
        if fname == "max" and any_nan:
            return pa.scalar(float("nan"), col.type)
        if fname == "min" and v.as_py() is None and any_nan:
            return pa.scalar(float("nan"), col.type)
        return v
    if fname == "min":
        return pc.min(col)
    if fname == "max":
        return pc.max(col)
    if fname in ("first", "last"):
        src = col if (fn is None or fn.ignore_nulls) else None
        vals = col.drop_null() if src is not None else col.combine_chunks()
        if len(vals) == 0:
            return pa.scalar(None, col.type)
        return vals[0] if fname == "first" else vals[-1]
    if fname in ("collectlist", "collectset"):
        xs = [x for x in col.to_pylist() if x is not None]
        if fname == "collectset":
            xs = _dedup_total_order(xs)
        return pa.scalar(xs, pa.list_(col.type))
    raise NotImplementedError(fname)


def _dedup_total_order(xs: list) -> list:
    """Keep-first dedup under Spark's total-order equality (NaN == NaN)
    — ONE implementation for grouped and grand collect_set."""
    import math as _math

    kept: list = []
    for x in xs:
        dup = any(
            (isinstance(x, float) and isinstance(y, float)
             and _math.isnan(x) and _math.isnan(y)) or x == y
            for y in kept)
        if not dup:
            kept.append(x)
    return kept


def _spark_sortable(arr: pa.Array) -> pa.Array:
    """pyarrow sorts NaN alongside nulls; Spark sorts NaN as the greatest
    value.  Encode floats as IEEE total-order int64 keys (nulls kept)."""
    if not pa.types.is_floating(arr.type):
        return arr
    v, valid = _np_vals(arr, pa.float64())
    bits = v.view(np.int64)
    bits = np.where(np.isnan(v), np.int64(0x7FF8000000000000), bits)
    keys = np.where(bits < 0, bits ^ np.int64(2**63 - 1), bits)
    return _from_np(keys, valid, pa.int64())


_INT_RE = None


def _cast_cpu_from_string(c: pa.Array, dst, at) -> pa.Array:
    """Spark non-ANSI string casts: trim whitespace, NULL on malformed.
    Strict ASCII-digit integer syntax (Python int() would accept '1_2'
    and Unicode digits that Spark rejects)."""
    global _INT_RE
    import re

    if _INT_RE is None:
        # Spark accepts a fractional tail and truncates toward zero
        # (cast('3.5' as int) = 3); exponents stay rejected
        _INT_RE = re.compile(r"^([+-]?)([0-9]*)(?:\.([0-9]*))?$")
    out = []
    if isinstance(dst, T.IntegralType):
        lo = np.iinfo(T.to_numpy_dtype(dst)).min
        hi = np.iinfo(T.to_numpy_dtype(dst)).max
        for v in c.to_pylist():
            if v is None:
                out.append(None)
                continue
            s = v.strip()
            m = _INT_RE.match(s)
            if not m or not (m.group(2) or m.group(3)):
                out.append(None)
                continue
            iv = int((m.group(1) or "") + (m.group(2) or "0"))
            out.append(iv if lo <= iv <= hi else None)
        return pa.array(out, at)
    if isinstance(dst, (T.FloatType, T.DoubleType)):
        for v in c.to_pylist():
            if v is None:
                out.append(None)
                continue
            s = v.strip()
            try:
                out.append(float(s))
            except ValueError:
                out.append(None)
        return pa.array(out, at)
    if isinstance(dst, T.BooleanType):
        true_set = {"true", "t", "yes", "y", "1"}
        false_set = {"false", "f", "no", "n", "0"}
        for v in c.to_pylist():
            if v is None:
                out.append(None)
                continue
            s = v.strip().lower()
            out.append(True if s in true_set
                       else False if s in false_set else None)
        return pa.array(out, at)
    if isinstance(dst, T.DateType):
        import datetime as _dt

        for v in c.to_pylist():
            if v is None:
                out.append(None)
                continue
            s = v.strip()
            try:
                out.append(_dt.date.fromisoformat(s))
            except ValueError:
                out.append(None)
        return pa.array(out, at)
    raise NotImplementedError(f"CPU cast string -> {dst}")


_SORT_KEY_PLACEMENT: list = []  # lazy probe: [] unknown, [bool] known


def _sort_indices(data, sort_keys, null_placement: str):
    """pyarrow >= 25 deprecates SortOptions-level ``null_placement``
    (FutureWarning on every call) in favor of per-sort-key placement
    passed as (name, order, null_placement) triples; older pyarrow
    rejects the triple form.  Probe once, then stick to whichever form
    this runtime supports."""
    if not _SORT_KEY_PLACEMENT:
        try:
            probe = pa.table({"__p": [1]})
            pc.sort_indices(
                probe,
                sort_keys=[("__p", "ascending", null_placement)])
            _SORT_KEY_PLACEMENT.append(True)
        except Exception:
            _SORT_KEY_PLACEMENT.append(False)
    if _SORT_KEY_PLACEMENT[0]:
        return pc.sort_indices(
            data, sort_keys=[(n, o, null_placement)
                             for n, o in sort_keys])
    return pc.sort_indices(data, sort_keys=sort_keys,
                           null_placement=null_placement)


def _sort_cpu(plan: L.Sort) -> pa.Table:
    child = execute_cpu(plan.children[0])
    # project sort keys as temp columns
    tmp = child
    keys = []
    for i, k in enumerate(plan.keys):
        name = f"__s{i}"
        tmp = tmp.append_column(
            name, _spark_sortable(cpu_eval(k.expr, child)))
        keys.append((name, "descending" if k.descending else "ascending"))
    placements = {k.nulls_last for k in plan.keys}
    if len(placements) == 1:
        idx = _sort_indices(
            tmp, keys,
            "at_end" if placements.pop() else "at_start")
    else:
        # mixed per-key null placement: stable multi-pass sort from the
        # least significant key (python fallback, oracle-grade only)
        idx_np = np.arange(tmp.num_rows)
        for (name, order), k in reversed(list(zip(keys, plan.keys))):
            col = tmp.column(name).combine_chunks().take(
                pa.array(idx_np, pa.int64()))
            sidx = _sort_indices(
                col, [("", order)],
                "at_end" if k.nulls_last else "at_start")
            idx_np = idx_np[np.asarray(sidx)]
        idx = pa.array(idx_np, pa.int64())
    return child.take(idx)


def _null_safe_codes(l, r) -> tuple:
    """Both sides' values of a `<=>` key as int32 codes of one
    dictionary in which NULL is an entry of its own, so that Arrow's
    join, whose NULL keys match nothing, matches NULL with NULL."""
    l, r = (a.combine_chunks() if isinstance(a, pa.ChunkedArray) else a
            for a in (l, r))
    if r.type != l.type:
        r = r.cast(l.type)
    codes = pc.dictionary_encode(pa.concat_arrays([l, r]),
                                 null_encoding="encode").indices
    return codes[:len(l)], codes[len(l):]


def _join_cpu(plan: L.Join) -> pa.Table:
    left = execute_cpu(plan.children[0])
    right = execute_cpu(plan.children[1])
    jt = plan.join_type
    if jt == "cross" or (jt == "inner" and not plan.left_keys):
        # cross product / keyless conditional inner join (nested loop)
        left = left.append_column("__ck", pa.array([1] * left.num_rows))
        right = right.append_column("__ck", pa.array([1] * right.num_rows))
        lkeys, rkeys = ["__ck"], ["__ck"]
        jt = "inner"
    else:
        tmpl, tmpr = left, right
        lkeys, rkeys = [], []
        for i, (lk, rk) in enumerate(zip(plan.left_keys, plan.right_keys)):
            ln, rn = f"__lk{i}", f"__rk{i}"
            lv, rv = cpu_eval(lk, left), cpu_eval(rk, right)
            if i < len(plan.null_safe) and plan.null_safe[i]:
                lv, rv = _null_safe_codes(lv, rv)
            tmpl = tmpl.append_column(ln, lv)
            tmpr = tmpr.append_column(rn, rv)
            lkeys.append(ln)
            rkeys.append(rn)
        left, right = tmpl, tmpr
    if plan.condition is not None and jt in ("left_semi", "left_anti"):
        return _conditional_semi_cpu(plan, left, right, lkeys, rkeys)
    pa_type = {"inner": "inner", "left_outer": "left outer",
               "right_outer": "right outer", "full_outer": "full outer",
               "left_semi": "left semi", "left_anti": "left anti"}[jt]
    res = left.join(right, keys=lkeys, right_keys=rkeys, join_type=pa_type,
                    left_suffix="", right_suffix="__r",
                    coalesce_keys=False)
    out_names = [f.name for f in plan.schema.fields]
    res_names = res.column_names
    arrays = []
    used = []
    for name in out_names:
        # account for pa.join suffixing duplicate names
        if name in res_names and name not in used:
            pick = name
        else:
            pick = f"{name}__r"
        used.append(pick)
        arrays.append(res.column(pick))
    out = pa.Table.from_arrays(arrays, names=out_names)
    if plan.condition is not None:
        mask = pc.fill_null(cpu_eval(plan.condition, out), False)
        out = out.filter(mask)
    return out.cast(schema_to_arrow(plan.schema))


def _conditional_semi_cpu(plan: L.Join, left: pa.Table, right: pa.Table,
                          lkeys: list, rkeys: list) -> pa.Table:
    """EXISTS / NOT EXISTS with a residual condition: the inner join,
    filtered by the condition over both sides' columns, then the left
    rows that have (semi) or have not (anti) a passing pair, by row
    identity and in the left's order.  A NULL condition is not TRUE; a
    left row with a NULL key has no pair, so the anti join keeps it."""
    n_left = len(plan.children[0].schema.fields)
    n_right = len(plan.children[1].schema.fields)
    ids = pa.array(np.arange(left.num_rows, dtype=np.int64))
    # positional names: the sides may share column names
    lt = pa.Table.from_arrays(
        [left.column(i) for i in range(n_left)]
        + [left.column(k) for k in lkeys] + [ids],
        names=[f"__l{i}" for i in range(n_left)] + lkeys + ["__row"])
    rt = pa.Table.from_arrays(
        [right.column(i) for i in range(n_right)]
        + [right.column(k) for k in rkeys],
        names=[f"__r{i}" for i in range(n_right)] + rkeys)
    pairs = lt.join(rt, keys=lkeys, right_keys=rkeys, join_type="inner",
                    coalesce_keys=False)
    joined = pa.Table.from_arrays(
        [pairs.column(f"__l{i}") for i in range(n_left)]
        + [pairs.column(f"__r{i}") for i in range(n_right)],
        names=[f"c{i}" for i in range(n_left + n_right)])
    passed = pc.fill_null(cpu_eval(plan.condition, joined), False)
    hit = np.zeros(left.num_rows, bool)
    hit[pairs.column("__row").filter(passed).to_numpy()] = True
    keep = hit if plan.join_type == "left_semi" else ~hit
    out = left.select(list(range(n_left))).filter(pa.array(keep))
    return out.cast(schema_to_arrow(plan.schema))


def _add_interval_us(us: int, months: int, days: int,
                     microseconds: int) -> int:
    """Epoch-us + calendar interval with Spark's add_months rule:
    month arithmetic clamps day-of-month to the target month's end;
    days/microseconds add after."""
    import calendar
    import datetime

    utc = datetime.timezone.utc
    dt = (datetime.datetime(1970, 1, 1, tzinfo=utc)
          + datetime.timedelta(microseconds=us))
    m0 = dt.month - 1 + months
    y = dt.year + m0 // 12
    m = m0 % 12 + 1
    day = min(dt.day, calendar.monthrange(y, m)[1])
    dt = dt.replace(year=y, month=m, day=day)
    dt += datetime.timedelta(days=days, microseconds=microseconds)
    return int((dt - datetime.datetime(1970, 1, 1, tzinfo=utc))
               / datetime.timedelta(microseconds=1))


def _java_split(pattern: str, s: str, limit: int) -> list[str]:
    """java.lang.String.split semantics: captured groups never leak
    into the result (unlike re.split), a leading zero-width match is
    skipped, limit > 0 caps the piece count, and limit == 0 drops
    trailing empty pieces."""
    import re

    out = []
    last = 0
    pieces = 0
    for m in re.finditer(pattern, s):
        if limit > 0 and pieces >= limit - 1:
            break
        if m.start() == m.end():
            if m.start() == 0 or m.start() == len(s):
                continue  # Java skips boundary zero-width matches
            if m.start() < last:
                continue
        out.append(s[last:m.start()])
        last = m.end()
        pieces += 1
    out.append(s[last:])
    if limit == 0:
        while out and out[-1] == "":
            out.pop()
    return out
